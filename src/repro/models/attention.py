"""Attention: GQA with RoPE, a full-sequence training/prefill path,
and a cached single-token decode path backed by the flash-decode
Pallas kernel.

The full-sequence path takes the fused flash-attention Pallas kernel
(``kernels/flash_attention``, forward and backward) on TPU for
self-attention of whole sequences whose shapes it tiles
(``uses_flash_kernel``). Every other case -- sliding windows,
cross-attention, a query block at an offset, lengths or head sizes
that are not multiples of 128, and every backend but the TPU -- runs
``blockwise_attention``: a pure-jnp online softmax over KV blocks
driven by ``lax.scan``, so the HLO stays small and the (S x S) score
matrix is never materialised. Causal and sliding-window masks are
applied per (q-block, kv-block) tile.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.kernels.decode_attention import ops as decode_ops
from repro.kernels.flash_attention import ops as flash_ops
from .layers import init_linear, linear, apply_rope

NEG_INF = -1e30


def init_attention(key, d_model: int, n_heads: int, n_kv_heads: int,
                   head_dim: int, *, qkv_bias: bool = False,
                   dtype: str = "float32"):
    kq, kk, kv, ko = jax.random.split(key, 4)
    return {
        "wq": init_linear(kq, d_model, n_heads * head_dim, bias=qkv_bias,
                          dtype=dtype),
        "wk": init_linear(kk, d_model, n_kv_heads * head_dim,
                          bias=qkv_bias, dtype=dtype),
        "wv": init_linear(kv, d_model, n_kv_heads * head_dim,
                          bias=qkv_bias, dtype=dtype),
        "wo": init_linear(ko, n_heads * head_dim, d_model, dtype=dtype),
    }


def _block_mask(q_pos, k_pos, *, causal: bool, window: Optional[int]):
    """(bq, bk) boolean mask tile from absolute positions."""
    qp = q_pos[:, None]
    kp = k_pos[None, :]
    mask = jnp.ones((q_pos.shape[0], k_pos.shape[0]), dtype=bool)
    if causal:
        mask &= kp <= qp
    if window is not None:
        mask &= kp > qp - window
    return mask


def blockwise_attention(q, k, v, *, causal: bool = True,
                        window: Optional[int] = None,
                        q_offset: int = 0,
                        block_q: int = 512, block_k: int = 512):
    """Online-softmax attention.

    q: (B, Sq, H, Dh); k, v: (B, Sk, KVH, Dh). H % KVH == 0.
    ``q_offset``: absolute position of q[0] (for cross-chunk prefill).
    Returns (B, Sq, H, Dh) in q.dtype.
    """
    B, Sq, H, Dh = q.shape
    Sk, KVH = k.shape[1], k.shape[2]
    G = H // KVH
    scale = Dh ** -0.5

    block_q = min(block_q, Sq)
    block_k = min(block_k, Sk)
    # Pad sequence dims to block multiples (masked out below).
    pq = (-Sq) % block_q
    pk = (-Sk) % block_k
    if pq:
        q = jnp.pad(q, ((0, 0), (0, pq), (0, 0), (0, 0)))
    if pk:
        k = jnp.pad(k, ((0, 0), (0, pk), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pk), (0, 0), (0, 0)))
    nq, nk = q.shape[1] // block_q, k.shape[1] // block_k

    # Keep tiles in the input dtype (bf16 on TPU) and accumulate the
    # dots in fp32 via preferred_element_type: halves the HBM/ICI bytes
    # of every attention tile vs f32 operands.
    qf = q.reshape(B, nq, block_q, KVH, G, Dh)
    kf = k.reshape(B, nk, block_k, KVH, Dh)
    vf = v.reshape(B, nk, block_k, KVH, Dh)

    def q_block(carry_q):
        qi, qb = carry_q          # qb: (B, block_q, KVH, G, Dh)
        q_pos = q_offset + qi * block_q + jnp.arange(block_q)

        def kv_step(carry, kb_idx):
            m, l, acc = carry
            kb = jax.lax.dynamic_index_in_dim(kf, kb_idx, 1, False)
            vb = jax.lax.dynamic_index_in_dim(vf, kb_idx, 1, False)
            k_pos = kb_idx * block_k + jnp.arange(block_k)
            s = jnp.einsum("bqhgd,bkhd->bhgqk", qb, kb,
                           preferred_element_type=jnp.float32) * scale
            mask = _block_mask(q_pos, k_pos, causal=causal, window=window)
            mask &= (k_pos < Sk)[None, :]
            s = jnp.where(mask[None, None, None], s, NEG_INF)
            m_new = jnp.maximum(m, s.max(-1))
            p = jnp.exp(s - m_new[..., None])
            corr = jnp.exp(m - m_new)
            l_new = corr * l + p.sum(-1)
            acc_new = corr[..., None] * acc + jnp.einsum(
                "bhgqk,bkhd->bhgqd", p.astype(qb.dtype), vb,
                preferred_element_type=jnp.float32)
            return (m_new, l_new, acc_new), None

        m0 = jnp.full((B, KVH, G, block_q), NEG_INF, jnp.float32)
        l0 = jnp.zeros((B, KVH, G, block_q), jnp.float32)
        a0 = jnp.zeros((B, KVH, G, block_q, Dh), jnp.float32)
        (m, l, acc), _ = jax.lax.scan(kv_step, (m0, l0, a0),
                                      jnp.arange(nk))
        out = acc / jnp.maximum(l, 1e-30)[..., None]
        return out  # (B, KVH, G, block_q, Dh)

    outs = jax.lax.map(q_block, (jnp.arange(nq),
                                 jnp.moveaxis(qf, 1, 0)))
    # outs: (nq, B, KVH, G, block_q, Dh) -> (B, nq*block_q, H, Dh)
    out = jnp.moveaxis(outs, 0, 1).transpose(0, 1, 4, 2, 3, 5)
    out = out.reshape(B, nq * block_q, H, Dh)[:, :Sq]
    return out.astype(q.dtype)


def uses_flash_kernel(q_shape, k_shape, *, cross: bool,
                      window: Optional[int], q_offset: int = 0) -> bool:
    """Whether full-sequence attention over q (B, Sq, H, Dh) and k
    (B, Sk, KVH, Dh) takes the flash-attention kernel: where the
    kernel is enabled (on TPU), for self-attention of a whole sequence
    (no window, no query offset) with shapes it tiles."""
    return (flash_ops.enabled() and not cross and window is None
            and q_offset == 0 and flash_ops.supports(q_shape, k_shape))


def attention_forward(p, x, *, n_heads: int, n_kv_heads: int,
                      head_dim: int, rope_theta: float,
                      causal: bool = True,
                      window: Optional[int] = None,
                      positions: Optional[jnp.ndarray] = None,
                      kv: Optional[jnp.ndarray] = None,
                      block_q: int = 512, block_k: int = 512):
    """Full-sequence attention (train / prefill / encoder).

    ``kv``: optional cross-attention source (B, Ssrc, D); when given,
    K/V come from it and masks are disabled unless causal is set.
    """
    B, S, _ = x.shape
    src = x if kv is None else kv
    q = linear(p["wq"], x).reshape(B, S, n_heads, head_dim)
    k = linear(p["wk"], src).reshape(B, src.shape[1], n_kv_heads, head_dim)
    v = linear(p["wv"], src).reshape(B, src.shape[1], n_kv_heads, head_dim)
    if positions is None:
        positions = jnp.arange(S)[None, :]
    if kv is None:  # self-attention: RoPE on both
        q = apply_rope(q, jnp.broadcast_to(positions, (B, S)), rope_theta)
        k = apply_rope(k, jnp.broadcast_to(
            jnp.arange(src.shape[1])[None, :], (B, src.shape[1])),
            rope_theta)
    if uses_flash_kernel(q.shape, k.shape, cross=kv is not None,
                         window=window):
        out = flash_ops.flash_attention(q, k, v, causal=causal)
    else:
        out = blockwise_attention(q, k, v, causal=causal and kv is None,
                                  window=window, block_q=block_q,
                                  block_k=block_k)
    return linear(p["wo"], out.reshape(B, S, n_heads * head_dim))


def attention_decode(p, x, cache, *, n_heads: int, n_kv_heads: int,
                     head_dim: int, rope_theta: float,
                     window: Optional[int] = None):
    """Single-token decode with KV cache.

    x: (B, 1, D). cache: {"k","v": (B, S, KVH, Dh), "pos": (B,) int32}.
    Writes the new K/V at position pos (mod window size for
    sliding-window caches) and attends over the valid prefix.
    Returns (out (B, 1, D), new_cache).
    """
    B = x.shape[0]
    S_cache = cache["k"].shape[1]
    pos = cache["pos"]  # (B,)
    q = linear(p["wq"], x).reshape(B, 1, n_heads, head_dim)
    k_new = linear(p["wk"], x).reshape(B, 1, n_kv_heads, head_dim)
    v_new = linear(p["wv"], x).reshape(B, 1, n_kv_heads, head_dim)
    q = apply_rope(q, pos[:, None], rope_theta)
    k_new = apply_rope(k_new, pos[:, None], rope_theta)

    slot = pos % S_cache if window is not None else pos
    # Scatter the new entry into the cache (per-batch dynamic slot).
    onehot = jax.nn.one_hot(slot, S_cache, dtype=cache["k"].dtype)
    k_cache = cache["k"] * (1 - onehot)[:, :, None, None] + \
        onehot[:, :, None, None] * k_new.astype(cache["k"].dtype)
    v_cache = cache["v"] * (1 - onehot)[:, :, None, None] + \
        onehot[:, :, None, None] * v_new.astype(cache["v"].dtype)

    lengths = jnp.minimum(pos + 1, S_cache).astype(jnp.int32)
    out = decode_ops.decode_attention(q[:, 0], k_cache, v_cache, lengths)
    out = linear(p["wo"], out.reshape(B, 1, n_heads * head_dim))
    new_cache = {"k": k_cache, "v": v_cache, "pos": pos + 1}
    return out, new_cache


def init_cache(batch: int, max_len: int, n_kv_heads: int, head_dim: int,
               dtype: str = "bfloat16", *, pos: int = 0):
    return {
        "k": jnp.zeros((batch, max_len, n_kv_heads, head_dim),
                       jnp.dtype(dtype)),
        "v": jnp.zeros((batch, max_len, n_kv_heads, head_dim),
                       jnp.dtype(dtype)),
        "pos": jnp.full((batch,), pos, jnp.int32),
    }
