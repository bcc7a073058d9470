"""Flash-decode Pallas TPU kernel: one query token vs a long KV cache.

This is the serving hot spot for ``decode_32k`` / ``long_500k``: the op
is entirely memory-bound (arithmetic intensity ~ 1 FLOP/byte), so the
kernel's job is to stream K/V through VMEM exactly once in MXU-friendly
tiles while keeping the online-softmax state (m, l, acc) resident.

Grid: (B, KVH, S // block_k). TPU iterates the last axis sequentially,
so the (m, l, acc) VMEM scratch accumulates across the KV blocks of one
(batch, kv-head) pair and is reset when the block index wraps to 0.
K/V tiles are (block_k, Dh) VMEM blocks: the (B, S, KVH, Dh) cache is
viewed as (B, S, KVH * Dh), and kv-head h is lane block h of width Dh,
so every block the TPU compiler sees is (block_k, Dh) with Dh a
multiple of 128 (its (8, 128) rule). The G = H/KVH query heads of the
group stay resident as a (G, Dh) tile. ``lengths`` rides in SMEM via
scalar prefetch so the mask needs no extra HBM traffic.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# float32 operands on the MXU: full float32 products, not one bf16 pass.
_HIGHEST = jax.lax.Precision.HIGHEST


def _decode_attn_kernel(lengths_ref,  # scalar prefetch: (B,) int32 SMEM
                        q_ref,        # (1, 1, G, Dh) VMEM
                        k_ref,        # (block_k, Dh) VMEM (squeezed)
                        v_ref,        # (block_k, Dh) VMEM (squeezed)
                        o_ref,        # (1, 1, G, Dh) VMEM
                        m_ref, l_ref, acc_ref,  # VMEM scratch
                        *, block_k: int, scale: float):
    b = pl.program_id(0)
    s = pl.program_id(2)
    num_s = pl.num_programs(2)

    @pl.when(s == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    q = q_ref[0, 0].astype(jnp.float32)            # (G, Dh)
    k = k_ref[...].astype(jnp.float32)             # (block_k, Dh)
    v = v_ref[...].astype(jnp.float32)             # (block_k, Dh)

    scores = jax.lax.dot_general(                   # (G, block_k) = q k^T
        q, k, (((1,), (1,)), ((), ())), precision=_HIGHEST,
        preferred_element_type=jnp.float32) * scale
    length = lengths_ref[b]
    positions = s * block_k + jax.lax.broadcasted_iota(
        jnp.int32, scores.shape, 1)
    scores = jnp.where(positions < length, scores, NEG_INF)

    m_prev = m_ref[...]                             # (G, 1)
    m_cur = jnp.max(scores, axis=-1, keepdims=True)
    m_new = jnp.maximum(m_prev, m_cur)
    p = jnp.exp(scores - m_new)                     # (G, block_k)
    correction = jnp.exp(m_prev - m_new)
    l_ref[...] = correction * l_ref[...] + jnp.sum(p, axis=-1,
                                                   keepdims=True)
    acc_ref[...] = correction * acc_ref[...] + jax.lax.dot(
        p, v, precision=_HIGHEST, preferred_element_type=jnp.float32)
    m_ref[...] = m_new

    @pl.when(s == num_s - 1)
    def _finalize():
        out = acc_ref[...] / jnp.maximum(l_ref[...], 1e-30)
        o_ref[0, 0] = out.astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=("block_k", "interpret"))
def decode_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                     lengths: jnp.ndarray, *, block_k: int = 512,
                     interpret: bool = False) -> jnp.ndarray:
    """q: (B, H, Dh); k, v: (B, S, KVH, Dh); lengths: (B,) int32.

    Returns (B, H, Dh) in q.dtype.
    """
    B, H, Dh = q.shape
    S, KVH = k.shape[1], k.shape[2]
    if H % KVH:
        raise ValueError("H must be a multiple of KVH")
    G = H // KVH
    block_k = min(block_k, S)
    if S % block_k:
        raise ValueError("S must be a multiple of block_k")
    qg = q.reshape(B, KVH, G, Dh)
    k = k.reshape(B, S, KVH * Dh)
    v = v.reshape(B, S, KVH * Dh)

    grid = (B, KVH, S // block_k)
    out = pl.pallas_call(
        functools.partial(_decode_attn_kernel, block_k=block_k,
                          scale=Dh ** -0.5),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((1, 1, G, Dh), lambda b, h, s, *_: (b, h, 0, 0)),
                pl.BlockSpec((None, block_k, Dh),
                             lambda b, h, s, *_: (b, s, h)),
                pl.BlockSpec((None, block_k, Dh),
                             lambda b, h, s, *_: (b, s, h)),
            ],
            out_specs=pl.BlockSpec((1, 1, G, Dh),
                                   lambda b, h, s, *_: (b, h, 0, 0)),
            scratch_shapes=[
                pltpu.VMEM((G, 1), jnp.float32),   # m
                pltpu.VMEM((G, 1), jnp.float32),   # l
                pltpu.VMEM((G, Dh), jnp.float32),  # acc
            ],
        ),
        out_shape=jax.ShapeDtypeStruct((B, KVH, G, Dh), q.dtype),
        name="decode_attention",
        interpret=interpret,
    )(lengths.astype(jnp.int32), qg, k, v)
    return out.reshape(B, H, Dh)
