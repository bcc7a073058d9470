"""Causal flash attention Pallas TPU kernels, forward and backward.

Layout: q (B, S, H, Dh) and k, v (B, S, KVH, Dh), as the model's
projections leave them, viewed as (B, S, H * Dh): head h is lane block
h of width Dh, so every tile the TPU compiler sees is a (block, Dh)
slab with Dh a multiple of 128 and no transpose to (B, H, S, Dh) goes
through HBM. Grouped-query attention maps query head h to K/V head
h // G in the BlockSpec index maps; K/V are never repeated in HBM.

Three kernels, one ``jax.custom_vjp``:

- ``flash_attention_fwd``, grid (B, H, q blocks, kv blocks): the
  online softmax with (m, l, acc) resident in VMEM across the kv
  blocks; writes ``o`` and the per-row log-sum-exp ``lse`` (B, H, 1, S).
- ``flash_attention_dkv``, grid (B, KVH, kv blocks, G * q blocks):
  dK and dV of one kv block accumulate over every query block of the
  G query heads that read it. Scores are computed transposed, (kv, q),
  so the saved ``lse`` and ``di = rowsum(o * do)`` broadcast as rows.
- ``flash_attention_dq``, grid (B, H, q blocks, kv blocks): dQ.

Both backward kernels recompute the probabilities from q, k and the
saved ``lse``; nothing of size S x S is saved.

A grid step covers a (block x block) pair of query and kv rows and
computes it in square score tiles (``block_sizes``): large blocks
amortise the per-step cost, small tiles let the causal mask skip work.
Under the causal mask a tile wholly above the diagonal is skipped, not
masked; only the tiles on it are masked; a grid step whose every tile
is skipped also fetches nothing, as its index map repeats the block of
the step before.

Numerics are those of ``models.attention.blockwise_attention``: tiles
in the input dtype, scores and softmax statistics in float32, ``p``
(and ``ds``) cast to the input dtype before their matmuls, float32
accumulation, outputs in the inputs' dtypes.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -1e30
# Contract the last dims of both operands: a @ b.T.
_NT = (((1,), (1,)), ((), ()))


def block_sizes(S: int) -> tuple[int, int, int]:
    """(block, forward tile, backward tile) for a sequence of S, a
    multiple of 128: the block is the largest of 1024, 512, 256, 128
    that divides S. The forward computes a block as one tile (the
    online softmax rescales its state once per tile); the backward,
    which has no such state, in tiles of at most 256, so the causal
    mask skips more. Tuned on a v5e at S = 1024, Dh = 128 (PERF.md)."""
    block = next(b for b in (1024, 512, 256, 128) if S % b == 0)
    return block, block, min(block, 256)


def _each_tile(causal, i, j, blocks, block, tile, body):
    """``body(a, c, masked)`` for each score tile of the grid step that
    pairs query block ``i`` with kv block ``j`` (of ``blocks`` each):
    query rows ``a * tile`` on, key rows ``c * tile`` on. Under the
    causal mask a tile runs unmasked below the diagonal, masked on it,
    and not above it. Which tiles those are is known while tracing
    within a block, so each is traced once, with or without the mask."""
    n = block // tile
    tiles = [(a, c) for a in range(n) for c in range(n)]

    def every(masked):
        for a, c in tiles:
            body(a, c, masked)

    def diagonal():
        for a, c in tiles:
            if c <= a:
                body(a, c, c == a)

    if not causal:
        every(False)
    elif blocks == 1:
        diagonal()
    else:
        pl.when(j == i)(diagonal)
        pl.when(j < i)(functools.partial(every, False))


def _causal(s, q_axis):
    """Mask a score tile on the diagonal whose query index runs along
    ``q_axis``."""
    qpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, q_axis)
    kpos = jax.lax.broadcasted_iota(jnp.int32, s.shape, 1 - q_axis)
    return jnp.where(kpos <= qpos, s, NEG_INF)


def _fwd_kernel(q_ref, k_ref, v_ref, o_ref, lse_ref, m_sc, l_sc, acc_sc,
                *, causal, scale, block, tile, blocks):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        m_sc[...] = jnp.full_like(m_sc, NEG_INF)
        l_sc[...] = jnp.zeros_like(l_sc)
        acc_sc[...] = jnp.zeros_like(acc_sc)

    def step(a, c, masked):
        rq, rk = pl.ds(a * tile, tile), pl.ds(c * tile, tile)
        v = v_ref[rk, :]
        s = jax.lax.dot_general(q_ref[rq, :], k_ref[rk, :], _NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = _causal(s, 0)
        m_prev = m_sc[rq, :]
        m_new = jnp.maximum(m_prev, s.max(-1, keepdims=True))
        p = jnp.exp(s - m_new)
        corr = jnp.exp(m_prev - m_new)
        l_sc[rq, :] = corr * l_sc[rq, :] + p.sum(-1, keepdims=True)
        acc_sc[rq, :] = corr * acc_sc[rq, :] + jax.lax.dot(
            p.astype(v.dtype), v, preferred_element_type=jnp.float32)
        m_sc[rq, :] = m_new

    _each_tile(causal, i, j, blocks, block, tile, step)

    @pl.when(j == blocks - 1)
    def _finish():
        l = l_sc[...]
        o_ref[...] = (acc_sc[...] / jnp.maximum(l, 1e-30)).astype(o_ref.dtype)
        lse = m_sc[...] + jnp.log(l)                       # (block, 1)
        lse_ref[...] = jnp.transpose(jnp.broadcast_to(lse, (block, 128)))[:1]


def _dkv_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dk_ref, dv_ref,
                dk_sc, dv_sc, *, causal, scale, block, tile, blocks):
    j, t = pl.program_id(2), pl.program_id(3)

    @pl.when(t == 0)
    def _init():
        dk_sc[...] = jnp.zeros_like(dk_sc)
        dv_sc[...] = jnp.zeros_like(dv_sc)

    def step(a, c, masked):
        rq, rk = pl.ds(a * tile, tile), pl.ds(c * tile, tile)
        q, do = q_ref[rq, :], do_ref[rq, :]
        st = jax.lax.dot_general(k_ref[rk, :], q, _NT,
                                 preferred_element_type=jnp.float32) * scale
        if masked:
            st = _causal(st, 1)
        pt = jnp.exp(st - lse_ref[:, rq])                   # (kv, q)
        dv_sc[rk, :] += jax.lax.dot(pt.astype(do.dtype), do,
                                    preferred_element_type=jnp.float32)
        dpt = jax.lax.dot_general(v_ref[rk, :], do, _NT,
                                  preferred_element_type=jnp.float32)
        dst = pt * (dpt - di_ref[:, rq]) * scale
        dk_sc[rk, :] += jax.lax.dot(dst.astype(q.dtype), q,
                                    preferred_element_type=jnp.float32)

    _each_tile(causal, t % blocks, j, blocks, block, tile, step)

    @pl.when(t == pl.num_programs(3) - 1)
    def _finish():
        dk_ref[...] = dk_sc[...].astype(dk_ref.dtype)
        dv_ref[...] = dv_sc[...].astype(dv_ref.dtype)


def _dq_kernel(q_ref, k_ref, v_ref, do_ref, lse_ref, di_ref, dq_ref,
               dq_sc, lse_sc, di_sc, *, causal, scale, block, tile, blocks):
    i, j = pl.program_id(2), pl.program_id(3)

    @pl.when(j == 0)
    def _init():
        dq_sc[...] = jnp.zeros_like(dq_sc)
        # The saved rows as (block, 1) columns, once per query block.
        lse_sc[...] = jnp.transpose(
            jnp.broadcast_to(lse_ref[...], (128, block)))[:, :1]
        di_sc[...] = jnp.transpose(
            jnp.broadcast_to(di_ref[...], (128, block)))[:, :1]

    def step(a, c, masked):
        rq, rk = pl.ds(a * tile, tile), pl.ds(c * tile, tile)
        k = k_ref[rk, :]
        s = jax.lax.dot_general(q_ref[rq, :], k, _NT,
                                preferred_element_type=jnp.float32) * scale
        if masked:
            s = _causal(s, 0)
        p = jnp.exp(s - lse_sc[rq, :])                      # (q, kv)
        dp = jax.lax.dot_general(do_ref[rq, :], v_ref[rk, :], _NT,
                                 preferred_element_type=jnp.float32)
        ds = p * (dp - di_sc[rq, :]) * scale
        dq_sc[rq, :] += jax.lax.dot(ds.astype(k.dtype), k,
                                    preferred_element_type=jnp.float32)

    _each_tile(causal, i, j, blocks, block, tile, step)

    @pl.when(j == blocks - 1)
    def _finish():
        dq_ref[...] = dq_sc[...].astype(dq_ref.dtype)


def _dims(q, k):
    B, S, H, Dh = q.shape
    return B, S, H, k.shape[2], Dh, H // k.shape[2]


def _params(*semantics):
    return pltpu.CompilerParams(dimension_semantics=semantics)


# Jitted so that each kernel's HLO instruction is named after its
# pallas_call (``flash_attention_fwd.3``) whatever transformation
# (jvp, transpose, remat) it is traced under.
@functools.partial(jax.jit, static_argnums=(3, 4))
def _forward(q, k, v, causal, interpret):
    B, S, H, KVH, Dh, G = _dims(q, k)
    block, tile, _ = block_sizes(S)
    n = S // block
    # Under the causal mask the kv steps past the diagonal repeat the
    # diagonal block, so nothing is fetched for them.
    last = (lambda i, j: jnp.minimum(j, i)) if causal else (lambda i, j: j)
    q_spec = pl.BlockSpec((None, block, Dh), lambda b, h, i, j: (b, i, h))
    kv_spec = pl.BlockSpec((None, block, Dh),
                           lambda b, h, i, j: (b, last(i, j), h // G))
    o, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, causal=causal, scale=Dh ** -0.5,
                          block=block, tile=tile, blocks=n),
        grid=(B, H, n, n),
        in_specs=[q_spec, kv_spec, kv_spec],
        out_specs=[q_spec,
                   pl.BlockSpec((None, None, 1, block),
                                lambda b, h, i, j: (b, h, 0, i))],
        out_shape=[jax.ShapeDtypeStruct((B, S, H * Dh), q.dtype),
                   jax.ShapeDtypeStruct((B, H, 1, S), jnp.float32)],
        scratch_shapes=[pltpu.VMEM((block, 1), jnp.float32),
                        pltpu.VMEM((block, 1), jnp.float32),
                        pltpu.VMEM((block, Dh), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        name="flash_attention_fwd",
        interpret=interpret,
    )(q.reshape(B, S, H * Dh), k.reshape(B, S, KVH * Dh),
      v.reshape(B, S, KVH * Dh))
    return o.reshape(q.shape), lse


@functools.partial(jax.jit, static_argnums=(6, 7))
def _backward(q, k, v, o, lse, do, causal, interpret):
    B, S, H, KVH, Dh, G = _dims(q, k)
    block, _, tile = block_sizes(S)
    n, scale = S // block, Dh ** -0.5
    di = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), -1)
    di = jnp.transpose(di, (0, 2, 1))[:, :, None, :]        # (B, H, 1, S)
    q2, do2 = q.reshape(B, S, H * Dh), do.reshape(B, S, H * Dh)
    k2, v2 = k.reshape(B, S, KVH * Dh), v.reshape(B, S, KVH * Dh)

    # dK, dV: step t runs over the query blocks of each of the G query
    # heads of kv head h. Under the causal mask the query blocks before
    # the diagonal repeat the diagonal block.
    first = (lambda j, t: jnp.maximum(t % n, j)) if causal else \
        (lambda j, t: t % n)
    q_rows = pl.BlockSpec((None, block, Dh), lambda b, h, j, t: (
        b, first(j, t), h * G + t // n))
    q_stat = pl.BlockSpec((None, None, 1, block), lambda b, h, j, t: (
        b, h * G + t // n, 0, first(j, t)))
    kv_blk = pl.BlockSpec((None, block, Dh), lambda b, h, j, t: (b, j, h))
    dk, dv = pl.pallas_call(
        functools.partial(_dkv_kernel, causal=causal, scale=scale,
                          block=block, tile=tile, blocks=n),
        grid=(B, KVH, n, G * n),
        in_specs=[q_rows, kv_blk, kv_blk, q_rows, q_stat, q_stat],
        out_specs=[kv_blk, kv_blk],
        out_shape=[jax.ShapeDtypeStruct(k2.shape, k.dtype),
                   jax.ShapeDtypeStruct(v2.shape, v.dtype)],
        scratch_shapes=[pltpu.VMEM((block, Dh), jnp.float32),
                        pltpu.VMEM((block, Dh), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        name="flash_attention_dkv",
        interpret=interpret,
    )(q2, k2, v2, do2, lse, di)

    last = (lambda i, j: jnp.minimum(j, i)) if causal else (lambda i, j: j)
    rows = pl.BlockSpec((None, block, Dh), lambda b, h, i, j: (b, i, h))
    kv_spec = pl.BlockSpec((None, block, Dh),
                           lambda b, h, i, j: (b, last(i, j), h // G))
    stat = pl.BlockSpec((None, None, 1, block),
                        lambda b, h, i, j: (b, h, 0, i))
    dq = pl.pallas_call(
        functools.partial(_dq_kernel, causal=causal, scale=scale,
                          block=block, tile=tile, blocks=n),
        grid=(B, H, n, n),
        in_specs=[rows, kv_spec, kv_spec, rows, stat, stat],
        out_specs=rows,
        out_shape=jax.ShapeDtypeStruct(q2.shape, q.dtype),
        scratch_shapes=[pltpu.VMEM((block, Dh), jnp.float32),
                        pltpu.VMEM((block, 1), jnp.float32),
                        pltpu.VMEM((block, 1), jnp.float32)],
        compiler_params=_params("parallel", "parallel", "parallel",
                                "arbitrary"),
        name="flash_attention_dq",
        interpret=interpret,
    )(q2, k2, v2, do2, lse, di)
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4))
def flash_attention(q, k, v, causal=True, interpret=False):
    """q: (B, S, H, Dh); k, v: (B, S, KVH, Dh), H % KVH == 0, S and Dh
    multiples of 128. Returns (B, S, H, Dh) in q.dtype."""
    return _forward(q, k, v, causal, interpret)[0]


def _vjp_fwd(q, k, v, causal, interpret):
    o, lse = _forward(q, k, v, causal, interpret)
    return o, (q, k, v, o, lse)


def _vjp_bwd(causal, interpret, res, do):
    return _backward(*res, do, causal, interpret)


flash_attention.defvjp(_vjp_fwd, _vjp_bwd)
