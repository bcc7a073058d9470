"""Fused tall-skinny Gram matvec Pallas TPU kernel: y = X^T (X v).

The matrix-free spectral pipeline (``core.spectral``) estimates
|Cov|_2 by Lanczos iteration whose only large-array work is this Gram
matvec against the centered (trials, n) alpha batch (or its transpose,
whichever orientation is tall-skinny). Each grid step owns a
(block_r, k) VMEM strip of X: it computes the strip's projection
y = X_blk v and immediately folds X_blk^T y into the (1, k) output
block on the MXU, so X streams through VMEM exactly once per matvec
and no (R,)-sized intermediate ever round-trips to HBM.

Grid: (R // block_r,); the output BlockSpec maps every step to the same
(1, k) tile (initialised at step 0) -- the standard revisiting-
accumulator pattern, safe because TPU grid steps run sequentially. The
k axis pads to the 128-lane boundary and R to the block size, both
with zeros (zero rows/columns contribute exactly zero).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# float32 operands on the MXU: full float32 products, not one bf16 pass.
_HIGHEST = jax.lax.Precision.HIGHEST


def _pick_block_r(rows: int, k: int) -> int:
    budget = 2 * 1024 * 1024 // (4 * max(k, 1))  # ~2 MiB strip
    br = max(8, min(rows, budget))
    if br > 8:
        br -= br % 8  # sublane alignment
    return br


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def gram_matvec(x: jnp.ndarray, v: jnp.ndarray, *,
                block_r: int | None = None,
                interpret: bool = False) -> jnp.ndarray:
    """x: (R, k); v: (k,) or (bv, k) -> float32 X^T (X v).

    A 1-D ``v`` returns (k,); a 2-D ``v`` is bv stacked right-hand
    sides (the block-Lanczos case) and returns (bv, k) -- the same
    revisiting-accumulator kernel, with the (1, kp) projection/output
    tiles widened to (bv, kp) so all bv columns ride one pass over X.
    """
    vec = v.ndim == 1
    rows, k = x.shape
    x = x.astype(jnp.float32)
    v = jnp.asarray(v, jnp.float32).reshape(-1, k)
    bv = v.shape[0]
    pad_k = (-k) % 128
    if pad_k:
        x = jnp.pad(x, ((0, 0), (0, pad_k)))
        v = jnp.pad(v, ((0, 0), (0, pad_k)))
    kp = k + pad_k
    br = block_r or _pick_block_r(rows, kp)
    pad_r = (-rows) % br
    if pad_r:
        x = jnp.pad(x, ((0, pad_r), (0, 0)))

    def body(x_ref, v_ref, o_ref):
        @pl.when(pl.program_id(0) == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        xb = x_ref[...]                              # (br, kp)
        y = jax.lax.dot_general(                     # (br, bv) = X_blk V^T
            xb, v_ref[...], (((1,), (1,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32)
        o_ref[...] += jax.lax.dot_general(           # (bv, kp) = Y^T X_blk
            y, xb, (((0,), (0,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32)

    out = pl.pallas_call(
        body,
        grid=((rows + pad_r) // br,),
        in_specs=[
            pl.BlockSpec((br, kp), lambda i: (i, 0)),
            pl.BlockSpec((bv, kp), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((bv, kp), lambda i: (0, 0)),
        out_shape=jax.ShapeDtypeStruct((bv, kp), jnp.float32),
        interpret=interpret,
    )(x, v)
    return out[0, :k] if vec else out[:, :k]


@functools.partial(jax.jit, static_argnames=("block_r", "interpret"))
def gram_matvec_batch(x: jnp.ndarray, v: jnp.ndarray, *,
                      block_r: int | None = None,
                      interpret: bool = False) -> jnp.ndarray:
    """x: (B, R, k); v: (B, k) -> (B, k) float32 per-slice X^T (X v).

    The lockstep-Lanczos batch form: grid (B, R // block_r) with the
    row axis innermost, so each slice's (1, 1, kp) output tile is
    revisited consecutively (the sequential-grid accumulator pattern of
    the single-slice kernel) and the whole stack runs in one kernel
    launch sequence instead of B.
    """
    nb, rows, k = x.shape
    x = x.astype(jnp.float32)
    v = jnp.asarray(v, jnp.float32).reshape(nb, 1, k)
    pad_k = (-k) % 128
    if pad_k:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad_k)))
        v = jnp.pad(v, ((0, 0), (0, 0), (0, pad_k)))
    kp = k + pad_k
    br = block_r or _pick_block_r(rows, kp)
    pad_r = (-rows) % br
    if pad_r:
        x = jnp.pad(x, ((0, 0), (0, pad_r), (0, 0)))

    def body(x_ref, v_ref, o_ref):
        @pl.when(pl.program_id(1) == 0)
        def _init():
            o_ref[...] = jnp.zeros_like(o_ref)

        xb = x_ref[0]                                # (br, kp)
        y = jax.lax.dot_general(                     # (br, 1) = X_blk v
            xb, v_ref[0], (((1,), (1,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32)
        o_ref[0] += jax.lax.dot_general(             # (1, kp) = y^T X_blk
            y, xb, (((0,), (0,)), ((), ())), precision=_HIGHEST,
            preferred_element_type=jnp.float32)

    out = pl.pallas_call(
        body,
        grid=(nb, (rows + pad_r) // br),
        in_specs=[
            pl.BlockSpec((1, br, kp), lambda b, i: (b, i, 0)),
            pl.BlockSpec((1, 1, kp), lambda b, i: (b, 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, 1, kp), lambda b, i: (b, 0, 0)),
        out_shape=jax.ShapeDtypeStruct((nb, 1, kp), jnp.float32),
        interpret=interpret,
    )(x, v)
    return out[:, 0, :k]
