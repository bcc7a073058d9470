"""Fused debias + decoding-error Pallas TPU kernel.

Computes errs_t = (1/n) sum_i (scale * alpha_{t,i} - 1)^2 for a whole
(trials, n) batch of decoded alphas in one pass: the debias rescale, the
subtraction and the squared-norm reduction fuse into a single VPU
streaming sweep (same roofline shape as ``coded_combine``: ~3 FLOPs per
4 bytes read, each alpha byte read exactly once).

Grid: (trials // block_t,); each step owns a (block_t, n) VMEM strip and
emits block_t per-trial errors as a (block_t, 1) column -- the output
block spans the whole (padded_trials, 1) array's lane dim, which keeps
it legal for the TPU compiler. The scalar ``scale`` rides in SMEM. The
n axis is padded to the 128-lane
boundary with 1/scale so padding contributes exactly zero error; padded
trailing trials are sliced off.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu


def _pick_block_t(trials: int, n: int) -> int:
    budget = 4 * 1024 * 1024 // (4 * max(n, 1))  # ~4 MiB tile
    bt = max(8, min(trials, budget))
    if bt > 8:
        bt -= bt % 8  # sublane alignment
    return bt


@functools.partial(jax.jit, static_argnames=("block_t", "interpret"))
def fused_error(alphas: jnp.ndarray, scale: jnp.ndarray, *,
                block_t: int | None = None,
                interpret: bool = False) -> jnp.ndarray:
    """alphas: (trials, n); scale: scalar -> (trials,) float32 errors."""
    trials, n = alphas.shape
    alphas = alphas.astype(jnp.float32)
    scale = jnp.asarray(scale, jnp.float32).reshape(1)
    pad_n = (-n) % 128
    if pad_n:
        fill = jnp.broadcast_to(1.0 / scale[0], (trials, pad_n))
        alphas = jnp.concatenate([alphas, fill], axis=1)
    n_pad = alphas.shape[1]
    bt = block_t or _pick_block_t(trials, n_pad)
    pad_t = (-trials) % bt
    if pad_t:
        alphas = jnp.pad(alphas, ((0, pad_t), (0, 0)))
    padded_trials = alphas.shape[0]
    inv_n = 1.0 / n  # true n: padding columns contribute 0 to the sum

    def body(a_ref, s_ref, o_ref):
        a = a_ref[...].astype(jnp.float32)      # (bt, n_pad)
        d = a * s_ref[0] - 1.0
        o_ref[...] = jnp.sum(d * d, axis=1, keepdims=True) * inv_n

    out = pl.pallas_call(
        body,
        grid=(padded_trials // bt,),
        in_specs=[
            pl.BlockSpec((bt, n_pad), lambda i: (i, 0)),
            pl.BlockSpec(memory_space=pltpu.SMEM),
        ],
        out_specs=pl.BlockSpec((bt, 1), lambda i: (i, 0)),
        out_shape=jax.ShapeDtypeStruct((padded_trials, 1), jnp.float32),
        name="batched_alpha_fused_error",
        interpret=interpret,
    )(alphas, scale)
    return out[:trials, 0]
