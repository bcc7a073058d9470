"""The cross entropy's softmax gradient as one Pallas TPU pass.

For logits l (T, V), their per-row log-sum-exp, labels and a per-row
scale s, it writes

    d[t, v] = s_t * (exp(l[t, v] - lse_t) - [v == label_t])

in bfloat16, and 0 for the padded vocabulary ids ``v >= vocab_size``
and for the rows whose label is negative. Each element is computed
once, so the two weight-gradient matmuls of the head read it from HBM
in place of re-deriving it per output tile.

Blocks are whole rows of the vocabulary where eight of them fit the
VMEM budget (contiguous DMAs), else column blocks that do; the grid
rounds up, and the rows and columns of a last partial block are
dropped on write. ``offset``, a scalar in SMEM, is the global id of
the first column: under a vocabulary-sharded mesh each device holds a
slice of the vocabulary and compares labels and ``vocab_size`` with
global ids.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

# Double-buffered float32 input and bfloat16 output blocks. The body's
# float32 intermediates take about as much again, which keeps the whole
# under the 16 MiB of VMEM a kernel may use by default on v5e.
VMEM_BUDGET = 8 * 2 ** 20
_BYTES = 2 * (4 + 2)  # per element of a block, both buffers
_ROWS = 16            # row granule: a bfloat16 tile's sublanes


def block_sizes(T: int, V: int, budget: int) -> tuple[int, int]:
    """(row block, column block) for logits (T, V), V a multiple of 128:
    whole rows where ``_ROWS`` of them fit ``budget``, else the widest
    multiple of 128 columns that does; then as many rows of that width
    as fit, all T where they do."""
    tv = V if _ROWS * V * _BYTES <= budget else \
        budget // (_ROWS * _BYTES) // 128 * 128
    tm = budget // (tv * _BYTES) // _ROWS * _ROWS
    return (T if T <= tm else tm), tv


def _kernel(off_ref, x_ref, lse_ref, label_ref, scale_ref, o_ref, *,
            vocab_size, tv):
    col = (off_ref[0] + pl.program_id(1) * tv
           + jax.lax.broadcasted_iota(jnp.int32, x_ref.shape, 1))
    label = label_ref[...]                                   # (tm, 1)
    d = jnp.exp(x_ref[...] - lse_ref[...]) - (col == label).astype(jnp.float32)
    keep = (col < vocab_size) & (label >= 0)
    o_ref[...] = jnp.where(keep, d * scale_ref[...], 0.0).astype(o_ref.dtype)


# Jitted so that the kernel's HLO instruction is named after its
# pallas_call (``xent_grad.2``) whatever transformation it is traced
# under.
@functools.partial(jax.jit, static_argnums=(5, 6))
def xent_grad(logits, lse, labels, scale, offset, vocab_size, interpret=False):
    """logits (T, V) float32, V a multiple of 128; lse, scale (T,)
    float32; labels (T,) int; offset, the global id of column 0 (an
    int32 scalar, traced or not). Returns (T, V) bfloat16."""
    T, V = logits.shape
    tm, tv = block_sizes(T, V, VMEM_BUDGET)
    rows = pl.BlockSpec((tm, tv), lambda i, j, off: (i, j))
    col = pl.BlockSpec((tm, 1), lambda i, j, off: (i, 0))
    return pl.pallas_call(
        functools.partial(_kernel, vocab_size=vocab_size, tv=tv),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(pl.cdiv(T, tm), pl.cdiv(V, tv)),
            in_specs=[rows, col, col, col],
            out_specs=rows),
        out_shape=jax.ShapeDtypeStruct((T, V), jnp.bfloat16),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel")),
        name="xent_grad",
        interpret=interpret,
    )(jnp.asarray(offset, jnp.int32).reshape(1), logits.astype(jnp.float32),
      lse.astype(jnp.float32).reshape(T, 1),
      labels.astype(jnp.int32).reshape(T, 1),
      scale.astype(jnp.float32).reshape(T, 1))
