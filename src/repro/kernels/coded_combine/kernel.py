"""Coded gradient combine Pallas TPU kernel: out = sum_b w_b * g_b.

The decode step of the paper (Eq. 1): the parameter server's weighted
sum of per-machine gradient messages. On TPU this runs on each host
over its locally-landed gradient shards before/after the cross-replica
reduce. It is a pure VPU streaming reduction (no MXU): arithmetic
intensity is ~2 FLOPs per 4 bytes, so the kernel tiles the parameter
axis into (n_blocks, block_d) VMEM strips, reads each gradient byte
exactly once, and keeps the fp32 accumulator implicit in registers.

Layout: the parameter axis is padded and viewed as lane-dense
(rows, 128) tiles, so every block the TPU compiler sees is a
(block_rows, 128) slab with block_rows a multiple of 32 -- the native
tile of the 8-bit payloads (and a multiple of the float32 and bfloat16
tiles). Grid: (rows // block_rows,); the weights (n_blocks,) ride in
SMEM and are read as scalars, so the combine is a chain of scalar x
tile multiply-adds on the VPU.

``quantized_combine`` is the compression-composed variant: the same
streaming reduction over an int8 (or float32) payload with per-row
dequant scales folded into the combine weights -- dequantize, weight
and reduce in one pass, reading 1 byte/component off the wire format
instead of 4.

``packed_sign_combine`` pushes the wire format to its 1-bit floor: the
payload is the ``sign_packed`` codec's uint8 bit-plane (8 signs/byte,
little-endian), and the kernel unpacks (shift/mask), maps bits to
+-1, weights and reduces in one pass -- 1/8 byte/component off the
wire, and as with ``quantized_combine`` no float32 per-machine
gradient tile is ever materialised. The kernel writes one float32
plane per bit position, (8, rows, 128); the wrapper interleaves the
planes back into component order.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

LANES = 128
# Sublane alignment of every block: the (32, 128) native tile of the
# int8/uint8 payloads, which is also a multiple of the float32 (8, 128)
# and bfloat16 (16, 128) tiles.
ROW_ALIGN = 32
_TILE_BYTES = 4 * 1024 * 1024  # ~4 MiB of payload per grid step

_SMEM = pl.BlockSpec(memory_space=pltpu.SMEM)


def _pick_block_rows(rows: int, bytes_per_row: int,
                     block: int | None) -> int:
    """Rows of 128 lanes per grid step. ``block`` (components, or bytes
    for the packed payload) overrides the ~4 MiB budget; the automatic
    choice is aligned to ROW_ALIGN and never exceeds the aligned row
    count."""
    if block:
        return -(-block // LANES)
    br = max(ROW_ALIGN, _TILE_BYTES // max(bytes_per_row, 1))
    br -= br % ROW_ALIGN
    return min(br, -(-rows // ROW_ALIGN) * ROW_ALIGN)


def _to_rows(x: jnp.ndarray, block: int | None, bytes_per_row: int):
    """(n, D) -> zero-padded (n, R, 128) with R a multiple of the block
    row count; returns (tiles, block_rows)."""
    n, d = x.shape
    rows = -(-d // LANES)
    br = _pick_block_rows(rows, bytes_per_row, block)
    rows = -(-rows // br) * br
    pad = rows * LANES - d
    if pad:
        x = jnp.pad(x, ((0, 0), (0, pad)))
    return x.reshape(n, rows, LANES), br


def _weighted_sum(u_ref, x_ref, term):
    """acc = 0; acc += u[b] * term(x[b]) for b in order -- the
    accumulation chain every combine shares with its ref."""
    acc = jnp.zeros(x_ref.shape[1:], jnp.float32)
    return jax.lax.fori_loop(
        0, x_ref.shape[0],
        lambda b, acc: acc + u_ref[b] * term(x_ref[b]), acc)


def _combine_kernel(w_ref, g_ref, o_ref):
    # w_ref: (n_blocks,) SMEM; g_ref: (n_blocks, br, 128); o_ref: (br, 128)
    o_ref[...] = _weighted_sum(
        w_ref, g_ref, lambda g: g.astype(jnp.float32)).astype(o_ref.dtype)


def _quantized_combine_kernel(u_ref, q_ref, o_ref):
    # The payload dequant stays a per-element cast inside the
    # multiply-accumulate -- no float32 (n_blocks, ...) gradient tile
    # ever exists. The chain is differential-tested against
    # ref.quantized_combine_np (bitwise on exactness-preserving inputs,
    # tolerance in general -- see its docstring on XLA's per-lane FMA
    # contraction).
    o_ref[...] = _weighted_sum(u_ref, q_ref,
                               lambda q: q.astype(jnp.float32))


def _launch(kernel, u, tiles, br, out_rows_shape, out_dtype, name,
            interpret):
    """One grid step per (n, br, 128) payload slab; ``out_rows_shape``
    is the output's leading dims before the (rows, 128) tile."""
    n, rows, _ = tiles.shape
    lead = len(out_rows_shape)
    return pl.pallas_call(
        kernel,
        grid=(rows // br,),
        in_specs=[_SMEM,
                  pl.BlockSpec((n, br, LANES), lambda i: (0, i, 0))],
        out_specs=pl.BlockSpec((*out_rows_shape, br, LANES),
                               lambda i: (0,) * lead + (i, 0)),
        out_shape=jax.ShapeDtypeStruct((*out_rows_shape, rows, LANES),
                                       out_dtype),
        name=name,
        interpret=interpret,
    )(u, tiles)


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def quantized_combine(q: jnp.ndarray, scales: jnp.ndarray,
                      w: jnp.ndarray, *, block_d: int | None = None,
                      interpret: bool = False) -> jnp.ndarray:
    """Fused dequantize-weight-combine: (n_blocks, D) quantized payload
    + (n_blocks,) scales + (n_blocks,) decoding weights -> (D,) float32.

    The dequant scale folds into the combine weight on the host side of
    the launch (u = w * scales, one tiny elementwise op), so the kernel
    streams the compressed payload once -- 1 byte/component for the
    int8/sign codecs against the float32 combine's 4 -- and the float32
    per-machine gradients are never materialised. Padding of the
    parameter axis contributes exact zeros (u * 0) and is sliced off.
    """
    n_blocks, d = q.shape
    u = w.astype(jnp.float32) * scales.astype(jnp.float32)
    tiles, br = _to_rows(q, block_d, n_blocks * LANES * q.dtype.itemsize)
    out = _launch(_quantized_combine_kernel, u, tiles, br, (),
                  jnp.float32, "quantized_combine", interpret)
    return out.reshape(-1)[:d]


def _packed_sign_combine_kernel(u_ref, q_ref, o_ref):
    # Same accumulation chain as _quantized_combine_kernel, with the
    # dequant replaced by an in-register unpack: bit plane k of the
    # byte tile maps {0,1} -> {-1,+1} and folds into accumulator k.
    # uint8 widens through int32 (the TPU has no uint8 -> float32 cast).
    def step(b, planes):
        x = q_ref[b].astype(jnp.int32)
        return tuple(
            acc + u_ref[b] * (2.0 * ((x >> k) & 1).astype(jnp.float32)
                              - 1.0)
            for k, acc in enumerate(planes))

    zero = jnp.zeros(q_ref.shape[1:], jnp.float32)
    planes = jax.lax.fori_loop(0, q_ref.shape[0], step, (zero,) * 8)
    for k, plane in enumerate(planes):
        o_ref[k] = plane


@functools.partial(jax.jit,
                   static_argnames=("d", "block_db", "interpret"))
def packed_sign_combine(q: jnp.ndarray, scales: jnp.ndarray,
                        w: jnp.ndarray, *, d: int,
                        block_db: int | None = None,
                        interpret: bool = False) -> jnp.ndarray:
    """Fused unpack-dequantize-weight-combine over a packed sign
    payload: (n_blocks, ceil(d/8)) uint8 bit-planes + (n_blocks,)
    scales + (n_blocks,) decoding weights -> (d,) float32.

    ``d`` is the true component count (static): byte padding -- both
    the codec's trailing-byte zero bits and the grid's block padding --
    unpacks to -1 signs at positions >= d, which the final slice
    drops before they can contribute. As in ``quantized_combine`` the
    dequant scale folds into the combine weight outside the grid
    (u = w * scales) and dead rows contribute exact zeros (u_b = 0).
    Byte j's bit k is component 8j + k, so the kernel's (8, rows, 128)
    bit planes interleave back with one transpose.
    """
    n_blocks, db = q.shape
    if db != (d + 7) // 8:
        raise ValueError(f"payload width {db} != ceil({d}/8)")
    u = w.astype(jnp.float32) * scales.astype(jnp.float32)
    # Per row of 128 bytes: the payload rows plus 8 float32 planes.
    tiles, br = _to_rows(q, block_db, (n_blocks + 32) * LANES)
    out = _launch(_packed_sign_combine_kernel, u, tiles, br, (8,),
                  jnp.float32, "packed_sign_combine", interpret)
    return jnp.moveaxis(out, 0, -1).reshape(-1)[:d]


@functools.partial(jax.jit, static_argnames=("block_d", "interpret"))
def coded_combine(grads: jnp.ndarray, w: jnp.ndarray, *,
                  block_d: int | None = None,
                  interpret: bool = False) -> jnp.ndarray:
    """grads: (n_blocks, D); w: (n_blocks,) -> (D,) in grads.dtype."""
    n_blocks, d = grads.shape
    tiles, br = _to_rows(grads, block_d,
                         n_blocks * LANES * grads.dtype.itemsize)
    out = _launch(_combine_kernel, w.astype(jnp.float32), tiles, br, (),
                  grads.dtype, "coded_combine", interpret)
    return out.reshape(-1)[:d]
