"""Public wrapper for flash attention: where the kernel is taken, and
how it runs under a mesh.

``enabled()`` is the backend half of the model's dispatch (the kernel
on TPU, ``blockwise_attention`` elsewhere) and ``supports`` the shape
half. ``_FORCE`` is a test hook: "pallas" takes the kernel (interpret
mode on CPU), "ref" keeps the model's blockwise path, None decides by
backend.

A Mosaic kernel is an opaque custom call that GSPMD cannot partition,
so under a multi-device mesh (``jax.set_mesh``) the kernel runs inside
``shard_map`` over the batch, forward and backward.
"""

from __future__ import annotations

import functools

import jax
from jax.sharding import PartitionSpec as P

from . import kernel

_FORCE = None  # test hook: None | "ref" | "pallas"


def enabled() -> bool:
    if _FORCE is not None:
        return _FORCE == "pallas"
    return jax.default_backend() == "tpu"


def supports(q_shape, k_shape) -> bool:
    """Whether the kernel tiles these shapes: query and key lengths
    equal and a multiple of 128, a head size that is a multiple of
    128, whole query groups."""
    _, S, H, Dh = q_shape
    Sk, KVH = k_shape[1], k_shape[2]
    return S == Sk and S % 128 == 0 and Dh % 128 == 0 and H % KVH == 0


def flash_attention(q, k, v, *, causal: bool = True):
    """q: (B, S, H, Dh); k, v: (B, S, KVH, Dh), shapes that ``supports``
    accepts. Differentiable. Returns (B, S, H, Dh) in q.dtype."""
    run = functools.partial(kernel.flash_attention, causal=causal,
                            interpret=jax.default_backend() != "tpu")
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return run(q, k, v)
    rows = P(mesh.axis_names if q.shape[0] % mesh.size == 0 else None)
    return jax.shard_map(run, in_specs=(rows, rows, rows), out_specs=rows,
                         check_vma=False)(q, k, v)
