"""jit'd public wrapper for RMSNorm.

Differentiable everywhere: custom_vjp whose forward dispatches to the
Pallas kernel on TPU (ref oracle elsewhere) and whose backward is the
closed-form jnp gradient. ``force`` overrides dispatch for tests:
"pallas" (interpret on CPU), "ref", or None (auto).

A Mosaic kernel is an opaque custom call that GSPMD cannot partition,
so under a multi-device mesh (``jax.set_mesh``) the kernel runs inside
``shard_map``: each device normalises its own rows.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from . import kernel, ref

_FORCE = None  # test hook: None | "ref" | "pallas"


def _tpu_forward(x, scale, eps):
    """The kernel on each device's rows: the leading dim split over
    every axis of the context mesh when it divides (else every device
    normalises all rows), the normalised dim whole, the scale
    replicated."""
    run = functools.partial(kernel.rmsnorm, eps=eps)
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return run(x, scale)
    rows = P(mesh.axis_names if x.shape[0] % mesh.size == 0 else None)
    return jax.shard_map(run, in_specs=(rows, P()), out_specs=rows,
                         check_vma=False)(x, scale)


def _forward(x, scale, eps):
    if _FORCE == "ref":
        return ref.rmsnorm(x, scale, eps=eps)
    if _FORCE == "pallas":
        return kernel.rmsnorm(x, scale, eps=eps,
                              interpret=jax.default_backend() != "tpu")
    if jax.default_backend() == "tpu":
        return _tpu_forward(x, scale, eps)
    return ref.rmsnorm(x, scale, eps=eps)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2,))
def rmsnorm(x, scale, eps=1e-6):
    return _forward(x, scale, eps)


def _fwd(x, scale, eps):
    return _forward(x, scale, eps), (x, scale)


def _bwd(eps, res, g):
    x, scale = res
    xf = x.astype(jnp.float32)
    gf = g.astype(jnp.float32)
    sf = scale.astype(jnp.float32)
    d = x.shape[-1]
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    inv = (var + eps) ** -0.5
    xhat = xf * inv
    # y = xhat * scale
    dscale = jnp.sum(gf * xhat, axis=tuple(range(x.ndim - 1)))
    gx_hat = gf * sf
    # dxhat/dx: inv * (I - xhat xhat^T / d)
    dx = inv * (gx_hat - xhat * jnp.mean(gx_hat * xhat, axis=-1,
                                         keepdims=True))
    return dx.astype(x.dtype), dscale.astype(scale.dtype)


rmsnorm.defvjp(_fwd, _bwd)
