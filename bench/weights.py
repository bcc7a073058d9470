"""Seeded weights for a cell, made on the device in one jitted call.

The benchmark makes the weights, not the program: the plain reference
(``reference.py``) regenerates the same values from the same seed after
the window, so it takes nothing the program has made. The tree layout
is the program's own (``models.model.init_params``), read as shapes
only; every value comes from here.

Per leaf, by the name of its last key:

* ``w`` (a matrix, stacked over layers or not): normal / sqrt(fan_in);
* ``table`` (embedding): normal * 0.02;
* ``b`` (a bias): normal * 0.02, so the bias path carries signal;
* ``scale`` (a norm scale): 1 + 0.05 * normal.

Layer-stacked leaves are generated one layer at a time under
``lax.map`` so that the generator's temporaries stay one layer wide.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp


def seed_key(seed: int):
    """A PRNG key that tells apart seeds wider than 32 bits."""
    seed = int(seed)
    key = jax.random.key(seed & 0xFFFFFFFF)
    return jax.random.fold_in(key, (seed >> 32) & 0xFFFFFFFF)


def _leaf(key, name: str, shape, dtype, stacked: bool):
    def one(k, shp):
        if name == "w":
            return jax.random.normal(k, shp, dtype) * shp[-2] ** -0.5
        if name == "table":
            return jax.random.normal(k, shp, dtype) * 0.02
        if name == "b":
            return jax.random.normal(k, shp, dtype) * 0.02
        if name == "scale":
            return 1.0 + 0.05 * jax.random.normal(k, shp, dtype)
        raise ValueError(f"no seeded rule for a leaf named {name!r}")

    if not stacked:
        return one(key, shape)
    keys = jax.random.split(key, shape[0])
    return jax.lax.map(lambda k: one(k, shape[1:]), keys)


def _is_stacked(path) -> bool:
    return str(getattr(path[0], "key", "")).endswith("blocks")


def param_shapes(cfg):
    """The program's parameter tree, as shapes."""
    from repro.models import model as M

    return jax.eval_shape(lambda k: M.init_params(cfg, k),
                          jax.random.PRNGKey(0))


def params_fn(cfg, shardings=None):
    """The jitted call key -> the cell's parameters (``seed_key`` makes
    the key). ``shardings`` (a pytree matching the tree, or one
    sharding) places the result. Build it once and call it again to
    regenerate the same values without compiling anew."""
    shapes = param_shapes(cfg)
    flat, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        leaves = []
        for i, (path, s) in enumerate(flat):
            name = str(getattr(path[-1], "key", path[-1]))
            leaves.append(_leaf(jax.random.fold_in(key, i), name,
                                s.shape, s.dtype, _is_stacked(path)))
        return jax.tree_util.tree_unflatten(treedef, leaves)

    return jax.jit(build, out_shardings=shardings)


def make_params(cfg, seed: int, shardings=None):
    """The cell's parameters from ``seed``, in one jitted call."""
    return params_fn(cfg, shardings)(seed_key(seed))
