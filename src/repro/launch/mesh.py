"""Mesh definitions.

Functions, not module-level constants, so importing this module never
touches jax device state; the dry-run asks for 512 placeholder CPU
devices in its ``main`` before any device is used.
"""

from __future__ import annotations

import jax


def _make_mesh(shape, axes):
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips. Multi-pod: a leading
    pod=2 axis = 512 chips. Coded gradient workers live on the
    (pod, data) axes; tensor parallelism on the model axis."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_test_mesh(shape=(1, 1), axes=("data", "model")):
    """Tiny mesh over whatever devices exist (CPU tests)."""
    return _make_mesh(shape, axes)


def make_device_mesh():
    """(data, model) mesh over every device the process sees: model
    parallelism 2 on an even count n > 1, so (n/2, 2); else (n, 1)."""
    n_dev = len(jax.devices())
    model_par = 2 if n_dev % 2 == 0 and n_dev > 1 else 1
    return make_test_mesh((n_dev // model_par, model_par))


def num_coded_workers(mesh) -> int:
    m = mesh.shape["data"]
    if "pod" in mesh.axis_names:
        m *= mesh.shape["pod"]
    return m
