"""Public wrapper for the cross entropy's softmax gradient: where the
kernel is taken, and how it runs under a mesh.

``enabled()`` is the backend half of the model's dispatch (the kernel
on TPU, autodiff of the head's cross entropy elsewhere) and
``supports`` the shape half. ``_FORCE`` is a test hook: "pallas" takes
the kernel (interpret mode on CPU), "ref" keeps autodiff, None decides
by backend.

A Mosaic kernel is an opaque custom call that GSPMD cannot partition,
so under a multi-device mesh (``jax.set_mesh``) the kernel runs inside
``shard_map``: over the vocabulary on the ``model`` axis where that
axis splits it into widths of whole lanes, and over the rows on the
other axes where they divide the rows.
"""

from __future__ import annotations

import functools
import math

import jax
from jax.sharding import PartitionSpec as P

from . import kernel

_FORCE = None  # test hook: None | "ref" | "pallas"


def enabled() -> bool:
    if _FORCE is not None:
        return _FORCE == "pallas"
    return jax.default_backend() == "tpu"


def supports(logits_shape) -> bool:
    """Whether the kernel tiles logits of this shape: a vocabulary of
    whole 128-lane columns (any number of rows)."""
    return logits_shape[-1] % 128 == 0


def xent_grad(logits, lse, labels, scale, *, vocab_size: int):
    """logits (T, V) float32 as the forward left them, V a multiple of
    128; lse, labels, scale (T,). Returns the bfloat16 (T, V) gradient
    ``scale * (softmax - one-hot)``, 0 at padded ids ``>= vocab_size``
    and on rows whose label is negative."""
    run = functools.partial(kernel.xent_grad, vocab_size=vocab_size,
                            interpret=jax.default_backend() != "tpu")
    mesh = jax.sharding.get_abstract_mesh()
    if mesh.empty or mesh.size == 1:
        return run(logits, lse, labels, scale, 0)
    T, V = logits.shape
    split = mesh.shape.get("model", 1)
    vocab = "model" if split > 1 and V % (128 * split) == 0 else None
    row_axes = tuple(a for a in mesh.axis_names if a != vocab)
    n_rows = math.prod(mesh.shape[a] for a in row_axes)
    rows = row_axes if row_axes and T % n_rows == 0 else None

    def local(lg, ls, lb, sc):
        off = 0 if vocab is None else jax.lax.axis_index(vocab) * lg.shape[1]
        return run(lg, ls, lb, sc, off)

    vec = P(rows)
    return jax.shard_map(local, in_specs=(P(rows, vocab), vec, vec, vec),
                         out_specs=P(rows, vocab),
                         check_vma=False)(logits, lse, labels, scale)
