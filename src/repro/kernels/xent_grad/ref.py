"""Pure-jnp oracle for the cross entropy's softmax gradient, in float32."""

import jax.numpy as jnp


def xent_grad(logits, lse, labels, scale, offset, vocab_size):
    """logits (T, V); lse, labels, scale (T,); offset, the global id of
    column 0. Returns (T, V) float32: s_t (softmax - one-hot), 0 at ids
    ``>= vocab_size`` and on rows whose label is negative."""
    col = offset + jnp.arange(logits.shape[1])
    p = jnp.exp(logits.astype(jnp.float32) - lse[:, None])
    d = (p - (col == labels[:, None])) * scale[:, None]
    keep = (col < vocab_size) & (labels[:, None] >= 0)
    return jnp.where(keep, d, 0.0)
