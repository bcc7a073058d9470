"""Pure-jnp oracle for flash attention: the whole (S x S) softmax in
float32, grouped-query."""

import jax
import jax.numpy as jnp


def flash_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                    causal: bool = True) -> jnp.ndarray:
    """q: (B, S, H, Dh); k, v: (B, S, KVH, Dh). Returns (B, S, H, Dh)
    in q.dtype."""
    B, S, H, Dh = q.shape
    KVH = k.shape[2]
    hi = jax.lax.Precision.HIGHEST
    qf = q.astype(jnp.float32).reshape(B, S, KVH, H // KVH, Dh)
    s = jnp.einsum("bqhgd,bkhd->bhgqk", qf, k.astype(jnp.float32),
                   precision=hi) * Dh ** -0.5
    if causal:
        s = jnp.where(jnp.tril(jnp.ones((S, S), bool)), s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    out = jnp.einsum("bhgqk,bkhd->bqhgd", p, v.astype(jnp.float32),
                     precision=hi)
    return out.reshape(B, S, H, Dh).astype(q.dtype)
