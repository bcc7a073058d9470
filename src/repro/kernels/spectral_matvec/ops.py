"""Public wrapper for the fused tall-skinny Gram matvec.

Backend dispatch as in the other kernel packages: the Pallas kernel on
TPU, the float64 NumPy oracle on CPU. Note the TPU path accumulates in
float32; the 1e-8-grade agreement of the matrix-free covariance norm
with the dense SVD is a property of the CPU/float64 path (callers that
enforce tolerances should branch on ``uses_pallas()``).
"""

from __future__ import annotations

import numpy as np

from . import ref

_FORCE = None  # None | "ref" | "pallas"


def _dispatch():
    """-> ('ref', False) or ('pallas', interpret)."""
    if _FORCE == "ref":
        return "ref", False
    import jax

    on_tpu = jax.default_backend() == "tpu"
    use_pallas = _FORCE == "pallas" or on_tpu
    interpret = not on_tpu
    return ("pallas", interpret) if use_pallas else ("ref", False)


def uses_pallas() -> bool:
    """True when gram_matvec will run the float32 Pallas kernel."""
    return _dispatch()[0] == "pallas"


def prepare_operand(x):
    """Stage the tall operand once for a run of gram_matvec calls
    (e.g. a Lanczos iteration): device float32 when the Pallas path is
    active -- avoiding a host upload per matvec -- float64 NumPy
    otherwise (a no-copy view for float64 input). Also stages stacked
    (B, R, k) operands for the batch/lockstep calls."""
    if uses_pallas():
        import jax.numpy as jnp

        return jnp.asarray(x, jnp.float32)
    return np.asarray(x, np.float64)


def gram_matvec(x, v) -> np.ndarray:
    """x: (R, k), v: (k,) -> x^T (x v) as float64 NumPy.

    ``x`` may be a NumPy array or an operand staged by
    ``prepare_operand`` (passed through without a host round-trip).
    """
    v = np.asarray(v)
    if getattr(x, "ndim", 0) != 2 or v.shape != (x.shape[1],):
        raise ValueError(f"need x (R, k) and v (k,), got "
                         f"{getattr(x, 'shape', None)} and {v.shape}")
    mode, interpret = _dispatch()
    if mode == "pallas":
        import jax.numpy as jnp

        from . import kernel

        out = kernel.gram_matvec(jnp.asarray(x, jnp.float32),
                                 jnp.asarray(v, jnp.float32),
                                 interpret=interpret)
        return np.asarray(out, np.float64)
    return ref.gram_matvec(x, v)


def gram_matvec_block(x, V) -> np.ndarray:
    """x: (R, k), V: (k, b) -> x^T (x V) as float64 NumPy -- the
    block-Lanczos form (b right-hand sides per pass over x)."""
    V = np.asarray(V)
    if getattr(x, "ndim", 0) != 2 or V.ndim != 2 or \
            V.shape[0] != x.shape[1]:
        raise ValueError(f"need x (R, k) and V (k, b), got "
                         f"{getattr(x, 'shape', None)} and {V.shape}")
    mode, interpret = _dispatch()
    if mode == "pallas":
        import jax.numpy as jnp

        from . import kernel

        out = kernel.gram_matvec(jnp.asarray(x, jnp.float32),
                                 jnp.asarray(V.T, jnp.float32),
                                 interpret=interpret)
        return np.asarray(out, np.float64).T
    return ref.gram_matvec_block(x, V)


def gram_matvec_batch(x, v) -> np.ndarray:
    """x: (B, R, k), v: (B, k) -> (B, k) per-slice x_b^T (x_b v_b) as
    float64 NumPy -- the lockstep-Lanczos batch form (one fused pass
    over the whole stack per iteration).

    ``x`` may be staged by ``prepare_operand`` (device-resident on the
    Pallas path, so only the small (B, k) vectors travel per call).
    """
    v = np.asarray(v)
    if getattr(x, "ndim", 0) != 3 or \
            v.shape != (x.shape[0], x.shape[2]):
        raise ValueError(f"need x (B, R, k) and v (B, k), got "
                         f"{getattr(x, 'shape', None)} and {v.shape}")
    mode, interpret = _dispatch()
    if mode == "pallas":
        import jax.numpy as jnp

        from . import kernel

        out = kernel.gram_matvec_batch(jnp.asarray(x, jnp.float32),
                                       jnp.asarray(v, jnp.float32),
                                       interpret=interpret)
        return np.asarray(out, np.float64)
    return ref.gram_matvec_batch(x, v)
