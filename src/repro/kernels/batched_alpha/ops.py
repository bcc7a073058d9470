"""Public wrapper for the fused batched-alpha error reduction.

Computes the debias scale (paper's alpha-bar normalisation) and the
per-trial normalized decoding errors in one call. Backend dispatch as in
the other kernels: the Pallas kernel on TPU, the float64 NumPy oracle on
CPU (which keeps ``monte_carlo_error`` bit-identical to the historical
per-trial path).
"""

from __future__ import annotations

from typing import Tuple

import numpy as np

from . import ref

_FORCE = None  # None | "ref" | "pallas"


def debias_scale(alphas: np.ndarray) -> float:
    """The paper's alpha-bar normalisation: |1|_2 / |E[alpha]|_2 =
    sqrt(n)/max(|mean|_2, tiny). Single source of truth, also used by
    ``decoding.debias_alpha``."""
    mean = alphas.mean(axis=0)
    return float(np.sqrt(alphas.shape[1]) /
                 max(np.linalg.norm(mean), 1e-30))


def fused_error(alphas, *, debias: bool = True) -> Tuple[np.ndarray, float]:
    """alphas: (trials, n) -> (errs (trials,), scale).

    scale is ``debias_scale`` when debias else 1.0;
    errs_t = (1/n)|scale * alpha_t - 1|^2.
    """
    a = np.asarray(alphas, dtype=np.float64)
    if a.ndim != 2:
        raise ValueError(f"alphas must be (trials, n), got {a.shape}")
    if a.shape[0] == 0:
        return np.zeros((0,), dtype=np.float64), 1.0
    scale = debias_scale(a) if debias else 1.0
    if _FORCE == "ref":
        return ref.fused_error(a, scale), scale
    import jax

    on_tpu = jax.default_backend() == "tpu"
    use_pallas = _FORCE == "pallas" or on_tpu
    interpret = not on_tpu
    if use_pallas:
        import jax.numpy as jnp

        from . import kernel

        errs = kernel.fused_error(jnp.asarray(a, jnp.float32),
                                  jnp.float32(scale), interpret=interpret)
        return np.asarray(errs, np.float64), scale
    return ref.fused_error(a, scale), scale
