"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

A device that is not in the table is an error: a share of a peak is
never taken against a guessed or default peak.
"""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "int8_ops_per_s": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "ici_bits_per_s": 1600e9,
        "source": "Google Cloud documentation, TPU v5e",
    },
}


def peaks_for(device_kind: str) -> dict:
    """The peak table row for ``device_kind``; KeyError if unknown."""
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; known: {sorted(PEAKS)}") from None
