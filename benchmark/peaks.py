"""Published peaks of the devices the benchmark may run on, keyed by the
``device_kind`` jax reports. A device that is not here is an error."""

from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": one chip
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud documentation, TPU v5e (per chip)",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a row "
            "to benchmark/peaks.py with its source"
        ) from None
