"""Published peaks of each accelerator, keyed by ``device.device_kind``.

A device that is not in the table is an error, never a default: a share of
a peak that does not belong to the chip it ran on means nothing.
"""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "int8_ops": 393e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "Google Cloud, TPU v5e",
    },
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a row "
            f"with its source to benchmarks/chip/chipbench/peaks.py"
        ) from None
