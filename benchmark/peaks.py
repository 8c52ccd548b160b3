"""Published peaks of the chips this benchmark may run on, keyed by the
``device_kind`` JAX reports.  A device that is not here is an error, never a
default: a share of a guessed peak is not a measurement."""

from __future__ import annotations

# Source: Google Cloud documentation, "TPU v5e" system architecture page:
# 197 TFLOP/s bf16, 16 GB of HBM2e at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes_per_s": 819e9,
        "hbm_bytes": 16e9,
        "source": "cloud.google.com/tpu/docs/v5e (TPU v5e, per chip)",
    },
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"benchmark: no peaks on record for device_kind "
            f"{device_kind!r}; add it to benchmark/peaks.py with its "
            "source") from None
