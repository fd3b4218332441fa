"""Published peaks of the chips the benchmark runs on, keyed by the
``device_kind`` JAX reports.  A kind that is not here is an error, never a
default: a share of the wrong chip's peak is not a number."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": per chip, bf16 and HBM
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise ValueError(f"no peaks for device kind {device_kind!r}; known: "
                         f"{sorted(PEAKS)}") from None
