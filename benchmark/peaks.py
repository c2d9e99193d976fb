"""Peaks of one chip, keyed by ``device_kind``. One table, with its source;
a kind that is not here is an error, never a default."""

from __future__ import annotations

# Google Cloud documentation, "TPU v5e" system architecture: 197 TFLOP/s
# bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
    "TPU v5e": {"bf16_flops": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks_for(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(
            f"no peaks for device kind {device_kind!r}: add it to benchmark/peaks.py "
            "with its source"
        ) from None
