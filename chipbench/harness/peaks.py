"""Published peaks per ``device_kind``.  A device that is not in the table
is an error, never a default."""
from __future__ import annotations

# source: Google Cloud documentation, "TPU v5e" (cloud.google.com/tpu/docs/v5e):
# 197 TFLOP/s bf16, 393 TOP/s int8, 16 GB HBM2e at 819 GB/s per chip.
PEAKS = {
    "TPU v5 lite": {"bf16_flops": 197e12, "int8_ops": 393e12,
                    "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                    "source": "Google Cloud documentation, TPU v5e"},
    "TPU v5e": {"bf16_flops": 197e12, "int8_ops": 393e12,
                "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9,
                "source": "Google Cloud documentation, TPU v5e"},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"no published peaks for device kind {device_kind!r}; add a "
            f"row with its source to chipbench/harness/peaks.py "
            f"(known: {sorted(PEAKS)})") from None
