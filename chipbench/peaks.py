"""Peak rates of one chip, keyed by ``device_kind``. A device that is not in
the table is an error, never a default."""
from __future__ import annotations

PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB HBM at
    # 819 GB/s. The chip reports itself as "TPU v5 lite" (PERF.md, PR 24).
    "TPU v5 lite": {"flops_per_s": 197e12, "hbm_bytes_per_s": 819e9,
                    "source": "Google Cloud documentation, TPU v5e"},
}


def peak(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise SystemExit(f"chipbench: no peak rates known for device kind "
                         f"{device_kind!r}; add a row to chipbench/peaks.py "
                         f"with its source") from None
