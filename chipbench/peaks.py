"""Published peaks of each accelerator the benchmark may run on, keyed by
the ``device_kind`` JAX reports.  A device not in the table is an error.

TPU v5e (JAX reports "TPU v5 lite"): Google Cloud documentation, "TPU
v5e" (cloud.google.com/tpu/docs/v5e): 197 TFLOP/s bf16, 393 TOP/s int8,
16 GB HBM at 819 GB/s.  No float32 peak is published for the chip; FLOP
shares divide by the bf16 peak.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"flops_bf16": 197e12, "hbm_bytes_per_s": 819e9,
                    "hbm_bytes": 16e9,
                    "source": "cloud.google.com/tpu/docs/v5e"},
}


def peaks_of(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to chipbench/peaks.py"
                       ) from None
