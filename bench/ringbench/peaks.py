"""Published peaks of each accelerator, keyed by JAX's ``device_kind``.

TPU v5e ("TPU v5 lite" to JAX), one chip: 16 GB of HBM at 819 GB/s
(Google Cloud documentation, "TPU v5e"; also 197 TFLOP/s bf16 and
393 TOP/s int8, which no metric here uses).  A kind that is not here is
an error: a roofline share needs its peak.
"""
from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"hbm_bytes_per_s": 819e9},
}


def of(device_kind: str) -> dict:
    if device_kind not in PEAKS:
        raise KeyError(f"no published peaks for device kind "
                       f"{device_kind!r}; add them to bench/ringbench/"
                       f"peaks.py with their source")
    return PEAKS[device_kind]
