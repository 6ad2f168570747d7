"""Peaks of the chips the benchmark runs on, keyed by `Device.device_kind`. A kind
that is not here is an error, never a default. Source: Google Cloud documentation,
"TPU v5e" (197 TFLOP/s bf16, 16 GB HBM at 819 GB/s per chip). The program keeps a
table of its own (`utils/mfu.py`, `obs/xprof.py`); this copy is the yardstick's."""

from __future__ import annotations

PEAKS = {
    "TPU v5 lite": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
    "TPU v5e": {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9, "hbm_bytes": 16e9},
}


def peaks(device_kind: str) -> dict:
    try:
        return PEAKS[device_kind]
    except KeyError:
        raise KeyError(
            f"device kind {device_kind!r} is not in perfbench/harness/devices.py: add its "
            "published peaks with their source before measuring on it"
        ) from None
