"""Published per-chip peaks, keyed by jax ``device_kind``: the
benchmark's own copy of the yardstick (``paddle_tpu/device_peaks.py`` is
the program's; a PR may change the program and may not change this).

A device that is not in the table is an error, never a default.
"""
from __future__ import annotations

#: device_kind -> peaks of ONE chip.  Source: Google Cloud documentation,
#: "TPU v5e" system architecture page: 197 TFLOP/s bf16, 393 TOP/s int8,
#: 16 GB of HBM2e at 819 GB/s, per chip.
PEAKS = {
    "TPU v5 lite": {
        "bf16_flops_per_s": 197e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
    },
}


def peaks_for(device_kind: str) -> dict:
    row = PEAKS.get(device_kind)
    if row is None:
        raise ValueError(
            "no published peak for device_kind %r: add a row with its "
            "source to benchmark/lib/peaks.py (known: %s)"
            % (device_kind, sorted(PEAKS)))
    return row
