"""Published per-chip peaks, keyed by jax ``device_kind`` — the one table.

Every utilization the repo reports (the benches' MFU, the training
ledger's ``train_mfu_ratio``) divides by a number from here.  A device
that is not in the table is an error, never a default: a utilization
against an assumed peak is not a measurement.  A CPU has no entry and
therefore no MFU.

Add a row only for a chip the code has run on, with the ``device_kind``
string as ``jax.devices()[0].device_kind`` printed it there.
"""
from __future__ import annotations

__all__ = ["PEAKS", "peak_flops"]

#: device_kind -> per-chip peaks.  ``bf16_flops`` in FLOP/s,
#: ``hbm_bytes_per_s`` in bytes/s, ``hbm_bytes`` in bytes.
PEAKS = {
    # Google Cloud documentation, "TPU v5e": 197 TFLOP/s bf16, 16 GB of
    # HBM2e at 819 GB/s per chip
    "TPU v5 lite": {
        "bf16_flops": 197e12,
        "hbm_bytes": 16e9,
        "hbm_bytes_per_s": 819e9,
    },
}


def peak_flops(device) -> float:
    """bf16 peak FLOP/s of one chip of ``device``'s kind; raises for a
    CPU (no MFU is defined there) and for any kind not in the table."""
    kind = getattr(device, "device_kind", None)
    row = PEAKS.get(kind)
    if row is None:
        raise ValueError(
            "no published peak for device_kind %r (platform %r): a "
            "utilization needs a row in paddle_tpu/device_peaks.py — "
            "known kinds: %s"
            % (kind, getattr(device, "platform", None), sorted(PEAKS)))
    return row["bf16_flops"]
