"""Readers of the per-layer metrics the ``openpangu_ultra_moe_718b`` cell
adds (``read(trace, spans, counters, cell)``, as ``lib/readers``): each
returns None where the program, the trace or the counters hold nothing
to read — a program without a dense latent read under a self-drafting
round, as every commit before PR 60 is.

The device trace names an operation by its HLO text (shapes; not
``jax.named_scope`` names), so the dense read's work is found by the
shapes only ITS tensors have — a key block of every slot's leaf, the two
fresh rows' heads' scores over it, their running context — and the
K-row append by the leaf's own shape on an instruction that bears none
of the read's (the walk's products take the leaf as an operand too).
The family lists both in ``counters`` from the pool's own sizes
(README_openpangu_ultra_moe_718b.md lists what each matched on the
chip).
"""
from __future__ import annotations

from benchmark.lib.readers_sparse_linear import _needle, _steps_traced


def _needles(counters, key):
    return [_needle(s) for s in counters.get(key) or []]


def _read_seconds(trace, counters):
    """Device seconds of the instructions that bear one of the dense
    read's shapes; None where there is nothing to look for or to read."""
    mine = _needles(counters, "dense_latent_shapes")
    if trace is None or not trace.busy_s or not mine:
        return None
    return trace.seconds_of_instructions(
        lambda text: any(n in text for n in mine)) or None


def dense_latent_roofline(trace, spans, counters, cell):
    """Least time the chip could take for the dense reads of the rounds
    traced — the GREATER of their arithmetic over the bf16 peak
    (``costs_latent_mtp.dense_read_flops``: every head of every fresh row
    over every position it may read) and their bytes over the HBM
    bandwidth (``dense_read_min_bytes``: every live row once a round and
    leaf, and the appends) — over the device time the read's
    instructions took: the same count whatever implements the read."""
    secs = _read_seconds(trace, counters)
    peaks = cell.get("peaks")
    if (secs is None or not peaks or not counters.get("dense_latent_flops")
            or not counters.get("dense_latent_min_bytes")):
        return None
    rounds = _steps_traced(trace, counters)
    if not rounds:
        return None
    least = max(counters["dense_latent_flops"] / peaks["bf16_flops_per_s"],
                counters["dense_latent_min_bytes"]
                / peaks["hbm_bytes_per_s"])
    return 100.0 * least * rounds / secs


def dense_latent_live_share(trace, spans, counters, cell):
    """Positions a round's reads had to read (live, at each slot's last
    fresh row, from the program's counters) over the positions the
    read's form touched (the program's own rule,
    ``decode_attention.dense_latent_positions_touched``: whole key blocks
    up to the pool's longest context, for every slot and leaf)."""
    live = counters.get("dense_latent_positions_live")
    touched = counters.get("dense_latent_positions_touched")
    if not live or not touched:
        return None
    return 100.0 * live / touched


def latent_append_time_share(trace, spans, counters, cell):
    """Share of the device's busy time in the K-row appends into the
    latent leaves: the instructions that bear a leaf's shape and none of
    the read's (a leaf re-laid for the read or the append would show
    here as rung-sized copies)."""
    leaf = _needles(counters, "latent_append_shapes")
    read = _needles(counters, "dense_latent_shapes")
    if trace is None or not trace.busy_s or not leaf:
        return None
    secs = trace.seconds_of_instructions(
        lambda text: any(n in text for n in leaf)
        and not any(n in text for n in read))
    if not secs:
        return None
    return 100.0 * secs / trace.busy_s / max(trace.chips, 1)
