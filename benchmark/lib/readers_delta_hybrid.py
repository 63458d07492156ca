"""Readers of the per-layer metrics the ``olmo_hybrid_7b`` cell adds
(``read(trace, spans, counters, cell)``, as ``lib/readers``): each
returns None where the program, the trace or the counters hold nothing
to read — a program without the gated delta-rule layers, as every commit
before PR 50 is.

The device trace names an operation by its HLO text (shapes; not
``jax.named_scope`` names), so a kind of layer's work is found by the
scope's name where a trace does carry it, else by the shapes only ITS
tensors have: the state leaf as the program declares it, the conv
window ``[slots, K - 1, 2 H dk + H dv]`` (and ``K`` rows long, with the
fresh row), a full layer's K/V leaf, its view by heads and the scores
over it — the family lists them in ``counters`` from the program's own
sizes.
"""
from __future__ import annotations

from benchmark.lib.readers_sparse_linear import _roofline, _share


def delta_state_time_share(trace, spans, counters, cell):
    """Share of the device's busy time in the delta rule: the decay, the
    read ``S^T k``, the write and the output ``S^T q`` (the ops that bear
    the state leaf's shape)."""
    return _share(trace, counters, "delta_state_scopes", "delta_state_shapes")


def delta_state_roofline(trace, spans, counters, cell):
    """Least time the chip could take for the rule of the steps traced
    (``costs_delta_hybrid.delta_update_min_bytes``: each stepped row's
    state read once and written once, plus q, k, v, the two gates and o,
    over the HBM bandwidth) over the device time it took."""
    return _roofline(trace, counters, cell, "delta_state_scopes",
                     "delta_state_shapes", "delta_state_min_bytes")


def short_conv_time_share(trace, spans, counters, cell):
    """Share of the device's busy time in the short convolution over
    ``[q; k; v]`` (the ops that bear the conv window's shape)."""
    return _share(trace, counters, "short_conv_scopes", "short_conv_shapes")


def full_attention_time_share(trace, spans, counters, cell):
    """Share of the device's busy time in the full layers' append and
    read (the ops that bear a K/V leaf's or its scores' shape)."""
    return _share(trace, counters, "full_attention_scopes",
                  "full_attention_shapes")


def full_attention_read_share(trace, spans, counters, cell):
    """K/V positions live in the steps run over the positions the form
    the full layers' read lowered to reads, from the program's two
    counters: 100% is a read of what is live, less is what reading the
    whole rung wastes."""
    read, live = counters.get("full_positions_read"), counters.get(
        "full_positions_live")
    if not read or not live:
        return None
    return 100.0 * live / read
