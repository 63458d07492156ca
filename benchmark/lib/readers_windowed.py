"""Readers of the per-layer metrics the ``smallthinker_21b_a3b`` cell
adds (``read(trace, spans, counters, cell)``, as ``lib/readers``): each
returns None where the program, the trace or the counters hold nothing
to read — a program without ring leaves, as every commit before PR 43
is.

The device trace names an operation by its HLO text (shapes; not
``jax.named_scope`` names), so a kind of layer's append-and-read is found
by the shapes only ITS tensors have: the ring leaf ``[slots, window,
d_kv]`` and the scores over it, the rung's leaf ``[slots, rung, d_kv]``
and the scores over that — the family lists both in ``counters`` from
the configuration's own sizes.
"""
from __future__ import annotations

from benchmark.lib.readers_sparse_linear import _roofline, _share


def window_attention_time_share(trace, spans, counters, cell):
    """Share of the device's busy time in the window layers' append and
    read (the ops that bear a ring leaf's or its scores' shape)."""
    return _share(trace, counters, "window_scopes", "window_shapes")


def global_attention_time_share(trace, spans, counters, cell):
    """Share of the device's busy time in the global layers' append and
    read (the ops that bear the rung's leaf's or its scores' shape)."""
    return _share(trace, counters, "global_scopes", "global_shapes")


def mixed_attention_roofline(trace, spans, counters, cell):
    """Least time the chip could take for both kinds of read of the
    steps traced (``costs_windowed.attention_min_bytes``: the positions
    a query may read, from the program's two position counters, once
    each, over the HBM bandwidth) over the device time the ops of both
    kinds took."""
    both = dict(counters, attention_shapes=[
        s for key in ("window_shapes", "global_shapes")
        for s in counters.get(key) or []])
    return _roofline(trace, both, cell, "attention_scopes",
                     "attention_shapes", "attention_min_bytes")


def window_read_share(trace, spans, counters, cell):
    """Positions the window layers' reads may read over the positions
    live for them, from the program's two counters: how much of a
    context the window leaves unread."""
    read, live = counters.get("window_positions_read"), counters.get(
        "window_positions_live")
    if not read or not live:
        return None
    return 100.0 * read / live


def kv_held_share(trace, spans, counters, cell):
    """Bytes the pool's sequence leaves hold over what they would hold
    at one length for every layer, from the program's two gauges."""
    held, whole = counters.get("kv_bytes_held"), counters.get(
        "kv_bytes_one_length")
    if not held or not whole:
        return None
    return 100.0 * held / whole
