"""Readers of the per-layer metrics the ``solar_open2_250b`` cell adds
(``read(trace, spans, counters, cell)``, as ``lib/readers``): each
returns None where the program, the trace or the counters hold nothing
to read — a program without Kimi Delta Attention, as every commit before
PR 56 is.

The device trace names an operation by its HLO text (shapes; not
``jax.named_scope`` names), so the making of the per-channel decay and
of the two sigmoid gates is found by the program's scope
(``delta_channel_gates``) where a trace does carry it, else by the
shapes only ITS tensors have: the two low-rank pairs' matrices
``[d_model, rank]`` and ``[rank, H dk]``, their ``[slots, rank]``
intermediate and the step gate's ``[d_model, H]`` — the family lists
them in ``counters`` from the program's own sizes.  What the compiler
fuses into those products (the softplus, the exponentials, the
sigmoids) is counted with them; what it fuses into the kernel's operand
layout is not.
"""
from __future__ import annotations

from benchmark.lib.readers_sparse_linear import _share


def channel_gate_time_share(trace, spans, counters, cell):
    """Share of the device's busy time in making the per-channel decay,
    the step gate and the sigmoid output gate of the K layers."""
    return _share(trace, counters, "channel_gate_scopes",
                  "channel_gate_shapes")


def expert_rows_per_held(trace, spans, counters, cell):
    """(row, choice) pairs routed to a held expert over held experts x
    layer-steps, from the program's counters over the window: the rows a
    held expert's group holds in a step (what ``grouped_matmul``'s
    padding of a group to a whole row tile is read against)."""
    pairs, steps = counters.get("expert_assignments"), counters.get(
        "expert_layer_steps")
    if not pairs or not steps or not counters.get("num_experts"):
        return None
    return pairs / (counters["num_experts"] * steps)
