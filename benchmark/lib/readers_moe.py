"""Readers of the per-layer metrics the ``lfm2_24b_a2b`` cell adds
(``read(trace, spans, counters, cell)``, as ``lib/readers``): each
returns None where the program, the trace or the counters hold nothing
to read — a program without routed experts, as every commit before PR 40
is.

The device trace names an operation by its HLO text (shapes, and a
Pallas kernel's name; not ``jax.named_scope`` names), so the experts'
product is found by the kernel's name where it runs and by the shapes
only expert tensors have (the stacked matrices, the gate-and-up rows),
and the routing by the shapes only it has (the router's scores, the
choices, the sorted pairs) — the family lists both in ``counters`` from
the configuration's own sizes.  An instruction that bears an expert
shape is the experts', whatever else it bears.
"""
from __future__ import annotations

from benchmark.lib.readers_sparse_linear import _needle, _steps_traced


def _finder(names, shapes):
    """A predicate over an instruction's text: it bears one of ``names``
    or one of ``shapes``; None where there is nothing to look for."""
    needles = list(names or []) + [_needle(s) for s in shapes or []]
    if not needles:
        return None
    return lambda text: any(n in text for n in needles)


def _expert_finder(counters):
    return _finder(counters.get("expert_kernel_names"),
                   counters.get("expert_shapes"))


def _expert_seconds(trace, counters):
    find = _expert_finder(counters)
    if trace is None or not trace.busy_s or find is None:
        return None
    return trace.seconds_of_instructions(find) or None


def moe_experts_time_share(trace, spans, counters, cell):
    """Share of the device's busy time in the experts' grouped products
    (and the gate's activation between them)."""
    secs = _expert_seconds(trace, counters)
    if secs is None:
        return None
    return 100.0 * secs / trace.busy_s / max(trace.chips, 1)


def moe_experts_roofline(trace, spans, counters, cell):
    """Least time the chip could take for the experts' products of the
    steps traced (``costs_moe.experts_min_bytes``: the matrices of the
    experts the program's counter says were touched, once each, plus the
    rows in and out, over the HBM bandwidth) over the device time they
    took."""
    secs = _expert_seconds(trace, counters)
    if (secs is None or not cell.get("peaks")
            or not counters.get("experts_min_bytes")):
        return None
    steps = _steps_traced(trace, counters)
    if not steps:
        return None
    least = (counters["experts_min_bytes"] * steps
             / cell["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / secs


def moe_route_time_share(trace, spans, counters, cell):
    """Share of the device's busy time in routing: the router's product
    and sigmoid, the top-k, the sort by expert, the gather of the rows
    and the weighed sum back — everything of the expert layer that is
    not the experts' product."""
    route = _finder(None, counters.get("route_shapes"))
    if trace is None or not trace.busy_s or route is None:
        return None
    expert = _expert_finder(counters)
    secs = trace.seconds_of_instructions(
        lambda text: route(text) and not (expert and expert(text)))
    if not secs:
        return None
    return 100.0 * secs / trace.busy_s / max(trace.chips, 1)


def expert_peak_over_mean(trace, spans, counters, cell):
    """The largest group over the mean group, from the program's
    counters over the window: experts x peak_load / assignments (1.0 =
    even routing; the stream's cost does not depend on it, a kernel's
    padding does)."""
    pairs, peak = counters.get("expert_assignments"), counters.get(
        "expert_peak_load")
    if not pairs or not peak or not counters.get("num_experts"):
        return None
    return counters["num_experts"] * peak / pairs
