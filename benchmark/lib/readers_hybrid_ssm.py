"""Readers of the per-layer metrics the ``falcon_h1_34b`` cells add
(``read(trace, spans, counters, cell)``, as ``lib/readers``): each
returns None where the program, the trace or the counters hold nothing
to read — a program without the ``ssm_state_update`` scope or the
recurrent-state gauge, as every commit before PR 27 is.
"""
from __future__ import annotations


def _update_seconds(trace, counters):
    """Device seconds of the SSM state update: the instructions under
    the program's ``ssm_state_update`` scope where the trace names it,
    else those that read or write a tensor of the pooled state's shape
    ``[slots, heads, d_head, d_state]`` (found anywhere in the
    instruction, outputs and operands, as ``attention_time_share.train``
    finds the scores)."""
    shape, scope = counters.get("ssm_state_shape"), counters.get(
        "ssm_update_scope")
    if trace is None or not trace.busy_s or not shape:
        return None
    needle = "[%s]" % ",".join(str(int(d)) for d in shape)
    secs = trace.seconds_of_instructions(
        lambda text: (scope and scope in text) or needle in text)
    return secs or None


def ssm_update_time_share(trace, spans, counters, cell):
    """Share of the device's busy time the state update takes."""
    secs = _update_seconds(trace, counters)
    if secs is None:
        return None
    return 100.0 * secs / trace.busy_s / max(trace.chips, 1)


def ssm_update_roofline(trace, spans, counters, cell):
    """Least time the chip could take for the state updates traced (each
    stepped row's state read and written once, plus the update's inputs,
    over the HBM bandwidth) over the device time they took.  The steps
    traced are the runs of the step program times its steps a run."""
    secs = _update_seconds(trace, counters)
    if secs is None or not cell.get("peaks") or not counters.get(
            "ssm_update_min_bytes"):
        return None
    main = trace.main_module()
    if main is None:
        return None
    _, one_run_s, _ = main
    steps = (sum(trace.modules[main[0]]) / one_run_s
             * counters["steps_per_dispatch"])
    least = (counters["ssm_update_min_bytes"] * steps
             / cell["peaks"]["hbm_bytes_per_s"])
    return 100.0 * least / secs


def recurrent_state_share(trace, spans, counters, cell):
    """Share of the pool's cache bytes held in recurrent leaves (no
    sequence axis), from the program's two gauges."""
    rec, kv = counters.get("recurrent_state_bytes"), counters.get(
        "kv_cache_bytes")
    if not rec or kv is None:
        return None
    return 100.0 * rec / (rec + kv)
