"""Shared arithmetic of the per-layer readers.  Each metric is still a
file of its own under ``benchmark/layer_metrics/`` (the harness finds it
by the metric's name); a family of metrics that differ only in the cells
they cover points at one function here.

Every reader is ``read(trace, spans, counters, cell)``: the trace
summary (``lib/xplane.TraceSummary`` or None), the program's host spans
over the traced stretch, the family's counters, and the cell's data.
A reader that finds nothing to read returns None and the metric is left
out of the line.
"""
from __future__ import annotations

import numpy as np


def _p(values, q):
    return float(np.percentile(values, q)) if len(values) else None


def gen_late_p95_ms(trace, spans, counters, cell):
    return _p(counters.get("gen_late_ms", []), 95)


def tick_ms(trace, spans, counters, cell):
    """Mean time from one scheduler tick to the next over the window."""
    ticks = counters.get("ticks")
    return counters["window_s"] * 1e3 / ticks if ticks else None


def prefill_step_share(trace, spans, counters, cell):
    """Share of the rows' steps that consumed a prompt token."""
    pre, gen = counters.get("prefill_tokens"), counters.get("generated_tokens")
    if pre is None or not (pre + gen):
        return None
    return 100.0 * pre / (pre + gen)


def tpot_p50_ms(trace, spans, counters, cell):
    return _p(counters.get("tpot_ms", []), 50)


def longest_stall_ms(trace, spans, counters, cell):
    stalls = counters.get("stall_ms", [])
    return float(max(stalls)) if len(stalls) else None


def peak_hbm_share(trace, spans, counters, cell):
    if not cell.get("peaks") or not counters.get("memory_peak_bytes"):
        return None
    return 100.0 * counters["memory_peak_bytes"] / cell["peaks"]["hbm_bytes"]


def host_ms_per_step(trace, spans, counters, cell):
    """Traced time in which the device ran nothing, per step traced:
    what the host adds on top of the device's own time.  The steps
    traced are the device's busy time over the time of one step (one
    whole run of the step program over its steps per dispatch)."""
    if trace is None or not trace.window_s:
        return None
    main = trace.main_module()
    if main is None:
        return None
    step_s = main[1] / counters["steps_per_dispatch"]
    return (trace.window_s - trace.busy_s) * 1e3 / (trace.busy_s / step_s)


def setup_compile_s(trace, spans, counters, cell):
    return counters.get("setup_compile_s")


def window_compiles(trace, spans, counters, cell):
    return counters.get("window_compiles")


def mfu(trace, spans, counters, cell):
    """Model FLOP/s utilization of the device while it runs the step
    program: FLOPs one step needs (no recomputation) x steps in one
    dispatch over the device time of one whole run of the program (the
    median of the traced runs), over the bf16 peak.  The host's time
    between dispatches is not in it (``host_ms_per_step.train`` and the
    idle share carry that); the rate over the host's window is in the
    log (``training``: ``mfu_host_window``)."""
    if trace is None or not cell.get("peaks"):
        return None
    main = trace.main_module()
    if main is None or not counters.get("flops_per_step"):
        return None
    _, one_run_s, _ = main
    rate = (counters["flops_per_step"] * counters["steps_per_dispatch"]
            / one_run_s)
    return 100.0 * rate / cell["peaks"]["bf16_flops_per_s"]


def attention_time_share(trace, spans, counters, cell):
    """Device time of the operations that read or write a score-shaped
    tensor [B, heads, S, S] (the score product, softmax and its
    reductions, the context product, their gradients), over busy.  XLA
    fuses the reductions so that many of these ops OUTPUT [B, heads, S];
    they are found by the score shape anywhere in the instruction."""
    want = counters.get("attention_score_shape")
    if trace is None or not trace.busy_s or not want:
        return None
    needle = "[%s]" % ",".join(str(int(d)) for d in want)
    secs = trace.seconds_of_instructions(lambda text: needle in text)
    return 100.0 * secs / trace.busy_s / max(trace.chips, 1)


def decode_step_roofline(trace, spans, counters, cell):
    """Least time the chip could take for the steps traced (bytes the
    step needs over the HBM bandwidth: the step is bandwidth-bound) over
    the device time the step program took."""
    if trace is None or not cell.get("peaks"):
        return None
    main = trace.main_module()
    if main is None or not counters.get("step_min_bytes"):
        return None
    _, one_run_s, _ = main
    least = counters["step_min_bytes"] / cell["peaks"]["hbm_bytes_per_s"]
    return 100.0 * least * counters["steps_per_dispatch"] / one_run_s


def device_idle_share(trace, spans, counters, cell):
    if trace is None or trace.idle_share is None:
        return None
    return 100.0 * trace.idle_share
