#!/usr/bin/env python
"""Run one benchmark cell with ``--trace 1`` and keep what the harness
throws away: every traced instruction with its self seconds, so that a
per-layer reader's shape needles can be checked against what the chip
really ran, and the program's own spans over the traced stretch.

    chiprun -- python tools/bench_keep_trace.py --workload <cell> --seed <n>

Arguments are ``benchmark/run.py``'s.  The cell's normal output is
printed as always; ``chiprun_out/<cell>.instructions.json`` gets
``[[self seconds, instruction text], ...]``, longest first, and the
modules' run times; ``chiprun_out/<cell>.spans.json`` gets, per span
name, how many were recorded and their seconds (a ``DecodeServer``
turn's phases: seconds a ``serving/decode_tick``, the share of the
ticks their leaves cover, and ``turns_ahead_share``, the share of the
turns read whose ``dispatch`` queued a chunk behind the one it waited
for), every device gap's name with its seconds,
and the ``serving/...`` events found on the trace's own host lines;
``chiprun_out/<cell>.counters.json`` gets what the process counted, from
its start to its end, of which form a step's attention was lowered to
(``decode_attention_*_lowered_total{path}``) and of the K/V positions
its servers' steps read and found live
(``serving_decode_kv_positions_{read,live}_total`` as each server had
them when it stopped, and ``read_over_live``).
A development aid: the driver never runs it.
"""
import collections
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def spans_summary(spans, trace):
    """Counts and seconds per span name over everything recorded; for
    the turns of a ``DecodeServer`` that the profile saw (the ticks the
    ``turn_*_ms`` metrics read) also what each phase costs a tick and
    how much of the ticks the phases cover."""
    from benchmark.lib import readers_turn

    by = collections.defaultdict(lambda: [0, 0.0, 0.0])
    for s in spans:
        row = by[s["name"]]
        row[0] += 1
        row[1] += s["dur"]
        row[2] += s.get("args", {}).get("cpu_s", 0.0)
    read = readers_turn.ticks_read(trace, spans)
    ticks = {s["id"]: s["dur"] for s in spans if s["id"] in read}
    under = collections.Counter()
    for s in spans:
        if s.get("parent") in ticks:
            under[s["name"]] += s["dur"]
    n = max(len(ticks), 1)
    # a turn's ``dispatch`` says whether it queued a chunk behind the one
    # it waited for (PR 62; a checkout from before it says nothing)
    launched = [s["args"] for s in spans if s.get("parent") in ticks
                and s["name"] == "serving/decode/dispatch"
                and "ahead" in s.get("args", {})]
    return {
        "turns_ahead_share": (sum(a["ahead"] for a in launched)
                              / len(launched) if launched else None),
        "chunks_a_dispatch_leaf": dict(collections.Counter(
            str(a["chunks"]) for a in launched)),
        "by_name": {k: {"count": v[0], "seconds": v[1], "cpu_s": v[2]}
                    for k, v in sorted(by.items())},
        "ticks": len(ticks),
        "tick_ms": 1e3 * sum(ticks.values()) / n,
        "leaf_ms_a_tick": {k: 1e3 * v / n for k, v in sorted(under.items())},
        "leaves_cover": (sum(under.values()) / sum(ticks.values())
                         if ticks else None),
        "idle_waits_that_dropped": sum(
            1 for s in spans if s["name"] == "serving/decode/idle_wait"
            and s["args"]["dropped"]),
    }


def host_events(path):
    """The ``serving/...`` events on the Python threads' lines of the
    trace's host plane: {name: [count, seconds]}."""
    from jax.profiler import ProfileData

    from benchmark.lib import xplane

    out = collections.defaultdict(lambda: [0, 0.0])
    for plane in ProfileData.from_file(path).planes:
        if plane.name != xplane.HOST_PLANE:
            continue
        for line in plane.lines:
            if not line.name.startswith("python"):
                continue
            for ev in line.events:
                if ev.name.startswith("serving/"):
                    out[ev.name][0] += 1
                    out[ev.name][1] += ev.duration_ns * 1e-9
    return dict(out)


def keep_kv_counters(kept):
    """Have every ``DecodeServer`` add its K/V position counters to
    ``kept`` as it stops (it takes its series out of the registry
    there)."""
    from paddle_tpu.serving.decode import DecodeServer

    stop = DecodeServer.stop

    def stop_and_keep(self, *args, **kw):
        seen = self.metrics()["decode"]
        for key in ("kv_positions_read", "kv_positions_live"):
            kept[key] = kept.get(key, 0) + seen[key]
        return stop(self, *args, **kw)

    DecodeServer.stop = stop_and_keep


def counters(kept):
    """The lowering counters by path as the process has them now, and
    the K/V positions its servers counted (:func:`keep_kv_counters`)."""
    from paddle_tpu import monitor

    out = {"%s{path=%s}" % (name, path): monitor.counter_value(name, path=path)
           for name, paths in (
               ("decode_attention_grouped_lowered_total", ("kernel", "xla")),
               ("decode_attention_ungrouped_lowered_total", ("kernel", "xla")),
               ("decode_attention_latent_lowered_total",
                ("xla", "dense_kernel", "dense_xla")))
           for path in paths}
    read, live = (kept.get("kv_positions_" + kind, 0)
                  for kind in ("read", "live"))
    return dict(out, kv_positions_read=read, kv_positions_live=live,
                read_over_live=read / live if live else None)


def main():
    from benchmark import run as bench_run
    from benchmark.lib import harness, xplane

    cell = sys.argv[sys.argv.index("--workload") + 1]
    cleanup = harness.Tracer.cleanup

    def keep(self):
        path = self.xplane_path()
        if path is not None:
            trace = xplane.reduce(path)
            out = os.path.join(ROOT, "chiprun_out")
            os.makedirs(out, exist_ok=True)
            with open(os.path.join(out, cell + ".instructions.json"),
                      "w") as f:
                json.dump({
                    "busy_s": trace.busy_s, "window_s": trace.window_s,
                    "modules": trace.top_modules(10),
                    "instructions": sorted(
                        ([v, k[:600]] for k, v in
                         trace.instructions.items()), reverse=True)[:400]},
                    f, indent=0)
            with open(os.path.join(out, cell + ".spans.json"), "w") as f:
                json.dump(dict(spans_summary(self.spans, trace),
                               gaps=trace.top_gaps(40),
                               trace_host_events=host_events(path)),
                          f, indent=1)
        cleanup(self)

    harness.Tracer.cleanup = keep
    kept = {}
    keep_kv_counters(kept)
    if "--trace" not in sys.argv:
        sys.argv += ["--trace", "1"]
    try:
        return bench_run.main(sys.argv[1:])
    finally:
        out = os.path.join(ROOT, "chiprun_out")
        os.makedirs(out, exist_ok=True)
        with open(os.path.join(out, cell + ".counters.json"), "w") as f:
            json.dump(counters(kept), f, indent=1)


if __name__ == "__main__":
    sys.exit(main())
