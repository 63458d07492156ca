"""The reduction from the profiler's ``.xplane.pb`` to numbers: device
busy and idle time, time per operation and per module, and what the
host was doing during the device's idle gaps.

Read with ``jax.profiler.ProfileData`` and nothing else.  What a TPU
trace of this installation holds (looked at by hand, PR 23; jax 0.9.0,
libtpu 0.0.34, one ``TPU v5 lite``):

* one plane per chip, ``/device:TPU:<n>``.  Its line ``XLA Ops`` has one
  event per executed HLO instruction whose NAME is the instruction's
  text (``%fusion.2098 = f32[320,12,512]{...} fusion(...)``); the line
  nests: a ``%while`` event spans the events of its body.  ``XLA
  Modules`` has one event per executed program (``jit_chunk(<id>)``);
  ``Async XLA Ops`` holds the start/done pairs of copies and slices,
  which overlap compute and are not counted as busy;
* ``/host:CPU`` with one line per host thread.  The Python threads'
  lines (named after the process, ``python3``) hold jax's own TraceMe
  events (``PjitFunction(jit(admit))``, ``DevicePut``,
  ``np.asarray(jax.Array)``) and this benchmark's ``bench/...``
  annotations.  Host and device events share one clock.

Busy time is the UNION of the ``XLA Ops`` intervals of a chip, averaged
over the chips that ran anything; the window runs from the first to the
last device event; the idle share is 1 - busy / window.  An operation's
seconds are its SELF time: its events' durations less the events nested
inside them, so a ``while`` does not count its body twice.
"""
from __future__ import annotations

import collections
import re
import statistics

DEVICE_PLANE = re.compile(r"^/device:TPU:\d+$")
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
HOST_PLANE = "/host:CPU"
SPAN_PREFIX = "bench/"
MIN_GAP_S = 50e-6  # shorter pauses between ops are launch latency, not idle

_SHAPE = re.compile(r"=\s*\(*\s*([a-z]+[0-9]*)\[([0-9,]*)\]")


def op_label(name: str):
    """``%multiply_reduce_fusion.12 = f32[320,12,512]{...} fusion(...)``
    -> (``multiply_reduce_fusion_f32_320_12_512_``, (320, 12, 512)): the
    kind (the instruction's name without its number), the dtype and the
    shape of its first output, so the label survives a renumbering."""
    kind = re.sub(r"[.\d]+$", "", name.lstrip("%").split(" ")[0])
    m = _SHAPE.search(name)
    if not m:
        return kind, None
    dims = tuple(int(d) for d in m.group(2).split(",") if d)
    return "%s_%s_%s_" % (kind, m.group(1),
                          "_".join(str(d) for d in dims)), dims


def union_seconds(intervals):
    """Total length of the union of [start, end) intervals, in the
    intervals' unit, and the merged intervals themselves."""
    merged = []
    for a, b in sorted(intervals):
        if merged and a <= merged[-1][1]:
            if b > merged[-1][1]:
                merged[-1][1] = b
        else:
            merged.append([a, b])
    return sum(b - a for a, b in merged), merged


class TraceSummary:
    def __init__(self):
        self.window_s = 0.0
        self.busy_s = 0.0
        self.chips = 0
        self.ops = {}      # label -> [self seconds, count]
        self.instructions = {}  # instruction text -> self seconds
        self.modules = {}  # program name -> [each run's seconds]
        self.gaps = {}     # what the host was doing -> idle seconds
        self.op_events = 0

    @property
    def idle_share(self):
        return 1.0 - self.busy_s / self.window_s if self.window_s else None

    def top_ops(self, n):
        return [[k, v[0]] for k, v in sorted(
            self.ops.items(), key=lambda kv: -kv[1][0])[:n]]

    def top_modules(self, n):
        return [[k, sum(v), len(v)] for k, v in sorted(
            self.modules.items(), key=lambda kv: -sum(kv[1]))[:n]]

    def top_gaps(self, n):
        return [[k, v] for k, v in sorted(
            self.gaps.items(), key=lambda kv: -kv[1])[:n]]

    def seconds_of_instructions(self, pred) -> float:
        """Self seconds of the instructions whose whole text (output
        AND operand shapes) satisfies ``pred``, summed over chips."""
        return sum(v for k, v in self.instructions.items() if pred(k))

    def main_module(self):
        """(name, seconds of one whole run, runs) of the program that
        took most device time.  The seconds are the MEDIAN over its
        runs: the trace starts and stops mid-run, so the first and last
        runs are cut short."""
        if not self.modules:
            return None
        name, runs = max(self.modules.items(), key=lambda kv: sum(kv[1]))
        return name, statistics.median(runs), len(runs)


def self_seconds(events):
    """``events``: (start, end, key) of ONE line, where an event may
    nest inside another.  Yields (key, seconds) with each event's nested
    events taken off its own duration."""
    stack = []  # [end, key, self_ns]
    for a, b, key in sorted(events, key=lambda e: (e[0], -e[1])):
        while stack and stack[-1][0] <= a:
            end, k, own = stack.pop()
            yield k, own * 1e-9
        if stack:
            stack[-1][2] -= min(b, stack[-1][0]) - a
        stack.append([b, key, b - a])
    while stack:
        end, k, own = stack.pop()
        yield k, own * 1e-9


def reduce(path: str) -> TraceSummary:
    from jax.profiler import ProfileData

    data = ProfileData.from_file(path)
    out = TraceSummary()
    bench_spans, host_events = [], []  # (start_ns, end_ns, name)
    per_chip = []  # (busy_ns, first_ns, last_ns, merged intervals)
    for plane in data.planes:
        if plane.name == HOST_PLANE:
            for line in plane.lines:
                if not line.name.startswith("python"):
                    continue  # runtime threads: not what Python was doing
                for ev in line.events:
                    span = (ev.start_ns, ev.start_ns + ev.duration_ns,
                            ev.name)
                    (bench_spans if ev.name.startswith(SPAN_PREFIX)
                     else host_events).append(span)
            continue
        if not DEVICE_PLANE.match(plane.name):
            continue
        events = []
        for line in plane.lines:
            if line.name == MODULES_LINE:
                for ev in line.events:
                    name = re.sub(r"\(\d+\)$", "", ev.name)
                    out.modules.setdefault(name, []).append(
                        ev.duration_ns * 1e-9)
            elif line.name == OPS_LINE:
                for ev in line.events:
                    events.append((ev.start_ns,
                                   ev.start_ns + ev.duration_ns, ev.name))
        if not events:
            continue
        out.op_events += len(events)
        labels = {}
        for name, secs in self_seconds(events):
            if name not in labels:
                labels[name] = op_label(name)
            row = out.ops.setdefault(labels[name][0], [0.0, 0])
            row[0] += secs
            row[1] += 1
            out.instructions[name] = out.instructions.get(name, 0.0) + secs
        busy, merged = union_seconds([(a, b) for a, b, _ in events])
        per_chip.append((busy, merged[0][0], merged[-1][1], merged))
    if not per_chip:
        return out
    out.chips = len(per_chip)
    first = min(c[1] for c in per_chip)
    last = max(c[2] for c in per_chip)
    out.window_s = (last - first) * 1e-9
    out.busy_s = sum(c[0] for c in per_chip) * 1e-9 / len(per_chip)
    gaps = collections.Counter()
    merged = per_chip[0][3]  # gaps are named on the first chip
    for (_, a1), (b0, _) in zip(merged, merged[1:]):
        if (b0 - a1) * 1e-9 >= MIN_GAP_S:
            gaps[host_activity(bench_spans, host_events, a1, b0)] += (
                b0 - a1) * 1e-9
    out.gaps = dict(gaps)
    return out


def host_activity(bench_spans, host_events, g0, g1) -> str:
    """What the host's Python threads were doing in the device's idle
    gap [g0, g1): the span that covers most of it, among the
    benchmark's own ``bench/`` annotations and jax's own host events
    (``host:PjitFunction(jit(admit))``, ``host:np.asarray(jax.Array)``).
    The innermost (shortest) wins a tie; a gap of which no span covers
    a third is ``no_span``: plain Python between the runtime's calls."""
    best, best_cover, best_len = "no_span", (g1 - g0) / 3.0, None
    for spans, prefix in ((bench_spans, ""), (host_events, "host:")):
        for s0, s1, name in spans:
            cover = min(g1, s1) - max(g0, s0)
            if cover > best_cover or (cover == best_cover and best_len
                                      is not None and s1 - s0 < best_len):
                best, best_cover, best_len = prefix + name, cover, s1 - s0
    return best
