"""Per-layer readers of a ``DecodeServer`` scheduler turn, from the
program's own spans (``paddle_tpu/serving/decode.py``; the harness
records them over the traced stretch beside the profiler).

A turn is one ``serving/decode_tick`` span; its phases are leaf spans
whose ``parent`` is its id (``serving/decode/admit_plan``,
``/admit_dispatch``, ``/prefill``, ``/dispatch``, ``/wait``, ``/copy``,
``/deliver``).  Each metric is the seconds of its leaves under the
ticks recorded, over the number of those ticks, in milliseconds: the
phases of a turn add up to ``tick_ms.*`` less what the window outside
the traced stretch differs by.  A leaf whose tick was not recorded (the
stretch ended inside the turn) is left out.  None where the stretch
holds no tick, or no leaf at all: a program that records none, as
before this file.

The traced stretch.  The harness stops recording spans only when
``jax.profiler.stop_trace`` has returned, and on the chip that takes
1.5 minutes with the traffic still on (read on the chip, PR 36: 862
ticks recorded in chat and 397 in offline where the profile holds 512
and 66), the main thread busy writing the profile.  So where there is
a profile the ticks read are those that start within its
``window_s`` of the first one recorded: the turns the profile saw, the
ones ``breakdown.idle_gaps`` names (every tick where the profile holds
no device op: a rehearsal on the CPU).
"""
from __future__ import annotations

TICK = "serving/decode_tick"
LEAF = "serving/decode/"


def ticks_read(trace, spans):
    """The ids of the ticks of the traced stretch (module docstring)."""
    ticks = [s for s in spans if s["name"] == TICK]
    if ticks and trace is not None and trace.window_s:
        end = min(s["ts"] for s in ticks) + trace.window_s
        ticks = [s for s in ticks if s["ts"] < end]
    return {s["id"] for s in ticks}


def _leaves(trace, spans, phases):
    """(the leaves of ``phases`` under the ticks of the traced stretch,
    the number of those ticks); (None, 0) where there is nothing to
    read."""
    ticks = ticks_read(trace, spans)
    leaves = [s for s in spans if s["name"].startswith(LEAF)
              and s.get("parent") in ticks]
    if not ticks or not leaves:
        return None, 0
    names = {LEAF + p for p in phases}
    return [s for s in leaves if s["name"] in names], len(ticks)


def _per_turn_ms(trace, spans, phases):
    leaves, ticks = _leaves(trace, spans, phases)
    if leaves is None:
        return None
    return 1e3 * sum(s["dur"] for s in leaves) / ticks


def turn_admit_ms(trace, spans, counters, cell):
    """Popping, expiring and looking up the turn's requests, then
    seating them: the resize, every ``admit_prefix``, the one ``admit``."""
    return _per_turn_ms(trace, spans, ("admit_plan", "admit_dispatch"))


def turn_dispatch_ms(trace, spans, counters, cell):
    """The host's calls that hand the device its work: the ``chunk``
    (or speculative) dispatch and, where the builder has one, the
    turn's ``prefill`` dispatch with its snapshot."""
    return _per_turn_ms(trace, spans, ("dispatch", "prefill"))


def turn_copy_ms(trace, spans, counters, cell):
    """``device_get`` of the five view arrays, from the instant the
    chunk's outputs were ready."""
    return _per_turn_ms(trace, spans, ("copy",))


def turn_deliver_ms(trace, spans, counters, cell):
    """The position counters, the per-slot loop that streams and
    completes, the release, the gauges."""
    return _per_turn_ms(trace, spans, ("deliver",))


def turn_off_cpu_ms(trace, spans, counters, cell):
    """Of the two phases that are Python alone, the time the scheduler
    thread was not on a CPU: wall less ``cpu_s`` (``thread_time``), the
    interpreter lock held by submitter threads or the machine.  Summed
    over the stretch BEFORE the subtraction: the thread clock of the
    chip's host ticks in hundredths of a second (read on the chip,
    PR 36), so one span's ``cpu_s`` is 0 or 0.01 and only the sums
    compare; floored at zero."""
    leaves, ticks = _leaves(trace, spans, ("admit_plan", "deliver"))
    if leaves is None:
        return None
    timed = [s for s in leaves if "cpu_s" in s.get("args", {})]
    return 1e3 * max(0.0, sum(s["dur"] - s["args"]["cpu_s"]
                              for s in timed)) / ticks
