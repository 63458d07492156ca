"""What set-up was made of, from the program's own record (PR 52).

The program times its builds where they happen
(``paddle_tpu/compile_cache.py``: the counter
``program_build_seconds_total{program, stage}``, and ``bookings()``, the
same bookings each with the instant it ended) and a decode server the
birth of its pool state (``serving_pool_state_seconds_total{server,
stage}``).  These readers run in the benchmark's process after the
window, so they read the live program; no family hands them anything.

Set-up ends where the window opens, and a family builds more AFTER the
window (its check: the reference's jitted programs, BERT's test
program), so the build readers take the bookings that ended before the
window's first instant: ``run.py``'s first clock reading plus the run's
``setup_s``.  The window itself builds nothing (``window_compiles``).

A program with no build record reads None, not 0: a checkout from
before it leaves the metric out of its line and is not given a perfect
score.  A record nobody has fed reads 0 (a cold run loads nothing from
the cache).
"""
from __future__ import annotations

import sys

POOL_STATE_SECONDS = "serving_pool_state_seconds_total"
IMPORT_SECONDS = "paddle_tpu_import_seconds"


def _registry():
    from paddle_tpu import monitor

    return monitor.REGISTRY


def _bookings():
    """The program's ``(perf_counter at its end, program, stage,
    seconds)`` bookings, or None where it keeps none."""
    from paddle_tpu import compile_cache

    read = getattr(compile_cache, "bookings", None)
    return None if read is None else read()


def _window_open(cell):
    """``perf_counter`` at the window's first instant: ``setup_s`` after
    ``benchmark/run.py`` read its first clock (its module global, under
    the name it runs or was imported by); None where either is unknown."""
    setup_s = (cell.get("end_to_end") or {}).get("setup_s")
    for name in ("__main__", "benchmark.run"):
        t_start = getattr(sys.modules.get(name), "T_PROCESS_START", None)
        if t_start is not None and setup_s:
            return t_start + setup_s
    return None


def _built(cell, *stages):
    """Seconds booked to ``stages`` (all, if none is named) before the
    window opened; None where that cannot be known."""
    booked, cut = _bookings(), _window_open(cell)
    if booked is None or cut is None:
        return None
    return sum(s for t, _, stage, s in booked
               if t <= cut and (not stages or stage in stages))


def _total(name):
    """Sum of every series of ``name``; None where the program has no
    such metric."""
    registry = _registry()
    return None if registry.get(name) is None else registry.value(name, 0.0)


def setup_trace_lower_s(trace, spans, counters, cell):
    """Seconds jax spent walking the program's Python (``trace``) and
    turning the result into XLA modules (``lower``), every executable
    set-up built: what a process pays whether the compile cache hits or
    not."""
    return _built(cell, "trace", "lower")


def setup_cache_load_s(trace, spans, counters, cell):
    """Seconds set-up spent loading executables from the persistent
    compile cache: what a warm process still pays an executable (0 in a
    cold checkout, where the same executables are ``compile`` seconds)."""
    return _built(cell, "cache_load")


def setup_pool_state_s(trace, spans, counters, cell):
    """Seconds the decode servers' fresh pool states cost: the zeros on
    the host (``alloc``) and their way to the device (``place``).  The
    counter runs for the process's life: a server that dropped an idle
    pool INSIDE the window and made it again would add to this (no cell
    leaves a server idle for 0.5 s in its window)."""
    return _total(POOL_STATE_SECONDS)


def setup_accounted_share(trace, spans, counters, cell):
    """How much of ``setup_s`` the program can name: every stage of
    every build before the window, the pool states' birth and the
    package's own import, over the run's ``setup_s``.  The rest is the
    interpreter, jax and the device runtime coming up, the family's
    weights and data, and the ramp."""
    built = _built(cell)
    if built is None:
        return None
    named = built + (_total(POOL_STATE_SECONDS) or 0.0) + (
        _total(IMPORT_SECONDS) or 0.0)
    return 100.0 * named / cell["end_to_end"]["setup_s"]
