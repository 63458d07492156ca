"""Where jax's persistent compilation cache lives — the one definition.

The contract (tests/test_aux_subsystems.py pins it in a subprocess):

* ``JAX_COMPILATION_CACHE_DIR`` set in the environment: jax itself keeps
  its cache there (it reads the variable at import) and this module
  sets nothing — not through ``jax.config`` either.  That is how a
  caller outside the program places the cache, e.g. on a disk that
  survives the machine.
* not set: the cache goes to ``<checkout>/.jax_cache`` (gitignored).
  The path holds no hostname, CPU hash, pid, time or temp name — the
  directory is part of how a later process finds the entries, so a
  directory that moves never hits.  The variable is also exported so
  child processes that import jax fresh land in the same place.

Call :func:`configure` before the first compile of the process: jax
decides once, at its first compile, whether the cache is in use.

The build record (PR 52).  This module is also where a process says
what building its executables cost: one counter of seconds,
``program_build_seconds_total{program, stage}``, fed at the call sites
where the work happens, on top of ``monitor.REGISTRY`` and
``monitor.spans`` — no second recorder.

* :class:`build` brackets ONE executable's build (``program`` is a small
  closed set, :data:`PROGRAMS`); while a span sink is live it is the
  ``build/<program>`` span, opened ``annotate=False`` because it
  encloses its stages, and what the site learns (the rung pair, the
  jaxpr's equations, the hoisted constants, the kernels walked) goes on
  that span's args, never into labels.
* :func:`build_stage` times one stage of it by the wall
  (:data:`STAGES`: ``trace``, ``lower``, ``compile`` or ``cache_load``,
  ``place``, ``first_run``), adds the seconds to the counter and, only
  while a sink is live, opens ``build/<stage>`` through
  ``spans.open_span`` — so the stage is also on the ``/host:CPU`` line
  of a profile taken across a start-up.  For COLD paths only: it costs
  two clock reads, a lock and a generator.
* one process-wide jax monitoring listener, registered when this module
  is imported, tells a persistent-cache hit from a miss
  (``program_builds_total{program, cache}``) and books the
  ``backend_compile_duration`` of every executable built OUTSIDE a
  ``build_stage``: to the open ``build``'s program (the Executor's
  first dispatch, the builder's weight copies), else to
  ``program="unscoped"`` (a family's jitted random draw).  Inside a
  ``build_stage`` the stage's own wall covers those seconds and the
  listener books none: each second is booked once.  :func:`bookings`
  keeps the counter's last bookings with the instant each ended, for a
  reader that must cut at a moment (the benchmark's set-up ends where
  its window opens; a family's check builds more after it).  jax's other
  duration events are not summed: ``jaxpr_trace_duration`` fires once a
  NESTED traced function, the outer containing the inner.
"""
from __future__ import annotations

import collections
import contextlib
import os
import threading
import time
from typing import Deque, Dict, List, Optional, Tuple

from paddle_tpu.monitor import spans as _spans
from paddle_tpu.monitor.registry import REGISTRY

__all__ = ["configure", "CHECKOUT_CACHE_DIR", "build", "build_stage",
           "bookings", "thread_build_seconds", "equations", "PROGRAMS",
           "STAGES"]

_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the default location: ``.jax_cache`` beside the ``paddle_tpu`` package
CHECKOUT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
    ".jax_cache")


def configure() -> Optional[str]:
    """Apply the contract above; returns the directory in use (None
    when the environment disabled the cache with an empty value)."""
    if _ENV in os.environ:
        return os.environ[_ENV] or None
    import jax

    os.environ[_ENV] = CHECKOUT_CACHE_DIR
    jax.config.update("jax_compilation_cache_dir", CHECKOUT_CACHE_DIR)
    return CHECKOUT_CACHE_DIR


# ---------------------------------------------------------------------------
# the build record
# ---------------------------------------------------------------------------
#: what is built: the slot pool's executable kinds, the Executor's step
#: program, the decode builder's weight copies, and whatever jax
#: compiles outside every site
PROGRAMS = ("chunk", "spec_chunk", "admit", "seat_prefill", "prefill",
            "admit_prefix", "snapshot", "release", "executor_step",
            "weight_copies", "unscoped")
STAGES = ("trace", "lower", "compile", "cache_load", "place", "first_run")

BUILD_SECONDS = REGISTRY.counter(
    "program_build_seconds_total",
    "wall seconds this process spent building executables, by what was "
    "built (a slot-pool kind, executor_step, weight_copies, unscoped) and "
    "by stage: trace (jax walks the Python), lower (to an XLA module), "
    "compile (XLA builds it: a persistent-cache miss, or no cache), "
    "cache_load (a hit: what a warm process still pays), place (host-born "
    "constants or copies onto the device), first_run (the rest of a "
    "jitted call's first dispatch)", ("program", "stage"))
BUILDS = REGISTRY.counter(
    "program_builds_total",
    "executables built, by program and by what the persistent compile "
    "cache did for it: hit, miss, or off (jax did not consult it)",
    ("program", "cache"))

_BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"
_CACHE_ASKED = "/jax/compilation_cache/compile_requests_use_cache"
_CACHE_HIT = "/jax/compilation_cache/cache_hits"

# .build: this thread's innermost open ``build``; .stages: how many
# ``build_stage`` it is inside; .cache: what jax said of the cache since
# the last executable; .outcome: that, of the last executable built
# inside a stage; .seconds: every second this thread booked
_tls = threading.local()


#: the counter's bookings with the instant each ended, oldest first: a
#: counter cannot say WHEN, and a reader that wants set-up's share alone
#: (the benchmark's: a family's check builds programs after the window)
#: cuts here.  Bounded: a process that rebuilds for ever keeps the last.
_bookings: Deque[Tuple[float, str, str, float]] = collections.deque(
    maxlen=4096)


def bookings() -> List[Tuple[float, str, str, float]]:
    """``(perf_counter at its end, program, stage, seconds)`` of every
    booking into ``program_build_seconds_total`` (the last 4096)."""
    return list(_bookings)


def _book(program: str, stage: str, seconds: float) -> None:
    BUILD_SECONDS.labels(program=program, stage=stage).inc(seconds)
    _bookings.append((time.perf_counter(), program, stage, seconds))
    _tls.seconds = getattr(_tls, "seconds", 0.0) + seconds
    owner = getattr(_tls, "build", None)
    if owner is not None:
        owner.booked += seconds


def thread_build_seconds() -> float:
    """Every second the calling thread has booked so far: a caller that
    times a stretch of its own (the decode server's pool-state birth)
    subtracts what was built inside it."""
    return getattr(_tls, "seconds", 0.0)


def _on_event(event: str, **_) -> None:
    # jax asks the cache, then says if it hit; its ``cache_misses``
    # fires only where an entry is WRITTEN (not for a compile quicker
    # than the cache's threshold), so a miss is an ask with no hit
    if event == _CACHE_HIT:
        _tls.cache = "hit"
    elif event == _CACHE_ASKED:
        _tls.cache = "miss"


def _on_duration(event: str, seconds: float, **_) -> None:
    if event != _BACKEND_COMPILE:
        return
    cache, _tls.cache = getattr(_tls, "cache", None) or "off", None
    owner = getattr(_tls, "build", None)
    program = "unscoped" if owner is None else owner.program
    BUILDS.labels(program=program, cache=cache).inc()
    if getattr(_tls, "stages", 0):
        _tls.outcome = cache  # the stage's wall holds these seconds
        return
    stage = "cache_load" if cache == "hit" else "compile"
    _book(program, stage, seconds)
    # the event arrives as the compile ends: a span after the fact
    _spans.record_span("build/" + stage, time.perf_counter() - seconds,
                       seconds, cat="build", program=program)


def _listen() -> None:
    import jax

    jax.monitoring.register_event_listener(_on_event)
    jax.monitoring.register_event_duration_secs_listener(_on_duration)


_listen()


class build:
    """Brackets the build of ONE executable of ``program`` on the
    calling thread (a context manager).  While it is open the listener
    books what jax compiles outside a :func:`build_stage` to
    ``program``; with ``rest`` given, what is left of its wall when
    every booked second is taken off goes to that stage at the end (the
    Executor's first dispatch: ``first_run``).  ``traced`` says whether
    a span sink was live when it opened; ``args`` become the
    ``build/<program>`` span's."""

    def __init__(self, program: str, rest: Optional[str] = None, **args):
        self.program, self.rest, self.args = program, rest, args
        self.booked = 0.0

    def __enter__(self) -> "build":
        self._outer = getattr(_tls, "build", None)
        _tls.build = self
        self.traced = _spans.recording()
        self._span = (_spans.open_span("build/" + self.program, cat="build",
                                       annotate=False)
                      if self.traced else None)
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, exc_type, exc, tb) -> None:
        end = time.perf_counter()
        if self.rest is not None:
            _book(self.program, self.rest,
                  max(0.0, end - self._t0 - self.booked))
        _tls.build = self._outer
        if self._outer is not None:
            self._outer.booked += self.booked
        if self._span is not None:
            self._span.close(error=exc_type is not None, end=end,
                             booked_s=self.booked, **self.args)


@contextlib.contextmanager
def build_stage(program: str, stage: str, span: Optional[str] = None,
                **args):
    """Time one stage of a build by the wall and add it to
    ``program_build_seconds_total{program, stage}``; inside an open
    :class:`build` the build's program wins over ``program`` (a
    Program's block traced inside a pool executable's trace).  A
    ``compile`` stage whose executable came from the persistent cache
    is booked, and its span recorded, as ``cache_load`` (the profile's
    own line has the name it was opened under).  ``span`` renames the
    span (``lowering/trace_block``); ``args`` are the span's.  A
    ``trace`` stage inside a traced build also leaves on the build's
    span the kernels it walked: which ``*_lowered_total`` series moved,
    and by how much."""
    owner = getattr(_tls, "build", None)
    if owner is not None:
        program = owner.program
    sp = (_spans.open_span(span or "build/" + stage, cat="build")
          if _spans.recording() else None)
    # a stage inside a stage (a Program's block traced inside a pool
    # executable's trace) is inside the outer one's wall: span only
    nested = getattr(_tls, "stages", 0)
    walked = (_lowered() if stage == "trace" and not nested
              and owner is not None and owner.traced else None)
    _tls.stages = nested + 1
    _tls.outcome = None
    err = False
    t0 = time.perf_counter()
    try:
        yield
    except BaseException:
        err = True
        raise
    finally:
        end = time.perf_counter()
        _tls.stages -= 1
        if stage == "compile" and _tls.outcome == "hit":
            stage = "cache_load"
        if not nested:
            _book(program, stage, end - t0)
        if walked is not None:
            moved = {k: v - walked.get(k, 0) for k, v in _lowered().items()
                     if v != walked.get(k, 0)}
            if moved:
                owner.args["kernels"] = moved
        if sp is not None:
            if span is None:
                sp.name = "build/" + stage
            sp.close(error=err, end=end, program=program, **args)


def _lowered() -> Dict[str, int]:
    """``{"<counter>{<path>}": count}`` of every ``*_lowered_total``
    series: each kernel entry point counts the sites it lowered, by the
    path it took."""
    out = {}
    for name, metric in REGISTRY.snapshot().items():
        if name.endswith("_lowered_total"):
            for series in metric["series"]:
                out["%s{%s}" % (name[:-len("_total")], ",".join(
                    series["labels"].values()))] = int(series["value"])
    return out


def equations(jaxpr) -> int:
    """Equations of ``jaxpr`` and of every jaxpr its equations carry (a
    kernel's body, a loop's, a branch's): what a trace binds and a
    lowering walks."""
    n = 0
    for eqn in jaxpr.eqns:
        n += 1
        for param in eqn.params.values():
            for sub in (param if isinstance(param, (list, tuple))
                        else [param]):
                sub = getattr(sub, "jaxpr", sub)
                if hasattr(sub, "eqns"):
                    n += equations(sub)
    return n
