"""The readers of set-up's make-up (``benchmark/lib/readers_setup.py``)
over a build record and a registry filled by hand."""
import os
import types

import pytest

from benchmark.lib import harness, readers_setup
from paddle_tpu.monitor import MetricsRegistry

READERS = ("setup_trace_lower_s", "setup_cache_load_s",
           "setup_pool_state_s", "setup_accounted_share")
T_START, SETUP_S = 1000.0, 50.0
INFO = {"end_to_end": {"setup_s": SETUP_S}}

#: what a warm serving cell leaves behind: two pool executables, the
#: builder's copies and a family's jitted draw in set-up; the window
#: (51 s) builds nothing; the family's check builds after it
BOOKINGS = [
    (1012.0, "unscoped", "cache_load", 0.5),
    (1020.0, "weight_copies", "first_run", 0.125),
    (1030.0, "chunk", "trace", 1.5), (1030.5, "chunk", "lower", 0.5),
    (1031.0, "chunk", "cache_load", 0.25), (1031.1, "chunk", "place", 0.125),
    (1032.0, "admit", "trace", 0.25), (1032.5, "admit", "lower", 0.25),
    (1034.5, "admit", "compile", 2.0),
    (1102.0, "executor_step", "trace", 4.0),
    (1103.0, "executor_step", "compile", 8.0),
    (1104.0, "unscoped", "cache_load", 16.0),
]


def registry(pool=True):
    reg = MetricsRegistry()
    if pool:
        born = reg.counter(readers_setup.POOL_STATE_SECONDS, "",
                           ("server", "stage"))
        born.labels(server="cell", stage="alloc").inc(3.0)
        born.labels(server="cell", stage="place").inc(5.0)
    reg.gauge(readers_setup.IMPORT_SECONDS, "").set(1.5)
    return reg


def read(name, monkeypatch, bookings=BOOKINGS, reg=None, info=INFO,
         t_start=T_START):
    monkeypatch.setattr(readers_setup, "_registry",
                        lambda: registry() if reg is None else reg)
    monkeypatch.setattr(readers_setup, "_bookings", lambda: bookings)
    monkeypatch.setitem(readers_setup.sys.modules, "benchmark.run",
                        types.SimpleNamespace(T_PROCESS_START=t_start))
    monkeypatch.setattr(readers_setup.sys.modules["__main__"],
                        "T_PROCESS_START", None, raising=False)
    return getattr(readers_setup, name)(None, [], {}, info)


@pytest.mark.parametrize("name,want", [
    ("setup_trace_lower_s", 1.5 + 0.5 + 0.25 + 0.25),
    ("setup_cache_load_s", 0.5 + 0.25),
    ("setup_pool_state_s", 3.0 + 5.0),
    # 5.5 s of builds + 8 s of pool state + 1.5 s of import, of 50 s
    ("setup_accounted_share", 100.0 * (5.5 + 8.0 + 1.5) / SETUP_S),
])
def test_each_reader_sums_what_ended_before_the_window(
        monkeypatch, name, want):
    """The 28 s a check built after the window are in none of them."""
    assert read(name, monkeypatch) == pytest.approx(want)


@pytest.mark.parametrize("name", READERS)
def test_a_program_without_the_record_reads_none_not_zero(
        monkeypatch, name):
    """A checkout from before the build record: the metric is left out
    of the line, never reported as a perfect 0."""
    assert read(name, monkeypatch, bookings=None,
                reg=MetricsRegistry()) is None


def test_a_record_nobody_fed_reads_zero(monkeypatch):
    """A cold checkout loads nothing from the cache, and a training cell
    has no pool: 0 and None — the share still counts the rest."""
    cold = [(1030.0, "executor_step", "compile", 10.0)]
    reg = registry(pool=False)
    assert read("setup_cache_load_s", monkeypatch, cold, reg) == 0.0
    assert read("setup_trace_lower_s", monkeypatch, cold, reg) == 0.0
    assert read("setup_pool_state_s", monkeypatch, cold, reg) is None
    assert read("setup_accounted_share", monkeypatch, cold,
                reg) == pytest.approx(100.0 * (10.0 + 1.5) / SETUP_S)


@pytest.mark.parametrize("name", [
    "setup_trace_lower_s", "setup_cache_load_s", "setup_accounted_share"])
def test_no_cut_no_reading(monkeypatch, name):
    """Without the run's ``setup_s`` or run.py's first clock reading the
    window's first instant is unknown: nothing is said."""
    assert read(name, monkeypatch, info={"end_to_end": {}}) is None
    assert read(name, monkeypatch, t_start=None) is None


@pytest.mark.parametrize("name", READERS)
def test_the_metric_files_point_at_the_readers(name):
    mod = harness.load_py(os.path.join(
        harness.BENCH, "layer_metrics", name + ".py"), name)
    assert mod.read is getattr(readers_setup, name)


def test_the_live_program_has_what_the_readers_name():
    """Registered at import (the pool state's with the decode server,
    which a training cell never imports); the bookings are the
    counter's own, stamped on this process's ``perf_counter``."""
    import time

    import paddle_tpu.serving.decode  # noqa: F401 — registers at import
    from paddle_tpu import compile_cache, monitor

    for name in (readers_setup.POOL_STATE_SECONDS,
                 readers_setup.IMPORT_SECONDS):
        assert monitor.REGISTRY.get(name) is not None, name
    assert readers_setup._registry() is monitor.REGISTRY
    before = monitor.counter_value("program_build_seconds_total")
    t0 = time.perf_counter()
    with compile_cache.build_stage("unscoped", "place"):
        pass
    (t, program, stage, s), = [b for b in readers_setup._bookings()
                               if b[0] >= t0]
    assert (program, stage) == ("unscoped", "place")
    assert t0 <= t <= time.perf_counter()
    assert monitor.counter_value(
        "program_build_seconds_total") - before == pytest.approx(s)
