"""The build record (``paddle_tpu.compile_cache``): set-up's seconds are
booked where the work happens, each once.

``program_build_seconds_total{program, stage}`` over a tiny slot pool's
warm-up, an Executor's first dispatch and a jit outside every site; the
``build/*`` spans of the same builds; and the pool state's birth in a
``DecodeServer`` (``serving_pool_state_seconds_total``, the
``serving/pool_placed`` event).  Counters are process-wide, so every
check is a difference over its own stretch.
"""
import json
import os
import subprocess
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import compile_cache, decoding, monitor
from paddle_tpu.monitor import spans
from paddle_tpu.serving.decode import DecodeServer
from paddle_tpu.serving.kv_pool import KVSlotPool

from conftest import WAIT

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V, EOS = 32, 31
SECONDS, BUILDS = "program_build_seconds_total", "program_builds_total"
KINDS = ("chunk", "admit", "release")
BACKEND_COMPILE = "/jax/core/compile/backend_compile_duration"


def chain_model(width=8):
    """next token = (consumed token + 1) % V over one K leaf: a step
    with something to trace, lower and compile, and nothing else."""
    def step_fn(cache, tokens, ts):
        rows = jnp.arange(tokens.shape[0])
        k = cache["k"].at[rows, jnp.maximum(ts, 0)].set(
            jax.nn.one_hot(tokens % width, width))
        logits = jax.nn.one_hot((tokens + 1) % V, V) * 10.0
        return logits + k.sum() * 0.0, {"k": k}

    def make_cache(n_rows, seq_len):
        return {"k": jnp.zeros((n_rows, seq_len, width), "float32")}

    decoding.declare(make_cache, decoding.CacheSpec(
        {"k": decoding.Leaf(1)}))
    return step_fn, make_cache


def tiny_pool(**kw):
    step_fn, make_cache = chain_model(**kw)
    return KVSlotPool(step_fn, make_cache, eos_id=EOS, max_slots=2,
                      max_seq_len=32, slot_ladder=[2], len_ladder=[16, 32],
                      steps=2)


def seconds(stage=None, **labels):
    if stage is not None:
        labels["stage"] = stage
    return monitor.counter_value(SECONDS, **labels)


def pool_seconds(*stages):
    return sum(seconds(stage, program=kind)
               for kind in KINDS for stage in stages)


def pool_builds():
    return sum(monitor.counter_value(BUILDS, program=kind) for kind in KINDS)


class CompileEvents:
    """jax's own compile events over a stretch, as the benchmark's
    ``CompileWatch`` sums them (a listener cannot be taken off again:
    one instance a process, switched on around each stretch)."""

    def __init__(self):
        self.on, self.n, self.s = False, 0, 0.0
        jax.monitoring.register_event_duration_secs_listener(self._event)

    def _event(self, event, secs, **_):
        if self.on and event == BACKEND_COMPILE:
            self.n += 1
            self.s += secs

    def __enter__(self):
        self.on, self.n, self.s = True, 0, 0.0
        return self

    def __exit__(self, *exc):
        self.on = False


@pytest.fixture(scope="module")
def compile_events():
    return CompileEvents()


def test_a_warmup_books_every_stage_and_the_stages_tile_its_wall():
    pool = tiny_pool()
    before = {st: pool_seconds(st) for st in compile_cache.STAGES}
    builds0 = pool_builds()
    t0 = time.perf_counter()
    compiles = pool.warmup()
    wall = time.perf_counter() - t0
    assert compiles == len(KINDS) * 2            # two rung pairs
    got = {st: pool_seconds(st) - before[st] for st in compile_cache.STAGES}
    assert pool_builds() - builds0 == compiles   # one build an executable
    assert got["trace"] > 0 and got["lower"] > 0 and got["place"] > 0
    assert got["compile"] + got["cache_load"] > 0
    assert got["first_run"] == 0                 # a pool runs nothing
    # each second is booked once: the stages tile the warm-up
    assert sum(got.values()) == pytest.approx(wall, rel=0.10)
    assert sum(got.values()) <= wall


def test_a_process_warm_rewarm_books_nothing():
    pool = tiny_pool()
    pool.warmup()
    before, builds0 = pool_seconds(*compile_cache.STAGES), pool_builds()
    assert pool.warmup() == 0
    assert pool_seconds(*compile_cache.STAGES) == before
    assert pool_builds() == builds0


def test_build_spans_hang_under_their_executable_and_it_says_what_it_was():
    pool = tiny_pool()
    spans.start_recording()
    try:
        compiles = pool.warmup()
    finally:
        got = spans.stop_recording()
    parents = {s["id"]: s for s in got
               if s["name"] in {"build/" + k for k in KINDS}}
    assert len(parents) == compiles
    for stage in ("trace", "lower", "place"):
        mine = [s for s in got if s["name"] == "build/" + stage]
        assert len(mine) == compiles             # once an executable
        assert {s["parent"] for s in mine} == set(parents)
    built = [s for s in got
             if s["name"] in ("build/compile", "build/cache_load")]
    assert {s["parent"] for s in built} == set(parents)
    assert len(built) == compiles
    for s in parents.values():
        args = s["args"]
        assert args["equations"] > 0 and args["rungs"][0] == 2
        assert args["rungs"][1] in (16, 32)
        assert args["constants"] >= args["host_born"] >= 0
        # the children lie inside the parent and leave it little else
        assert args["booked_s"] <= s["dur"]
        assert "parent" not in s


def test_a_trace_that_walks_a_kernel_site_names_it_on_the_build_span():
    """Which ``*_lowered_total`` series moved during the trace goes on
    the ``build/<program>`` span: a step over the pooled transformer
    LM's attention lowers one decode-attention site a layer."""
    from paddle_tpu.decoding import (make_transformer_lm_pooled_step_fn,
                                     random_transformer_lm_state)

    dims = dict(vocab=V, d_model=16, n_layer=2, n_head=2, d_inner=32)
    state = random_transformer_lm_state(
        np.random.RandomState(7), max_pos=32, **dims)
    step_fn, make_cache = make_transformer_lm_pooled_step_fn(
        state, dims["vocab"], dims["d_model"], dims["n_layer"],
        dims["n_head"], dims["d_inner"])
    pool = KVSlotPool(step_fn, make_cache, eos_id=EOS, max_slots=2,
                      max_seq_len=16, slot_ladder=[2], len_ladder=[16],
                      steps=2)
    spans.start_recording()
    try:
        pool.warmup()
    finally:
        got = spans.stop_recording()
    (chunk,) = [s for s in got if s["name"] == "build/chunk"]
    walked = chunk["args"]["kernels"]
    assert walked and all(n > 0 for n in walked.values())
    assert all(name.endswith("}") and "_lowered{" in name for name in walked)
    (release,) = [s for s in got if s["name"] == "build/release"]
    assert "kernels" not in release["args"]      # it walks no attention


def test_compile_and_cache_load_seconds_are_jaxs_own_compile_events(
        compile_events):
    """Over a pool's warm-up, an Executor's first dispatch and a jit
    outside every site, stages ``compile`` + ``cache_load`` sum to what
    a ``CompileWatch`` sums of ``backend_compile_duration``: the
    benchmark's ``setup_compile_s``.  (A pool's ``.compile()`` is timed
    by the wall, which is the event plus the executable's wrapping.)"""
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [8])
        out = fluid.layers.fc(x, 4)
    exe = fluid.Executor(fluid.CPUPlace())
    before = seconds("compile") + seconds("cache_load")
    builds0 = monitor.counter_value(BUILDS)
    unscoped0 = monitor.counter_value(BUILDS, program="unscoped")
    with compile_events as seen:
        tiny_pool(width=12).warmup()
        exe.run(startup)
        exe.run(main, feed={"x": np.ones((3, 8), "float32")},
                fetch_list=[out])
        jax.jit(lambda a: a * 3 + 1)(jnp.arange(7.0)).block_until_ready()
    booked = seconds("compile") + seconds("cache_load") - before
    assert seen.n >= 6 + 2 + 1
    assert monitor.counter_value(BUILDS) - builds0 == seen.n
    assert monitor.counter_value(BUILDS, program="unscoped") > unscoped0
    assert booked >= seen.s * 0.999
    assert booked == pytest.approx(seen.s, rel=0.10, abs=0.05)


def test_an_executors_first_dispatch_is_one_build_and_later_ones_none():
    main, startup = fluid.Program(), fluid.Program()
    with fluid.program_guard(main, startup):
        x = fluid.layers.data("x", [6])
        out = fluid.layers.fc(x, 5, act="relu")
    exe = fluid.Executor(fluid.CPUPlace())
    exe.run(startup)
    feed = {"x": np.ones((2, 6), "float32")}

    def booked():
        return {st: seconds(st, program="executor_step")
                for st in compile_cache.STAGES}

    before = booked()
    spans.start_recording()
    try:
        exe.run(main, feed=feed, fetch_list=[out])
    finally:
        got = spans.stop_recording()
    first = {st: v - before[st] for st, v in booked().items()}
    assert first["trace"] > 0 and first["first_run"] > 0
    assert first["compile"] + first["cache_load"] > 0
    assert first["lower"] == first["place"] == 0
    (parent,) = [s for s in got if s["name"] == "build/executor_step"]
    # trace + compile + the rest are the dispatch's wall, each once
    assert sum(first.values()) == pytest.approx(parent["dur"], rel=0.01)
    (trace,) = [s for s in got if s["name"] == "lowering/trace_block"]
    assert trace["parent"] == parent["id"]
    assert trace["args"]["program"] == "executor_step"
    (jit,) = [s for s in got if s["name"] == "executor/jit_compile"]
    assert jit["dur"] == pytest.approx(parent["dur"], rel=0.05, abs=1e-3)
    after = booked()
    exe.run(main, feed=feed, fetch_list=[out])   # the same cache key
    assert booked() == after


def test_a_rebuilt_pool_loads_from_a_persistent_cache(tmp_path):
    """A second pool of the same step in a process whose persistent
    cache keeps every entry: its executables are cache hits, booked as
    ``cache_load`` and counted ``cache="hit"``; the first pool's were
    misses, booked as ``compile``."""
    prog = """
import json, sys
sys.path.insert(0, %r)
sys.path.insert(0, %r)
import jax
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
from paddle_tpu import monitor
import test_compile_cache_builds as t

def read():
    return {"compile": t.pool_seconds("compile"),
            "cache_load": t.pool_seconds("cache_load"),
            "hit": sum(monitor.counter_value(t.BUILDS, program=k,
                                             cache="hit") for k in t.KINDS),
            "miss": sum(monitor.counter_value(t.BUILDS, program=k,
                                              cache="miss") for k in t.KINDS)}

n1 = t.tiny_pool().warmup(); first = read()
n2 = t.tiny_pool().warmup(); second = read()
print(json.dumps({"n": [n1, n2], "first": first, "second": second}))
""" % (ROOT, os.path.join(ROOT, "tests"))
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(tmp_path / "cache"))
    out = subprocess.run([sys.executable, "-c", prog], env=env, timeout=WAIT,
                         capture_output=True, text=True)
    assert out.returncode == 0, out.stderr[-2000:]
    got = json.loads(out.stdout.strip().splitlines()[-1])
    first, second = got["first"], got["second"]
    assert got["n"] == [6, 6]
    assert (first["miss"], first["hit"]) == (6, 0)
    assert first["compile"] > 0 and first["cache_load"] == 0
    assert (second["miss"], second["hit"]) == (6, 6)
    assert second["compile"] == first["compile"]
    assert second["cache_load"] > 0


def test_a_fresh_pool_states_birth_is_booked_each_time(monkeypatch):
    """alloc -> first delivery -> idle drop -> re-admit: ``alloc`` and
    ``place`` are booked twice, the bytes twice, and
    ``serving/pool_placed`` is left twice — the twin of
    ``serving/pool_dropped``."""
    from paddle_tpu.serving import decode as decode_mod

    name = "birth-cycle"
    monkeypatch.setattr(decode_mod, "_IDLE_WAIT_S", 0.05)
    step_fn, make_cache = chain_model()
    srv = DecodeServer(step_fn, make_cache, eos_id=EOS, max_seq_len=16,
                       max_slots=2, steps_per_tick=2, name=name)

    def state_seconds(stage):
        return monitor.counter_value("serving_pool_state_seconds_total",
                                     None, server=name, stage=stage)

    def placed():
        return [e for e in monitor.eventz()["events"]
                if e["kind"] == "serving/pool_placed"
                and e["server"] == name]

    try:
        t0 = time.perf_counter()
        srv.warmup(configure_cache=False)
        warm_wall = time.perf_counter() - t0
        assert state_seconds("alloc") is None    # nothing born yet
        booked, requests_wall = [], 0.0
        for cycle in (1, 2):
            t0 = time.perf_counter()
            srv.submit({"tokens": np.array([10], np.int32)},
                       max_new_tokens=3).result(timeout=WAIT)
            requests_wall += time.perf_counter() - t0
            events = placed()
            assert len(events) == cycle
            booked.append((state_seconds("alloc"), state_seconds("place")))
            assert sum(booked[-1]) <= requests_wall
            deadline = time.monotonic() + WAIT
            while (monitor.counter_value(
                    "serving_decode_idle_drops_total", server=name) < cycle
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert monitor.counter_value(
                "serving_decode_idle_drops_total", server=name) == cycle
    finally:
        srv.stop()
    (a1, p1), (a2, p2) = booked
    assert 0 < a1 < a2 and 0 < p1 < p2
    first, second = events
    # every leaf of the smallest rungs' state: its [1, 8, 8] K leaf is 256
    nbytes = monitor.counter_value("serving_pool_state_bytes_placed_total",
                                   server=name)
    assert first["bytes"] == second["bytes"] == nbytes / 2 > 256
    assert first["alloc_s"] == pytest.approx(a1)
    assert first["place_s"] == pytest.approx(p1)
    assert second["place_s"] == pytest.approx(p2 - p1)
    assert 0 < monitor.counter_value("serving_warmup_seconds",
                                     server=name) <= warm_wall


def test_a_servers_warmup_is_a_span_over_its_builds():
    step_fn, make_cache = chain_model(width=5)
    srv = DecodeServer(step_fn, make_cache, eos_id=EOS, max_seq_len=16,
                       max_slots=2, steps_per_tick=2, name="warm-span")
    spans.start_recording()
    try:
        compiles = srv.warmup(configure_cache=False)
    finally:
        got = spans.stop_recording()
        srv.stop()
    (warm,) = [s for s in got if s["name"] == "serving/warmup"]
    assert warm["args"]["compiles"] == compiles > 0
    assert warm["args"]["rung_pairs"] == len(srv._pool.rung_pairs())
    builds = [s for s in got
              if s["name"] in {"build/" + k for k in compile_cache.PROGRAMS}]
    assert len(builds) == compiles
    assert {s["parent"] for s in builds} == {warm["id"]}
    # the gauge is that span's seconds; the builds fill most of it
    assert monitor.counter_value(
        "serving_warmup_seconds", server="warm-span") == pytest.approx(
            warm["dur"])
    assert sum(s["dur"] for s in builds) <= warm["dur"]


def test_an_unwarmed_servers_first_build_is_not_counted_as_placing():
    """A server nobody warmed builds its executables inside its first
    turns: those seconds are the build record's, and ``place`` is what
    is left of the stretch."""
    name = "birth-unwarmed"
    step_fn, make_cache = chain_model(width=7)
    srv = DecodeServer(step_fn, make_cache, eos_id=EOS, max_seq_len=16,
                       max_slots=2, steps_per_tick=2, name=name)
    built0 = pool_seconds(*compile_cache.STAGES)
    try:
        t0 = time.perf_counter()
        srv.submit({"tokens": np.array([10], np.int32)},
                   max_new_tokens=3).result(timeout=WAIT)
        wall = time.perf_counter() - t0
    finally:
        srv.stop()
    built = pool_seconds(*compile_cache.STAGES) - built0
    place = monitor.counter_value("serving_pool_state_seconds_total",
                                  server=name, stage="place")
    assert built > 0 and place >= 0
    assert place + built <= wall
