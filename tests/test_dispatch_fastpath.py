"""Steady-state dispatch fast path (PR 3): run-plan cache semantics and
host-overhead budget, program-uid jit-cache identity, and the
non-blocking (``return_numpy=False``) fetch path through Executor and
AnalysisPredictor.

The acceptance bar: for a >=100-op program, cached-dispatch host
overhead must be >=3x lower than the per-run-analysis path, asserted
via the executor's plan-cache counters + ``dispatch_overhead_s``
accounting (not wall-clock guesswork).
"""
import gc

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import framework


def median_overhead_s(exe, one_run, iters):
    """Median per-run host dispatch overhead (seconds) over ``iters``
    runs, read from the executor's own ``dispatch_overhead_s``
    accounting: the one measurement definition behind both bars below."""
    stats = exe._cache_stats
    samples = []
    for _ in range(iters):
        o0 = stats["dispatch_overhead_s"]
        one_run()
        samples.append(stats["dispatch_overhead_s"] - o0)
    samples.sort()
    return samples[len(samples) // 2]


def _build_chain(layers=20, dim=32, seed=7):
    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = seed
    with framework.program_guard(prog, startup):
        x = fluid.layers.data("x", [dim])
        h = x
        for _ in range(layers):
            h = fluid.layers.fc(h, dim, act="relu")
        loss = fluid.layers.mean(h)
        fluid.optimizer.SGDOptimizer(0.01).minimize(loss)
    return prog, startup, loss


# ---------------------------------------------------------------------------
# plan cache: hit accounting + the 3x overhead bar
# ---------------------------------------------------------------------------
def test_plan_cache_hits_and_overhead_budget():
    import jax

    prog, startup, loss = _build_chain()
    n_ops = sum(len(b.ops) for b in prog.blocks)
    assert n_ops >= 100, "regression bar needs a >=100-op block (got %d)" % n_ops

    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    dev = jax.devices()[0]
    rng = np.random.RandomState(0)
    feed = {"x": jax.device_put(rng.rand(8, 32).astype(np.float32), dev)}
    with fluid.scope_guard(scope):
        exe.run(startup)

        def one_run():
            exe.run(prog, feed=feed, fetch_list=[loss], return_numpy=False)

        for _ in range(3):
            one_run()  # compile + settle state avals

        s0 = dict(exe._cache_stats)
        cached = median_overhead_s(exe, one_run, iters=60)
        s1 = dict(exe._cache_stats)
        # steady state: every run was a plan hit AND a jit hit
        n = s1["runs"] - s0["runs"]
        assert s1["plan_hits"] - s0["plan_hits"] == n
        assert s1["plan_misses"] == s0["plan_misses"]
        assert s1["misses"] == s0["misses"]

        # the pre-plan-cache regime: rebuild the plan every run (the jit
        # cache stays hot — plan rebuilds land on the same jit key)
        def uncached_run():
            exe._plans.clear()
            one_run()

        m0 = exe.jit_cache_stats()["misses"]
        uncached = median_overhead_s(exe, uncached_run, iters=60)
        assert exe.jit_cache_stats()["misses"] == m0  # no recompiles

    assert uncached / cached >= 3.0, (
        "cached dispatch %.1fus vs per-run analysis %.1fus — fast path "
        "regressed below the 3x bar" % (cached * 1e6, uncached * 1e6))
    # absolute budget: a ~160-op cached dispatch measures ~0.1ms host-side
    # on this CPU CI machine; the 5ms bound (~50x headroom, loose to ride
    # out loaded CI) still catches O(n_ops) work sneaking back in — the
    # uncached path is what a full re-analysis costs and the 3x ratio
    # above is the primary guard
    assert cached < 5e-3, "cached dispatch overhead %.2fms" % (cached * 1e3)


def test_plan_reanalysis_on_persistable_toggle():
    """Toggling ``persistable`` after a run bumps program.version, so
    the cached plan's state analysis cannot go stale (the flag drives
    state_mut/ro/out — a stale plan would stop persisting the var)."""
    prog, startup = framework.Program(), framework.Program()
    with framework.program_guard(prog, startup):
        x = fluid.layers.data("x", [4])
        y = fluid.layers.scale(x, scale=2.0)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    feed = {"x": np.ones((2, 4), np.float32)}
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(prog, feed=feed, fetch_list=[y])
        v0 = prog.version
        prog.global_block().var(y.name).persistable = True  # mark-before-save
        assert prog.version > v0
        m0 = exe._cache_stats["plan_misses"]
        exe.run(prog, feed=feed, fetch_list=[y])
        assert exe._cache_stats["plan_misses"] == m0 + 1  # re-analyzed
        # the newly persistable output now lands in the scope
        assert scope.get(y.name) is not None


def test_plan_reanalysis_on_structural_edit():
    """Appending an op after a run must invalidate the cached plan/jit
    entry (op count guards the key even without a version bump)."""
    import jax  # noqa: F401

    prog, startup = framework.Program(), framework.Program()
    with framework.program_guard(prog, startup):
        x = fluid.layers.data("x", [4])
        y = fluid.layers.scale(x, scale=2.0)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    feed = {"x": np.ones((2, 4), np.float32)}
    with fluid.scope_guard(scope):
        exe.run(startup)
        (out1,) = exe.run(prog, feed=feed, fetch_list=[y])
        np.testing.assert_allclose(out1, 2.0 * np.ones((2, 4)), rtol=1e-6)
        with framework.program_guard(prog, startup):
            z = fluid.layers.scale(y, scale=3.0)
        (out2,) = exe.run(prog, feed=feed, fetch_list=[z])
        np.testing.assert_allclose(out2, 6.0 * np.ones((2, 4)), rtol=1e-6)
        assert exe._cache_stats["plan_misses"] >= 2


# ---------------------------------------------------------------------------
# sharded dispatch (PR 4): mesh-fed cached dispatch stays cheap
# ---------------------------------------------------------------------------
def _measure_cached(exe, prog, loss, feed, run_kwargs, iters):
    """Warm the jit/plan caches, then return the median cached host
    overhead (seconds), the plan-hit count and the jit-miss count over
    the measured runs.  Each run BLOCKS on its fetch before the next
    (outside the measured pre-dispatch window): the async device
    compute of the 8-way virtual mesh otherwise contends with the next
    run's host section."""

    def one_run():
        (out,) = exe.run(prog, feed=feed, fetch_list=[loss],
                         return_numpy=False, **run_kwargs)
        out.block_until_ready()

    for _ in range(3):  # compile + settle state avals
        one_run()
    h0 = exe._cache_stats["plan_hits"]
    m0 = exe.jit_cache_stats()["misses"]
    cached = median_overhead_s(exe, one_run, iters)
    return (cached, exe._cache_stats["plan_hits"] - h0,
            exe.jit_cache_stats()["misses"] - m0)


def test_sharded_dispatch_overhead_within_2x_of_single_device():
    """The scale-out acceptance bar: per-STEP host overhead of the
    sharded pipeline (device_buffered(compiled=...) chunks -> steps=N
    per_step_feed dispatch on an 8-device CPU mesh) within 2x of the
    single-device cached path, measured through the same
    ``dispatch_overhead_s`` accounting as the single-device bar — i.e.
    sharding the feed must not reintroduce O(n_devices) hot-path work.
    Also pins the mechanism: the steady state re-stages NOTHING (the
    prefetcher's per-shard placement passes straight through)."""
    import jax

    from paddle_tpu import reader as _reader
    from paddle_tpu.parallel import mesh as mesh_lib
    from paddle_tpu.parallel.compiled_program import CompiledProgram

    iters, chunk = 60, 4
    mesh = mesh_lib.data_parallel_mesh()
    n_dev = int(mesh.devices.size)
    assert n_dev == 8  # conftest's virtual CPU mesh
    prog, startup, loss = _build_chain()
    compiled = CompiledProgram(prog).with_mesh(mesh)
    host = {"x": np.random.RandomState(0).rand(n_dev, 32).astype(np.float32)}

    exe = fluid.Executor()
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        # single-device yardstick first: once the compiled path runs,
        # the scope state is mesh-sharded and single-device runs of the
        # same program would see mismatched devices
        feed1 = {"x": jax.device_put(host["x"], jax.devices()[0])}
        single_s, _, _ = _measure_cached(exe, prog, loss, feed1, {}, iters)

        # the sharded production regime: per_step_feed chunks straight
        # from the sharded prefetcher
        gen = _reader.device_buffered(
            (host for _ in iter(int, 1)), size=2, steps=chunk,
            compiled=compiled)()
        try:
            chunk_s, plan_hits, recompiles = _measure_cached(
                exe, compiled, loss, next(gen),
                dict(steps=chunk, per_step_feed=True), iters)
        finally:
            gen.close()

    assert recompiles == 0
    assert len(compiled._steady_tokens) >= 1  # nothing re-staged
    assert plan_hits == iters
    per_step_s = chunk_s / chunk
    ratio = per_step_s / single_s
    assert ratio <= 2.0, (
        "sharded per-step dispatch overhead %.1fus vs single-device "
        "%.1fus — %.2fx exceeds the 2x scale-out bar"
        % (per_step_s * 1e6, single_s * 1e6, ratio))


# ---------------------------------------------------------------------------
# LRU-bounded plan/jit caches (PR 4): long-lived processes stay bounded
# ---------------------------------------------------------------------------
def test_plan_and_jit_caches_are_lru_bounded():
    from paddle_tpu import monitor

    exe = fluid.Executor(fluid.CPUPlace(), plan_cache_capacity=2,
                         jit_cache_capacity=2)
    feed = {"x": np.ones((2, 3), np.float32)}
    progs = []
    for i in range(4):
        prog, startup = framework.Program(), framework.Program()
        with framework.program_guard(prog, startup):
            x = fluid.layers.data("x", [3])
            y = fluid.layers.scale(x, scale=float(i + 1))
        progs.append((prog, startup, y))
    for i, (prog, startup, y) in enumerate(progs):
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            (out,) = exe.run(prog, feed=feed, fetch_list=[y])
        np.testing.assert_allclose(out, (i + 1.0) * np.ones((2, 3)), rtol=1e-6)
    stats = exe.jit_cache_stats()
    assert stats["entries"] <= 2 and stats["plan_entries"] <= 2, stats
    assert stats["jit_evictions"] >= 1 and stats["plan_evictions"] >= 1, stats
    # registry counters see the evictions too (collect-on-read)
    assert monitor.counter_value("executor_plan_cache_evictions_total") >= 1
    assert monitor.counter_value("executor_jit_cache_evictions_total") >= 1

    # an evicted program still runs correctly — it just re-analyzes
    prog, startup, y = progs[0]
    scope = fluid.Scope()
    m0 = stats["plan_misses"]
    with fluid.scope_guard(scope):
        exe.run(startup)
        (out,) = exe.run(prog, feed=feed, fetch_list=[y])
    np.testing.assert_allclose(out, np.ones((2, 3)), rtol=1e-6)
    assert exe.jit_cache_stats()["plan_misses"] > m0


def test_lru_keeps_recently_used_entries():
    """Touching an entry refreshes it: with capacity 2, re-running
    program A before adding C must evict B, not A."""
    from paddle_tpu.executor import _LRUCache

    evicted = []
    c = _LRUCache(2, on_evict=lambda: evicted.append(1))
    c["a"] = 1
    c["b"] = 2
    assert c.get("a") == 1  # refresh a
    c["c"] = 3              # evicts b
    assert c.get("b") is None and c.get("a") == 1 and c.get("c") == 3
    assert len(evicted) == 1


def test_default_cache_capacities_are_generous():
    exe = fluid.Executor(fluid.CPUPlace())
    assert exe._plans.capacity >= 256
    assert exe._cache.capacity >= 128


# ---------------------------------------------------------------------------
# program uid: jit-cache identity must survive id() reuse
# ---------------------------------------------------------------------------
def test_program_uid_monotonic_and_clone_fresh():
    a, b = framework.Program(), framework.Program()
    assert a._ptpu_uid != b._ptpu_uid
    c = a.clone()
    assert c._ptpu_uid not in (a._ptpu_uid, b._ptpu_uid)
    assert framework._program_uid(a) == a._ptpu_uid  # stable


def test_distinct_programs_never_share_jit_entries():
    """Build-run-discard identical programs in a loop: CPython may hand
    later programs the SAME id() as a collected earlier one, which used
    to alias their jit-cache entries.  With uid keys every program must
    compile fresh (a miss), never hit a dead program's entry."""
    exe = fluid.Executor(fluid.CPUPlace())
    deltas = []
    for i in range(3):
        prog, startup = framework.Program(), framework.Program()
        with framework.program_guard(prog, startup):
            x = fluid.layers.data("x", [3])
            y = fluid.layers.scale(x, scale=float(i + 1))
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            # delta read AFTER the startup run, so it isolates prog's own
            # compile — a spurious hit on a dead program's entry would
            # make the delta 0
            m0 = exe.jit_cache_stats()["misses"]
            (out,) = exe.run(prog, feed={"x": np.ones((2, 3), np.float32)},
                             fetch_list=[y])
        np.testing.assert_allclose(out, (i + 1.0) * np.ones((2, 3)), rtol=1e-6)
        deltas.append(exe.jit_cache_stats()["misses"] - m0)
        del prog, startup, scope
        gc.collect()
    # each program is a distinct identity -> at least its own compile
    assert all(d >= 1 for d in deltas), deltas


# ---------------------------------------------------------------------------
# donation policy: never donate on the CPU backend
# ---------------------------------------------------------------------------
def test_no_donation_on_cpu_backend():
    """Buffer donation + jax's persistent compilation cache corrupts
    results on CPU: a warm-cache process's fetches observe the
    in-place-mutated params (reproduced with a DynamicRNN+Adam module —
    cold compiles correct, every cache-loaded run wrong).  Donation is a
    TPU HBM optimization; on CPU it must be off."""
    import jax

    from paddle_tpu.executor import _donate_kwargs

    assert _donate_kwargs(jax.devices("cpu")[0]) == {}

    class _FakeTpu:
        platform = "tpu"

    assert _donate_kwargs(_FakeTpu()) == {"donate_argnums": (0,)}


# ---------------------------------------------------------------------------
# non-blocking fetch
# ---------------------------------------------------------------------------
def test_return_numpy_false_returns_device_arrays():
    import jax

    prog, startup, loss = _build_chain(layers=2)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    feed = {"x": np.ones((4, 32), np.float32)}
    with fluid.scope_guard(scope):
        exe.run(startup)
        (dev_out,) = exe.run(prog, feed=feed, fetch_list=[loss],
                             return_numpy=False)
        assert isinstance(dev_out, jax.Array)
        # same computation, materialized: values must agree (the rerun is
        # a jit-cache hit, so state advanced identically is not expected —
        # compare against the device value itself)
        np.asarray(dev_out)  # d2h works and the value is finite
        assert np.isfinite(np.asarray(dev_out)).all()


def test_predictor_nonblocking_run_padded(tmp_path):
    import jax

    from paddle_tpu.inference import AnalysisConfig, create_paddle_predictor

    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 5
    with framework.program_guard(prog, startup):
        x = fluid.layers.data("x", [6])
        p = fluid.layers.fc(x, 3, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.save_inference_model(str(tmp_path / "m"), ["x"], [p], exe, prog)

    pred = create_paddle_predictor(AnalysisConfig(str(tmp_path / "m")))
    rows = np.random.RandomState(0).rand(3, 6).astype(np.float32)
    padded = np.zeros((4, 6), np.float32)
    padded[:3] = rows
    (dev_out,) = pred.run_padded({"x": padded}, n_valid=3, return_numpy=False)
    assert isinstance(dev_out, jax.Array)
    assert dev_out.shape[0] == 3  # n_valid slice happened on device
    (np_out,) = pred.run_padded({"x": padded}, n_valid=3)
    assert isinstance(np_out, np.ndarray)
    np.testing.assert_allclose(np.asarray(dev_out), np_out, rtol=1e-6)


def test_serving_overlap_results_consistent():
    """The overlapped worker (dispatch N+1 before finalizing N) must
    deliver every request its own rows — hammer a server with distinct
    single-row requests and check each result round-trips."""
    import os
    import tempfile

    from paddle_tpu import serving
    from paddle_tpu.inference import AnalysisConfig, create_paddle_predictor

    with tempfile.TemporaryDirectory() as tmp:
        d = os.path.join(tmp, "m")
        prog, startup = framework.Program(), framework.Program()
        prog.random_seed = startup.random_seed = 5
        with framework.program_guard(prog, startup):
            x = fluid.layers.data("x", [4])
            y = fluid.layers.scale(x, scale=10.0)
        exe = fluid.Executor(fluid.CPUPlace())
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            fluid.save_inference_model(d, ["x"], [y], exe, prog)
        pred = create_paddle_predictor(AnalysisConfig(d))
        server = serving.InferenceServer(
            pred, max_batch_size=8, batch_timeout_ms=1, queue_capacity=64,
            name="overlap-test")
        assert server._nonblocking  # AnalysisPredictor supports the fast path
        try:
            server.warmup()
            futs = []
            for i in range(40):
                row = np.full((1, 4), float(i), np.float32)
                futs.append((i, server.submit({"x": row})))
            for i, fut in futs:
                (out,) = fut.result(timeout=30)
                np.testing.assert_allclose(
                    out, np.full((1, 4), 10.0 * i), rtol=1e-6)
        finally:
            server.stop(drain=True)
