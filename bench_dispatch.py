"""Micro-benchmark: steady-state dispatch cost of ``Executor.run``.

The paper's claim is that one jitted XLA module subsumes Fluid's
per-op dispatch; this bench pins what the HOST still pays per cached
``run()`` call for a ~100-op block — the run-plan + jit cache hit path
(plan lookup -> feed coercion -> jitted call).  Two numbers:

* ``cached_overhead_us`` — median host-side overhead per run with the
  run-plan cache hot (the steady-state number; regressions here are
  regressions in every training step and serving request);
* ``uncached_overhead_us`` — the same runs with the plan cache cleared
  each call, i.e. the pre-PR-3 per-run O(n_ops) block re-analysis, with
  the jit cache still hot (so the delta isolates the analysis cost).

``speedup`` = uncached/cached (the PR-3 acceptance bar is >= 3x, pinned
in tests/test_dispatch_fastpath.py).  Host overhead is read from the
executor's ``dispatch_overhead_s`` accounting, not inferred from wall
time, so device execution doesn't pollute the number.

``--sharded`` (or ``run_sharded()``): the multi-device variant — the
same block compiled data-parallel over the local mesh, fed by the
SHARDED device-prefetch pipeline (each replica's slice staged in its
own HBM), measuring cached dispatch overhead on the mesh path against
the single-device number.  The acceptance bar (tests/
test_dispatch_fastpath.py) is sharded <= 2x single-device: sharding the
feed must not reintroduce O(n_devices) host work per step.

``--sharded-train`` (or ``run_sharded_train()``): the SHARDED TRAINING
variant — the same block with Adam (real optimizer moments) trained
replicated vs fsdp-2 through ``paddle_tpu.sharding.train`` rules, so
params, grads, AND moments live dim-0-sharded on the mesh.  Reports
examples/s both ways plus the per-device param+moment bytes ratio (the
capacity win the layout buys) and asserts 0 recompiles during the
measured window.  On a host-SIMULATED mesh the examples/s ratio
reflects the XLA:CPU collective emulation tax, not the TPU number —
the bytes ratio is the portable claim.

``--checkpoint`` (or ``run_checkpoint()``): the CHECKPOINT stage — the
same Adam block sharded fsdp-2, measuring ``TrainCheckpoint`` sync
shard-wise save time (+ bytes/s), SAME-mesh restore (direct per-shard
re-place) and CROSS-mesh restore onto fsdp-4 (the topology-elastic
shard-exchange assembly), with the exchange host-buffer high-water
reported alongside so the never-a-full-tensor claim has a number.

``--train-obs`` (or ``run_train_obs()``): the TRAINING-OBSERVABILITY
tax — the same Adam block looped through ``train_from_dataset`` with
the step-phase ledger + anomaly watchdog armed vs disarmed, rounds
alternated on the same compiled state.  Asserts the armed tax on the
best round stays under 2% (the control tower must not tax the second
it attributes) and that the armed ledger's books balance (phases sum
to the epoch wall clock).

Every mode measures HOST-side cost (dispatch overhead, file I/O,
instrumentation tax), so each runs on the process default device —
``Executor()`` with no place — and names its platform in the result
line; bench.py declares them CPU stages.

Env knobs: BENCH_DISPATCH_LAYERS (default 20 -> ~190 ops with backward
+ sgd), BENCH_DISPATCH_DIM (default 32), BENCH_DISPATCH_ITERS (default
200), BENCH_DISPATCH_BATCH (default 8; the sharded mode rounds it up to
a multiple of the mesh size), BENCH_CKPT_LAYERS/BENCH_CKPT_DIM (default
4/512 — sized so the checkpoint is ~10 MB of real shard files).
"""
import os
import time

import numpy as np

LAYERS = int(os.environ.get("BENCH_DISPATCH_LAYERS", "20"))
DIM = int(os.environ.get("BENCH_DISPATCH_DIM", "32"))
ITERS = int(os.environ.get("BENCH_DISPATCH_ITERS", "200"))
BATCH = int(os.environ.get("BENCH_DISPATCH_BATCH", "8"))


def build_program(layers=LAYERS, dim=DIM):
    import paddle_tpu as fluid
    from paddle_tpu import framework

    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 7
    with framework.program_guard(prog, startup):
        x = fluid.layers.data("x", [dim])
        h = x
        for _ in range(layers):
            h = fluid.layers.fc(h, dim, act="relu")
        loss = fluid.layers.mean(h)
        fluid.optimizer.SGDOptimizer(0.01).minimize(loss)
    return prog, startup, loss


def median_overhead_s(exe, one_run, iters):
    """Median per-run host dispatch overhead (seconds) over ``iters``
    runs, read from the executor's own ``dispatch_overhead_s``
    accounting (also used by tests/test_dispatch_fastpath.py — one
    measurement definition for the bench and the acceptance bar)."""
    stats = exe._cache_stats
    samples = []
    for _ in range(iters):
        o0 = stats["dispatch_overhead_s"]
        one_run()
        samples.append(stats["dispatch_overhead_s"] - o0)
    samples.sort()
    return samples[len(samples) // 2]


def run(layers=LAYERS, dim=DIM, iters=ITERS, batch=BATCH):
    import jax

    import paddle_tpu as fluid

    platform = jax.devices()[0].platform
    prog, startup, loss = build_program(layers, dim)
    n_ops = sum(len(b.ops) for b in prog.blocks)

    scope = fluid.Scope()
    exe = fluid.Executor()
    dev = jax.devices()[0]
    rng = np.random.RandomState(0)
    # device-resident feed (the prefetch regime): h2d is a passthrough,
    # so the measured overhead is pure dispatch rent
    feed = {"x": jax.device_put(rng.rand(batch, dim).astype(np.float32), dev)}

    with fluid.scope_guard(scope):
        exe.run(startup)

        def one_run():
            exe.run(prog, feed=feed, fetch_list=[loss], return_numpy=False)

        for _ in range(3):  # warmup: compile + settle state avals
            one_run()

        h0 = exe._cache_stats["plan_hits"]
        cached_us = median_overhead_s(exe, one_run, iters) * 1e6
        plan_hits = exe._cache_stats["plan_hits"] - h0
        m0 = exe.jit_cache_stats()["misses"]

        # the pre-plan-cache regime: force the O(n_ops) re-analysis per
        # run while keeping the jit cache hot (plan rebuilds land on the
        # same jit key, so no recompiles pollute the comparison)
        def uncached_run():
            exe._plans.clear()
            one_run()

        uncached_us = median_overhead_s(exe, uncached_run, iters) * 1e6
        recompiles = exe.jit_cache_stats()["misses"] - m0

    from paddle_tpu import monitor

    return {
        "metric": "cached_dispatch_host_overhead_us",
        "value": round(cached_us, 1),
        "unit": "us",
        "uncached_overhead_us": round(uncached_us, 1),
        "speedup_vs_per_run_analysis": round(uncached_us / cached_us, 2),
        "n_ops": n_ops,
        "iters": iters,
        "plan_cache_hits": int(plan_hits),
        "plan_cache_hits_total": int(
            monitor.counter_value("executor_plan_cache_hits_total")),
        "recompiles_during_measure": int(recompiles),
        "batch": batch,
        "dim": dim,
        "platform": platform,
    }


def _measure_cached(exe, prog, loss, feed, run_kwargs, iters):
    """Warm the jit/plan caches, then return the median cached host
    overhead (seconds) plus the plan-hit count over the measured runs.

    Each run BLOCKS on its fetch before the next (outside the measured
    pre-dispatch window): the async device compute — ~20ms of 8-way
    virtual-CPU collectives in the sharded mode — otherwise contends
    with the next run's host section and pollutes the overhead number
    with GIL/thread noise that is not host dispatch work."""

    def one_run():
        (out,) = exe.run(prog, feed=feed, fetch_list=[loss],
                         return_numpy=False, **run_kwargs)
        out.block_until_ready()

    for _ in range(3):  # warmup: compile + settle state avals
        one_run()
    h0 = exe._cache_stats["plan_hits"]
    m0 = exe.jit_cache_stats()["misses"]
    cached = median_overhead_s(exe, one_run, iters)
    return cached, exe._cache_stats["plan_hits"] - h0, \
        exe.jit_cache_stats()["misses"] - m0


SHARDED_CHUNK = int(os.environ.get("BENCH_DISPATCH_SHARDED_CHUNK", "4"))


def run_sharded(layers=LAYERS, dim=DIM, iters=ITERS, batch=BATCH,
                chunk=SHARDED_CHUNK):
    """Per-STEP cached dispatch overhead on an N-device data-parallel
    mesh, fed by the sharded device-prefetch pipeline, against the
    single-device cached path measured in the same process.

    The sharded production regime is the chunked one (``steps=chunk``
    per_step_feed fori_loop, chunks assembled by
    ``device_buffered(steps=..., compiled=...)``), so the headline
    ``value`` is host overhead PER STEP in that regime.  The raw
    per-call steps=1 number rides along as
    ``sharded_call_overhead_us`` — on a HOST-SIMULATED mesh it carries
    the XLA:CPU client's per-replica buffer lifecycle on the dispatch
    thread (every replicated param materializes n_dev host copies per
    step), a virtual-mesh artifact a real TPU mesh doesn't pay."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu import reader as _reader
    from paddle_tpu.parallel import mesh as mesh_lib
    from paddle_tpu.parallel.compiled_program import CompiledProgram

    platform = jax.devices()[0].platform
    mesh = mesh_lib.data_parallel_mesh()
    n_dev = int(mesh.devices.size)
    batch = ((max(batch, 1) + n_dev - 1) // n_dev) * n_dev  # round UP

    prog, startup, loss = build_program(layers, dim)
    n_ops = sum(len(b.ops) for b in prog.blocks)
    compiled = CompiledProgram(prog).with_mesh(mesh)
    rng = np.random.RandomState(0)
    host = {"x": rng.rand(batch, dim).astype(np.float32)}

    scope = fluid.Scope()
    exe = fluid.Executor()
    with fluid.scope_guard(scope):
        exe.run(startup)

        # single-device yardstick first: once the compiled path runs,
        # the scope state is mesh-sharded and single-device runs of the
        # same program would see mismatched devices
        dev = jax.devices()[0]
        feed1 = {"x": jax.device_put(host["x"], dev)}
        single_s, _, _ = _measure_cached(exe, prog, loss, feed1, {}, iters)

        # sharded steps=1: raw per-call overhead for visibility
        gen = _reader.device_buffered(
            (host for _ in iter(int, 1)), size=2, compiled=compiled)()
        try:
            call_s, _, _ = _measure_cached(
                exe, compiled, loss, next(gen), {}, iters)
        finally:
            gen.close()

        # sharded chunked regime (the production pipeline): per_step_feed
        # chunks straight from the sharded prefetcher
        gen = _reader.device_buffered(
            (host for _ in iter(int, 1)), size=2, steps=chunk,
            compiled=compiled)()
        try:
            chunk_s, plan_hits, recompiles = _measure_cached(
                exe, compiled, loss, next(gen),
                dict(steps=chunk, per_step_feed=True), iters)
        finally:
            gen.close()
        # the sharded steady state must re-stage nothing per dispatch
        passthrough = len(compiled._steady_tokens) >= 1

    per_step_s = chunk_s / chunk
    return {
        "metric": "sharded_dispatch_host_overhead_per_step_us",
        "value": round(per_step_s * 1e6, 1),
        "unit": "us",
        "single_device_overhead_us": round(single_s * 1e6, 1),
        "ratio_vs_single_device": round(per_step_s / single_s, 2),
        "sharded_call_overhead_us": round(call_s * 1e6, 1),
        "sharded_chunk_overhead_us": round(chunk_s * 1e6, 1),
        "chunk": chunk,
        "steady_passthrough": bool(passthrough),
        "n_devices": n_dev,
        "n_ops": n_ops,
        "iters": iters,
        "plan_cache_hits": int(plan_hits),
        "recompiles_during_measure": int(recompiles),
        "batch": batch,
        "dim": dim,
        "platform": platform,
    }


def build_train_program(layers=LAYERS, dim=DIM, seed=7):
    """The fc-stack block with a REAL Adam (moments + beta pows) — the
    sharded-training bench needs accumulators to exercise the rule-
    inheritance path.  Returns (prog, startup, loss, optimizer)."""
    import paddle_tpu as fluid
    from paddle_tpu import framework

    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = seed
    with framework.program_guard(prog, startup):
        x = fluid.layers.data("x", [dim])
        h = x
        for _ in range(layers):
            h = fluid.layers.fc(h, dim, act="relu")
        loss = fluid.layers.mean(h)
        opt = fluid.optimizer.AdamOptimizer(1e-3)
        opt.minimize(loss)
    return prog, startup, loss, opt


def _train_eps(exe, prog_or_compiled, startup, loss, feed, batch, iters):
    """examples/s over ``iters`` measured steps (after 3 warmup steps),
    each step blocking on its loss fetch."""
    import paddle_tpu as fluid

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)

        def one():
            (out,) = exe.run(prog_or_compiled, feed=feed,
                             fetch_list=[loss], return_numpy=False)
            out.block_until_ready()

        for _ in range(3):  # compile + settle state avals
            one()
        m0 = exe.jit_cache_stats()["misses"]
        t0 = time.perf_counter()
        for _ in range(iters):
            one()
        dt = time.perf_counter() - t0
        recompiles = exe.jit_cache_stats()["misses"] - m0
    return batch * iters / dt, recompiles, scope


def run_sharded_train(layers=LAYERS, dim=DIM, iters=ITERS, batch=BATCH):
    """Training examples/s: replicated single-device vs fsdp-2 through
    the train-rules surface, same block, same feeds."""
    import jax
    from jax.sharding import PartitionSpec as P

    import paddle_tpu as fluid
    from paddle_tpu.sharding import sharded_train_program
    from paddle_tpu.sharding.rules import PartitionRules
    from paddle_tpu.sharding.train import (
        per_device_bytes,
        retire_state_bytes,
        state_bytes,
    )

    platform = jax.devices()[0].platform
    exe = fluid.Executor()
    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(batch, dim).astype(np.float32)}

    def scope_bytes(scope, names):
        vals = {n: scope.get(n) for n in names}
        missing = sorted(n for n, v in vals.items() if v is None)
        assert not missing, (
            "state names not in scope (accumulator_map/param drift?): %s"
            % missing[:4])
        return sum(per_device_bytes(v) for v in vals.values())

    def state_names(prog, opt):
        accs = set(opt.accumulator_map())
        params = {p.name for p in prog.global_block().all_parameters()}
        return params | accs

    # replicated yardstick (fresh program so no mesh-committed state)
    prog_r, startup_r, loss_r, opt_r = build_train_program(layers, dim)
    rep_eps, rep_rc, rep_scope = _train_eps(
        exe, prog_r, startup_r, loss_r, feed, batch, iters)
    rep_bytes = scope_bytes(rep_scope, state_names(prog_r, opt_r))

    # fsdp-2: every param dim-0 sharded, moments inherit via train rules
    prog_s, startup_s, loss_s, opt_s = build_train_program(layers, dim)
    compiled = sharded_train_program(
        prog_s, PartitionRules([(r".", P("fsdp"))], name="bench/fsdp"),
        optimizer=opt_s, mesh_axes={"fsdp": 2})
    shr_eps, shr_rc, shr_scope = _train_eps(
        exe, compiled, startup_s, loss_s, feed, batch, iters)
    names_s = state_names(prog_s, opt_s)
    shr_bytes = scope_bytes(shr_scope, names_s)
    kind_of = compiled.sharding_rules.state_kind
    placed = {n: shr_scope.get(n) for n in names_s
              if shr_scope.get(n) is not None}
    by_kind = state_bytes(kind_of, placed)
    retire_state_bytes()

    n_ops = sum(len(b.ops) for b in prog_s.blocks)
    return {
        "metric": "sharded_train_examples_per_sec",
        "value": round(shr_eps, 1),
        "unit": "examples/sec",
        "replicated_examples_per_sec": round(rep_eps, 1),
        "ratio_vs_replicated": round(shr_eps / rep_eps, 3),
        "state_bytes_per_device_fsdp2": int(shr_bytes),
        "state_bytes_replicated": int(rep_bytes),
        "hbm_ratio_vs_replicated": round(shr_bytes / rep_bytes, 3),
        "state_bytes_by_kind": {k: int(v) for k, v in by_kind.items()},
        "recompiles_during_measure": int(rep_rc + shr_rc),
        "n_devices": 2,
        "n_ops": n_ops,
        "iters": iters,
        "batch": batch,
        "dim": dim,
        "platform": platform,
    }


def run_checkpoint(layers=None, dim=None, batch=BATCH):
    """TrainCheckpoint throughput on an fsdp-2-sharded Adam block:
    save_s + bytes/s, then same-mesh vs cross-mesh (fsdp-4) restore —
    the cross-mesh leg IS the shard-exchange path (exchanged > 0 and a
    bounded host buffer are asserted, same contract as the tests)."""
    import shutil
    import tempfile

    import jax
    from jax.sharding import PartitionSpec as P

    import paddle_tpu as fluid
    from paddle_tpu.faults.checkpoint import TrainCheckpoint
    from paddle_tpu.sharding import sharded_train_program
    from paddle_tpu.sharding.rules import PartitionRules
    from paddle_tpu.sharding.train import retire_state_bytes

    layers = layers or int(os.environ.get("BENCH_CKPT_LAYERS", "4"))
    dim = dim or int(os.environ.get("BENCH_CKPT_DIM", "512"))
    platform = jax.devices()[0].platform
    exe = fluid.Executor()
    prog, startup, loss, opt = build_train_program(layers, dim, seed=11)

    def compiled_for(n):
        return sharded_train_program(
            prog, PartitionRules([(r".", P("fsdp"))],
                                 name="ckptbench/fsdp"),
            optimizer=opt, mesh_axes={"fsdp": n})

    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(batch, dim).astype(np.float32)}
    c2 = compiled_for(2)
    run_dir = tempfile.mkdtemp(prefix="ptpu_ckpt_bench_")
    try:
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            for _ in range(3):  # compile + settle the state avals
                (out,) = exe.run(c2, feed=feed, fetch_list=[loss],
                                 return_numpy=False)
                out.block_until_ready()
            ck = TrainCheckpoint(run_dir, keep=2)
            ck.save(prog, scope, step=1, compiled=c2)  # warm the fs path
            t0 = time.perf_counter()
            path = ck.save(prog, scope, step=2, compiled=c2)
            save_s = time.perf_counter() - t0
        ckpt_bytes = sum(
            os.path.getsize(os.path.join(dp, f))
            for dp, _, fs in os.walk(path) for f in fs)

        # same-mesh restore: direct per-shard re-place
        s_same = fluid.Scope()
        with fluid.scope_guard(s_same):
            exe.run(startup)
            t0 = time.perf_counter()
            ck.restore(prog, s_same, compiled=c2)
            restore_same_s = time.perf_counter() - t0
        same_stats = dict(ck.last_restore_stats or {})
        assert same_stats.get("exchanged", 0) == 0  # direct fast path

        # cross-mesh restore: fsdp-2 shards re-sliced onto fsdp-4
        c4 = compiled_for(4)
        s_cross = fluid.Scope()
        with fluid.scope_guard(s_cross):
            exe.run(startup)
            t0 = time.perf_counter()
            ck.restore(prog, s_cross, compiled=c4)
            restore_cross_s = time.perf_counter() - t0
        cross_stats = dict(ck.last_restore_stats or {})
        assert cross_stats.get("exchanged", 0) > 0  # real exchange
        full_var_bytes = dim * dim * 4
        assert 0 < cross_stats["max_region_bytes"] < full_var_bytes
    finally:
        retire_state_bytes()
        shutil.rmtree(run_dir, ignore_errors=True)

    return {
        "metric": "checkpoint_save_mbytes_per_sec",
        "value": round(ckpt_bytes / save_s / 1e6, 2),
        "unit": "MB/sec",
        "save_s": round(save_s, 4),
        "restore_same_mesh_s": round(restore_same_s, 4),
        "restore_cross_mesh_s": round(restore_cross_s, 4),
        "restore_same_mbytes_per_sec": round(
            ckpt_bytes / restore_same_s / 1e6, 2),
        "restore_cross_mbytes_per_sec": round(
            ckpt_bytes / restore_cross_s / 1e6, 2),
        "checkpoint_bytes": int(ckpt_bytes),
        "cross_mesh_exchanged_regions": int(cross_stats["exchanged"]),
        "cross_mesh_max_region_bytes": int(
            cross_stats["max_region_bytes"]),
        "full_var_bytes": int(full_var_bytes),
        "shard_files_read_cross": int(cross_stats["shard_files_read"]),
        "layers": layers,
        "dim": dim,
        "platform": platform,
    }


def run_train_obs(layers=10, dim=256, batch=256, steps=60, rounds=5):
    """Armed-ledger tax: ``train_from_dataset`` epochs over the same
    compiled Adam block with the step-phase ledger + watchdog armed vs
    disarmed, rounds alternated so drift hits both arms.  Asserts the
    best-round armed tax < 2% and that the armed ledger's books balance
    (phases sum to the epoch wall within its 1% tolerance).  Sized for
    a realistic ~12 ms CPU step (NOT the dispatch bench's deliberately
    tiny block): the armed cost is a fixed few tens of µs per step, and
    judging it against a sub-2 ms toy step measures interpreter churn,
    not the control tower's tax on training anyone runs.  Best-of
    protocol: a noisy host can only slow a round down, so on a tax miss
    up to two more round batches extend both minima before judging."""
    import jax

    import paddle_tpu as fluid
    from paddle_tpu.monitor import train as mtrain

    platform = jax.devices()[0].platform
    exe = fluid.Executor()
    prog, startup, loss, _ = build_train_program(layers, dim, seed=13)
    rng = np.random.RandomState(0)
    feeds = [{"x": rng.rand(batch, dim).astype(np.float32)}
             for _ in range(steps)]

    def timed_feeds(periods):
        # identical instrument in both arms: per-step period from the
        # batch iterator's cadence — the median ignores host spikes an
        # epoch total would charge to whichever arm was running
        prev = time.perf_counter()
        for f in feeds:
            yield f
            now = time.perf_counter()
            periods.append(now - prev)
            prev = now

    scope = fluid.Scope()
    off, on = [], []
    led = None
    with fluid.scope_guard(scope):
        exe.run(startup)

        def epoch(**kw):
            periods = []
            exe.train_from_dataset(program=prog,
                                   dataset=timed_feeds(periods),
                                   fetch_list=[loss], **kw)
            return sorted(periods)[len(periods) // 2]

        def paired_tax():
            # adjacent off/on epochs share the host's speed regime, so
            # their ratio cancels drift; the median over rounds is the
            # tax estimate (min-of-epochs is one lucky epoch, this is a
            # consensus of paired comparisons)
            ratios = sorted(b / a for a, b in zip(off, on))
            return ratios[len(ratios) // 2] - 1.0

        epoch()  # compile + settle state avals
        for batch_no in range(3):
            for _ in range(rounds):
                off.append(epoch())
                led = mtrain.StepPhaseLedger()
                on.append(epoch(phase_ledger=led, watchdog=True))
            if paired_tax() < 0.02:
                break

    snap = led.snapshot()
    booked = sum(snap["phases"].values())
    assert abs(booked - snap["wall_s"]) <= 0.01 * snap["wall_s"] + 1e-6, \
        "ledger books off: %.6f booked vs %.6f wall" % (
            booked, snap["wall_s"])

    best_off, best_on = min(off), min(on)
    tax = paired_tax()
    assert tax < 0.02, "armed train-obs tax %.4f >= 2%%" % tax
    return {
        "metric": "train_obs_armed_tax_pct",
        "value": round(tax * 100.0, 3),
        "unit": "%",
        "disarmed_steps_per_sec": round(1.0 / best_off, 2),
        "armed_steps_per_sec": round(1.0 / best_on, 2),
        "armed_device_execute_frac": round(
            snap["fractions"].get("device_execute", 0.0), 4),
        "steps": steps,
        "rounds": rounds,
        "layers": layers,
        "dim": dim,
        "batch": batch,
        "platform": platform,
    }


def main():
    import sys

    sharded = "--sharded" in sys.argv[1:]
    sharded_train = "--sharded-train" in sys.argv[1:]
    checkpoint = "--checkpoint" in sys.argv[1:]
    train_obs = "--train-obs" in sys.argv[1:]
    import bench_common

    if sharded or sharded_train or checkpoint:
        # a CPU host needs the virtual multi-device platform; only
        # effective when jax has not been imported yet (bench.py's
        # orchestrator sets it in the subprocess env instead)
        os.environ["XLA_FLAGS"] = bench_common.virtual_mesh_env()["XLA_FLAGS"]

    from paddle_tpu import compile_cache

    compile_cache.configure()
    if checkpoint:
        bench_common.emit_result(run_checkpoint())
    elif train_obs:
        bench_common.emit_result(run_train_obs())
    elif sharded_train:
        bench_common.emit_result(run_sharded_train())
    else:
        bench_common.emit_result(run_sharded() if sharded else run())


if __name__ == "__main__":
    main()
