"""Benchmark: DeepFM CTR training step (BASELINE config 5 — sparse
embedding + high-dim lookup).

The HBM-resident dense-table path: a 1M-feature table lives on the chip
and the [N, 39] id lookups ride the gather unit; the deep tower's fc
stack is the matmul work.  Metric = examples/sec (CTR's unit); MFU is
reported for context but lookups dominate, so there's no 50% bar here —
the baseline story is throughput.
"""
import os
import time

import numpy as np

# bs4096 / chunk=160 is the regime BENCH_r05.json's deepfm block
# (248.5k examples/s, 16.48 ms/step) was recorded in.
BATCH = int(os.environ.get("BENCH_DEEPFM_BATCH", "4096"))
STEPS = int(os.environ.get("BENCH_DEEPFM_STEPS", "320"))
CHUNK = int(os.environ.get("BENCH_DEEPFM_CHUNK", "160"))
NUM_FEATURES = int(os.environ.get("BENCH_DEEPFM_FEATURES", "1000000"))
FIELDS = 39
EMBED = 16
# BENCH_DEEPFM_MESH=N: run data-parallel over N local devices with the
# SHARDED device-prefetch pipeline (reader stages each replica's batch
# slice straight into its own HBM).  0/unset = single device.
MESH_DEVICES = int(os.environ.get("BENCH_DEEPFM_MESH", "0"))


def run(batch=BATCH, steps=STEPS, chunk=CHUNK):
    import paddle_tpu as fluid
    from paddle_tpu import device_peaks, framework, models

    place = fluid.TPUPlace(0)  # a chip bench: no chip, no run

    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 42
    with framework.program_guard(prog, startup):
        ids = fluid.layers.data("ids", [FIELDS, 1], dtype="int64")
        vals = fluid.layers.data("vals", [FIELDS])
        lbl = fluid.layers.data("lbl", [1], dtype="int64")
        avg_loss, _ = models.deepfm.deepfm_ctr(
            ids, vals, lbl, num_features=NUM_FEATURES, num_fields=FIELDS,
            embed_dim=EMBED,
        )
        fluid.optimizer.AdamOptimizer(1e-3).minimize(avg_loss)

    n_fc = 0
    for p in prog.all_parameters():
        if "_emb" not in p.name:
            n_fc += int(np.prod([max(1, int(s)) for s in p.shape]))

    # chunk distinct batches per jitted call (per_step_feed);
    # BENCH_FRESH=0 restores the same-batch regime
    import bench_common

    fresh = bench_common.fresh_enabled()
    n_b = chunk if fresh else 1
    rng = np.random.RandomState(0)
    idsv = rng.randint(0, NUM_FEATURES, (n_b, batch, FIELDS, 1)).astype(np.int32)
    valsv = rng.rand(n_b, batch, FIELDS).astype(np.float32)
    lblv = rng.randint(0, 2, (n_b, batch, 1)).astype(np.int32)

    # BENCH_DEEPFM_MESH=N: data-parallel CompiledProgram; the prefetcher
    # then stages each replica's slice per shard (the scale-out regime)
    run_target = prog
    compiled = None
    if MESH_DEVICES > 1:
        from paddle_tpu.parallel.compiled_program import CompiledProgram
        from paddle_tpu.parallel import mesh as mesh_lib

        mesh = mesh_lib.data_parallel_mesh(MESH_DEVICES)
        run_target = compiled = CompiledProgram(prog).with_mesh(mesh)

    scope = fluid.Scope()
    exe = fluid.Executor(place)
    dev = exe._device()
    with fluid.scope_guard(scope):
        exe.run(startup)
        stacked = {"ids": idsv, "vals": valsv, "lbl": lblv}
        # device-prefetch input pipeline (reader.device_buffered): a
        # background thread stages each chunk feed in HBM ahead of the
        # consumer, so h2d of chunk N+1 overlaps compute of chunk N and
        # run() pays only the cached-dispatch rent
        chunks, close_chunks, feed1, run_kw = bench_common.prefetch_feeds(
            stacked, fresh, chunk, dev, compiled=compiled)
        try:
            for _ in range(2):
                (l,) = exe.run(run_target, feed=feed1, fetch_list=[avg_loss], return_numpy=False)
                np.asarray(l)
            (l,) = exe.run(run_target, feed=next(chunks), fetch_list=[avg_loss], **run_kw)
            np.asarray(l)
            # post-warmup the jit cache must never miss — a recompile in
            # the timed loop would fold XLA compile time into examples/sec
            misses0 = exe.jit_cache_stats()["misses"]
            done = 0
            t0 = time.perf_counter()
            while done < steps:
                (l,) = exe.run(run_target, feed=next(chunks), fetch_list=[avg_loss], **run_kw)
                done += chunk
                lv = np.asarray(l)
            dt = time.perf_counter() - t0
        finally:
            close_chunks()
        recompiles = exe.jit_cache_stats()["misses"] - misses0
        from paddle_tpu import monitor

        if recompiles != 0:
            raise AssertionError(
                "deepfm recompiled %d time(s) after warmup on the "
                "device-prefetch path (registry misses=%s)"
                % (recompiles, monitor.counter_value(
                    "executor_jit_cache_misses_total")))

    step_time = dt / done
    flops = 6.0 * n_fc * batch  # deep tower fwd+bwd; lookups aren't matmul
    mfu = (flops / step_time) / device_peaks.peak_flops(dev)
    return {
        "metric": "deepfm_ctr_examples_per_sec_per_chip",
        "value": round(batch / step_time, 1),
        "unit": "examples/sec",
        "step_time_ms": round(step_time * 1e3, 2),
        "mfu": round(mfu, 4),
        "batch": batch,
        "num_features": NUM_FEATURES,
        "embed_dim": EMBED,
        "per_step_feed": fresh,
        "chunk": chunk,
        "device_prefetch": True,
        "mesh_devices": MESH_DEVICES,
        "recompiles_after_warmup": int(recompiles),
        "platform": dev.platform,
        "device_kind": dev.device_kind,
        "loss": float(lv),
    }


# ---------------------------------------------------------------------------
# Sparse scale-out stages (ISSUE 14): mesh-resident row-sharded tables,
# serial vs overlapped PS prefetch, and the Zipf hot-id serving cache.
# Env knobs (defaults sized for the 90 s deepfm_sparse budget):
SPARSE_FEATURES = int(os.environ.get("BENCH_DEEPFM_SPARSE_FEATURES",
                                     "1000000"))
SPARSE_BATCH = int(os.environ.get("BENCH_DEEPFM_SPARSE_BATCH", "512"))
SPARSE_STEPS = int(os.environ.get("BENCH_DEEPFM_SPARSE_STEPS", "16"))
SPARSE_MESH = int(os.environ.get("BENCH_DEEPFM_SPARSE_MESH", "8"))
# Simulated PS network RTT for the overlap drill: the in-process
# loopback server has ~zero wire latency, so without it the drill
# measures only CPU contention, not the round trip overlap actually
# hides.  Injected via the ps.pull delay fault (a sleep — no CPU), paid
# identically by BOTH legs; 0 disables.
SPARSE_NET_MS = float(os.environ.get("BENCH_DEEPFM_SPARSE_NET_MS", "30"))
SPARSE_OVERLAP_STEPS = int(os.environ.get(
    "BENCH_DEEPFM_SPARSE_OVERLAP_STEPS", "24"))
# int8-row leg: a smaller table (the bytes ratio is size-independent —
# exactly (D + 4) / (4 * D) per row) trained twice (fp32 vs int8 rows)
# for per-step loss parity at the pinned rtol.
SPARSE_INT8_FEATURES = int(os.environ.get(
    "BENCH_DEEPFM_SPARSE_INT8_FEATURES", "200000"))
SPARSE_INT8_STEPS = int(os.environ.get(
    "BENCH_DEEPFM_SPARSE_INT8_STEPS", "8"))
SPARSE_INT8_RTOL = float(os.environ.get(
    "BENCH_DEEPFM_SPARSE_INT8_RTOL", "2e-3"))


def _sparse_model(num_features, fields=8, embed=16, seed=42,
                  deep_layers=(64, 64)):
    import paddle_tpu as fluid
    from paddle_tpu import framework, models

    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = seed
    with framework.program_guard(prog, startup):
        ids = fluid.layers.data("ids", [fields, 1], dtype="int64")
        vals = fluid.layers.data("vals", [fields])
        lbl = fluid.layers.data("lbl", [1], dtype="int64")
        avg_loss, _ = models.deepfm.deepfm_ctr(
            ids, vals, lbl, num_features=num_features, num_fields=fields,
            embed_dim=embed, deep_layers=deep_layers, distributed_emb=True,
        )
        fluid.optimizer.SGDOptimizer(1e-2).minimize(avg_loss)
    return prog, startup, avg_loss


def _sparse_feeds(num_features, batch, n, fields=8, seed=0):
    rng = np.random.RandomState(seed)
    return [
        {"ids": rng.randint(0, num_features,
                            (batch, fields, 1)).astype("int64"),
         "vals": rng.rand(batch, fields).astype("float32"),
         "lbl": rng.randint(0, 2, (batch, 1)).astype("int64")}
        for _ in range(n)
    ]


def _run_mesh_tables(steps, batch):
    """Mesh-resident row-sharded tables: examples/s + per-device table
    bytes at a table whose REPLICATED form exceeds one virtual chip's
    1/n share (the sharded layout is what makes it placeable)."""
    import paddle_tpu as fluid
    from paddle_tpu.parallel import mesh as mesh_lib
    from paddle_tpu.parallel.compiled_program import CompiledProgram
    from paddle_tpu.sharding.sparse import bind_mesh_tables

    prog, startup, avg_loss = _sparse_model(SPARSE_FEATURES)
    mesh = mesh_lib.make_mesh({"mp": SPARSE_MESH})
    compiled = CompiledProgram(prog).with_mesh(mesh)
    rt = bind_mesh_tables(compiled, optimizer="sgd", lr=1e-2,
                          initializer="uniform")
    feeds = _sparse_feeds(SPARSE_FEATURES, batch, 4)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        # warm every bucket the id mix can produce + the program shape
        from paddle_tpu.executor import pow2_id_bucket

        uniq_counts = {pow2_id_bucket(len(np.unique(f["ids"])))
                       for f in feeds}
        rt.warmup(sorted(uniq_counts))
        for f in feeds[:2]:
            (l,) = exe.run(compiled, feed=dict(f), fetch_list=[avg_loss])
            np.asarray(l)
        c0, m0 = rt.compiles, exe.jit_cache_stats()["misses"]
        done = 0
        t0 = time.perf_counter()
        while done < steps:
            (l,) = exe.run(compiled, feed=dict(feeds[done % len(feeds)]),
                           fetch_list=[avg_loss])
            np.asarray(l)
            done += 1
        dt = time.perf_counter() - t0
        recompiles = (exe.jit_cache_stats()["misses"] - m0) + (
            rt.compiles - c0)
    stats = rt.stats()["tables"]
    per_dev = sum(t["bytes_per_device"] for t in stats.values())
    replicated = sum(t["replicated_bytes"] for t in stats.values())
    out = {
        "examples_per_sec": round(batch * done / dt, 1),
        "table_bytes_per_device": int(per_dev),
        "table_bytes_replicated": int(replicated),
        "per_device_share_of_replicated": round(per_dev / replicated, 4),
        "n_shards": SPARSE_MESH,
        "recompiles_after_warmup": int(recompiles),
    }
    rt.close()
    if recompiles != 0:
        raise AssertionError(
            "mesh-table stage recompiled %d time(s) after warmup"
            % recompiles)
    return out


def _run_int8_rows(steps, batch):
    """int8 embedding rows (ISSUE 18): the same DeepFM train drill on
    mesh-resident tables storing fp32 vs int8 rows (per-row fp32 scales
    sharded alongside; dequant after the gather, before the psum; the
    grad push dequant-accumulates and requantizes whole rows).  Per-step
    loss parity at the pinned rtol and per-device table bytes <= 0.35x
    fp32 are both asserted — the JSON block carries the measured
    numbers either way."""
    import paddle_tpu as fluid
    from paddle_tpu.parallel import mesh as mesh_lib
    from paddle_tpu.parallel.compiled_program import CompiledProgram
    from paddle_tpu.sharding.sparse import bind_mesh_tables

    feeds = _sparse_feeds(SPARSE_INT8_FEATURES, batch, steps, seed=2)

    def leg(row_dtype):
        prog, startup, avg_loss = _sparse_model(SPARSE_INT8_FEATURES)
        mesh = mesh_lib.make_mesh({"mp": SPARSE_MESH})
        compiled = CompiledProgram(prog).with_mesh(mesh)
        rt = bind_mesh_tables(compiled, optimizer="sgd", lr=1e-2,
                              initializer="zeros", row_dtype=row_dtype)
        try:
            from paddle_tpu.executor import pow2_id_bucket

            exe = fluid.Executor(fluid.CPUPlace())
            losses = []
            with fluid.scope_guard(fluid.Scope()):
                exe.run(startup)
                rt.warmup(sorted({pow2_id_bucket(len(np.unique(f["ids"])))
                                  for f in feeds}))
                t0 = time.perf_counter()
                for f in feeds:
                    (l,) = exe.run(compiled, feed=dict(f),
                                   fetch_list=[avg_loss])
                    losses.append(float(np.asarray(l)))
                dt = time.perf_counter() - t0
            tables = {n: dict(t)
                      for n, t in rt.stats()["tables"].items()}
            return losses, tables, round(batch * len(feeds) / dt, 1)
        finally:
            rt.close()

    l32, t32, eps32 = leg("fp32")
    l8, t8, eps8 = leg("int8")
    worst = max(abs(a - b) / max(1e-9, abs(a)) for a, b in zip(l32, l8))
    # per-table bytes: the acceptance bound applies to the real
    # embedding table (dim >= 8 — the ratio is (D + 4) / (4 * D)); the
    # FM first-order dim-1 table is where int8 does NOT pay (a 4-byte
    # scale per 1-byte row) and its ratio rides the block as the
    # documented counterexample, unasserted.
    per_table = {
        name: {
            "dim": t8[name]["dim"],
            "bytes_per_device_fp32": int(t32[name]["bytes_per_device"]),
            "bytes_per_device_int8": int(t8[name]["bytes_per_device"]),
            "bytes_vs_fp32": round(
                t8[name]["bytes_per_device"]
                / t32[name]["bytes_per_device"], 4),
        }
        for name in sorted(t8)
    }
    out = {
        "train_parity_max_rel_err": round(worst, 6),
        "train_parity_rtol": SPARSE_INT8_RTOL,
        "tables": per_table,
        "examples_per_sec_fp32": eps32,
        "examples_per_sec_int8": eps8,
        "num_features": SPARSE_INT8_FEATURES,
        "steps": steps,
    }
    if worst > SPARSE_INT8_RTOL:
        raise AssertionError(
            "int8-row train loss diverged from fp32 rows: %s" % out)
    wide = {n: t for n, t in per_table.items() if t["dim"] >= 8}
    if not wide:
        raise AssertionError("no embedding table with dim >= 8: %s" % out)
    for name, t in wide.items():
        if t["bytes_vs_fp32"] > 0.35:
            raise AssertionError(
                "int8 rows on table %r rent more than 0.35x fp32 "
                "per-device bytes: %s" % (name, out))
    return out


def _run_prefetch_overlap(steps, batch):
    """Serial vs overlapped PS prefetch (both async-push mode, so the
    ONLY delta is whether batch N+1's pulls hide behind batch N):
    examples/s must strictly improve, and the
    executor_ps_pull_overlap_seconds_total accounting shows the hidden
    latency beside the visible wait.  Both legs pay the same simulated
    PS network RTT (SPARSE_NET_MS via the ps.pull delay fault) — the
    loopback server has none, and the RTT is exactly what the overlap
    exists to hide."""
    import contextlib

    import paddle_tpu as fluid
    from paddle_tpu import faults
    from paddle_tpu.distributed.ps import ParameterServer

    feeds = _sparse_feeds(SPARSE_FEATURES, batch, steps, seed=1)
    net = (faults.armed("ps.pull=delay:%.4f" % (SPARSE_NET_MS / 1e3))
           if SPARSE_NET_MS > 0 else contextlib.nullcontext())

    def drill(overlap):
        server = ParameterServer().start()
        try:
            # a real tower (the train step must have compute for the
            # pull to hide BEHIND — the lookup-only module is pull-bound
            # and caps the overlap win at ~1.1x)
            prog, startup, avg_loss = _sparse_model(
                SPARSE_FEATURES, deep_layers=(512, 512, 512))
            fluid.distributed.bind_distributed_tables(
                prog, [server.endpoint], optimizer="sgd", lr=1e-2,
                initializer="zeros", async_mode=True)
            prog._sparse_overlap = overlap
            exe = fluid.Executor(fluid.CPUPlace())
            scope = fluid.Scope()
            with fluid.scope_guard(scope):
                exe.run(startup)
                # warm the EXACT timed entry (no fetch list — the epoch
                # below runs none; a different fetch set is a different
                # jit key and its compile would land in the window)
                for _ in range(2):
                    exe.run(prog, feed=dict(feeds[0]))
                t0 = time.perf_counter()
                exe.train_from_dataset(program=prog, dataset=feeds,
                                       scope=scope)
                dt = time.perf_counter() - t0
            stats = exe.jit_cache_stats()
            prog._ps_communicator.stop()
            return (round(batch * len(feeds) / dt, 1),
                    round(stats["ps_pull_overlap_s"], 4),
                    round(stats["ps_pull_wait_s"], 4))
        finally:
            server.stop()

    with net:
        # best-of-2 per leg: a transient CPU-contention spike in one
        # measurement window (the legs share cores with the pull
        # threads and anything else on the box) must not decide the
        # strict-improvement comparison
        serial_eps = max(drill(False)[0] for _ in range(2))
        runs = [drill(True) for _ in range(2)]
        overlap_eps, hidden_s, wait_s = max(runs, key=lambda r: r[0])
    out = {
        "serial_examples_per_sec": serial_eps,
        "overlapped_examples_per_sec": overlap_eps,
        "speedup": round(overlap_eps / serial_eps, 3),
        "pull_hidden_s": hidden_s,
        "pull_wait_s": wait_s,
        "simulated_net_ms": SPARSE_NET_MS,
    }
    if overlap_eps <= serial_eps:
        raise AssertionError(
            "overlapped sparse prefetch did not improve examples/s: "
            "%s" % out)
    return out


def _run_zipf_serving():
    """Zipf(1.0) hot-id traffic against the serving cache tier: lookup
    p99 + hit ratio with the cache on vs the raw PS path."""
    from paddle_tpu.distributed.ps import ParameterServer, PSClient
    from paddle_tpu.serving.embedding_cache import EmbeddingRowCache

    TABLE_ROWS = 200_000
    ACTIVE = 20_000
    CAPACITY = 10_000  # 5% of the table
    B, WARM, MEAS = 1024, 30, 30
    server = ParameterServer().start()
    client = PSClient([server.endpoint])
    client.create_table("zipf", EMBED, initializer="uniform", seed=3)
    try:
        rng = np.random.RandomState(0)
        p = 1.0 / np.arange(1, ACTIVE + 1)
        p /= p.sum()
        cdf = np.cumsum(p)

        def batch():
            ids = np.searchsorted(cdf, rng.rand(B)).astype(np.int64)
            uniq, counts = np.unique(ids, return_counts=True)
            return uniq, counts

        def measure(cache):
            lats, pulled = [], 0
            for _ in range(MEAS):
                uniq, counts = batch()
                t0 = time.perf_counter()
                if cache is not None:
                    cache.lookup_through(client, "zipf", uniq,
                                         counts=counts)
                else:
                    client.pull_sparse("zipf", uniq)
                    pulled += len(uniq)
                lats.append(time.perf_counter() - t0)
            return lats, pulled

        off_lats, off_pulled = measure(None)
        cache = EmbeddingRowCache(capacity_rows=CAPACITY, name="bench")
        for _ in range(WARM):
            uniq, counts = batch()
            cache.lookup_through(client, "zipf", uniq, counts=counts)
        s0 = cache.stats()
        on_lats, _ = measure(cache)
        s1 = cache.stats()
        d_hits = s1["hits"] - s0["hits"]
        d_miss = s1["misses"] - s0["misses"]
        out = {
            "hit_ratio": round(d_hits / (d_hits + d_miss), 4),
            "cache_capacity_rows": CAPACITY,
            "cache_pct_of_table": round(CAPACITY / TABLE_ROWS, 4),
            # the PS offload: unique rows actually fetched during the
            # measured window, cache on vs off (the capacity win even
            # on a loopback server whose RTT is ~zero)
            "ps_rows_pulled_cache_on": int(
                s1["pulled_rows"] - s0["pulled_rows"]),
            "ps_rows_pulled_cache_off": int(off_pulled),
            "lookup_p99_ms_cache_on": round(
                float(np.percentile(on_lats, 99)) * 1e3, 3),
            "lookup_p99_ms_cache_off": round(
                float(np.percentile(off_lats, 99)) * 1e3, 3),
            "lookup_p50_ms_cache_on": round(
                float(np.percentile(on_lats, 50)) * 1e3, 3),
            "lookup_p50_ms_cache_off": round(
                float(np.percentile(off_lats, 50)) * 1e3, 3),
        }
        cache.close()
        return out
    finally:
        client.close()
        server.stop()


def run_sparse():
    """The deepfm_sparse bench stage: one JSON line with the four
    sparse scale-out sub-stages (mesh tables, prefetch overlap, the
    Zipf cache drill, and the int8-row fp32-parity leg)."""
    import jax

    platform = jax.devices()[0].platform
    line = {
        "metric": "deepfm_sparse_mesh_examples_per_sec",
        "unit": "examples/sec",
        "platform": platform,
        "num_features": SPARSE_FEATURES,
        "batch": SPARSE_BATCH,
    }
    mesh_stage = _run_mesh_tables(SPARSE_STEPS, SPARSE_BATCH)
    line["value"] = mesh_stage["examples_per_sec"]
    line["mesh_tables"] = mesh_stage
    line["prefetch_overlap"] = _run_prefetch_overlap(
        SPARSE_OVERLAP_STEPS, SPARSE_BATCH)
    line["zipf_serving"] = _run_zipf_serving()
    line["int8_rows"] = _run_int8_rows(SPARSE_INT8_STEPS, SPARSE_BATCH)
    return line


if __name__ == "__main__":
    import json
    import sys

    if "--sparse" in sys.argv[1:]:
        import bench_common

        os.environ.update(bench_common.virtual_mesh_env(SPARSE_MESH))
        print(json.dumps(run_sparse()))
    else:
        print(json.dumps(run()))
