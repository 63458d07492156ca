"""Benchmark: dynamic-batching serving (paddle_tpu/serving/) sustained
throughput + latency for two inference endpoints — LeNet (dense vision)
and DeepFM (sparse CTR).

Prints ONE JSON line like bench.py: per-endpoint sustained rows/sec,
request p50/p99 latency, mean batch occupancy, warmup compile count,
and the recompile counter (must stay 0 after warmup — the bucket
ladder's whole point).  Traffic is an open-loop storm of concurrent
submitters with mixed request sizes, so the DynamicBatcher actually
coalesces rather than replaying fixed batches.

Since PR 3 the server worker runs the non-blocking fetch path
(AnalysisPredictor ``return_numpy=False``): batch N's d2h materialize
overlaps batch N+1's merge/pad/dispatch, so the numbers here include
the overlap discipline a production deployment would run with
(``d2h_overlap`` in the line records it).

Env knobs: BENCH_SERVING_THREADS (default 8), BENCH_SERVING_REQUESTS
(per thread, default 100), BENCH_SERVING_MAX_BATCH (default 16),
BENCH_SERVING_TIMEOUT_MS (batch window, default 2),
BENCH_SERVING_TRACE (JSONL trace path, default off).

``--trace-out PATH`` (or $BENCH_SERVING_TRACE_OUT) additionally runs
the storm under a flight recorder and dumps the SLOWEST 1% of bench
requests' full span trees (client -> queue wait -> batch -> executor
phases, one trace id each) to PATH alongside the JSON line — the
latency tail, explained.  Without it the bench asserts the recorder
stays absent and every span gate off: zero recorder overhead on the
measured warm path.

``--wire loopback`` (or $BENCH_SERVING_WIRE=loopback) measures the
WIRE TAX instead: each endpoint is benched in-process AND over
loopback TCP through a launched serving child
(``paddle_tpu.serving.wire``), and the JSON line reports the
client-observed p50/p99 for both plus their delta
(``wire_tax_p50_ms``/``wire_tax_p99_ms``) — the cost of the codec +
HTTP hop as a measured number.  The child warms up through the same
persistent compile cache, and its recompile counter must stay 0
(asserted via ``/statusz`` over the wire).

``--overload`` (or $BENCH_SERVING_OVERLOAD=1) runs the graceful-
degradation sweep instead: measure the endpoint's saturation
throughput closed-loop, then drive OPEN-loop offered load at 1x/2x/3x
saturation with mixed priority classes and record, per stage and per
priority, goodput / shed / expired counts and client-observed p99 —
plus the adaptive admit limit and brownout level the server settled
at, and the median ``retry_after_ms`` hint the sheds carried.  The
headline value is goodput at 3x as a fraction of saturation: a
production edge must keep it flat past the knee (the chaos suite
asserts the >= 0.7 floor; the bench records the curve).
Env knobs: BENCH_OVERLOAD_SECONDS (per stage, default 3),
BENCH_OVERLOAD_MULTIPLIERS (default "1,2,3").

``--decode`` (or $BENCH_SERVING_DECODE=1) benches the CONTINUOUS-
BATCHING decode scheduler (``serving.decode``) on a transformer-LM
endpoint under mixed prompt/decode traffic: the same interleaved
long/short workload is decoded request-at-a-time (admit in groups of
``max_slots``, wait out each group — what the request-batching server
does to an autoregressive endpoint) and continuously (finished
sequences free slots mid-flight, queued prompts join at the next
tick).  The line reports tokens/s for both, their ratio (the
acceptance bar is >= 2x on this mixed workload), streamed-client TTFT
percentiles, the late-arrival drill (a request submitted mid-decode
must reach its first token before the in-flight batch finishes), the
prefill/decode token ratio, and the recompile count (0 after warmup —
the slot pool's bucket ladders keep the compiled-shape set closed).
Env knobs: BENCH_DECODE_REQUESTS (default 24), BENCH_DECODE_SLOTS
(default 8), BENCH_DECODE_STEPS (per tick, default 4).

Since decode tier 2 the ``--decode`` line also carries the three
independently toggleable decode-tier-2 legs, each measured against its
own off-baseline on the same staggered drill:

* ``prefix_cache``: ten requests sharing a 48-token prompt prefix,
  submitted staggered (each waits its result so the freed slot's
  prefix KV is offered before the next probe) against a server with
  and without a :class:`serving.prefix_cache.PrefixKVCache` — the
  prefill-token counter must drop >= 50% with the cache on (asserted),
  and TTFT p50 rides the line for both.
* ``speculative``: the same prompts decoded with and without
  draft-then-verify rounds on ONE server at ``steps_per_tick=1`` (the
  dispatch-bound regime a k-wide accepted run amortizes), using a
  unigram transition-table draft distilled from the baseline pass's
  own greedy rollouts.  Greedy-exact acceptance pins parity — the
  speculative pass must emit bit-identical sequences (asserted) — and
  the line reports tokens/s both ways plus the acceptance telemetry.
* ``affinity``: a REAL 2-child wire fleet hosting one saved decode
  endpoint with per-child prefix caches, driven by returning
  "sessions" (prompts sharing a per-session head) through a
  prefix-affinity balancer and a plain least-loaded one — per-child
  ``/healthz`` prefix-cache hit deltas, fleet ``affinity_hits``, and
  both children's ``/statusz`` jit-cache misses (must be 0; asserted)
  ride the line.

Env knobs: BENCH_DECODE_PREFIX_REQUESTS (default 10),
BENCH_DECODE_SPEC_REQUESTS / BENCH_DECODE_SPEC_GEN /
BENCH_DECODE_SPEC_K (default 8/24/8), BENCH_DECODE_AFFINITY_SESSIONS /
BENCH_DECODE_AFFINITY_ROUNDS (default 4/3).

``--sharded`` (or $BENCH_SERVING_SHARDED=1) benches MODEL-PARALLEL
serving (``paddle_tpu.sharding``): the same transformer-LM endpoint
served replicated vs as a 2-way tp group on the 8-device CPU mesh
(the canonical layout rides the saved model's manifest, so the
predictor reconstructs the placement on load exactly like a serving
child would).  The line reports QPS for both, the post-warmup
recompile count (must stay 0 — sharded out_shardings pin the state
layout, so the jit-cache shape set stays closed), and the per-device
HBM footprint vs the replicated baseline (sharded params hold 1/tp of
their bytes per device — the capacity headroom the layout buys).
Env knobs: BENCH_SHARDED_TP (default 2).

``--long-context`` (or $BENCH_SERVING_LONG_CONTEXT=1) benches
LONG-CONTEXT serving: the fused-attention transformer LM at a sequence
length whose UNSHARDED activations exceed the per-chip budget
(BENCH_LC_CHIP_BUDGET_BYTES, default 16 MiB), served three ways —
unsharded, sp-2, sp-4 (the canonical ``sp`` layout rides the manifest;
attention runs as ring attention over the sp mesh axis) — plus the
same export as a pp-2 ``PipelinePredictor`` micro-batched (M=4) vs
sequential (M=1).  The line reports tokens/s and activation
bytes/device per leg and asserts: sp-4 logits match unsharded at
rtol 2e-4, sp-4 activation bytes/device are exactly 1/4 of unsharded
(and fit the budget the unsharded footprint exceeds), a post-warmup
mixed-length storm never recompiles, pipelined output is exact, and
the executed pp-2/M-4 schedule's bubble ratio is < 0.5 (the
sequential M=1 schedule pins the 0.5 worst case it must beat).
Env knobs: BENCH_LC_SEQ (default 512), BENCH_LC_BATCH (4),
BENCH_LC_REPS (6), BENCH_LC_CHIP_BUDGET_BYTES.

``--precision`` (or $BENCH_SERVING_PRECISION=1) benches MIXED-PRECISION
serving (``contrib/mixed_precision`` pointed at the inference path):
LeNet and DeepFM each served plain fp32 vs under a bf16 precision
policy (the policy rides the saved-model manifest; the loader rebuilds
the rewrite and casts hoisted params to bf16 at placement time).  The
line reports QPS and p99 both ways plus their ratios, the export-time
and runtime parity vs fp32 (both must sit inside the exported rtol
bound), per-endpoint padding waste, and the recompile counters (0
after warmup for BOTH the bf16 default and the per-request fp32
opt-out — warmup compiles every bucket rung for every serving dtype).
The acceptance leg launches a REAL 2-child wire fleet over the bf16
manifest dir: children reconstruct the variant from the manifest,
fleet warmup covers both ladders in both processes, a mixed
bf16/fp32-opt-out storm runs through the balancer, and each child's
``/statusz`` recompile count must stay 0.

NOTE on the CPU backend the qps ratio is typically < 1: CPUs emulate
bf16 (upcast-compute-downcast), so the variant pays cast cost with no
bandwidth win.  The line measures the HARNESS (parity, recompiles,
manifest transport, both ladders warmed); the speedup itself is a TPU
number — bf16 halves the HBM bytes an inference step moves, which is
the binding constraint at MFU 0.13 (BENCH_r05).

``--fleet-obs`` (or $BENCH_SERVING_FLEET_OBS=1) benches the FLEET
OBSERVABILITY control tower: one REAL 2-child wire fleet serving the
LeNet endpoint, driven by the same staggered-arrival storm twice —
once bare, once with the balancer's federated admin tier up, the
scraper riding the health loop, and a latency SLO burn-rate engine
evaluating every 100 ms.  The line asserts the tower's three
contracts: (1) the federated ``/metrics`` carries every child
``serving_*`` counter series verbatim under a distinct ``backend=``
label and ``/statusz``'s fleet aggregate equals the children's sum
exactly; (2) an injected-latency window (``fleet.dispatch`` delay
fault in the balancer) drives the fast-burn pair of the p99 SLO to
fire — visible in ``/sloz`` and as a critical ``slo/fired`` event in
``/eventz`` — and clean traffic clears it again; (3) observability-on
QPS stays within 2% of bare (BENCH_OBS_QPS_FLOOR, default 0.98) and
both children's recompile counters stay 0.
Env knobs: BENCH_OBS_QPS_FLOOR, BENCH_OBS_FAULT_DELAY_S (default 0.6).
"""
import json
import os
import tempfile
import threading
import time

import numpy as np

THREADS = int(os.environ.get("BENCH_SERVING_THREADS", "8"))
REQUESTS = int(os.environ.get("BENCH_SERVING_REQUESTS", "100"))
MAX_BATCH = int(os.environ.get("BENCH_SERVING_MAX_BATCH", "16"))
TIMEOUT_MS = float(os.environ.get("BENCH_SERVING_TIMEOUT_MS", "2"))
# request sizes cycle through this ladder so batches mix row counts
REQ_SIZES = (1, 2, 3, 4)
# Every launched serving child here measures the wire or the control
# plane on a toy endpoint, so its platform is pinned to the CPU
# explicitly: a chip belongs to one process, and a child that inherited
# the parent's accelerator platform would fail or hang behind it.
CPU_CHILD_ENV = {"JAX_PLATFORMS": "cpu"}


def _save_lenet(dirname, precision=None):
    import paddle_tpu as fluid
    from paddle_tpu import framework, models

    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 11
    with framework.program_guard(prog, startup):
        img = fluid.layers.data("img", [1, 28, 28])
        lbl = fluid.layers.data("lbl", [1], dtype="int64")
        _, _, pred = models.lenet5(img, lbl)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.save_inference_model(dirname, ["img"], [pred], exe, prog,
                                   precision_policy=precision)

    def make_rows(n, rng):
        return {"img": rng.uniform(-1, 1, (n, 1, 28, 28)).astype(np.float32)}

    return make_rows


def _save_deepfm(dirname, num_features=10000, num_fields=39, precision=None):
    import paddle_tpu as fluid
    from paddle_tpu import framework, models

    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 13
    with framework.program_guard(prog, startup):
        ids = fluid.layers.data("feat_ids", [num_fields, 1], dtype="int64")
        vals = fluid.layers.data("feat_vals", [num_fields])
        lbl = fluid.layers.data("lbl", [1], dtype="int64")
        _, prob = models.deepfm_ctr(
            ids, vals, lbl, num_features=num_features, num_fields=num_fields,
            embed_dim=8, deep_layers=(64, 64))
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.save_inference_model(dirname, ["feat_ids", "feat_vals"], [prob],
                                   exe, prog, precision_policy=precision)

    def make_rows(n, rng):
        return {
            "feat_ids": rng.randint(0, num_features, (n, num_fields, 1)).astype(np.int64),
            "feat_vals": rng.uniform(0, 1, (n, num_fields)).astype(np.float32),
        }

    return make_rows


def _trace_out_path(argv=None):
    """Opt-in flight-recorder dump target: ``--trace-out PATH`` /
    ``--trace-out=PATH`` on the command line, or $BENCH_SERVING_TRACE_OUT."""
    import bench_common

    return bench_common.flag_path(
        "--trace-out", "BENCH_SERVING_TRACE_OUT", argv)


def _bench_endpoint(name, save_fn):
    from paddle_tpu import monitor, serving
    from paddle_tpu.inference import AnalysisConfig, create_paddle_predictor
    from paddle_tpu.monitor import flight as _flight

    with tempfile.TemporaryDirectory() as tmp:
        d = os.path.join(tmp, name)
        make_rows = save_fn(d)
        predictor = create_paddle_predictor(AnalysisConfig(d))
        server = serving.InferenceServer(
            predictor, max_batch_size=MAX_BATCH, batch_timeout_ms=TIMEOUT_MS,
            queue_capacity=max(64, THREADS * 8), name=name)
        t0 = time.perf_counter()
        warmup_compiles = server.warmup()
        warmup_s = time.perf_counter() - t0
        cli = serving.Client(server)
        if _flight.get() is None:
            # recorder at defaults (absent): every span gate the serving
            # and executor hot paths consult must be off, so the number
            # below carries ZERO recorder overhead (the --trace-out mode
            # opts into the capture cost explicitly)
            assert not monitor.recording(), (
                "span recording leaked into the bench warm path")

        total_rows = [0] * THREADS
        shed = [0] * THREADS
        start = threading.Barrier(THREADS + 1)
        # padding-waste accounting around the storm only (warmup pads
        # every rung fully by construction — counting it would dilute
        # the number the ladder autotuner is judged on): the predictor
        # counters have been collected since PR 2; this REPORTS them
        padded0 = monitor.counter_value("predictor_padded_rows_total")
        waste0 = monitor.counter_value("predictor_padding_waste_rows_total")

        def storm(tid):
            rng = np.random.RandomState(100 + tid)
            start.wait()
            for i in range(REQUESTS):
                n = REQ_SIZES[(tid + i) % len(REQ_SIZES)]
                try:
                    cli.infer(make_rows(n, rng))
                    total_rows[tid] += n
                except serving.ServerOverloaded:
                    shed[tid] += 1  # open-loop storm may outrun the queue

        threads = [threading.Thread(target=storm, args=(t,)) for t in range(THREADS)]
        for t in threads:
            t.start()
        start.wait()
        t0 = time.perf_counter()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        # the PR-1 zero-recompile guarantee, enforced IN the bench via
        # the monitor registry (not just tests): after warmup the jit
        # cache must never miss, or the rows/sec number is a lie that
        # includes XLA compiles.  Read BEFORE stop(): every request has
        # completed (cli.infer blocks), and stop() retires this server's
        # series from the registry exposition.
        from paddle_tpu import monitor

        registry_recompiles = monitor.counter_value(
            "serving_recompiles_total", default=-1, server=name)
        padded_rows = (
            monitor.counter_value("predictor_padded_rows_total") - padded0)
        waste_rows = (
            monitor.counter_value("predictor_padding_waste_rows_total")
            - waste0)
        server.stop(drain=True)
        m = server.metrics()
        if registry_recompiles != 0 or m["recompiles"] != 0:
            raise AssertionError(
                "endpoint %r recompiled after warmup: registry=%s snapshot=%s"
                % (name, registry_recompiles, m["recompiles"]))
        rows = sum(total_rows)
        sharding_stats = None
        if getattr(predictor, "sharded", False):
            sharding_stats = predictor.sharding_stats()
        return {
            "rows_per_sec": round(rows / elapsed, 1),
            **({"sharding": sharding_stats} if sharding_stats else {}),
            "d2h_overlap": bool(server._nonblocking),
            "requests_per_sec": round(m["completed"] / elapsed, 1),
            "latency_p50_ms": m["latency_p50_ms"],
            "latency_p99_ms": m["latency_p99_ms"],
            "mean_batch_occupancy": m["mean_batch_occupancy"],
            # the bucket ladder's measured rent: padding rows computed
            # then sliced away, as a fraction of all padded rows — the
            # number an autotuned ladder must strictly reduce
            "padding_waste_ratio": (
                round(waste_rows / padded_rows, 4) if padded_rows else None),
            "padding_waste_rows": int(waste_rows),
            "arrival_histogram": m["arrival_histogram"],
            "batches": m["batches"],
            "completed": m["completed"],
            "shed": m["shed"],
            "expired": m["expired"],
            "recompiles_after_warmup": m["recompiles"],
            "warmup_compiles": warmup_compiles,
            "warmup_s": round(warmup_s, 2),
            "bucket_ladder": m["bucket_ladder"],
            "elapsed_s": round(elapsed, 2),
        }


def _bench_endpoint_wire(name, save_fn):
    """Client-observed latency for one endpoint served by a launched
    child process over loopback TCP (the wire half of the tax
    measurement; the in-process half is _bench_endpoint)."""
    from paddle_tpu.serving import wire

    with tempfile.TemporaryDirectory() as tmp:
        d = os.path.join(tmp, name)
        make_rows = save_fn(d)
        handle = wire.launch_server(
            d, name="%s-wire" % name, max_batch_size=MAX_BATCH,
            batch_timeout_ms=TIMEOUT_MS,
            queue_capacity=max(64, THREADS * 8), env=CPU_CHILD_ENV)
        cli = wire.RemoteClient(handle.address)
        try:
            t0 = time.perf_counter()
            warmup_compiles = handle.warmup()
            warmup_s = time.perf_counter() - t0

            lats = [[] for _ in range(THREADS)]
            shed = [0] * THREADS
            start = threading.Barrier(THREADS + 1)

            def storm(tid):
                import paddle_tpu.serving as serving

                rng = np.random.RandomState(200 + tid)
                start.wait()
                for i in range(REQUESTS):
                    n = REQ_SIZES[(tid + i) % len(REQ_SIZES)]
                    feed = make_rows(n, rng)
                    r0 = time.perf_counter()
                    try:
                        cli.infer(feed)
                        lats[tid].append(time.perf_counter() - r0)
                    except serving.ServerOverloaded:
                        shed[tid] += 1

            threads = [threading.Thread(target=storm, args=(t,))
                       for t in range(THREADS)]
            for t in threads:
                t.start()
            start.wait()
            t0 = time.perf_counter()
            for t in threads:
                t.join()
            elapsed = time.perf_counter() - t0

            status = wire.HttpTransport(*handle.address).get_json("/statusz")
            recompiles = status["metrics"]["recompiles"]
            if recompiles != 0:
                raise AssertionError(
                    "wire endpoint %r recompiled after warmup: %s"
                    % (name, recompiles))
            all_lats = np.asarray(
                [v for per in lats for v in per], dtype=np.float64)
            rows = sum(
                REQ_SIZES[(t + i) % len(REQ_SIZES)]
                for t in range(THREADS)
                for i in range(len(lats[t])))
            return {
                "rows_per_sec": round(rows / elapsed, 1),
                "requests_per_sec": round(all_lats.size / elapsed, 1),
                "latency_p50_ms": round(
                    float(np.percentile(all_lats, 50)) * 1e3, 3),
                "latency_p99_ms": round(
                    float(np.percentile(all_lats, 99)) * 1e3, 3),
                "completed": int(all_lats.size),
                "shed": int(sum(shed)),
                "server_metrics": {
                    k: status["metrics"][k]
                    for k in ("completed", "batches", "latency_p50_ms",
                              "latency_p99_ms", "mean_batch_occupancy")},
                "recompiles_after_warmup": int(recompiles),
                "warmup_compiles": int(warmup_compiles),
                "warmup_s": round(warmup_s, 2),
                "elapsed_s": round(elapsed, 2),
                "backend_pid": handle.pid,
            }
        finally:
            cli.close()
            handle.shutdown()


def run_wire():
    """The ``--wire loopback`` line: in-process vs loopback-TCP numbers
    for the same endpoints, plus the measured wire tax."""
    import jax

    from paddle_tpu import compile_cache

    if jax.default_backend() != "cpu":
        # the tax is in-process minus child latency on ONE platform, and
        # the children are CPU children (CPU_CHILD_ENV): a parent on an
        # accelerator would subtract a chip latency from a CPU one
        raise RuntimeError(
            "the wire-tax stage is a CPU stage (a host-side latency "
            "delta): run it with JAX_PLATFORMS=cpu, got backend %r"
            % jax.default_backend())
    compile_cache.configure()
    endpoints = {}
    for name, save_fn in (("lenet", _save_lenet), ("deepfm", _save_deepfm)):
        inproc = _bench_endpoint(name, save_fn)
        over_wire = _bench_endpoint_wire(name, save_fn)
        endpoints[name] = {
            "inprocess": inproc,
            "wire": over_wire,
            "wire_tax_p50_ms": round(
                over_wire["latency_p50_ms"] - inproc["latency_p50_ms"], 3),
            "wire_tax_p99_ms": round(
                over_wire["latency_p99_ms"] - inproc["latency_p99_ms"], 3),
        }
    from paddle_tpu import monitor

    # parent-side codec cost across the whole wire storm (the children
    # have their own registries): histogram sum/count over both ops
    codec = monitor.snapshot().get("wire_codec_seconds") or {}
    codec_sum = sum(
        s["value"]["sum"] for s in codec.get("series", ()))
    codec_count = sum(
        s["value"]["count"] for s in codec.get("series", ()))
    return {
        "metric": "serving_wire_tax",
        "unit": "ms",
        "value": endpoints["lenet"]["wire_tax_p50_ms"],
        "endpoints": endpoints,
        "codec_seconds_sum": round(codec_sum, 4),
        "codec_messages": int(codec_count),
        "threads": THREADS,
        "requests_per_thread": REQUESTS,
        "max_batch_size": MAX_BATCH,
        "batch_timeout_ms": TIMEOUT_MS,
        "platform": jax.devices()[0].platform,
    }


def _bench_overload(name, save_fn):
    """The graceful-degradation sweep for one endpoint: saturation
    throughput first (closed loop), then open-loop offered load at
    multiples of it with mixed priorities."""
    from paddle_tpu import serving
    from paddle_tpu.inference import AnalysisConfig, create_paddle_predictor

    stage_s = float(os.environ.get("BENCH_OVERLOAD_SECONDS", "3"))
    multipliers = tuple(
        float(m) for m in os.environ.get(
            "BENCH_OVERLOAD_MULTIPLIERS", "1,2,3").split(","))
    deadline_ms = float(os.environ.get("BENCH_OVERLOAD_DEADLINE_MS", "2000"))
    prios = (("high", serving.PRIORITY_HIGH),
             ("normal", serving.PRIORITY_NORMAL),
             ("low", serving.PRIORITY_LOW))

    with tempfile.TemporaryDirectory() as tmp:
        d = os.path.join(tmp, name)
        make_rows = save_fn(d)
        predictor = create_paddle_predictor(AnalysisConfig(d))
        server = serving.InferenceServer(
            predictor, max_batch_size=MAX_BATCH, batch_timeout_ms=TIMEOUT_MS,
            queue_capacity=max(64, THREADS * 8), name=name)
        try:
            server.warmup()
            cli = serving.Client(server)

            # --- saturation: closed-loop storm, completed requests/sec
            done = [0] * THREADS
            stop_flag = threading.Event()
            start = threading.Barrier(THREADS + 1)

            def closed(tid):
                rng = np.random.RandomState(300 + tid)
                start.wait()
                while not stop_flag.is_set():
                    n = REQ_SIZES[(tid + done[tid]) % len(REQ_SIZES)]
                    try:
                        cli.infer(make_rows(n, rng), timeout_ms=deadline_ms)
                        done[tid] += 1
                    except serving.ServingError:
                        pass

            threads = [threading.Thread(target=closed, args=(t,))
                       for t in range(THREADS)]
            for t in threads:
                t.start()
            start.wait()
            t0 = time.perf_counter()
            time.sleep(stage_s)
            stop_flag.set()
            for t in threads:
                t.join()
            sat_rps = sum(done) / (time.perf_counter() - t0)

            # --- overload sweep: open-loop submission at mult * sat_rps
            stages = {}
            rng = np.random.RandomState(7)
            for mult in multipliers:
                target_rps = max(1.0, mult * sat_rps)
                interval = 1.0 / target_rps
                per = {
                    label: {"offered": 0, "completed": 0, "shed": 0,
                            "expired": 0, "lat": []}
                    for label, _ in prios
                }
                hints = []
                pending = []
                t0 = time.perf_counter()
                i = 0
                while True:
                    now = time.perf_counter()
                    if now - t0 >= stage_s:
                        break
                    # paced submission: catch up to the offered-load
                    # schedule, then sleep to the next slot (open loop —
                    # the arrival process does not care who completed)
                    while i * interval <= now - t0:
                        label, prio = prios[i % len(prios)]
                        n = REQ_SIZES[i % len(REQ_SIZES)]
                        per[label]["offered"] += 1
                        try:
                            req = server.submit(
                                make_rows(n, rng), timeout_ms=deadline_ms,
                                priority=prio)
                            pending.append((label, time.perf_counter(), req))
                        except serving.ServerOverloaded as e:
                            per[label]["shed"] += 1
                            if e.retry_after_ms is not None:
                                hints.append(e.retry_after_ms)
                        except serving.DeadlineExceeded:
                            per[label]["expired"] += 1
                        i += 1
                    time.sleep(min(interval, 0.002))
                elapsed_submit = time.perf_counter() - t0
                for label, t_sub, req in pending:
                    try:
                        req.result()
                        per[label]["completed"] += 1
                        # done_t is stamped at COMPLETION, so latency is
                        # honest even though this gather loop drains
                        # sequentially after the submission window
                        per[label]["lat"].append(
                            ((req.done_t or time.perf_counter()) - t_sub)
                            * 1e3)
                    except serving.ServerOverloaded as e:
                        per[label]["shed"] += 1  # evicted while queued
                        if e.retry_after_ms is not None:
                            hints.append(e.retry_after_ms)
                    except serving.ServingError:
                        per[label]["expired"] += 1
                completed = sum(p["completed"] for p in per.values())
                for label in per:
                    lat = sorted(per[label].pop("lat"))
                    per[label]["p99_ms"] = (
                        round(lat[int(0.99 * (len(lat) - 1))], 3)
                        if lat else None)
                stages["%gx" % mult] = {
                    "offered_rps": round(i / elapsed_submit, 1),
                    "goodput_rps": round(completed / elapsed_submit, 1),
                    "goodput_vs_saturation": round(
                        completed / elapsed_submit / sat_rps, 3)
                    if sat_rps else None,
                    "per_priority": per,
                    "retry_after_ms_p50": (
                        round(sorted(hints)[len(hints) // 2], 2)
                        if hints else None),
                    "admit_limit_end": server._batcher.queue.limit,
                    "brownout_level_end": server._brownout.level,
                }
            m = server.metrics()
            return {
                "saturation_rps": round(sat_rps, 1),
                "stages": stages,
                "shed_total": m["shed"],
                "expired_total": m["expired"],
                "admit_limit_final": m["admit_limit"],
            }
        finally:
            server.stop(drain=False)


def run_overload():
    """The ``--overload`` line: the degradation curve past saturation."""
    import jax

    from paddle_tpu import compile_cache

    compile_cache.configure()
    endpoints = {"lenet": _bench_overload("lenet", _save_lenet)}
    # numeric, not lexicographic: "10x" must beat "5x" for the headline
    last = max(endpoints["lenet"]["stages"], key=lambda k: float(k[:-1]))
    return {
        "metric": "serving_overload_goodput",
        "unit": "fraction_of_saturation",
        "value": endpoints["lenet"]["stages"][last]["goodput_vs_saturation"],
        "endpoints": endpoints,
        "threads": THREADS,
        "max_batch_size": MAX_BATCH,
        "batch_timeout_ms": TIMEOUT_MS,
        "platform": jax.devices()[0].platform,
    }


def _wire_mode(argv=None):
    """``--wire loopback`` / $BENCH_SERVING_WIRE."""
    import sys

    argv = sys.argv[1:] if argv is None else argv
    for i, a in enumerate(argv):
        if a == "--wire" and i + 1 < len(argv):
            return argv[i + 1]
        if a.startswith("--wire="):
            return a.split("=", 1)[1]
    return os.environ.get("BENCH_SERVING_WIRE")


def _dump_flight_trace(recorder, path):
    """Write the slowest 1% of bench requests (by client-observed
    latency) with their full span trees — the /tracez document shape,
    pre-filtered to the tail."""
    recs = recorder.snapshot()
    recs.sort(key=lambda r: r.get("latency_ms", 0.0), reverse=True)
    n_keep = max(1, len(recs) // 100)
    with open(path, "w") as f:
        json.dump({
            "metric": "serving_flight_trace",
            "slowest_pct": 1,
            "total_requests": len(recs),
            "slow_ms": recorder.slow_ms,
            "requests": recs[:n_keep],
        }, f)
    return n_keep


def run():
    import jax

    from paddle_tpu import monitor, profiler

    from paddle_tpu import compile_cache

    compile_cache.configure()
    trace = os.environ.get("BENCH_SERVING_TRACE")
    trace_out = _trace_out_path()
    recorder = None
    if trace_out:
        # slow_ms=0 retains EVERY request so the slowest 1% is an exact
        # post-hoc selection, not a guessed threshold
        recorder = monitor.flight_recorder(
            capacity=2 * THREADS * REQUESTS + 64, slow_ms=0.0)
    if trace:
        profiler.start_jsonl_trace(trace)
    try:
        endpoints = {
            "lenet": _bench_endpoint("lenet", _save_lenet),
            "deepfm": _bench_endpoint("deepfm", _save_deepfm),
        }
    finally:
        if trace:
            profiler.stop_jsonl_trace()
    result = {
        "metric": "serving_dynamic_batching",
        "unit": "rows/sec",
        "value": endpoints["lenet"]["rows_per_sec"],
        "endpoints": endpoints,
        "threads": THREADS,
        "requests_per_thread": REQUESTS,
        "max_batch_size": MAX_BATCH,
        "batch_timeout_ms": TIMEOUT_MS,
        "platform": jax.devices()[0].platform,
    }
    if recorder is not None:
        result["trace_out"] = trace_out
        result["trace_out_requests"] = _dump_flight_trace(recorder, trace_out)
        recorder.close()
    return result


# ---------------------------------------------------------------------------
# --sharded: a 2-way tp model-parallel group vs the replicated baseline
# ---------------------------------------------------------------------------
SHARDED_TP = int(os.environ.get("BENCH_SHARDED_TP", "2"))
_LM_V, _LM_D, _LM_L, _LM_H, _LM_DI, _LM_S = 512, 64, 2, 4, 128, 32


def _save_lm_bench(sharded: bool, precision=None):
    """Save-fn factory for the transformer-LM endpoint (the "giant
    model" stand-in): same weights both ways (seeded), with the
    canonical tp layout + mesh embedded in the manifest when
    ``sharded`` — the predictor then loads as ONE model-parallel group
    spanning ``BENCH_SHARDED_TP`` devices of the virtual CPU mesh.
    ``precision`` composes a precision policy into the same export (the
    --precision sharded-bf16 leg rides this)."""
    def save_fn(dirname):
        import paddle_tpu as fluid
        from paddle_tpu import framework, models, sharding

        prog, startup = framework.Program(), framework.Program()
        prog.random_seed = startup.random_seed = 17
        with framework.program_guard(prog, startup):
            ids = fluid.layers.data("src_ids", [_LM_S], dtype="int64")
            _, logits = models.transformer_lm(
                ids, None, vocab_size=_LM_V, d_model=_LM_D,
                n_layer=_LM_L, n_head=_LM_H, d_inner=_LM_DI,
                seq_len=_LM_S, max_pos=2 * _LM_S)
        exe = fluid.Executor(fluid.CPUPlace())
        kw = {}
        if sharded:
            kw = dict(sharding_rules=sharding.transformer_lm_rules("tp"),
                      sharding_mesh={"tp": SHARDED_TP})
        if precision is not None:
            kw["precision_policy"] = precision
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            fluid.save_inference_model(
                dirname, ["src_ids"], [logits], exe, prog, **kw)

        def make_rows(n, rng):
            return {"src_ids": rng.randint(
                1, _LM_V, (n, _LM_S)).astype(np.int64)}

        return make_rows

    return save_fn


def run_sharded():
    """The ``--sharded`` line: the same transformer-LM endpoint served
    replicated (one chip's replica) vs as a 2-way tp model-parallel
    group on the 8-device CPU mesh — QPS and post-warmup recompile
    count for both, plus the per-device HBM footprint the sharding
    buys (sharded params hold 1/tp of their bytes per device)."""
    import sys

    import bench_common

    if "jax" not in sys.modules:
        # standalone invocation (`python bench_serving.py --sharded`):
        # the tp group needs the virtual multi-device CPU mesh, and the
        # env only takes effect before the first jax import (bench.py's
        # serving_sharded stage injects the same env into its
        # subprocess; this covers the direct path)
        os.environ.update(bench_common.virtual_mesh_env())
    import jax

    from paddle_tpu import compile_cache

    compile_cache.configure()
    replicated = _bench_endpoint("lm-replicated", _save_lm_bench(False))
    shard = _bench_endpoint("lm-tp%d" % SHARDED_TP, _save_lm_bench(True))
    stats = shard.get("sharding") or {}
    return {
        "metric": "serving_sharded_qps",
        "unit": "rows/sec",
        "value": shard["rows_per_sec"],
        "replicated_rows_per_sec": replicated["rows_per_sec"],
        "qps_vs_replicated": round(
            shard["rows_per_sec"] / max(1e-9, replicated["rows_per_sec"]),
            3),
        "tp": SHARDED_TP,
        "recompiles_after_warmup": shard["recompiles_after_warmup"],
        "hbm_bytes_per_device": stats.get("hbm_bytes_per_device"),
        "replicated_hbm_bytes": stats.get("replicated_bytes"),
        "params_sharded": stats.get("n_sharded"),
        "endpoints": {"replicated": replicated, "sharded": shard},
        "threads": THREADS,
        "requests_per_thread": REQUESTS,
        "max_batch_size": MAX_BATCH,
        "batch_timeout_ms": TIMEOUT_MS,
        "platform": jax.devices()[0].platform,
    }


# ---------------------------------------------------------------------------
# --long-context: sequence-parallel ring attention + pipelined predictor
# ---------------------------------------------------------------------------
_LC_S = int(os.environ.get("BENCH_LC_SEQ", "512"))
_LC_B = int(os.environ.get("BENCH_LC_BATCH", "4"))
_LC_REPS = int(os.environ.get("BENCH_LC_REPS", "6"))
_LC_BUDGET = int(os.environ.get("BENCH_LC_CHIP_BUDGET_BYTES",
                                str(16 << 20)))
_LC_DIMS = (512, 64, 2, 4, 128)  # V, D, L, H, DI


def _save_lc_lm(n_sp):
    """Save-fn factory for the LONG-CONTEXT fused-attention LM export
    (seq ``_LC_S``; causality is the fused op's attr, so no [S, S]
    bias tensor exists to blow the activation budget or block the
    pipeline cut).  ``n_sp > 1`` embeds the canonical ``sp`` layout +
    mesh in the manifest: the loaded predictor then constrains every
    [*, S, *] intermediate onto ``n_sp`` devices and dispatches
    attention as ring attention over the sp axis."""
    V, D, L, H, DI = _LC_DIMS

    def save_fn(dirname):
        import paddle_tpu as fluid
        from paddle_tpu import framework, models, sharding

        prog, startup = framework.Program(), framework.Program()
        prog.random_seed = startup.random_seed = 11
        with framework.program_guard(prog, startup):
            ids = fluid.layers.data("src_ids", [_LC_S], dtype="int64")
            _, logits = models.transformer_lm(
                ids, None, vocab_size=V, d_model=D, n_layer=L,
                n_head=H, d_inner=DI, seq_len=_LC_S, max_pos=2 * _LC_S)
        exe = fluid.Executor(fluid.CPUPlace())
        kw = {}
        if n_sp > 1:
            kw = dict(sharding_rules=sharding.transformer_lm_rules("sp"),
                      sharding_mesh={"sp": n_sp})
        with fluid.scope_guard(fluid.Scope()):
            exe.run(startup)
            fluid.save_inference_model(
                dirname, ["src_ids"], [logits], exe, prog, **kw)

    return save_fn


def _lc_tokens_per_s(run_fn):
    """tokens/s over ``_LC_REPS`` steady dispatches of a [B, S] batch
    (one untimed dispatch first: compile + placement)."""
    run_fn()
    t0 = time.perf_counter()
    out = None
    for _ in range(_LC_REPS):
        out = run_fn()
    np.asarray(out[0])
    elapsed = time.perf_counter() - t0
    return round(_LC_REPS * _LC_B * _LC_S / elapsed, 1)


def run_long_context():
    """The ``--long-context`` line (see module docstring)."""
    import sys

    import bench_common

    if "jax" not in sys.modules:
        os.environ.update(bench_common.virtual_mesh_env())
    import jax

    from paddle_tpu.inference import AnalysisConfig, create_paddle_predictor
    from paddle_tpu.parallel.pipeline_predictor import PipelinePredictor

    from paddle_tpu import compile_cache

    compile_cache.configure()
    V = _LC_DIMS[0]
    rng = np.random.RandomState(42)
    x = rng.randint(1, V, (_LC_B, _LC_S)).astype(np.int64)
    x_small = x[:2]

    with tempfile.TemporaryDirectory() as tmp:
        legs = {}
        preds = {}
        for n_sp in (1, 2, 4):
            name = "unsharded" if n_sp == 1 else "sp%d" % n_sp
            d = os.path.join(tmp, name)
            _save_lc_lm(n_sp)(d)
            pred = create_paddle_predictor(AnalysisConfig(d))
            preds[name] = (pred, d)
            tps = _lc_tokens_per_s(lambda p=pred: p.run({"src_ids": x}))
            leg = {"tokens_per_s": tps}
            if pred.sharded:
                stats = pred.sharding_stats()
                leg["activation_bytes_per_device"] = (
                    stats["activation_bytes_per_device"])
                leg["activation_bytes_unsharded"] = (
                    stats["activation_bytes_unsharded"])
            legs[name] = leg

        # parity: the sp-4 ring-attention group must reproduce the
        # unsharded logits (the acceptance rtol)
        ref, _ = preds["unsharded"]
        sp4, _ = preds["sp4"]
        out_r, = ref.run({"src_ids": x_small})
        out_s, = sp4.run({"src_ids": x_small})
        np.testing.assert_allclose(out_s, out_r, rtol=2e-4, atol=2e-4)

        # capacity: the unsharded activation footprint exceeds the
        # per-chip budget; the sp-4 share is exactly 1/4 and fits it
        unsharded_act = legs["sp4"]["activation_bytes_unsharded"]
        sp4_act = legs["sp4"]["activation_bytes_per_device"]
        if unsharded_act <= _LC_BUDGET:
            raise AssertionError(
                "long-context leg is not long enough: unsharded "
                "activations %d <= budget %d (raise BENCH_LC_SEQ)"
                % (unsharded_act, _LC_BUDGET))
        if sp4_act * 4 != unsharded_act or sp4_act > _LC_BUDGET:
            raise AssertionError(
                "sp-4 activation share %d is not 1/4 of %d within the "
                "%d budget" % (sp4_act, unsharded_act, _LC_BUDGET))

        # zero-recompile across a mixed-length storm: warm the padded
        # sizes once each, then a shuffled storm must never miss again
        storm_sizes = sorted({_LC_B, max(1, _LC_B // 2), 1})
        feeds = {n: {"src_ids": x[:n]} for n in storm_sizes}
        for f in feeds.values():
            sp4.run(f)
        misses0 = sp4.jit_cache_stats()["misses"]
        order = [storm_sizes[i % len(storm_sizes)] for i in range(12)]
        rng.shuffle(order)
        for n in order:
            sp4.run(feeds[n])
        recompiles = sp4.jit_cache_stats()["misses"] - misses0
        if recompiles:
            raise AssertionError(
                "sp-4 predictor recompiled %d time(s) during the "
                "mixed-length storm" % recompiles)

        # pipeline: the SAME unsharded export served pp-2 micro-batched
        # (M=4) vs sequential (M=1, the structural 0.5-bubble worst
        # case) — outputs must be exact, executed bubble < 0.5
        _, udir = preds["unsharded"]
        out_ref, = ref.run({"src_ids": x})
        for label, m in (("pp2_m4", 4), ("pp2_m1", 1)):
            pipe = PipelinePredictor(udir, n_stages=2, num_microbatches=m)
            tps = _lc_tokens_per_s(
                lambda p=pipe: p.run({"src_ids": x}))
            out_p, = pipe.run({"src_ids": x})
            if np.abs(out_p - out_ref).max() != 0.0:
                raise AssertionError(
                    "pipelined (%s) output is not exact vs unpipelined"
                    % label)
            st = pipe.pipeline_stats()
            legs[label] = {
                "tokens_per_s": tps,
                "bubble_ratio": st["bubble_ratio"],
                "stage_occupancy": st["stage_occupancy"],
                "cut_vars": st["cut_vars"],
            }
        if not legs["pp2_m4"]["bubble_ratio"] < 0.5:
            raise AssertionError(
                "pp-2/M-4 bubble ratio %r is not < 0.5"
                % legs["pp2_m4"]["bubble_ratio"])

    return {
        "metric": "serving_long_context_tokens_per_s",
        "unit": "tokens/sec",
        "value": legs["sp4"]["tokens_per_s"],
        "seq_len": _LC_S,
        "batch": _LC_B,
        "chip_budget_bytes": _LC_BUDGET,
        "unsharded_activation_bytes": unsharded_act,
        "sp4_activation_bytes_per_device": sp4_act,
        "recompiles_after_warmup": 0,
        "pipeline_bubble_ratio": legs["pp2_m4"]["bubble_ratio"],
        "legs": legs,
        "platform": jax.devices()[0].platform,
    }


# ---------------------------------------------------------------------------
# --decode: continuous batching vs request-at-a-time on a transformer LM
# ---------------------------------------------------------------------------
def _decode_workload(rng, n, max_seq_len):
    """Interleaved long/short prompts (the mixed-length traffic that
    makes request-at-a-time batching waste freed slots): every 4th
    request decodes near the length cap, the rest are short."""
    reqs = []
    for i in range(n):
        if i % 4 == 0:
            plen, gen = 12, max_seq_len - 16
        else:
            plen, gen = 2 + i % 5, 4 + i % 6
        prompt = rng.randint(3, 400, plen).astype(np.int32)
        reqs.append((prompt, gen))
    return reqs


# target-LM dims shared by the decode legs (the tier-2 legs rebuild
# draft/verify fns and the fleet endpoint around the same weights)
_DEC_DIMS = (512, 64, 2, 4, 128, 64)  # V, D, L, H, DI, max_seq_len


def _decode_prefix_drill(srv, prefix, suffixes, gen=4):
    """The staggered shared-prefix drill: sequential requests (each
    waits its result, so the freed slot's prefix KV is offered before
    the next prompt probes).  Returns (prefill-token delta, sorted
    TTFT list) — the on/off comparison runs this twice."""
    d0 = int(srv.metrics()["decode"]["prefill_tokens"])
    ttfts = []
    for sfx in suffixes:
        prompt = np.concatenate([prefix, sfx]).astype(np.int32)
        r = srv.submit({"tokens": prompt}, max_new_tokens=gen)
        r.result(timeout=300.0)
        ttfts.append(r.first_token_t - r.submit_t)
        time.sleep(0.02)  # let the release tick offer the prefix KV
    ttfts.sort()
    return int(srv.metrics()["decode"]["prefill_tokens"]) - d0, ttfts


def _decode_spec_block(state, spec_prompts, spec_gen, refs, rollouts):
    """The speculative leg: distill a unigram transition-table draft
    from the baseline pass's OWN greedy rollouts (the cheapest draft
    that still tracks the target — ~70% of this LM's greedy transitions
    are last-token-predictable), then decode the same prompts with and
    without draft-then-verify on one server at ``steps_per_tick=1``,
    the dispatch-bound regime where a k-wide accepted run amortizes
    scheduler dispatches.  Greedy-exact acceptance pins parity: both
    passes must emit sequences bit-identical to ``refs``."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.decoding import (
        make_transformer_lm_pooled_step_fn,
        make_transformer_lm_pooled_verify_fn,
    )
    from paddle_tpu.serving.decode import DecodeServer
    from paddle_tpu.serving.speculative import SpeculativeConfig

    V, D, L, H, DI, ML = _DEC_DIMS
    k = int(os.environ.get("BENCH_DECODE_SPEC_K", "8"))
    counts = {}
    for seq in rollouts:
        for a, b in zip(seq[:-1].tolist(), seq[1:].tolist()):
            row = counts.setdefault(a, {})
            row[b] = row.get(b, 0) + 1
    table_np = np.zeros((V,), np.int32)
    for a, nxt in counts.items():
        table_np[a] = max(nxt.items(), key=lambda kv: kv[1])[0]
    table = jnp.asarray(table_np)

    def draft_step_fn(cache, tok, ts):
        # one table lookup as logits — argmax lands on table[tok]
        return jax.nn.one_hot(table[tok], V, dtype=jnp.float32) * 10.0, cache

    step_fn, make_cache = make_transformer_lm_pooled_step_fn(
        state, V, D, L, H, DI)
    verify_fn = make_transformer_lm_pooled_verify_fn(
        state, V, D, L, H, DI)
    def draft_make_cache(s, t):
        return {"bias": jnp.zeros((s, 1), jnp.float32)}

    draft_make_cache.leaf_seq_axes = {"bias": -1}
    spec = SpeculativeConfig(verify_fn, draft_step_fn, draft_make_cache,
                             k=k)
    srv = DecodeServer(step_fn, make_cache, eos_id=1, max_seq_len=ML,
                       max_slots=4, steps_per_tick=1,
                       name="bench-decode-spec", speculative=spec)
    warm = srv.warmup()

    def one_pass(speculative):
        g0 = int(srv.metrics()["decode"]["generated_tokens"])
        t0 = time.perf_counter()
        outs = []
        for g in range(0, len(spec_prompts), 4):
            grp = [srv.submit({"tokens": p}, max_new_tokens=spec_gen,
                              speculative=speculative)
                   for p in spec_prompts[g:g + 4]]
            outs.extend(np.asarray(r.result(timeout=300.0)[0])
                        for r in grp)
        elapsed = time.perf_counter() - t0
        toks = int(srv.metrics()["decode"]["generated_tokens"]) - g0
        return outs, toks / elapsed

    base_outs, base_tps = one_pass(False)
    spec_outs, spec_tps = one_pass(True)
    sm = srv.metrics()
    telemetry = dict(sm["decode"].get("speculative") or {})
    recompiles = int(sm.get("recompiles", 0))
    srv.stop(drain=True, timeout=60.0)
    for ref, b_out, s_out in zip(refs, base_outs, spec_outs):
        if not (np.array_equal(ref, b_out) and np.array_equal(ref, s_out)):
            raise AssertionError(
                "speculative decode broke greedy parity: ref=%r base=%r "
                "spec=%r" % (ref.tolist(), b_out.tolist(), s_out.tolist()))
    if recompiles:
        raise AssertionError(
            "speculative server recompiled after warmup: %d" % recompiles)
    telemetry.update(
        steps_per_tick=1,
        baseline_tokens_per_s=round(base_tps, 1),
        speculative_tokens_per_s=round(spec_tps, 1),
        speedup=round(spec_tps / max(1e-9, base_tps), 2),
        parity=True,
        warmup_compiles=int(warm),
        recompiles=recompiles)
    return telemetry


def _decode_affinity_fleet_block(state):
    """The cache-affinity leg: a REAL 2-child fleet hosting one saved
    decode endpoint with a per-child prefix KV cache, driven by
    returning "sessions" (prompts sharing a per-session head).  With
    prefix affinity ON the balancer re-routes a returning prefix hash
    to the child whose cache last served it (a bounded tie-break that
    never defeats load balancing); OFF, least-loaded routing scatters
    the sessions across children and the child-side caches miss.  Each
    phase uses DISJOINT session prefixes so both start cold."""
    from paddle_tpu.serving import wire
    from paddle_tpu.serving.decode import save_decode_endpoint

    V, D, L, H, DI, ML = _DEC_DIMS
    sessions = int(os.environ.get("BENCH_DECODE_AFFINITY_SESSIONS", "4"))
    rounds = int(os.environ.get("BENCH_DECODE_AFFINITY_ROUNDS", "3"))

    def drill(fb, bases):
        ttfts, toks = [], [0]
        lock = threading.Lock()

        def session(si):
            srng = np.random.RandomState(1000 + si)
            for r_i in range(rounds):
                sfx = srng.randint(3, 400, 2 + r_i).astype(np.int32)
                prompt = np.concatenate([bases[si], sfx])
                t0 = time.perf_counter()
                first, n = None, 0
                for c in fb.infer_stream({"tokens": prompt},
                                         max_new_tokens=4):
                    if first is None:
                        first = time.perf_counter() - t0
                    n += int(np.asarray(c).reshape(-1).size)
                with lock:
                    ttfts.append(first)
                    toks[0] += n
                time.sleep(0.05)  # freed slot offers its prefix KV

        t0 = time.perf_counter()
        threads = [threading.Thread(target=session, args=(i,))
                   for i in range(sessions)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        elapsed = time.perf_counter() - t0
        ttfts.sort()
        return {
            "tokens_per_s": round(toks[0] / elapsed, 1),
            "ttft_ms_p50": round(ttfts[len(ttfts) // 2] * 1e3, 2),
            "ttft_ms_p99": round(
                ttfts[min(len(ttfts) - 1,
                          int(len(ttfts) * 0.99))] * 1e3, 2),
            "requests": len(ttfts),
        }

    rng = np.random.RandomState(11)
    bases_off = [rng.randint(3, 400, 32).astype(np.int32)
                 for _ in range(sessions)]
    bases_on = [rng.randint(3, 400, 32).astype(np.int32)
                for _ in range(sessions)]
    with tempfile.TemporaryDirectory() as tmp:
        d = os.path.join(tmp, "lm-decode-affinity")
        save_decode_endpoint(
            d, state, vocab_size=V, d_model=D, n_layer=L, n_head=H,
            d_inner=DI, eos_id=1, max_seq_len=ML, max_slots=4,
            steps_per_tick=4, prefix_cache_bytes=16 << 20)
        fleet = wire.FleetBalancer.from_launch(
            d, 2, name="decode-affinity", prefix_affinity=True,
            launch_kwargs={"env": CPU_CHILD_ENV})
        try:
            warmup_compiles = fleet.warmup()

            def child_cache_stats():
                out = {}
                for be in fleet._backends:
                    h = be.transport.get_json("/healthz")
                    out[be.name] = dict(h.get("prefix_cache") or {})
                return out

            # OFF phase: a plain least-loaded balancer over the SAME
            # children (bare addresses — no relaunch, same warm caches)
            fb_off = wire.FleetBalancer(
                [(be.handle.host, be.handle.port)
                 for be in fleet._backends],
                name="decode-affinity-off", prefix_affinity=False)
            try:
                c0 = child_cache_stats()
                off = drill(fb_off, bases_off)
            finally:
                fb_off.stop()
            c1 = child_cache_stats()
            on = drill(fleet, bases_on)
            c2 = child_cache_stats()

            def hit_delta(a, b):
                return sum(int(b[n].get("hits", 0)) - int(a[n].get("hits", 0))
                           for n in b)

            off["child_prefix_hits"] = hit_delta(c0, c1)
            on["child_prefix_hits"] = hit_delta(c1, c2)
            on["affinity_hits"] = sum(
                s["affinity_hits"]
                for s in fleet.backend_stats().values())
            if on["affinity_hits"] <= 0:
                raise AssertionError(
                    "prefix-affinity fleet recorded no affinity hits")
            recompiles = {}
            for be in fleet._backends:
                st = be.transport.get_json("/statusz")
                recompiles[be.name] = int(st["jit_cache"]["misses"])
            if any(recompiles.values()):
                raise AssertionError(
                    "decode-affinity fleet recompiled after warmup: %r"
                    % recompiles)
            return {
                "children": 2,
                "sessions": sessions,
                "rounds": rounds,
                "affinity_on": on,
                "affinity_off": off,
                "warmup_compiles": int(warmup_compiles),
                "jit_misses_after_warmup": recompiles,
            }
        finally:
            fleet.stop(shutdown_backends=True)


def _decode_int8_kv_block(state, prompts, gen, max_slots, steps):
    """The int8 KV-slot leg: the SAME LM weights behind a fp32-KV and
    an int8-KV decode server — greedy token parity asserted exactly,
    tokens/s both ways, and concurrent sequences at a fixed HBM budget
    from the pool's own ``kv_rung_bytes`` accounting (the int8 rung
    must buy >= 1.8x, the acceptance floor; per-slot-per-head fp32
    scales cost 4/d_head extra so the exact ratio is
    (d_head + 4) / (4 * d_head))."""
    from paddle_tpu.decoding import make_transformer_lm_pooled_step_fn
    from paddle_tpu.serving.decode import DecodeServer

    V, D, L, H, DI, ML = _DEC_DIMS
    legs, tokens = {}, {}
    for dt in ("fp32", "int8"):
        step_fn, make_cache = make_transformer_lm_pooled_step_fn(
            state, V, D, L, H, DI, kv_dtype=dt)
        srv = DecodeServer(step_fn, make_cache, eos_id=1, max_seq_len=ML,
                           max_slots=max_slots, steps_per_tick=steps,
                           name="bench-decode-kv-" + dt, kv_dtype=dt)
        warm = srv.warmup()
        outs = []
        t0 = time.perf_counter()
        for g in range(0, len(prompts), max_slots):
            grp = [srv.submit({"tokens": p}, max_new_tokens=gen)
                   for p in prompts[g:g + max_slots]]
            outs.extend(np.asarray(r.result(timeout=300.0)[0])
                        for r in grp)
        elapsed = time.perf_counter() - t0
        m = srv.metrics()
        generated = int(m["decode"]["generated_tokens"])
        recompiles = int(m.get("recompiles", 0))
        pool = srv._pool
        rungs = pool.rung_pairs()
        rung_bytes = {r: pool.kv_rung_bytes(*r) for r in rungs}
        srv.stop(drain=True, timeout=60.0)
        if recompiles:
            raise AssertionError(
                "%s-KV decode server recompiled after warmup: %d"
                % (dt, recompiles))
        tokens[dt] = outs
        legs[dt] = {
            "tokens_per_s": round(generated / elapsed, 1),
            "kv_bytes_top_rung": int(rung_bytes[rungs[-1]]),
            "warmup_compiles": int(warm),
            "recompiles": recompiles,
            "_rung_bytes": rung_bytes,
        }
    for a, b in zip(tokens["fp32"], tokens["int8"]):
        if not np.array_equal(a, b):
            raise AssertionError(
                "int8-KV greedy tokens diverged from fp32-KV: %r vs %r"
                % (a.tolist(), b.tolist()))
    # fixed HBM budget: at every (slots, len) rung pair, how many
    # concurrent sequences does a budget sized for 4 fp32 rungs buy?
    worst = None
    rb32 = legs["fp32"].pop("_rung_bytes")
    rb8 = legs["int8"].pop("_rung_bytes")
    for (s, t), b32 in rb32.items():
        budget = 4 * b32
        seq32 = (budget // b32) * s
        seq8 = (budget // rb8[(s, t)]) * s
        ratio = seq8 / max(1, seq32)
        if worst is None or ratio < worst[0]:
            worst = (ratio, s, t, seq32, seq8)
    if worst[0] < 1.8:
        raise AssertionError(
            "int8 KV bought only %.2fx concurrent sequences at rung "
            "(%d, %d) — the acceptance floor is 1.8x" % worst[:3])
    return {
        "concurrent_sequences_vs_fp32": round(worst[0], 2),
        "worst_rung": [worst[1], worst[2]],
        "sequences_at_budget_fp32": int(worst[3]),
        "sequences_at_budget_int8": int(worst[4]),
        "kv_bytes_vs_fp32": round(
            legs["int8"]["kv_bytes_top_rung"]
            / legs["fp32"]["kv_bytes_top_rung"], 4),
        "token_parity_exact": True,
        "requests": len(prompts),
        "max_new_tokens": gen,
        "fp32": legs["fp32"],
        "int8": legs["int8"],
    }


def run_decode():
    """The ``--decode`` line: token-level scheduling, measured."""
    import jax

    from paddle_tpu.decoding import (
        make_transformer_lm_pooled_step_fn,
        random_transformer_lm_state,
    )
    from paddle_tpu.serving.client import Client
    from paddle_tpu.serving.decode import DecodeServer

    from paddle_tpu import compile_cache

    compile_cache.configure()
    n_requests = int(os.environ.get("BENCH_DECODE_REQUESTS", "24"))
    max_slots = int(os.environ.get("BENCH_DECODE_SLOTS", "8"))
    steps = int(os.environ.get("BENCH_DECODE_STEPS", "4"))
    V, D, L, H, DI, ML = _DEC_DIMS
    rng = np.random.RandomState(0)
    state = random_transformer_lm_state(rng, V, D, L, H, DI, ML)
    step_fn, make_cache = make_transformer_lm_pooled_step_fn(
        state, V, D, L, H, DI)
    srv = DecodeServer(step_fn, make_cache, eos_id=1, max_seq_len=ML,
                       max_slots=max_slots, steps_per_tick=steps,
                       name="bench-decode")
    t0 = time.perf_counter()
    compiles = srv.warmup()
    warmup_s = time.perf_counter() - t0
    work = _decode_workload(rng, n_requests, ML)

    def gen_tokens():
        return int(srv.metrics()["decode"]["generated_tokens"])

    # request-at-a-time: admit in arrival-order groups of max_slots,
    # wait the WHOLE group before the next
    g0, t0 = gen_tokens(), time.perf_counter()
    for g in range(0, len(work), max_slots):
        group = [srv.submit({"tokens": p}, max_new_tokens=c)
                 for p, c in work[g:g + max_slots]]
        for r in group:
            r.result(timeout=300.0)
    rat_s = time.perf_counter() - t0
    rat_tokens = gen_tokens() - g0

    # continuous: streamed clients, all submitted up front; TTFT is
    # first-chunk arrival as the CLIENT sees it
    cli = Client(srv)
    ttfts = []
    lock = threading.Lock()

    def stream_one(prompt, cap):
        t_submit = time.perf_counter()
        first = None
        for _ in cli.infer_stream({"tokens": prompt}, max_new_tokens=cap):
            if first is None:
                first = time.perf_counter() - t_submit
        with lock:
            ttfts.append(first)

    g0, t0 = gen_tokens(), time.perf_counter()
    threads = [threading.Thread(target=stream_one, args=(p, c))
               for p, c in work]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    cont_s = time.perf_counter() - t0
    cont_tokens = gen_tokens() - g0

    # the late-arrival drill: fill the pool with STAGGERED long decodes
    # (the shortest frees its slot while the longest still runs), submit
    # one short request mid-flight, compare scheduler timestamps
    longs = [srv.submit({"tokens": work[0][0]},
                        max_new_tokens=min(ML - 16, 16 + 4 * i))
             for i in range(max_slots)]
    while srv.metrics()["decode"]["slot_occupancy"] == 0.0:
        time.sleep(0.001)
    late = srv.submit({"tokens": np.array([5, 6], np.int32)},
                      max_new_tokens=4)
    late.result(timeout=300.0)
    for r in longs:
        r.result(timeout=300.0)
    late_before_batch = late.first_token_t < max(r.done_t for r in longs)
    late_ttft_ms = (late.first_token_t - late.submit_t) * 1e3

    m = srv.metrics()
    d = m["decode"]

    # --- decode tier 2: prefix-cache OFF leg + the speculative leg's
    # baseline rollouts, both on the (cache-less) main server ---------
    rng2 = np.random.RandomState(7)
    n_prefix = int(os.environ.get("BENCH_DECODE_PREFIX_REQUESTS", "10"))
    shared = rng2.randint(3, 400, 48).astype(np.int32)
    suffixes = [rng2.randint(3, 400, 2 + i % 4).astype(np.int32)
                for i in range(n_prefix)]
    off_prefill, off_ttfts = _decode_prefix_drill(srv, shared, suffixes)

    spec_n = int(os.environ.get("BENCH_DECODE_SPEC_REQUESTS", "8"))
    spec_gen = int(os.environ.get("BENCH_DECODE_SPEC_GEN", "24"))
    spec_prompts = [rng2.randint(3, 400, 4 + i % 5).astype(np.int32)
                    for i in range(spec_n)]
    refs, rollouts = [], []
    for g in range(0, spec_n, max_slots):
        grp = [srv.submit({"tokens": p}, max_new_tokens=spec_gen)
               for p in spec_prompts[g:g + max_slots]]
        for p, r in zip(spec_prompts[g:g + max_slots], grp):
            out = np.asarray(r.result(timeout=300.0)[0])
            refs.append(out)
            rollouts.append(np.concatenate([p, out]))
    recompiles = int(srv.metrics().get("recompiles", 0))
    srv.stop(drain=True, timeout=60.0)

    # prefix-cache ON leg: the same staggered drill against a server
    # whose freed slots offer their prefix KV for shared-prefix admits
    psrv = DecodeServer(step_fn, make_cache, eos_id=1, max_seq_len=ML,
                        max_slots=max_slots, steps_per_tick=steps,
                        name="bench-decode-prefix",
                        prefix_cache=32 << 20)
    prefix_warm = psrv.warmup()
    on_prefill, on_ttfts = _decode_prefix_drill(psrv, shared, suffixes)
    pm = psrv.metrics()
    prefix_stats = dict(pm["decode"].get("prefix_cache") or {})
    prefix_recompiles = int(pm.get("recompiles", 0))
    psrv.stop(drain=True, timeout=60.0)
    prefill_cut = 1.0 - on_prefill / max(1, off_prefill)
    if prefill_cut < 0.5:
        raise AssertionError(
            "shared-prefix cache cut prefill tokens by only %.0f%% "
            "(off=%d on=%d) — the acceptance bar is >= 50%%"
            % (prefill_cut * 100, off_prefill, on_prefill))
    if prefix_recompiles:
        raise AssertionError(
            "prefix-cache server recompiled after warmup: %d"
            % prefix_recompiles)
    prefix_block = {
        "requests": n_prefix,
        "prefill_tokens_off": off_prefill,
        "prefill_tokens_on": on_prefill,
        "prefill_cut": round(prefill_cut, 3),
        "ttft_ms_p50_off": round(off_ttfts[len(off_ttfts) // 2] * 1e3, 2),
        "ttft_ms_p50_on": round(on_ttfts[len(on_ttfts) // 2] * 1e3, 2),
        "cache": prefix_stats,
        "warmup_compiles": int(prefix_warm),
        "recompiles": prefix_recompiles,
    }

    spec_block = _decode_spec_block(
        state, spec_prompts, spec_gen, refs, rollouts)
    affinity_block = _decode_affinity_fleet_block(state)
    int8_n = int(os.environ.get("BENCH_DECODE_INT8_REQUESTS", "6"))
    int8_gen = int(os.environ.get("BENCH_DECODE_INT8_GEN", "16"))
    int8_prompts = [rng2.randint(3, 400, 3 + i % 4).astype(np.int32)
                    for i in range(int8_n)]
    int8_block = _decode_int8_kv_block(
        state, int8_prompts, int8_gen, max_slots, steps)
    ttfts.sort()
    cont_tps = cont_tokens / cont_s
    rat_tps = rat_tokens / rat_s
    return {
        "metric": "serving_decode_tokens_per_s",
        "unit": "tokens/s",
        "value": round(cont_tps, 1),
        "request_at_a_time_tokens_per_s": round(rat_tps, 1),
        "continuous_speedup": round(cont_tps / rat_tps, 2),
        "ttft_ms_p50": round(ttfts[len(ttfts) // 2] * 1e3, 2),
        "ttft_ms_p99": round(
            ttfts[min(len(ttfts) - 1, int(len(ttfts) * 0.99))] * 1e3, 2),
        "late_arrival_ttft_ms": round(late_ttft_ms, 2),
        "late_arrival_before_batch_done": bool(late_before_batch),
        "prefill_decode_ratio": round(
            d["prefill_tokens"] / max(1, d["generated_tokens"]), 3),
        "ticks": d["ticks"],
        "steps_per_tick": steps,
        "max_slots": max_slots,
        "requests": n_requests,
        "warmup_compiles": compiles,
        "warmup_s": round(warmup_s, 1),
        "recompiles": recompiles,
        "prefix_cache": prefix_block,
        "speculative": spec_block,
        "affinity": affinity_block,
        "int8_kv": int8_block,
        "platform": jax.devices()[0].platform,
    }


# ---------------------------------------------------------------------------
# --precision: bf16 serving vs fp32 on the same endpoints, plus a real
# 2-child wire fleet serving the mixed-precision manifest
# ---------------------------------------------------------------------------
def _parity_check(name, save_fn):
    """Load the bf16-policy endpoint once and compare its default
    (bf16) output against its own fp32 opt-out on a seeded feed — the
    runtime confirmation of the bound the export parity gate measured
    (both numbers ride the JSON line)."""
    from paddle_tpu.inference import AnalysisConfig, create_paddle_predictor

    with tempfile.TemporaryDirectory() as tmp:
        d = os.path.join(tmp, name)
        make_rows = save_fn(d, precision={"dtype": "bf16"})
        pred = create_paddle_predictor(AnalysisConfig(d))
        policy = pred.precision_policy
        rng = np.random.RandomState(42)
        feed = make_rows(4, rng)
        out_low = pred.run(feed)
        out_fp32 = pred.run(feed, precision="fp32")
        from paddle_tpu.contrib.mixed_precision.inference import max_rel_err

        worst = max_rel_err(out_fp32, out_low)
        if worst > policy["rtol"]:
            raise AssertionError(
                "endpoint %r bf16 parity %.4g exceeds exported rtol %.4g"
                % (name, worst, policy["rtol"]))
        return {
            "rtol": policy["rtol"],
            "export_max_rel_err": policy["max_rel_err"],
            "runtime_max_rel_err": round(worst, 6),
        }


def _precision_fleet_block(save_fn, requests=48):
    """The acceptance leg: a REAL 2-child wire fleet serving one
    mixed-precision (bf16-manifest) endpoint dir.  Every child
    reconstructs the variant from the manifest, the fleet-wide warmup
    compiles both ladders in both processes, a mixed bf16/fp32-opt-out
    storm runs through the balancer, and each child's /statusz is the
    recompile ground truth (must be 0)."""
    from paddle_tpu.serving import wire

    with tempfile.TemporaryDirectory() as tmp:
        d = os.path.join(tmp, "lenet-prec-fleet")
        make_rows = save_fn(d, precision={"dtype": "bf16"})
        fleet = wire.FleetBalancer.from_launch(
            d, 2, name="prec-fleet",
            launch_kwargs={"max_batch_size": MAX_BATCH,
                           "batch_timeout_ms": TIMEOUT_MS,
                           "env": CPU_CHILD_ENV})
        try:
            t0 = time.perf_counter()
            warmup_compiles = fleet.warmup()
            warmup_s = time.perf_counter() - t0
            health = fleet._backends[0].transport.get_json("/healthz")
            rng = np.random.RandomState(9)
            lat = []
            for i in range(requests):
                n = REQ_SIZES[i % len(REQ_SIZES)]
                kw = {"precision": "fp32"} if i % 4 == 0 else {}
                r0 = time.perf_counter()
                fleet.infer(make_rows(n, rng), **kw)
                lat.append(time.perf_counter() - r0)
            recompiles = {}
            for be in fleet._backends:
                status = be.transport.get_json("/statusz")
                recompiles[be.name] = int(status["metrics"]["recompiles"])
            if any(recompiles.values()):
                raise AssertionError(
                    "mixed-precision fleet recompiled after warmup: %r"
                    % recompiles)
            lat.sort()
            return {
                "children": 2,
                "endpoint_precision": health.get("precision"),
                "precision_dtypes": health.get("precision_dtypes"),
                "completed": len(lat),
                "latency_p50_ms": round(lat[len(lat) // 2] * 1e3, 3),
                "warmup_compiles": int(warmup_compiles),
                "warmup_s": round(warmup_s, 2),
                "recompiles_after_warmup": recompiles,
            }
        finally:
            fleet.stop(shutdown_backends=True)


def _precision_sharded_block():
    """The composed precision × sharding leg (the tentpole's
    acceptance number): the transformer-LM endpoint exported
    sharded-fp32 vs exported with BOTH the tp layout and a bf16
    precision policy in one manifest.  QPS both ways plus the
    dtype-aware ``hbm_bytes_per_device`` from ``sharding_stats()`` —
    the composed endpoint must rent strictly fewer per-device bytes
    (the hoisted params live bf16 at shard shape; embedding lookups
    stay fp32, so the saving is the cast set's half-width, not exactly
    half the total).  Both endpoints enforce the zero-recompile
    contract inside ``_bench_endpoint``."""
    f32 = _bench_endpoint("lm-tp%d-fp32" % SHARDED_TP,
                          _save_lm_bench(True))
    bf16 = _bench_endpoint(
        "lm-tp%d-bf16" % SHARDED_TP,
        _save_lm_bench(True, precision={"dtype": "bf16"}))
    hbm_f32 = (f32.get("sharding") or {}).get("hbm_bytes_per_device")
    hbm_bf16 = (bf16.get("sharding") or {}).get("hbm_bytes_per_device")
    if not hbm_f32 or not hbm_bf16 or hbm_bf16 >= hbm_f32:
        raise AssertionError(
            "composed sharded-bf16 endpoint did not cut per-device HBM: "
            "fp32=%r bf16=%r" % (hbm_f32, hbm_bf16))
    return {
        "tp": SHARDED_TP,
        "qps_vs_sharded_fp32": round(
            bf16["rows_per_sec"] / max(1e-9, f32["rows_per_sec"]), 3),
        "hbm_bytes_per_device_fp32": int(hbm_f32),
        "hbm_bytes_per_device_bf16": int(hbm_bf16),
        "hbm_bytes_vs_fp32": round(hbm_bf16 / hbm_f32, 4),
        "endpoints": {"sharded_fp32": f32, "sharded_bf16": bf16},
    }


def run_precision():
    """The ``--precision`` line: the same endpoints served fp32 vs
    under a bf16 precision policy — QPS and p99 both ways, parity
    within the exported rtol bound, 0 recompiles after warmup
    (bf16-default AND fp32-opt-out requests), the 2-child wire fleet
    leg serving the mixed-precision manifest, and the sharded-bf16
    composed leg (the tp transformer-LM endpoint fp32 vs with a bf16
    policy in the same manifest: QPS + dtype-aware per-device HBM)."""
    import functools
    import sys

    import bench_common

    if "jax" not in sys.modules:
        # standalone invocation (`python bench_serving.py --precision`):
        # the sharded-bf16 composed leg loads a tp group and needs the
        # virtual multi-device CPU mesh (env only effective before the
        # first jax import; bench.py's serving_precision stage injects
        # the same env into its subprocess)
        os.environ.update(bench_common.virtual_mesh_env())
    import jax

    from paddle_tpu import compile_cache

    compile_cache.configure()
    endpoints = {}
    for name, save_fn in (("lenet", _save_lenet), ("deepfm", _save_deepfm)):
        fp32 = _bench_endpoint(name + "-fp32", save_fn)
        bf16 = _bench_endpoint(
            name + "-bf16",
            functools.partial(save_fn, precision={"dtype": "bf16"}))
        endpoints[name] = {
            "fp32": fp32,
            "bf16": bf16,
            "qps_vs_fp32": round(
                bf16["requests_per_sec"]
                / max(1e-9, fp32["requests_per_sec"]), 3),
            "p99_vs_fp32": (
                round(bf16["latency_p99_ms"] / fp32["latency_p99_ms"], 3)
                if fp32["latency_p99_ms"] else None),
            "parity": _parity_check(name, save_fn),
        }
    fleet = _precision_fleet_block(_save_lenet)
    sharded_bf16 = _precision_sharded_block()
    return {
        "metric": "serving_precision_qps_vs_fp32",
        "unit": "ratio",
        "value": endpoints["lenet"]["qps_vs_fp32"],
        "endpoints": endpoints,
        "fleet": fleet,
        "sharded_bf16": sharded_bf16,
        "threads": THREADS,
        "requests_per_thread": REQUESTS,
        "max_batch_size": MAX_BATCH,
        "batch_timeout_ms": TIMEOUT_MS,
        "platform": jax.devices()[0].platform,
    }


def _fleet_obs_storm(fleet, make_rows, threads, requests,
                     stagger_s=0.02, seed=300):
    """Staggered-arrival open storm through the balancer: every thread
    starts ``stagger_s`` after its predecessor (an arrival ramp, not a
    thundering herd), mixed request sizes.  Returns the client-observed
    throughput/latency block."""
    from paddle_tpu import serving

    lats = [[] for _ in range(threads)]
    shed = [0] * threads
    start = threading.Barrier(threads + 1)

    def storm(tid):
        rng = np.random.RandomState(seed + tid)
        start.wait()
        time.sleep(stagger_s * tid)
        for i in range(requests):
            n = REQ_SIZES[(tid + i) % len(REQ_SIZES)]
            feed = make_rows(n, rng)
            r0 = time.perf_counter()
            try:
                fleet.infer(feed, timeout_ms=30000)
                lats[tid].append(time.perf_counter() - r0)
            except serving.ServerOverloaded:
                shed[tid] += 1

    workers = [threading.Thread(target=storm, args=(t,))
               for t in range(threads)]
    for t in workers:
        t.start()
    start.wait()
    t0 = time.perf_counter()
    for t in workers:
        t.join()
    elapsed = time.perf_counter() - t0
    all_lats = np.asarray(
        [v for per in lats for v in per], dtype=np.float64)
    return {
        "requests_per_sec": round(all_lats.size / elapsed, 1),
        "latency_p50_ms": round(
            float(np.percentile(all_lats, 50)) * 1e3, 3),
        "latency_p99_ms": round(
            float(np.percentile(all_lats, 99)) * 1e3, 3),
        "completed": int(all_lats.size),
        "shed": int(sum(shed)),
        "elapsed_s": round(elapsed, 2),
    }


def _fleet_obs_federation_check(fleet, admin):
    """The exact-sum federation contract, checked while the fleet is
    idle: every child ``serving_*`` counter series must appear in the
    federated ``/metrics`` verbatim under that child's ``backend=``
    label, and ``/statusz``'s fleet aggregate must equal the children's
    sum exactly."""
    from paddle_tpu.monitor import registry as _registry

    # direct child expositions first, then a forced scrape: with no
    # traffic in flight the serving_* counters cannot move in between,
    # so the cached docs the federated views serve match these exactly
    children = {}
    for be in fleet._backends:
        children[be.name] = _registry.parse_exposition(
            be.transport.get_text("/metrics"))
    fleet.scrape_once()
    fed = _registry.parse_exposition(admin.get_text("/metrics"))
    statusz = admin.get_json("/statusz")

    fed_index = {}
    for fam_name, fam in fed.items():
        if fam["type"] != "counter":
            continue
        for name, labels, value in fam["samples"]:
            fed_index[(name, tuple(sorted(labels.items())))] = value

    series_checked = 0
    families = set()
    sums = {}
    for backend, fams in children.items():
        for fam_name, fam in fams.items():
            if fam["type"] != "counter" or not fam_name.startswith(
                    "serving_"):
                continue
            for name, labels, value in fam["samples"]:
                want = dict(labels)
                want["backend"] = backend
                key = (name, tuple(sorted(want.items())))
                got = fed_index.get(key)
                if got != value:
                    raise AssertionError(
                        "federated /metrics mismatch for %s%r: child %s "
                        "has %r, federation has %r"
                        % (name, labels, backend, value, got))
                series_checked += 1
                families.add(fam_name)
                sums[fam_name] = sums.get(fam_name, 0.0) + value
    if series_checked == 0:
        raise AssertionError("no child serving_* counter series federated")

    agg = (statusz.get("aggregate") or {}).get("counters") or {}
    for fam_name, want in sums.items():
        got = agg.get(fam_name)
        if got != want:
            raise AssertionError(
                "federated /statusz aggregate mismatch for %s: children "
                "sum to %r, aggregate says %r" % (fam_name, want, got))

    backends_seen = {
        labels.get("backend")
        for fam in fed.values()
        for _, labels, _ in fam["samples"]}
    missing = {be.name for be in fleet._backends} - backends_seen
    if missing:
        raise AssertionError(
            "federated /metrics missing backend label(s): %r" % missing)
    return {
        "counter_families_checked": len(families),
        "series_checked": series_checked,
        "aggregate_families_checked": len(sums),
        "backends": sorted(be.name for be in fleet._backends),
    }


def _fleet_obs_slo_drill(fleet, admin, make_rows, slo_name, delay_s):
    """The injected-latency fire/clear drill: arm a delay fault on the
    balancer's own dispatch so every routed request blows the latency
    SLO's threshold, poll ``/sloz`` until the fast-burn pair fires,
    disarm, drive clean traffic until it clears, and verify both
    transitions landed in ``/eventz``."""
    from paddle_tpu import faults

    def fast_alert(doc):
        for obj in doc.get("objectives") or ():
            if obj.get("name") != slo_name:
                continue
            for a in obj.get("alerts") or ():
                if a.get("pair") == "fast":
                    return a, obj
        return None, None

    # continuous injectors keep the SCALED short window populated: a
    # serial one-at-a-time loop leaves sub-second gaps with no
    # completions at all, and an empty window reads as burn 0
    stop = threading.Event()

    def injector(seed):
        rng_l = np.random.RandomState(seed)
        while not stop.is_set():
            try:
                fleet.infer(make_rows(1, rng_l), timeout_ms=60000)
            except Exception:
                pass  # the drill only needs completions, not answers

    injectors = [threading.Thread(target=injector, args=(900 + i,))
                 for i in range(4)]
    fired_doc = None
    cleared = False
    fired_after_s = cleared_after_s = None
    try:
        t0 = time.perf_counter()
        with faults.armed("fleet.dispatch=delay:%g" % delay_s):
            for t in injectors:
                t.start()
            deadline = time.monotonic() + 30.0
            while time.monotonic() < deadline:
                doc = admin.get_json("/sloz")
                alert, obj = fast_alert(doc)
                if alert is not None and alert.get("firing"):
                    fired_doc = {
                        "alert": alert,
                        "burn_5m": (obj["windows"].get("5m")
                                    or {}).get("burn"),
                        "burn_1h": (obj["windows"].get("1h")
                                    or {}).get("burn"),
                    }
                    break
                time.sleep(0.05)
            fired_after_s = time.perf_counter() - t0
        if fired_doc is None:
            raise AssertionError(
                "fast-burn SLO alert never fired in /sloz under an "
                "injected %gs dispatch delay" % delay_s)

        # fault disarmed, injectors still running: clean completions
        # drain the short window and the alert must clear
        t0 = time.perf_counter()
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            alert, _ = fast_alert(admin.get_json("/sloz"))
            if alert is not None and not alert.get("firing"):
                cleared = True
                break
            time.sleep(0.05)
        cleared_after_s = time.perf_counter() - t0
    finally:
        stop.set()
        for t in injectors:
            t.join(timeout=90.0)
    if not cleared:
        raise AssertionError(
            "fast-burn SLO alert never cleared in /sloz after the "
            "injection window ended")

    events = (admin.get_json("/eventz").get("events") or ())
    transitions = {
        e["kind"]: e for e in events
        if e.get("kind") in ("slo/fired", "slo/cleared")
        and e.get("slo") == slo_name and e.get("pair") == "fast"}
    if "slo/fired" not in transitions:
        raise AssertionError(
            "no fast-pair slo/fired event for %r in federated /eventz"
            % slo_name)
    if transitions["slo/fired"].get("severity") != "critical":
        raise AssertionError(
            "fast-pair slo/fired event is not critical: %r"
            % transitions["slo/fired"])
    if "slo/cleared" not in transitions:
        raise AssertionError(
            "no fast-pair slo/cleared event for %r in federated /eventz"
            % slo_name)
    return {
        "fired_after_s": round(fired_after_s, 2),
        "cleared_after_s": round(cleared_after_s, 2),
        "burn_5m_at_fire": fired_doc["burn_5m"],
        "burn_1h_at_fire": fired_doc["burn_1h"],
        "events": sorted(transitions),
    }


def run_fleet_obs():
    """The ``--fleet-obs`` line: the observability control tower on a
    real 2-child fleet — federation exactness, the SLO fire/clear
    drill, and the cost of watching (QPS with the tower on vs off)."""
    import jax

    from paddle_tpu import monitor
    from paddle_tpu.monitor import slo as slo_mod
    from paddle_tpu.serving import wire

    from paddle_tpu import compile_cache

    compile_cache.configure()
    qps_floor = float(os.environ.get("BENCH_OBS_QPS_FLOOR", "0.98"))
    delay_s = float(os.environ.get("BENCH_OBS_FAULT_DELAY_S", "0.6"))
    slo_name = "fleet-p99-latency"

    with tempfile.TemporaryDirectory() as tmp:
        d = os.path.join(tmp, "lenet-obs-fleet")
        make_rows = _save_lenet(d)
        fleet = wire.FleetBalancer.from_launch(
            d, 2, name="obs-fleet",
            launch_kwargs={"max_batch_size": MAX_BATCH,
                           "batch_timeout_ms": TIMEOUT_MS,
                           "queue_capacity": max(64, THREADS * 8),
                           "env": CPU_CHILD_ENV},
            health_interval_s=0.5, scrape_interval_s=0.5)
        engine = None
        try:
            t0 = time.perf_counter()
            warmup_compiles = fleet.warmup()
            warmup_s = time.perf_counter() - t0

            # rinse storm: sockets opened, ladders exercised, so the
            # off/on comparison below measures the tower, not warmup
            _fleet_obs_storm(fleet, make_rows, THREADS,
                             max(4, REQUESTS // 4), seed=100)

            # interleaved off/on pairs, capacity = best storm per mode:
            # identical-config storms on this shared host jitter ~3-10%
            # (one-sided — interference only ever slows a storm), so the
            # max over several interleaved runs is the capacity estimate,
            # and a below-floor ratio earns extra pairs before failing —
            # only a REPRODUCIBLE tower tax trips the assert
            min_pairs = int(os.environ.get("BENCH_OBS_MIN_PAIRS", "3"))
            max_pairs = int(os.environ.get("BENCH_OBS_MAX_PAIRS", "6"))

            def tower_up():
                addr = fleet.start_admin()
                slo_mod.install(
                    [slo_mod.latency(
                        slo_name,
                        histogram="serving_request_latency_seconds",
                        threshold_s=0.25, target=0.99,
                        server="obs-fleet")],
                    interval_s=0.1, window_scale=0.001)
                return wire.HttpTransport(*addr)

            def tower_down():
                slo_mod.uninstall()
                fleet._stop_admin()

            off_runs, on_runs = [], []
            admin = None
            pair = 0
            while True:
                pair += 1
                if admin is not None:
                    tower_down()
                off_runs.append(_fleet_obs_storm(
                    fleet, make_rows, THREADS, REQUESTS, seed=200 + pair))
                admin = tower_up()
                engine = slo_mod.get()
                on_runs.append(_fleet_obs_storm(
                    fleet, make_rows, THREADS, REQUESTS, seed=300 + pair))
                off = max(off_runs, key=lambda r: r["requests_per_sec"])
                on = max(on_runs, key=lambda r: r["requests_per_sec"])
                qps_ratio = round(
                    on["requests_per_sec"]
                    / max(1e-9, off["requests_per_sec"]), 3)
                if pair >= min_pairs and qps_ratio >= qps_floor:
                    break
                if pair >= max_pairs:
                    break

            federation = _fleet_obs_federation_check(fleet, admin)
            drill = _fleet_obs_slo_drill(
                fleet, admin, make_rows, slo_name, delay_s)

            recompiles = {}
            for be in fleet._backends:
                status = be.transport.get_json("/statusz")
                recompiles[be.name] = int(status["metrics"]["recompiles"])
            if any(recompiles.values()):
                raise AssertionError(
                    "observed fleet recompiled after warmup: %r"
                    % recompiles)
            if qps_ratio < qps_floor:
                raise AssertionError(
                    "observability tax too high: QPS with federation+SLO "
                    "on is %.3fx off (floor %.2f; off=%s on=%s)"
                    % (qps_ratio, qps_floor, off["requests_per_sec"],
                       on["requests_per_sec"]))

            burn = monitor.snapshot().get("slo_burn_rate") or {}
            return {
                "metric": "serving_fleet_obs_qps_ratio",
                "unit": "ratio",
                "value": qps_ratio,
                "children": 2,
                "off": off,
                "on": on,
                "qps_floor": qps_floor,
                "storm_pairs": pair,
                "federation": federation,
                "slo_drill": drill,
                "burn_gauge_series": len(burn.get("series", ())),
                "recompiles_after_warmup": recompiles,
                "warmup_compiles": int(warmup_compiles),
                "warmup_s": round(warmup_s, 2),
                "threads": THREADS,
                "requests_per_thread": REQUESTS,
                "max_batch_size": MAX_BATCH,
                "batch_timeout_ms": TIMEOUT_MS,
                "platform": jax.devices()[0].platform,
            }
        finally:
            if engine is not None:
                slo_mod.uninstall()
            fleet.stop(shutdown_backends=True)


def main():
    import bench_common

    # --metrics-out <path> (or $BENCH_METRICS_OUT) dumps the monitor
    # registry snapshot next to the JSON line
    import sys

    if "--fleet-obs" in sys.argv[1:] or os.environ.get(
            "BENCH_SERVING_FLEET_OBS"):
        bench_common.emit_result(run_fleet_obs())
        return
    if "--precision" in sys.argv[1:] or os.environ.get(
            "BENCH_SERVING_PRECISION"):
        bench_common.emit_result(run_precision())
        return
    if "--overload" in sys.argv[1:] or os.environ.get(
            "BENCH_SERVING_OVERLOAD"):
        bench_common.emit_result(run_overload())
        return
    if "--decode" in sys.argv[1:] or os.environ.get(
            "BENCH_SERVING_DECODE"):
        bench_common.emit_result(run_decode())
        return
    if "--sharded" in sys.argv[1:] or os.environ.get(
            "BENCH_SERVING_SHARDED"):
        bench_common.emit_result(run_sharded())
        return
    if "--long-context" in sys.argv[1:] or os.environ.get(
            "BENCH_SERVING_LONG_CONTEXT"):
        bench_common.emit_result(run_long_context())
        return
    mode = _wire_mode()
    if mode:
        if mode != "loopback":
            raise SystemExit("--wire supports only 'loopback' (got %r)" % mode)
        bench_common.emit_result(run_wire())
        return
    bench_common.emit_result(run())


if __name__ == "__main__":
    main()
