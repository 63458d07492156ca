"""Serving subsystem tests (paddle_tpu/serving/): dynamic batching,
bucket padding, deadlines, admission control, graceful drain, and the
zero-recompiles-after-warmup guarantee (verified through the executor's
jit-cache stats, not inferred from timing).
"""
import json
import threading
import time

import numpy as np
import pytest

from conftest import WAIT

import paddle_tpu as fluid
from paddle_tpu import framework, profiler, serving
from paddle_tpu.inference import AnalysisConfig, create_paddle_predictor
from paddle_tpu.serving import (
    BucketPolicy,
    Client,
    DeadlineExceeded,
    InferenceServer,
    ServerClosed,
    ServerOverloaded,
)

IN_DIM, OUT_DIM = 16, 4


@pytest.fixture(scope="module")
def predictor(tmp_path_factory):
    """A small fc/relu/softmax endpoint saved + reloaded through the
    real inference path (save_inference_model -> AnalysisPredictor)."""
    d = str(tmp_path_factory.mktemp("serving") / "mlp")
    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 7
    with framework.program_guard(prog, startup):
        x = fluid.layers.data("x", [IN_DIM])
        h = fluid.layers.fc(x, 32, act="relu")
        pred = fluid.layers.fc(h, OUT_DIM, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.save_inference_model(d, ["x"], [pred], exe, prog)
    return create_paddle_predictor(AnalysisConfig(d))


def _rows(n, seed=0):
    return np.random.RandomState(seed).uniform(-1, 1, (n, IN_DIM)).astype("float32")


class SlowPredictor:
    """Predictor stub whose run blocks — deterministic worker stalls for
    the deadline/overload/drain tests (no XLA in the hot loop)."""

    def __init__(self, delay_s=0.0):
        self.delay_s = delay_s
        self.calls = 0

    def get_input_names(self):
        return ["x"]

    def get_output_names(self):
        return ["y"]

    def input_specs(self):
        return {"x": ((IN_DIM,), np.dtype("float32"))}

    def jit_cache_stats(self):
        return {"entries": 0, "hits": 0, "misses": 0}

    def run_padded(self, feed, n_valid=None):
        self.calls += 1
        if self.delay_s:
            time.sleep(self.delay_s)
        return [np.asarray(feed["x"][:n_valid]).sum(axis=1, keepdims=True)]


# ---------------------------------------------------------------------------
# bucket policy unit behavior
# ---------------------------------------------------------------------------
def test_bucket_ladder_and_rounding():
    p = BucketPolicy(12)
    assert p.ladder == [1, 2, 4, 8, 12]
    assert [p.bucket_for(n) for n in (1, 2, 3, 5, 8, 9, 12)] == [1, 2, 4, 8, 8, 12, 12]
    with pytest.raises(ValueError):
        p.bucket_for(13)
    padded = p.pad_feed({"x": _rows(3)}, 4)
    assert padded["x"].shape == (4, IN_DIM)
    np.testing.assert_array_equal(padded["x"][3], padded["x"][2])  # last-row repeat


# ---------------------------------------------------------------------------
# coalescing + padding correctness on the real predictor
# ---------------------------------------------------------------------------
def test_batch_coalescing_under_concurrent_submitters(predictor):
    server = InferenceServer(
        predictor, max_batch_size=8, batch_timeout_ms=40, name="coalesce")
    try:
        server.warmup()
        cli = Client(server)
        xb = _rows(1, seed=3)
        want = np.asarray(predictor.run({"x": xb})[0])
        n_req, results = 16, [None] * 16
        start = threading.Barrier(n_req)

        def go(i):
            start.wait(WAIT)
            (results[i],) = cli.infer({"x": xb})

        threads = [threading.Thread(target=go, args=(i,),
                                    daemon=True) for i in range(n_req)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(WAIT)
        assert not any(t.is_alive() for t in threads)
        # a coalesced request runs in an 8-row (or smaller) bucket's
        # executable, ``want`` in the 1-row one: executables compiled
        # for different batch shapes may order a sum differently, so
        # the rows agree to a few fp32 ulps, not bitwise.  The outputs
        # are softmax probabilities (<= 1, ulp 6e-8): 4 eps = 4.8e-7.
        for r in results:
            np.testing.assert_allclose(
                r, want, rtol=0, atol=4 * np.finfo(np.float32).eps)
        m = server.metrics()
        assert m["completed"] == n_req
        # the whole point of the batcher: far fewer executions than requests
        assert m["batches"] < n_req
        assert m["mean_batch_occupancy"] is not None
    finally:
        server.stop()


def test_bucket_padding_outputs_bitwise_equal(predictor):
    """A 3-row request runs as a padded 4-row bucket; the real rows must
    be BITWISE equal to the unpadded direct run (rows are independent
    through fc/relu/softmax, so padding may not perturb them at all)."""
    server = InferenceServer(
        predictor, max_batch_size=8, batch_timeout_ms=1, name="pad")
    try:
        server.warmup()
        xb = _rows(3, seed=5)
        (got,) = server.submit({"x": xb}).result(timeout=30)
        (want,) = predictor.run({"x": xb})
        np.testing.assert_array_equal(got, np.asarray(want))
        hist = server.metrics()["batch_histogram"]
        assert hist["4"]["batches"] == 1 and hist["4"]["valid_rows"] == 3
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# deadlines, shedding, drain (stub predictor: deterministic stalls)
# ---------------------------------------------------------------------------
def test_deadline_expiry_is_timeout_error_not_hang():
    slow = SlowPredictor(delay_s=0.3)
    server = InferenceServer(
        slow, max_batch_size=4, batch_timeout_ms=1, queue_capacity=8,
        name="deadline")
    try:
        # first request occupies the worker for 300 ms...
        blocker = server.submit({"x": _rows(1)})
        time.sleep(0.1)  # worker is now inside the slow run, batch closed
        # ...so this one's 40 ms deadline expires while it waits queued
        fut = server.submit({"x": _rows(1)}, timeout_ms=40)
        t0 = time.monotonic()
        with pytest.raises(DeadlineExceeded):
            # no timeout of the wait's own: the request's 40 ms bound it
            fut.result(timeout=None)
        assert time.monotonic() - t0 < 5.0  # error, not a hang
        blocker.result(timeout=5)
        # the worker eventually pops the expired request and sheds it
        deadline = time.monotonic() + 5
        while server.metrics()["expired"] == 0 and time.monotonic() < deadline:
            time.sleep(0.01)
        assert server.metrics()["expired"] == 1
    finally:
        server.stop()


def test_overload_shedding_raises_typed_error():
    slow = SlowPredictor(delay_s=0.2)
    server = InferenceServer(
        slow, max_batch_size=1, batch_timeout_ms=1, queue_capacity=2,
        name="overload")
    try:
        futs = [server.submit({"x": _rows(1)})]  # worker picks this up
        time.sleep(0.05)  # let the worker start, freeing queue slots
        with pytest.raises(ServerOverloaded):
            for _ in range(16):
                futs.append(server.submit({"x": _rows(1)}))
        assert server.metrics()["shed"] >= 1
        for f in futs:
            f.result(timeout=10)
    finally:
        server.stop()


def test_graceful_drain_completes_queued_work():
    slow = SlowPredictor(delay_s=0.05)
    server = InferenceServer(
        slow, max_batch_size=2, batch_timeout_ms=1, queue_capacity=32,
        name="drain")
    futs = [server.submit({"x": _rows(1, seed=i)}) for i in range(6)]
    server.stop(drain=True)
    assert all(f.done() for f in futs)
    for f in futs:
        assert f.result(timeout=0)[0].shape == (1, 1)
    assert not server._worker.is_alive()
    with pytest.raises(ServerClosed):
        server.submit({"x": _rows(1)})
    assert server.metrics()["completed"] == 6


def test_submit_racing_stop_fails_typed_not_hang():
    """A submit that passed the admission check before stop() ran must
    come back as ServerClosed, never a forever-pending future (the
    worker is gone; nothing would serve the queue)."""
    server = InferenceServer(
        SlowPredictor(), max_batch_size=2, batch_timeout_ms=1, name="race")
    server.stop(drain=True)
    server._closed = False  # simulate losing the admission-check race
    with pytest.raises(ServerClosed):
        server.submit({"x": _rows(1)})


def test_stop_without_drain_fails_queued_requests():
    slow = SlowPredictor(delay_s=0.2)
    server = InferenceServer(
        slow, max_batch_size=1, batch_timeout_ms=1, queue_capacity=32,
        name="abort")
    running = server.submit({"x": _rows(1)})
    time.sleep(0.05)  # worker is now inside the slow run
    queued = [server.submit({"x": _rows(1)}) for _ in range(4)]
    server.stop(drain=False)
    running.result(timeout=10)  # in-flight work still completes
    closed = 0
    for f in queued:
        try:
            f.result(timeout=10)
        except ServerClosed:
            closed += 1
    assert closed >= 1  # everything not yet started was failed, not run


# ---------------------------------------------------------------------------
# the headline guarantee: zero XLA compiles after warmup
# ---------------------------------------------------------------------------
def test_zero_recompiles_after_warmup_mixed_concurrent_sizes(predictor):
    server = InferenceServer(
        predictor, max_batch_size=8, batch_timeout_ms=10, name="warm")
    try:
        compiles = server.warmup()
        assert compiles >= 0  # module-scope predictor may be pre-warmed
        assert server.bucket_ladder == [1, 2, 4, 8]
        misses0 = predictor.jit_cache_stats()["misses"]

        cli = Client(server)
        sizes = [1, 2, 3, 5, 7, 8, 4, 6, 1, 3, 2, 5]
        errors = []

        def go(i, n):
            try:
                (out,) = cli.infer({"x": _rows(n, seed=i)})
                assert out.shape == (n, OUT_DIM)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [
            threading.Thread(target=go, args=(i, n),
                             daemon=True) for i, n in enumerate(sizes)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(WAIT)
        assert not any(t.is_alive() for t in threads)
        assert not errors
        stats = predictor.jit_cache_stats()
        assert stats["misses"] == misses0, (
            "serving recompiled after warmup: %s" % stats)
        m = server.metrics()
        assert m["recompiles"] == 0
        assert m["completed"] == len(sizes)
        # the registry's series says the same (read before stop(),
        # which retires this server's series from the exposition)
        from paddle_tpu import monitor

        assert monitor.counter_value(
            "serving_recompiles_total", default=-1, server="warm") == 0
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# metrics + profiler JSONL trace integration
# ---------------------------------------------------------------------------
def test_metrics_snapshot_and_jsonl_trace(predictor, tmp_path):
    trace = str(tmp_path / "serving_trace.jsonl")
    with profiler.jsonl_trace(trace):
        server = InferenceServer(
            predictor, max_batch_size=4, batch_timeout_ms=1, name="traced")
        try:
            server.warmup()
            for i in range(3):
                server.submit({"x": _rows(2, seed=i)}).result(timeout=30)
        finally:
            server.stop()
        m = server.metrics()
    assert m["batches"] == 3 and m["completed"] == 3
    assert m["latency_p50_ms"] > 0 and m["latency_p99_ms"] >= m["latency_p50_ms"]
    assert m["qps"] > 0
    assert m["mean_batch_occupancy"] == 1.0  # 2 rows in bucket 2, thrice
    events = [json.loads(ln) for ln in open(trace)]
    batches = [e for e in events if e["event"] == "serving.batch"]
    assert len(batches) == 3
    assert all(e["server"] == "traced" and e["bucket"] == 2 and e["valid"] == 2
               for e in batches)
    assert all("ts" in e and "run_ms" in e for e in batches)


def test_feed_validation_is_loud(predictor):
    server = InferenceServer(predictor, max_batch_size=4, name="valid")
    try:
        with pytest.raises(ValueError, match="feed names"):
            server.submit({"nope": _rows(1)})
        with pytest.raises(ValueError, match="expects"):
            server.submit({"x": np.zeros((1, IN_DIM + 1), "float32")})
        with pytest.raises(ValueError, match="exceeds max_batch_size"):
            server.submit({"x": _rows(5)})
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# multi-replica dispatch (PR 4): N predictors behind one batcher
# ---------------------------------------------------------------------------
class KillablePredictor(SlowPredictor):
    """SlowPredictor that can be flipped into a hard-failing state —
    the deterministic 'replica died' stand-in."""

    def __init__(self, delay_s=0.0):
        super().__init__(delay_s)
        self.killed = False

    def run_padded(self, feed, n_valid=None):
        if self.killed:
            raise RuntimeError("replica hardware lost")
        return super().run_padded(feed, n_valid=n_valid)


def _storm(server, n_req, start_val=0):
    futs = []
    for i in range(n_req):
        row = np.full((1, IN_DIM), float(start_val + i), np.float32)
        futs.append((start_val + i, server.submit({"x": row})))
    return futs


def _measure_throughput(n_replicas, n_req=20, delay=0.03):
    preds = [SlowPredictor(delay) for _ in range(n_replicas)]
    server = InferenceServer(
        preds if n_replicas > 1 else preds[0], max_batch_size=1,
        batch_timeout_ms=1, queue_capacity=128,
        name="tp%d" % n_replicas)
    try:
        server.warmup(configure_cache=False)
        t0 = time.perf_counter()
        futs = [server.submit({"x": _rows(1, seed=i)}) for i in range(n_req)]
        for f in futs:
            f.result(timeout=30)
        elapsed = time.perf_counter() - t0
        m = server.metrics()
        assert m["recompiles"] == 0  # zero recompiles after warmup
        assert m["completed"] == n_req
        return elapsed
    finally:
        server.stop()


def test_two_replica_throughput_exceeds_1_5x_single():
    """The scale-out acceptance bar: two replicas behind the one
    batcher must beat 1.5x single-replica throughput on a synthetic
    slow endpoint (the sleeps release the GIL like device compute
    does), with zero recompiles after warmup."""
    t1 = _measure_throughput(1)
    t2 = _measure_throughput(2)
    speedup = t1 / t2
    assert speedup > 1.5, (
        "2-replica speedup %.2fx (1 rep %.3fs vs 2 reps %.3fs)"
        % (speedup, t1, t2))


def test_killed_replica_drains_without_dropping_requests():
    """A replica that starts failing mid-traffic is retired and its
    batches re-route to the survivor: every ACCEPTED request completes
    with its own correct result — none dropped, none failed."""
    p0, p1 = KillablePredictor(0.02), KillablePredictor(0.02)
    server = InferenceServer(
        [p0, p1], max_batch_size=1, batch_timeout_ms=1,
        queue_capacity=128, name="killtest")
    try:
        server.warmup(configure_cache=False)
        futs = []
        for i in range(30):
            futs.append(_storm(server, 1, start_val=i)[0])
            if i == 10:
                p0.killed = True  # replica r0 dies mid-stream
        for val, fut in futs:
            (out,) = fut.result(timeout=30)
            np.testing.assert_allclose(out[0, 0], val * IN_DIM, rtol=1e-5)
        m = server.metrics()
        assert m["completed"] == 30 and m["failed"] == 0
        reps = m["replicas"]
        # exactly one replica survived; batches were re-routed, and the
        # failing replica was retired from routing after repeated faults
        assert sorted(r["alive"] for r in reps.values()) == [False, True]
        assert m["requeued"] >= 1
        assert server.num_replicas == 1
    finally:
        server.stop(drain=True)


def test_all_replicas_dead_fails_typed_not_hang():
    p0, p1 = KillablePredictor(), KillablePredictor()
    server = InferenceServer(
        [p0, p1], max_batch_size=1, batch_timeout_ms=1, name="alldead")
    try:
        p0.killed = p1.killed = True
        futs = [server.submit({"x": _rows(1)}) for _ in range(4)]
        failed = 0
        for f in futs:
            try:
                f.result(timeout=30)
            except (serving.ServingError, RuntimeError):
                failed += 1
        assert failed == 4  # typed errors, never hangs
    finally:
        server.stop(drain=True)


def test_remove_replica_graceful():
    """remove_replica: stops routing, finishes queued work, refuses to
    remove the last live replica."""
    pa, pb = SlowPredictor(0.01), SlowPredictor(0.01)
    server = InferenceServer(
        [pa, pb], max_batch_size=1, batch_timeout_ms=1,
        queue_capacity=128, name="rmtest")
    try:
        server.warmup(configure_cache=False)
        futs = [server.submit({"x": _rows(1, seed=i)}) for i in range(10)]
        server.remove_replica(0)
        futs += [server.submit({"x": _rows(1, seed=i)}) for i in range(10)]
        for f in futs:
            f.result(timeout=30)
        assert server.num_replicas == 1
        assert server.metrics()["replicas"]["r0"]["alive"] is False
        with pytest.raises(ValueError, match="last live replica"):
            server.remove_replica("r1")
        assert server.metrics()["completed"] == 20
    finally:
        server.stop(drain=True)


def test_multi_replica_warmup_compiles_every_replica(predictor, tmp_path):
    """The zero-recompile guarantee holds FLEET-wide: warmup touches
    every replica, and mixed-size traffic after warmup never misses any
    replica's jit cache (real AnalysisPredictors)."""
    from paddle_tpu.inference import AnalysisConfig, create_paddle_predictor

    d = str(tmp_path / "mlp2")
    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 7
    with framework.program_guard(prog, startup):
        x = fluid.layers.data("x", [IN_DIM])
        h = fluid.layers.fc(x, 32, act="relu")
        pred = fluid.layers.fc(h, OUT_DIM, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.save_inference_model(d, ["x"], [pred], exe, prog)
    second = create_paddle_predictor(AnalysisConfig(d))

    server = InferenceServer(
        [predictor, second], max_batch_size=8, batch_timeout_ms=5,
        name="fleetwarm")
    try:
        server.warmup()
        misses0 = [predictor.jit_cache_stats()["misses"],
                   second.jit_cache_stats()["misses"]]
        cli = Client(server)
        sizes = [1, 2, 3, 5, 7, 8, 4, 6, 1, 3, 2, 5, 8, 7]
        errors = []

        def go(i, n):
            try:
                (out,) = cli.infer({"x": _rows(n, seed=i)})
                assert out.shape == (n, OUT_DIM)
            except Exception as e:  # noqa: BLE001
                errors.append(e)

        threads = [threading.Thread(target=go, args=(i, n), daemon=True)
                   for i, n in enumerate(sizes)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(WAIT)
        assert not any(t.is_alive() for t in threads)
        assert not errors
        assert [predictor.jit_cache_stats()["misses"],
                second.jit_cache_stats()["misses"]] == misses0, (
            "a replica recompiled after fleet warmup")
        m = server.metrics()
        assert m["recompiles"] == 0 and m["completed"] == len(sizes)
        # both replicas actually served traffic (least-loaded routing)
        executed = [r["executed"] for r in m["replicas"].values()]
        assert sum(executed) == m["batches"]
    finally:
        server.stop()


def test_idle_batcher_sleeps_on_condition_not_poll():
    """The CV rewrite: a consumer parked on an empty queue wakes
    promptly on offer() (no 20ms poll quantum), and wake() unparks it
    at shutdown."""
    from paddle_tpu.serving.batching import DynamicBatcher

    b = DynamicBatcher(max_batch_size=4, batch_timeout_ms=1,
                       queue_capacity=8)
    stop = threading.Event()
    got = []

    def worker():
        got.append(b.next_batch(stop, lambda r: None, block=True))

    t = threading.Thread(target=worker, daemon=True)
    t.start()
    time.sleep(0.15)  # worker is parked on the condition
    t0 = time.perf_counter()
    b.offer(ServingRequestStub())
    t.join(timeout=5)
    latency = time.perf_counter() - t0
    assert got and got[0] is not None and len(got[0]) == 1
    assert latency < 0.1, "offer->wake latency %.3fs (poll, not CV?)" % latency

    # wake() releases a parked consumer once stopped
    got.clear()
    t = threading.Thread(target=worker, daemon=True)
    t.start()
    time.sleep(0.05)
    stop.set()
    t0 = time.perf_counter()
    b.wake()
    t.join(timeout=5)
    assert time.perf_counter() - t0 < 0.1
    assert got == [None]


class ServingRequestStub:
    """Minimal live request for batcher-level tests."""

    n_rows = 1
    deadline = None

    def expired(self, now=None):
        return False


# ---------------------------------------------------------------------------
# request-scoped tracing (PR 5): trace-id propagation + flight recorder
# ---------------------------------------------------------------------------
def test_trace_id_propagates_client_to_executor_spans(predictor):
    """One trace id, minted at the client, must appear on every span in
    the chain: client span, queue wait, predictor hop, and the
    executor's h2d/execute phases recorded on the replica thread."""
    from paddle_tpu import monitor

    server = InferenceServer(
        predictor, max_batch_size=4, batch_timeout_ms=1, name="tracey")
    try:
        server.warmup()
        cli = Client(server)
        with monitor.trace_session() as sess:
            cli.infer({"x": _rows(2, seed=9)}, trace_id="feedbeef00000001")
        # client minted a fresh id when not given one
        out = cli.infer({"x": _rows(1)})
        assert len(out) == 1 and len(cli.last_trace_id) == 16
    finally:
        server.stop()
    by_name = {}
    for s in sess.spans:
        if "feedbeef00000001" in (s.get("trace_ids") or ()):
            by_name.setdefault(s["name"], []).append(s)
    assert "serving/client_infer" in by_name
    assert "serving/queue_wait" in by_name
    assert "predictor/run_padded" in by_name
    assert "serving/materialize" in by_name
    assert "executor/h2d_feed" in by_name
    # warmup ran before the session; the traced request executes from
    # the jit cache
    assert "executor/device_execute" in by_name
    # the client span covers the whole request; queue wait nests inside
    q = by_name["serving/queue_wait"][0]
    c = by_name["serving/client_infer"][0]
    assert c["dur"] >= q["dur"] >= 0


def test_flight_recorder_tail_samples_slow_requests(predictor):
    """Tail sampling: with a recorder installed, a slow request's full
    span tree is retained (keyed by its trace id) and served by
    /tracez; fast requests under slow_ms are not."""
    import json as _json
    import urllib.request

    from paddle_tpu import monitor
    from paddle_tpu.monitor import flight as _flight

    slow = SlowPredictor(delay_s=0.05)
    server = InferenceServer(
        slow, max_batch_size=2, batch_timeout_ms=1, name="flighty")
    with monitor.flight_recorder(capacity=16, slow_ms=20.0) as rec:
        try:
            server.warmup(configure_cache=False)
            cli = Client(server)
            cli.infer({"x": _rows(1)}, trace_id="aaaa000011112222")
            record = rec.get_record("aaaa000011112222")
            assert record is not None, "50ms request above slow_ms=20 dropped"
            names = [s["name"] for s in record["spans"]]
            assert "serving/queue_wait" in names
            assert "serving/materialize" in names
            assert "serving/client_infer" in names  # attached post-result
            assert record["status"] == "ok"
            assert record["latency_ms"] >= 20.0
            assert record["replica"] == "r0"

            host, port = server.start_admin(port=0)
            with urllib.request.urlopen(
                    "http://%s:%d/tracez" % (host, port), timeout=10) as resp:
                doc = _json.load(resp)
            assert doc["recorder"] is True
            assert any(r["trace_id"] == "aaaa000011112222"
                       for r in doc["requests"])

            # a fast request stays below the threshold -> not retained
            slow.delay_s = 0.0
            cli.infer({"x": _rows(1)}, trace_id="bbbb000011112222")
            assert rec.get_record("bbbb000011112222") is None
        finally:
            server.stop()
    assert _flight.get() is None  # context exit uninstalls


def test_flight_recorder_retains_deadline_missed_requests():
    from paddle_tpu import monitor

    slow = SlowPredictor(delay_s=0.3)
    server = InferenceServer(
        slow, max_batch_size=4, batch_timeout_ms=1, queue_capacity=8,
        name="flightdl")
    with monitor.flight_recorder(capacity=16, slow_ms=1e9) as rec:
        try:
            blocker = server.submit({"x": _rows(1)})
            time.sleep(0.1)
            fut = server.submit({"x": _rows(1)},
                                timeout_ms=40, trace_id="dead000011112222")
            with pytest.raises(DeadlineExceeded):
                fut.result(timeout=None)  # its 40 ms deadline is the bound
            blocker.result(timeout=5)
            deadline = time.monotonic() + 5
            while (rec.get_record("dead000011112222") is None
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            record = rec.get_record("dead000011112222")
            assert record is not None and record["status"] == "deadline"
        finally:
            server.stop()


def test_openmetrics_exemplar_links_latency_bucket_to_trace(predictor):
    """The OpenMetrics exposition must carry a trace_id exemplar on the
    latency histogram bucket the traced request landed in."""
    from paddle_tpu import monitor

    server = InferenceServer(
        predictor, max_batch_size=2, batch_timeout_ms=1, name="exemplary")
    try:
        server.warmup()
        Client(server).infer({"x": _rows(1)}, trace_id="cafe000011112222")
        text = monitor.render_openmetrics()
        lat_lines = [l for l in text.splitlines()
                     if l.startswith("serving_request_latency_seconds_bucket")
                     and 'server="exemplary"' in l]
        assert any('# {trace_id="cafe000011112222"}' in l for l in lat_lines), (
            "no exemplar found:\n" + "\n".join(lat_lines))
        assert text.rstrip().endswith("# EOF")
    finally:
        server.stop()


# ---------------------------------------------------------------------------
# serving lifecycle markers (PR 5): incidents visible on the timeline
# ---------------------------------------------------------------------------
def test_lifecycle_markers_agree_with_requeue_counter():
    """Replica retirement / batch requeue / graceful drain emit instant
    trace markers carrying the replica id, and the requeue markers agree
    with the serving_requeued_total counter delta."""
    from paddle_tpu import monitor

    p0, p1 = KillablePredictor(0.02), KillablePredictor(0.02)
    server = InferenceServer(
        [p0, p1], max_batch_size=1, batch_timeout_ms=1,
        queue_capacity=128, name="marktest")
    with monitor.trace_session() as sess:
        try:
            server.warmup(configure_cache=False)
            requeued0 = monitor.counter_value(
                "serving_requeued_total", server="marktest")
            futs = []
            for i in range(30):
                futs.append(_storm(server, 1, start_val=i)[0])
                if i == 10:
                    p0.killed = True
            for _, fut in futs:
                fut.result(timeout=30)
            requeued = monitor.counter_value(
                "serving_requeued_total", server="marktest") - requeued0
        finally:
            server.stop(drain=True)
    markers = [s for s in sess.spans
               if s.get("args", {}).get("instant")
               and s["args"].get("server") == "marktest"]
    retire = [m for m in markers if m["name"] == "serving/replica_retired"]
    requeue = [m for m in markers if m["name"] == "serving/batch_requeue"]
    drain = [m for m in markers if m["name"] == "serving/server_drain"]
    assert len(retire) == 1 and retire[0]["args"]["replica"] == "r0"
    assert requeued >= 1
    assert len(requeue) == requeued, (
        "counter says %d requeues, timeline shows %d markers"
        % (requeued, len(requeue)))
    assert all(m["args"]["replica"] == "r0" for m in requeue)
    assert len(drain) == 1  # stop(drain=True)


def test_remove_replica_emits_drain_marker():
    from paddle_tpu import monitor

    pa, pb = SlowPredictor(0.01), SlowPredictor(0.01)
    server = InferenceServer(
        [pa, pb], max_batch_size=1, batch_timeout_ms=1, name="drainmark")
    with monitor.trace_session() as sess:
        try:
            server.warmup(configure_cache=False)
            server.remove_replica("r0")
        finally:
            server.stop(drain=True)
    drains = [s for s in sess.spans
              if s["name"] == "serving/replica_drain"
              and s.get("args", {}).get("server") == "drainmark"]
    assert len(drains) == 1 and drains[0]["args"]["replica"] == "r0"
