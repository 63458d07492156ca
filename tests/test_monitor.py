"""paddle_tpu.monitor tests: registry semantics, Prometheus text
exposition, executor run-phase spans + jit hit/miss counters, JSONL
trace concurrency, the merged Chrome-trace export (LeNet train loop +
serving warmup/run -> one trace.json), serving admin endpoints, reader
stall counters, and the near-zero-cost-when-idle guarantee.
"""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

from conftest import WAIT

import paddle_tpu as fluid
from paddle_tpu import framework, models, monitor, profiler
from paddle_tpu.inference import AnalysisConfig, create_paddle_predictor
from paddle_tpu.monitor.registry import MetricsRegistry
from paddle_tpu.serving import InferenceServer

IN_DIM, OUT_DIM = 16, 4


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------
def test_counter_semantics():
    reg = MetricsRegistry()
    c = reg.counter("requests_total", "requests", ("endpoint",))
    c.labels(endpoint="a").inc()
    c.labels(endpoint="a").inc(4)
    c.labels(endpoint="b").inc(2.5)
    assert c.labels(endpoint="a").value == 5
    assert c.labels(endpoint="b").value == 2.5
    assert reg.value("requests_total") == 7.5          # sum across series
    assert reg.value("requests_total", endpoint="a") == 5
    assert reg.value("nonexistent_total", default=-1) == -1
    with pytest.raises(ValueError):
        c.labels(endpoint="a").inc(-1)                 # counters only go up
    with pytest.raises(ValueError):
        c.labels(wrong="a")                            # label names enforced
    with pytest.raises(ValueError):
        c.inc()                                        # labeled metric needs labels()


def test_gauge_and_histogram_semantics():
    reg = MetricsRegistry()
    g = reg.gauge("depth", "queue depth")
    g.set(7)
    g.inc(3)
    g.dec()
    assert g.value == 9

    h = reg.histogram("lat", "latency", buckets=(0.01, 0.1, 1.0))
    for v in (0.005, 0.05, 0.05, 0.5, 50.0):
        h.observe(v)
    snap = h.labels().value
    assert snap["count"] == 5
    assert snap["sum"] == pytest.approx(50.605)
    assert snap["buckets"] == {"0.01": 1, "0.1": 3, "1": 4, "+Inf": 5}


def test_registration_is_idempotent_and_typed():
    reg = MetricsRegistry()
    c1 = reg.counter("x_total", "first", ("a",))
    c2 = reg.counter("x_total", "ignored on re-register", ("a",))
    assert c1 is c2
    with pytest.raises(ValueError):
        reg.gauge("x_total")                 # same name, different type
    with pytest.raises(ValueError):
        reg.counter("x_total", labelnames=("b",))  # different labels
    with pytest.raises(ValueError):
        reg.counter("bad name")              # invalid metric name
    snap = reg.snapshot()
    assert set(snap) == {"x_total"}
    assert snap["x_total"]["type"] == "counter"


def test_text_exposition_format():
    reg = MetricsRegistry()
    c = reg.counter("rpc_total", "total\nrpcs", ("method",))
    c.labels(method='get"x"\\y').inc(3)
    reg.gauge("temp", "degrees").set(1.5)
    h = reg.histogram("dur_seconds", "", ("op",), buckets=(0.5,))
    h.labels(op="run").observe(0.25)
    h.labels(op="run").observe(2.0)
    text = reg.render_text()
    lines = text.splitlines()
    # HELP newline-escaped, TYPE lines present, label values escaped
    assert "# HELP rpc_total total rpcs" in lines
    assert "# TYPE rpc_total counter" in lines
    assert 'rpc_total{method="get\\"x\\"\\\\y"} 3' in lines
    assert "# TYPE temp gauge" in lines and "temp 1.5" in lines
    assert "# TYPE dur_seconds histogram" in lines
    assert 'dur_seconds_bucket{op="run",le="0.5"} 1' in lines  # le last, like the official client
    assert 'dur_seconds_bucket{op="run",le="+Inf"} 2' in lines
    assert 'dur_seconds_sum{op="run"} 2.25' in lines
    assert 'dur_seconds_count{op="run"} 2' in lines
    assert text.endswith("\n")


# ---------------------------------------------------------------------------
# executor run-phase spans + jit cache counters
# ---------------------------------------------------------------------------
def _small_program(seed=5):
    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = seed
    with framework.program_guard(prog, startup):
        x = fluid.layers.data("x", [8])
        y = fluid.layers.fc(x, 4)
        loss = fluid.layers.mean(y)
    return prog, startup, loss


def test_executor_phase_spans_and_jit_counters():
    prog, startup, loss = _small_program()
    exe = fluid.Executor(fluid.CPUPlace())
    feed = {"x": np.zeros((2, 8), "float32")}
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        hits0 = monitor.counter_value("executor_jit_cache_hits_total")
        misses0 = monitor.counter_value("executor_jit_cache_misses_total")
        stats0 = exe.jit_cache_stats()
        with monitor.trace_session() as sess:
            exe.run(prog, feed=feed, fetch_list=[loss])
            exe.run(prog, feed=feed, fetch_list=[loss])
    names = [s["name"] for s in sess.spans]
    # first dispatch compiles, second executes from the cache
    assert names.count("executor/jit_compile") == 1
    assert names.count("executor/device_execute") == 1
    assert names.count("executor/h2d_feed") == 2
    assert names.count("executor/d2h_fetch") == 2
    # the first dispatch is one build: the in-jit trace of the block
    # and XLA's compile (or cache load) hang under build/executor_step
    assert names.count("build/executor_step") == 1
    (build,) = [s for s in sess.spans if s["name"] == "build/executor_step"]
    inside = {s["name"] for s in sess.spans
              if s.get("parent") == build["id"]}
    assert "lowering/trace_block" in inside
    assert inside & {"build/compile", "build/cache_load"}
    for s in sess.spans:
        assert s["dur"] >= 0 and "ts" in s and "tid" in s
    # registry counters move in lockstep with the executor's own stats
    assert monitor.counter_value("executor_jit_cache_misses_total") - misses0 == 1
    assert monitor.counter_value("executor_jit_cache_hits_total") - hits0 == 1
    stats = exe.jit_cache_stats()
    assert stats["misses"] - stats0["misses"] == 1
    assert stats["hits"] - stats0["hits"] == 1


def test_spans_off_outside_session():
    prog, startup, loss = _small_program(seed=6)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        assert not monitor.recording()
        exe.run(prog, feed={"x": np.zeros((2, 8), "float32")}, fetch_list=[loss])
    assert monitor.stop_recording() == []  # nothing buffered


def test_instrumentation_overhead_when_idle(monkeypatch):
    """With no trace session and nothing scraping the registry, what the
    instrumentation adds to a warmed ``Executor.run`` is COUNTED, not
    timed (two timings of near-identical code differ by scheduler noise
    far larger than the real delta, and under five other test workers
    they failed the suite): the jit counters are collect-on-read, so a
    run makes ONE ``recording()`` gate call, reads the clock twice (the
    dispatch-overhead counter's pair), never calls ``record_span`` and
    leaves nothing in the span buffer."""
    import types

    from paddle_tpu import executor as executor_mod
    from paddle_tpu.monitor import spans as mon_spans

    prog, startup, loss = _small_program(seed=7)
    exe = fluid.Executor(fluid.CPUPlace())
    feed = {"x": np.zeros((2, 8), "float32")}
    calls = {}

    def counted(name, fn):
        def wrapper(*a, **kw):
            calls[name] = calls.get(name, 0) + 1
            return fn(*a, **kw)
        return wrapper

    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        for _ in range(3):  # warm the jit cache + the dispatch path
            exe.run(prog, feed=feed, fetch_list=[loss])
        assert not mon_spans.recording()  # the premise: no active session
        # executor.py's own clock reads: its ``time`` alone is wrapped
        monkeypatch.setattr(executor_mod, "time", types.SimpleNamespace(
            perf_counter=counted("perf_counter", time.perf_counter)))
        monkeypatch.setattr(mon_spans, "recording",
                            counted("recording", mon_spans.recording))
        monkeypatch.setattr(mon_spans, "record_span",
                            counted("record_span", mon_spans.record_span))
        n = 20
        for _ in range(n):
            exe.run(prog, feed=feed, fetch_list=[loss])
    assert calls == {"recording": n, "perf_counter": 2 * n}
    assert not mon_spans._buffer


# ---------------------------------------------------------------------------
# JSONL trace concurrency (satellite): concurrent emitters vs sink cycling
# ---------------------------------------------------------------------------
def test_jsonl_trace_concurrent_emit_and_restart(tmp_path):
    n_emitters, n_files = 4, 6
    paths = [str(tmp_path / ("trace_%d.jsonl" % i)) for i in range(n_files)]
    stop = threading.Event()
    errors = []

    def emitter(tid):
        i = 0
        try:
            while not stop.is_set():
                profiler.emit_trace_event(
                    {"event": "spin", "tid": tid, "i": i, "pad": "x" * 64})
                i += 1
        except Exception as exc:  # write-after-close would land here
            errors.append(exc)

    threads = [
        threading.Thread(target=emitter, args=(t,),
                         daemon=True) for t in range(n_emitters)
    ]
    for t in threads:
        t.start()
    # cycle the sink under fire: every start implicitly stops the
    # previous sink, plus explicit stop/start interleavings
    for i, p in enumerate(paths):
        profiler.start_jsonl_trace(p)
        time.sleep(0.05)
        if i % 2:
            profiler.stop_jsonl_trace()
    profiler.stop_jsonl_trace()
    stop.set()
    for t in threads:
        t.join(WAIT)
    assert not any(t.is_alive() for t in threads)
    assert not errors, errors
    total = 0
    for p in paths:
        with open(p) as f:
            for line in f:
                rec = json.loads(line)  # every line parses: no interleaving
                assert rec["event"] == "spin" and "ts" in rec
                total += 1
    assert total > 0  # the emitters actually hit the live sinks


# ---------------------------------------------------------------------------
# merged Chrome trace: LeNet train loop + serving warmup/run -> trace.json
# ---------------------------------------------------------------------------
def _save_mlp(dirname):
    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 7
    with framework.program_guard(prog, startup):
        x = fluid.layers.data("x", [IN_DIM])
        h = fluid.layers.fc(x, 32, act="relu")
        pred = fluid.layers.fc(h, OUT_DIM, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.save_inference_model(dirname, ["x"], [pred], exe, prog)


def test_merged_chrome_trace_lenet_train_plus_serving(tmp_path):
    jsonl = str(tmp_path / "events.jsonl")
    trace_path = str(tmp_path / "trace.json")

    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 11
    with framework.program_guard(prog, startup):
        img = fluid.layers.data("img", [1, 28, 28])
        lbl = fluid.layers.data("lbl", [1], dtype="int64")
        avg_loss, _, _ = models.lenet5(img, lbl)
        fluid.optimizer.SGDOptimizer(learning_rate=0.001).minimize(avg_loss)
    rng = np.random.RandomState(0)
    feed = {
        "img": rng.uniform(-1, 1, (16, 1, 28, 28)).astype("float32"),
        "lbl": rng.randint(0, 10, (16, 1)).astype("int64"),
    }
    mlp_dir = str(tmp_path / "mlp")
    _save_mlp(mlp_dir)

    with monitor.trace_session(path=trace_path, jsonl_path=jsonl):
        profiler.start_jsonl_trace(jsonl)
        try:
            # train loop: compile on step 1, cached execute on step 2
            exe = fluid.Executor(fluid.CPUPlace())
            with fluid.scope_guard(fluid.Scope()):
                exe.run(startup)
                for _ in range(2):
                    exe.run(prog, feed=feed, fetch_list=[avg_loss])
            # serving warmup + one request on the same timeline
            server = InferenceServer(
                create_paddle_predictor(AnalysisConfig(mlp_dir)),
                max_batch_size=2, batch_timeout_ms=1, name="traced")
            try:
                server.warmup()
                server.submit(
                    {"x": np.zeros((2, IN_DIM), "float32")}).result(timeout=60)
            finally:
                server.stop()
        finally:
            profiler.stop_jsonl_trace()

    data = json.load(open(trace_path))
    events = data["traceEvents"]
    names = {e["name"] for e in events}
    # the distinct run phases, all in ONE file
    assert {"build/executor_step", "executor/jit_compile",
            "executor/device_execute", "executor/h2d_feed",
            "executor/d2h_fetch", "lowering/trace_block"} <= names
    assert names & {"build/compile", "build/cache_load"}
    # RecordEvent spans (serving warmup/batch) merged in
    assert "serving/traced/warmup" in names
    # the JSONL stream (serving.batch discrete events) merged in
    jsonl_events = [e for e in events if e.get("cat") == "jsonl"]
    assert any(e["name"] == "serving.batch" for e in jsonl_events)
    for e in events:
        if e["ph"] == "X":
            assert e["ts"] >= 0 and e["dur"] >= 0
            assert isinstance(e["pid"], int) and isinstance(e["tid"], int)
    durations = [e for e in events if e["ph"] == "X" and e["dur"] > 0]
    assert durations  # something measurable actually landed


def test_merged_trace_with_device_timeline_two_replica_fleet(tmp_path):
    """The PR-5 acceptance trace: a LeNet train loop + a 2-replica
    serving run produce ONE trace.json holding the client ->
    queue-wait -> replica -> executor span chain (every hop sharing the
    request's trace id), named replica worker lanes, AND time-aligned
    device-side events ingested from the jax.profiler trace dir."""
    from paddle_tpu.monitor.chrome_trace import _DEVICE_PID_BASE
    from paddle_tpu.serving import Client

    jsonl = str(tmp_path / "events.jsonl")
    trace_path = str(tmp_path / "trace.json")
    prof_dir = str(tmp_path / "prof")

    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 11
    with framework.program_guard(prog, startup):
        img = fluid.layers.data("img", [1, 28, 28])
        lbl = fluid.layers.data("lbl", [1], dtype="int64")
        avg_loss, _, _ = models.lenet5(img, lbl)
        fluid.optimizer.SGDOptimizer(learning_rate=0.001).minimize(avg_loss)
    rng = np.random.RandomState(0)
    feed = {
        "img": rng.uniform(-1, 1, (8, 1, 28, 28)).astype("float32"),
        "lbl": rng.randint(0, 10, (8, 1)).astype("int64"),
    }
    mlp_dir = str(tmp_path / "mlp")
    _save_mlp(mlp_dir)

    with monitor.trace_session(path=trace_path, jsonl_path=jsonl,
                               device_trace_dir=prof_dir) as sess:
        profiler.start_jsonl_trace(jsonl)
        profiler.start_profiler(trace_dir=prof_dir)
        try:
            exe = fluid.Executor(fluid.CPUPlace())
            with fluid.scope_guard(fluid.Scope()):
                exe.run(startup)
                for _ in range(2):
                    exe.run(prog, feed=feed, fetch_list=[avg_loss])
            server = InferenceServer(
                [create_paddle_predictor(AnalysisConfig(mlp_dir)),
                 create_paddle_predictor(AnalysisConfig(mlp_dir))],
                max_batch_size=2, batch_timeout_ms=1, name="fleet2")
            try:
                server.warmup()
                cli = Client(server)
                for i in range(4):
                    cli.infer({"x": np.zeros((1, IN_DIM), "float32")},
                              trace_id="f1ee7%011d" % i)
            finally:
                server.stop()
        finally:
            profiler.stop_profiler(profile_path=str(tmp_path / "prof.txt"))
            profiler.stop_jsonl_trace()

    data = json.load(open(trace_path))  # loadable JSON
    events = data["traceEvents"]
    names = {e["name"] for e in events}
    # the full host-side chain, one file
    assert {"serving/client_infer", "serving/queue_wait",
            "predictor/run_padded", "serving/materialize",
            "executor/h2d_feed", "executor/device_execute"} <= names
    # one request's trace id on every hop of its chain
    tid = "f1ee7%011d" % 0
    chain = {e["name"] for e in events
             if tid in (e.get("args", {}).get("trace_ids") or ())}
    assert {"serving/client_infer", "serving/queue_wait",
            "predictor/run_padded", "serving/materialize"} <= chain
    assert chain & {"executor/device_execute", "executor/jit_compile"}
    # replica workers render as named parallel lanes
    lanes = {e["args"]["name"] for e in events
             if e["ph"] == "M" and e["name"] == "thread_name"}
    assert {"serving/fleet2/r0 worker", "serving/fleet2/r1 worker",
            "serving/fleet2/dispatcher"} <= lanes
    # device-side events ingested from the jax.profiler dir, rebased
    # onto the shared (non-negative) timebase
    device_events = [e for e in events
                     if e.get("pid", 0) >= _DEVICE_PID_BASE
                     and e["ph"] != "M"]
    assert device_events, "no device-side events merged"
    assert all(e["ts"] >= 0 for e in device_events if "ts" in e)
    # both sources overlap in time (alignment sanity: the device window
    # must intersect the host window, not sit off to one side)
    host_ts = [e["ts"] for e in events
               if e.get("pid", 0) < _DEVICE_PID_BASE and e["ph"] == "X"]
    dev_ts = [e["ts"] for e in device_events if "ts" in e]
    assert min(dev_ts) <= max(host_ts) and min(host_ts) <= max(dev_ts)


# ---------------------------------------------------------------------------
# serving admin surface: /metrics + /statusz
# ---------------------------------------------------------------------------
def test_serving_admin_metrics_and_statusz(tmp_path):
    mlp_dir = str(tmp_path / "mlp")
    _save_mlp(mlp_dir)
    server = InferenceServer(
        create_paddle_predictor(AnalysisConfig(mlp_dir)),
        max_batch_size=2, batch_timeout_ms=1, name="adminz")
    try:
        server.warmup()
        server.submit({"x": np.zeros((2, IN_DIM), "float32")}).result(timeout=60)
        host, port = server.start_admin(port=0)
        assert server.start_admin() == (host, port)  # idempotent
        base = "http://%s:%d" % (host, port)

        with urllib.request.urlopen(base + "/metrics", timeout=10) as resp:
            assert resp.headers["Content-Type"].startswith("text/plain")
            text = resp.read().decode()
        assert "# TYPE serving_requests_total counter" in text
        assert 'serving_completed_total{instance=' in text
        assert 'server="adminz"' in text
        assert "# TYPE executor_runs_total counter" in text  # whole registry

        with urllib.request.urlopen(base + "/statusz", timeout=10) as resp:
            status = json.load(resp)
        assert status["server"] == "adminz"
        assert status["metrics"]["completed"] == 1
        assert status["metrics"]["recompiles"] == 0
        assert status["metrics"]["bucket_ladder"] == [1, 2]
        assert status["metrics"]["batch_histogram"]["2"]["batches"] == 1
        assert status["jit_cache"]["misses"] >= 2  # one per warmup rung
        assert "serving_requests_total" in status["registry"]

        with pytest.raises(urllib.error.HTTPError):
            urllib.request.urlopen(base + "/nope", timeout=10)
    finally:
        server.stop()
    assert server.admin_address is None  # stop() tears the admin down
    # stop() retires this instance's registry series (no unbounded
    # exposition growth across server constructions)...
    assert 'server="adminz"' not in monitor.render_text()
    # ...but the local snapshot keeps working off the detached children
    assert server.metrics()["completed"] == 1


def test_trace_session_on_failing_body_still_writes_trace(tmp_path):
    trace_path = str(tmp_path / "trace.json")
    missing_jsonl = str(tmp_path / "never_created.jsonl")
    with pytest.raises(RuntimeError, match="boom"):
        with monitor.trace_session(path=trace_path, jsonl_path=missing_jsonl):
            with monitor.span("doomed"):
                pass
            raise RuntimeError("boom")  # body dies before any jsonl exists
    # the body's exception propagated (not masked by the export) AND the
    # trace still landed, with the missing jsonl tolerated
    data = json.load(open(trace_path))
    assert any(e["name"] == "doomed" for e in data["traceEvents"])
    assert not monitor.recording()


# ---------------------------------------------------------------------------
# reader pipeline stall counters
# ---------------------------------------------------------------------------
def test_reader_stall_counters():
    from paddle_tpu import reader as reader_mod

    def slow_source():
        for i in range(5):
            time.sleep(0.01)
            yield i

    stalls0 = monitor.counter_value("reader_consumer_stalls_total")
    stall_s0 = monitor.counter_value("reader_consumer_stall_seconds_total")
    out = list(reader_mod.buffered(slow_source, 2)())
    assert out == [0, 1, 2, 3, 4]
    # a fast consumer over a slow producer stalls on nearly every item
    assert monitor.counter_value("reader_consumer_stalls_total") - stalls0 >= 3
    assert monitor.counter_value("reader_consumer_stall_seconds_total") > stall_s0

    def fast_source():
        yield from range(8)

    bp0 = monitor.counter_value("reader_producer_stalls_total")
    gen = reader_mod.buffered(fast_source, 2)()
    next(gen)
    time.sleep(0.1)  # producer fills the size-2 queue and blocks
    assert monitor.counter_value("reader_producer_stalls_total") > bp0
    assert list(gen) == [1, 2, 3, 4, 5, 6, 7]


# ---------------------------------------------------------------------------
# PR 3: bounded span buffer + dispatch-overhead instrumentation
# ---------------------------------------------------------------------------
def test_trace_session_ring_buffer_drop_oldest():
    from paddle_tpu.monitor import spans

    before_total = spans.dropped_total()
    with monitor.trace_session(max_spans=5) as sess:
        for i in range(12):
            monitor.record_span("s%d" % i, time.perf_counter(), 0.001)
    assert len(sess.spans) == 5
    assert [s["name"] for s in sess.spans] == ["s7", "s8", "s9", "s10", "s11"]
    assert sess.dropped == 7  # drop-oldest, counted
    assert spans.dropped_total() == before_total + 7
    assert monitor.counter_value("trace_dropped_spans_total") >= 7

    # unbounded sessions are unaffected
    with monitor.trace_session() as sess2:
        for i in range(12):
            monitor.record_span("u%d" % i, time.perf_counter(), 0.001)
    assert len(sess2.spans) == 12 and sess2.dropped == 0

    with pytest.raises(ValueError):
        monitor.start_recording(max_spans=0)


def test_openmetrics_exposition_format():
    """OpenMetrics 1.0: counter families drop the _total suffix in
    HELP/TYPE (samples keep it), histogram buckets may carry exemplars,
    and the document ends with # EOF."""
    reg = MetricsRegistry()
    reg.counter("rpc_total", "total rpcs", ("method",)).labels(
        method="get").inc(3)
    reg.gauge("temp", "degrees").set(1.5)
    h = reg.histogram("dur_seconds", "latency", buckets=(0.5,))
    h.observe(0.25, exemplar={"trace_id": "abc123"})
    h.observe(2.0)
    text = reg.render_openmetrics()
    lines = text.splitlines()
    assert "# TYPE rpc counter" in lines          # family name, no _total
    assert "# HELP rpc total rpcs" in lines
    assert 'rpc_total{method="get"} 3' in lines   # sample keeps _total
    assert "# TYPE temp gauge" in lines and "temp 1.5" in lines
    assert "# TYPE dur_seconds histogram" in lines
    # the 0.25 observation's exemplar rides its bucket line
    ex = [l for l in lines if l.startswith('dur_seconds_bucket{le="0.5"}')]
    assert len(ex) == 1 and '# {trace_id="abc123"} 0.25' in ex[0]
    assert 'dur_seconds_bucket{le="+Inf"} 2' in lines
    assert lines[-1] == "# EOF"
    body, ctype = reg.expose(openmetrics=True)
    assert body == text and ctype.startswith("application/openmetrics-text")
    body, ctype = reg.expose()
    assert ctype.startswith("text/plain") and body == reg.render_text()


def test_flight_recorder_ring_and_merge_semantics():
    from paddle_tpu.monitor.flight import FlightRecorder

    rec = FlightRecorder(capacity=3, slow_ms=10.0)
    assert rec.consider("t1", 0.005, "ok", ()) is False       # fast: dropped
    assert rec.consider("t2", 0.020, "ok", ()) is True        # slow: kept
    assert rec.consider("t3", 0.001, "error", ()) is True     # errored: kept
    assert rec.consider("t4", 0.001, "deadline", ()) is True  # deadline: kept
    # merge into an existing record: status upgrades, spans append
    assert rec.consider("t2", 0.001, "error",
                        [{"name": "late", "ts": 0.0, "dur": 0.0}]) is True
    r2 = rec.get_record("t2")
    assert r2["status"] == "error" and r2["latency_ms"] == 20.0
    assert [s["name"] for s in r2["spans"]] == ["late"]
    # capacity 3: a fourth retained record evicts the oldest (t2)
    assert rec.consider("t5", 0.500, "ok", ()) is True
    assert rec.get_record("t2") is None
    assert len(rec) == 3
    assert [r["trace_id"] for r in rec.snapshot()] == ["t5", "t4", "t3"]
    assert rec.add_span("t5", {"name": "x", "ts": 1.0, "dur": 0.1})
    assert not rec.add_span("gone", {"name": "x"})
    doc = rec.statusz()
    assert doc["retained"] == 3 and doc["capacity"] == 3
    json.dumps(doc)  # /tracez must be JSON-serializable


def test_flight_recorder_chrome_export(tmp_path):
    from paddle_tpu.monitor.flight import FlightRecorder

    rec = FlightRecorder(capacity=4, slow_ms=0.0)
    rec.consider("tt00000000000001", 0.05, "ok", [
        {"name": "serving/queue_wait", "ts": 100.0, "dur": 0.01,
         "tid": 1, "cat": "serving", "trace_ids": ["tt00000000000001"]},
        {"name": "executor/device_execute", "ts": 100.01, "dur": 0.04,
         "tid": 2, "cat": "execute", "trace_ids": ["tt00000000000001"]},
    ])
    path = rec.export_chrome_trace(str(tmp_path / "flight.json"))
    data = json.load(open(path))
    evs = [e for e in data["traceEvents"] if e["ph"] == "X"]
    assert {e["name"] for e in evs} == {
        "serving/queue_wait", "executor/device_execute"}
    assert all(e["args"]["trace_ids"] == ["tt00000000000001"] for e in evs)


def test_push_gateway_delivers_exposition(tmp_path):
    """The push loop PUTs the exposition to <url>/metrics/job/<job>,
    pushes a final snapshot on stop, and never raises on a dead
    gateway."""
    import http.server

    bodies, paths = [], []

    class _Gw(http.server.BaseHTTPRequestHandler):
        def do_PUT(self):
            n = int(self.headers.get("Content-Length", 0))
            bodies.append(self.rfile.read(n).decode())
            paths.append(self.path)
            self.send_response(200)
            self.end_headers()

        def log_message(self, *a):
            pass

    gw = http.server.ThreadingHTTPServer(("127.0.0.1", 0), _Gw)
    t = threading.Thread(target=gw.serve_forever, daemon=True)
    t.start()
    try:
        url = "http://127.0.0.1:%d" % gw.server_address[1]
        pusher = monitor.push_gateway(url, interval_s=0.05, job="bench job")
        deadline = time.monotonic() + 10
        while not bodies and time.monotonic() < deadline:
            time.sleep(0.01)
        pusher.stop()  # final push
        assert bodies, "no push arrived"
        assert paths[0] == "/metrics/job/bench%20job"
        assert "# TYPE executor_runs_total counter" in bodies[0]
        pushes = monitor.counter_value("monitor_push_total")
        assert pushes >= 2  # at least one interval push + the final one
    finally:
        gw.shutdown()
        gw.server_close()
    # dead gateway: push_now reports failure, raises nothing
    dead = monitor.push_gateway(
        "http://127.0.0.1:1", interval_s=60, timeout_s=0.2)
    errs0 = monitor.counter_value("monitor_push_errors_total")
    assert dead.push_now() is False
    assert monitor.counter_value("monitor_push_errors_total") == errs0 + 1
    dead.stop(push_final=False)


def test_plan_cache_counters_and_dispatch_histogram():
    """The executor's plan-cache counters reach the registry, and the
    per-run dispatch-overhead histogram records under a trace session."""
    prog, startup = framework.Program(), framework.Program()
    with framework.program_guard(prog, startup):
        x = fluid.layers.data("x", [IN_DIM])
        y = fluid.layers.fc(x, OUT_DIM)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    feed = {"x": np.ones((2, IN_DIM), np.float32)}

    hist = monitor.REGISTRY.get("executor_dispatch_overhead_seconds")
    h0 = hist.labels().value["count"]
    p_hits0 = monitor.counter_value("executor_plan_cache_hits_total")
    p_miss0 = monitor.counter_value("executor_plan_cache_misses_total")
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(prog, feed=feed, fetch_list=[y])  # plan miss
        with monitor.trace_session() as sess:
            for _ in range(3):
                exe.run(prog, feed=feed, fetch_list=[y])  # plan hits
    assert monitor.counter_value("executor_plan_cache_misses_total") >= p_miss0 + 1
    assert monitor.counter_value("executor_plan_cache_hits_total") >= p_hits0 + 3
    # histogram observed only inside the session (hot path stays lean)
    assert hist.labels().value["count"] == h0 + 3
    assert monitor.counter_value("executor_dispatch_overhead_seconds_total") > 0
    assert any(s["name"] == "executor/device_execute" for s in sess.spans)


# ---------------------------------------------------------------------------
# span hierarchy: explicit parent ids (PR-6; nesting is no longer
# inferred from timestamps)
# ---------------------------------------------------------------------------
def test_span_parent_ids_from_nesting():
    from paddle_tpu.monitor import spans as _spans

    with monitor.trace_session() as sess:
        with monitor.span("outer"):
            with monitor.span("inner"):
                monitor.record_span(
                    "leaf", time.perf_counter(), 0.0)
            with profiler.RecordEvent("sibling"):
                pass
    by = {s["name"]: s for s in sess.spans}
    assert set(by) == {"outer", "inner", "leaf", "sibling"}
    assert all(s.get("id") for s in sess.spans)
    assert by["inner"]["parent"] == by["outer"]["id"]
    assert by["leaf"]["parent"] == by["inner"]["id"]
    assert by["sibling"]["parent"] == by["outer"]["id"]
    assert "parent" not in by["outer"]
    # the stack is clean after the session
    assert _spans.current_parent() is None


def test_open_spans_nest_tile_and_cancel():
    """``open_span`` is ``span()`` without the context manager: what is
    recorded while one is open nests under it, ``cpu=True`` adds the
    thread's CPU seconds, spans that tile share their edges (``t0=`` /
    ``end=``), and a cancelled one leaves nothing but a clean stack."""
    from paddle_tpu.monitor import spans as _spans

    with monitor.trace_session() as sess:
        outer = _spans.open_span("outer", cat="serving", annotate=False)
        first = _spans.open_span("first", cpu=True, t0=outer.t0)
        monitor.record_span("inside", time.perf_counter(), 0.0)
        edge = first.close(n=1)
        second = _spans.open_span("second", t0=edge)
        edge = second.close(error=True)
        _spans.open_span("nothing in it").cancel()
        assert outer.close(end=edge, k="v") == edge
    by = {s["name"]: s for s in sess.spans}
    assert set(by) == {"outer", "first", "inside", "second"}
    assert by["first"]["parent"] == by["second"]["parent"] == by["outer"]["id"]
    assert by["inside"]["parent"] == by["first"]["id"]
    assert "parent" not in by["outer"] and by["outer"]["cat"] == "serving"
    assert by["first"]["args"]["n"] == 1
    assert by["first"]["args"]["cpu_s"] >= 0.0
    assert "cpu_s" not in by["second"].get("args", {})
    assert by["second"]["error"] and by["outer"]["args"] == {"k": "v"}
    # the three share their edges: the two tile the outer exactly
    assert by["first"]["ts"] == by["outer"]["ts"]
    assert by["first"]["dur"] + by["second"]["dur"] == pytest.approx(
        by["outer"]["dur"], abs=1e-9)
    assert _spans.current_parent() is None


def test_span_ids_are_distinct_16_hex_from_a_process_counter():
    from paddle_tpu.monitor import spans as _spans

    ids = [monitor.new_span_id() for _ in range(1000)]
    assert len(set(ids)) == 1000
    assert all(len(i) == 16 and int(i, 16) >= 0 for i in ids)
    # eight digits drawn once a process (anew in a forked child), then
    # a counter: the next id is the last one's successor
    assert {i[:8] for i in ids} == {_spans._id_process}
    assert int(ids[-1][8:], 16) - int(ids[0][8:], 16) == 999


def test_span_remote_parent_graft():
    """A foreign id (e.g. the remote parent from a wire traceparent)
    pushed onto the stack parents local spans under a span recorded in
    another process."""
    from paddle_tpu.monitor import spans as _spans

    with monitor.trace_session() as sess:
        with _spans.parent_scope("feedfacefeedface"):
            with monitor.span("local_root"):
                pass
    (s,) = sess.spans
    assert s["parent"] == "feedfacefeedface"


def test_flight_span_tree_builder():
    from paddle_tpu.monitor.flight import span_tree

    spans = [
        {"name": "root", "id": "r", "dur": 0.002},
        {"name": "child", "id": "c", "parent": "r", "dur": 0.001},
        {"name": "grandchild", "id": "g", "parent": "c", "dur": 0.0005},
        {"name": "orphan", "id": "o", "parent": "missing", "dur": 0.0},
        {"name": "idless", "dur": 0.0},
    ]
    roots = span_tree(spans)
    names = [n["name"] for n in roots]
    assert names == ["root", "orphan", "idless"]
    root = roots[0]
    assert [c["name"] for c in root["children"]] == ["child"]
    assert [c["name"] for c in root["children"][0]["children"]] == [
        "grandchild"]


def test_chrome_trace_carries_span_ids_and_cross_lane_flows(tmp_path):
    """Exported events carry span_id/parent_id args, and a parent edge
    that crosses thread lanes gets explicit flow arrows."""
    path = str(tmp_path / "trace.json")
    spans = [
        {"name": "parent", "id": "aa11", "ts": 1.0, "dur": 0.01, "tid": 1},
        {"name": "same_lane_child", "id": "bb22", "parent": "aa11",
         "ts": 1.001, "dur": 0.001, "tid": 1},
        {"name": "cross_lane_child", "id": "cc33", "parent": "aa11",
         "ts": 1.002, "dur": 0.001, "tid": 2},
    ]
    monitor.export_chrome_trace(path, spans=spans)
    with open(path) as f:
        doc = json.load(f)
    evs = doc["traceEvents"]
    named = {e["name"]: e for e in evs if e.get("ph") == "X"}
    assert named["parent"]["args"]["span_id"] == "aa11"
    assert named["cross_lane_child"]["args"]["parent_id"] == "aa11"
    flows = [e for e in evs if e.get("cat") == "flow"]
    # exactly one s/f pair: only the cross-lane edge needs an arrow
    assert sorted(e["ph"] for e in flows) == ["f", "s"]
    assert flows[0]["id"] == flows[1]["id"]


def test_train_from_dataset_trace_ids():
    """PR-6 satellite: a training epoch is correlatable like a serving
    request — one trace id through every step, real step->epoch->run
    parent edges."""
    prog, startup = framework.Program(), framework.Program()
    with framework.program_guard(prog, startup):
        x = fluid.layers.data("x", [IN_DIM])
        y = fluid.layers.data("y", [1])
        pred = fluid.layers.fc(x, 1)
        loss = fluid.layers.reduce_mean(
            fluid.layers.square(pred - y))
        fluid.optimizer.SGD(0.1).minimize(loss)
    exe = fluid.Executor(fluid.CPUPlace())
    rng = np.random.RandomState(0)
    ds = [{"x": rng.rand(4, IN_DIM).astype("float32"),
           "y": rng.rand(4, 1).astype("float32")} for _ in range(3)]
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        with monitor.trace_session() as sess:
            exe.train_from_dataset(
                prog, ds, fetch_list=[loss], trace_id="beadbeadbeadbead")
        assert exe.last_train_trace_id == "beadbeadbeadbead"
        # a second epoch mints a FRESH id
        exe.train_from_dataset(prog, ds, fetch_list=[loss])
        assert exe.last_train_trace_id != "beadbeadbeadbead"
    steps = [s for s in sess.spans if s["name"] == "executor/train_step"]
    epochs = [s for s in sess.spans if s["name"] == "executor/train_epoch"]
    assert len(steps) == 3 and len(epochs) == 1
    assert all(s["trace_ids"] == ["beadbeadbeadbead"]
               for s in steps + epochs)
    assert all(s["parent"] == epochs[0]["id"] for s in steps)
    step_ids = {s["id"] for s in steps}
    execs = [s for s in sess.spans
             if s["name"] in ("executor/device_execute",
                              "executor/jit_compile")]
    assert execs and all(s["parent"] in step_ids for s in execs)
    assert all(s["trace_ids"] == ["beadbeadbeadbead"] for s in execs)
