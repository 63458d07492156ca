"""Cross-host serving tests (paddle_tpu/serving/wire/): codec framing
and bounded-read rejection, the HTTP transport + RemoteClient error
contract, the front-end balancer's retirement/requeue state machine,
and the acceptance path — a REAL 2-child-process fleet over loopback
TCP with fleet-wide warmup (zero recompiles), a mid-traffic child kill
that loses no accepted request, and one merged cross-process span tree
per request under a single ``traceparent``-carried trace id.
"""
import io
import json
import threading
import time
import urllib.request

import numpy as np
import pytest

from conftest import WAIT

import paddle_tpu as fluid
from paddle_tpu import framework, monitor
from paddle_tpu.monitor import flight as _flight
from paddle_tpu.serving import wire
from paddle_tpu.serving.errors import (
    BackendUnavailable,
    DeadlineExceeded,
    ServerOverloaded,
    WireProtocolError,
)
from paddle_tpu.serving.server import InferenceServer
from paddle_tpu.serving.wire import codec

IN_DIM, OUT_DIM = 16, 4


# ---------------------------------------------------------------------------
# codec: round trips + bounded-read rejection (a malformed peer must be
# a typed per-request failure, never a wedged server process)
# ---------------------------------------------------------------------------
_DTYPES = ["bool", "int8", "uint8", "int16", "int32", "int64",
           "float16", "float32", "float64", "complex64"]
_SHAPES = [(), (1,), (7,), (0,), (3, 4), (2, 0, 5), (2, 3, 4, 2)]


def _arbitrary_arrays(seed):
    """Arbitrary dtype/shape/contiguity: C-order, F-order, and strided
    views all cross the wire byte-exact."""
    rng = np.random.RandomState(seed)
    out = []
    for i, (dt, shape) in enumerate(
            (d, s) for d in _DTYPES for s in _SHAPES):
        arr = (rng.uniform(-100, 100, shape) * 3).astype(dt)
        mode = i % 3
        if mode == 1 and arr.ndim >= 2:
            arr = np.asfortranarray(arr)
        elif mode == 2 and arr.ndim >= 1 and arr.shape[0] >= 4:
            arr = arr[::2]  # non-contiguous view
        out.append(arr)
    return out


def test_codec_roundtrip_arbitrary_arrays():
    arrays = _arbitrary_arrays(0)
    meta = {"feed_names": ["a%d" % i for i in range(len(arrays))],
            "nested": {"k": [1, 2.5, "uniçode", None, True]}}
    body = codec.encode_message(meta, arrays)
    rmeta, rarrays = codec.decode_message(body)
    assert rmeta == meta
    assert len(rarrays) == len(arrays)
    for a, b in zip(arrays, rarrays):
        assert a.dtype == b.dtype and a.shape == b.shape
        np.testing.assert_array_equal(a, b)


def test_codec_rejects_truncation_everywhere():
    """EVERY strict prefix of a valid message is a typed error — the
    fuzz half of the bounded-read contract (stride 7 keeps it fast but
    covers magic, headers, payload bodies, and the end frame)."""
    body = codec.encode_message(
        {"feed_names": ["x"]}, [np.arange(300, dtype=np.float64)])
    for cut in list(range(0, len(body), 7)) + [len(body) - 1]:
        with pytest.raises(WireProtocolError):
            codec.decode_message(body[:cut])


def test_codec_rejects_oversized_and_malformed_frames():
    body = codec.encode_message({}, [np.zeros(1000, dtype=np.float64)])
    with pytest.raises(WireProtocolError, match="oversized"):
        codec.decode_message(body, max_frame_bytes=64)
    with pytest.raises(WireProtocolError, match="magic"):
        codec.decode_message(b"NOPE" + body[4:])
    with pytest.raises(WireProtocolError, match="kind"):
        codec.decode_message(codec.MAGIC + b"Z" + b"\x00" * 4)
    with pytest.raises(WireProtocolError, match="trailing"):
        codec.decode_message(body + b"x")
    # an array frame whose payload is not npy
    bad = io.BytesIO()
    bad.write(codec.MAGIC)
    bad.write(codec._HEADER.pack(b"J", 2))
    bad.write(b"{}")
    bad.write(codec._HEADER.pack(b"A", 4))
    bad.write(b"junk")
    bad.write(codec._HEADER.pack(b"E", 0))
    with pytest.raises(WireProtocolError, match="array"):
        codec.decode_message(bad.getvalue())
    # unbounded frame streams are refused
    loop = io.BytesIO()
    loop.write(codec.MAGIC)
    loop.write(codec._HEADER.pack(b"J", 2))
    loop.write(b"{}")
    for _ in range(10):
        loop.write(codec._HEADER.pack(b"A", 0))
    with pytest.raises(WireProtocolError):
        codec.decode_message(loop.getvalue(), max_frames=5)


def test_codec_refuses_object_dtype():
    with pytest.raises(WireProtocolError):
        codec.encode_message({}, [np.array([{"a": 1}], dtype=object)])


def test_traceparent_roundtrip_and_malformed():
    tid, sid = monitor.new_trace_id(), monitor.new_span_id()
    hdr = codec.format_traceparent(tid, sid)
    assert codec.parse_traceparent(hdr) == (tid, sid)
    for bad in (None, "", "garbage", "00-zz-yy-01",
                "00-" + "0" * 32 + "-" + sid + "-01",
                "00-" + tid.rjust(32, "0") + "-" + "0" * 16 + "-01"):
        assert codec.parse_traceparent(bad) is None


# ---------------------------------------------------------------------------
# transport + ServingProcess over a stub predictor (no XLA in the loop)
# ---------------------------------------------------------------------------
class StubPredictor:
    def __init__(self, delay_s=0.0):
        self.delay_s = delay_s

    def get_input_names(self):
        return ["x"]

    def get_output_names(self):
        return ["y"]

    def input_specs(self):
        return {"x": ((IN_DIM,), np.dtype("float32"))}

    def jit_cache_stats(self):
        return {"entries": 0, "hits": 0, "misses": 0}

    def run_padded(self, feed, n_valid=None):
        if self.delay_s:
            time.sleep(self.delay_s)
        return [np.asarray(feed["x"][:n_valid]).sum(axis=1, keepdims=True)]


def _stub_wire_server(name, delay_s=0.0, **kw):
    srv = InferenceServer(
        StubPredictor(delay_s=delay_s), max_batch_size=8,
        batch_timeout_ms=1, name=name, **kw)
    sp = wire.ServingProcess(srv)
    sp.start()
    return sp


def _rows(n, seed=0):
    return np.random.RandomState(seed).uniform(
        -1, 1, (n, IN_DIM)).astype("float32")


def test_remote_client_roundtrip_and_typed_errors():
    sp = _stub_wire_server("rc")
    try:
        cli = wire.RemoteClient(sp.address)
        x = _rows(3, seed=1)
        out, = cli.infer({"x": x})
        np.testing.assert_allclose(
            out, x.sum(axis=1, keepdims=True), rtol=1e-6)
        assert set(cli.infer_named({"x": x})) == {"y"}
        outs = cli.infer_many([{"x": x}, {"x": x[:1]}])
        assert [o[0].shape[0] for o in outs] == [3, 1]
        # positional feeds work like the in-process client
        out2, = cli.infer([x])
        np.testing.assert_array_equal(out2, out)
        # validation errors map back typed — client-side (feed names)
        # and in-band from the server (row count beyond max_batch_size)
        with pytest.raises(ValueError):
            cli.infer({"nope": x})
        with pytest.raises(ValueError):
            cli.infer({"x": _rows(999)})
        # a non-decode endpoint refuses infer_stream typed, in-band,
        # AT THE CALL (the streaming contract's pre-stream failure)
        from paddle_tpu.serving.errors import ServingError
        with pytest.raises(ServingError, match="does not stream"):
            cli.infer_stream({"x": x})
        h = cli.healthz()
        assert h["ok"] and h["input_names"] == ["x"]
    finally:
        sp.stop()
    # a stopped process fails typed in the RETRYABLE class: ServerClosed
    # while a keep-alive handler still answers in-band, then
    # BackendUnavailable once the socket actually dies — the balancer
    # re-routes both
    from paddle_tpu.serving.errors import ServerClosed

    with pytest.raises((BackendUnavailable, ServerClosed)):
        cli.infer({"x": _rows(1)})
    cli.close()


def test_wire_deadline_and_overload_are_end_states():
    sp = _stub_wire_server("slow", delay_s=0.3, queue_capacity=1)
    cli = wire.RemoteClient(sp.address)
    try:
        with pytest.raises(DeadlineExceeded):
            cli.infer({"x": _rows(1)}, timeout_ms=30)
        # saturate: the replica holds 2 dispatched batches, the blocked
        # dispatcher holds one more, the queue holds 1 — a burst of
        # concurrent submits beyond that sheds typed at admission, and
        # the overload answer crosses the wire as ServerOverloaded
        outcomes = []
        lock = threading.Lock()

        def one():
            try:
                cli.infer({"x": _rows(1)}, timeout_ms=5000)
                res = "ok"
            except ServerOverloaded:
                res = "overload"
            except DeadlineExceeded:
                res = "deadline"
            with lock:
                outcomes.append(res)

        threads = [threading.Thread(target=one, daemon=True)
                   for _ in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(WAIT)
        assert not any(t.is_alive() for t in threads)
        assert "overload" in outcomes, outcomes
    finally:
        cli.close()
        sp.stop(drain=False)


def test_wire_admin_surfaces():
    sp = _stub_wire_server("admin")
    try:
        host, port = sp.address
        base = "http://%s:%d" % (host, port)

        def get(path):
            return urllib.request.urlopen(base + path, timeout=WAIT)

        h = json.load(get("/healthz"))
        assert h["ok"] and h["live_replicas"] == 1
        text = get("/metrics").read().decode()
        assert "wire_requests_total" in text
        st = json.load(get("/statusz"))
        assert st["server"] == "admin"
        tz = json.load(get("/tracez"))
        assert "requests" in tz
        with pytest.raises(urllib.error.HTTPError):
            get("/nope")
    finally:
        sp.stop()


def test_wire_single_process_trace_chain():
    """Loopback hop in ONE process: the flight record still holds one
    connected, de-duplicated tree — client span -> wire/request ->
    wire/server_request (remote parent from traceparent) -> queue_wait,
    with the batch subtree under the same trace id."""
    sp = _stub_wire_server("trace1")
    cli = wire.RemoteClient(sp.address)
    fr = monitor.flight_recorder(slow_ms=0.0)
    try:
        cli.infer({"x": _rows(2, seed=5)})
        tid = cli.last_trace_id
        rec = fr.get_record(tid)
        assert rec is not None
        names = [s["name"] for s in rec["spans"]]
        assert names.count("serving/queue_wait") == 1  # dedup by span id
        by_name = {s["name"]: s for s in rec["spans"]}
        ci = by_name["serving/client_infer"]
        wr = by_name["wire/request"]
        ws = by_name["wire/server_request"]
        qw = by_name["serving/queue_wait"]
        assert wr["parent"] == ci["id"]
        assert ws["parent"] == wr["id"]
        assert qw["parent"] == ws["id"]
        for s in (ci, wr, ws, qw):
            assert s["trace_ids"] == [tid]
        # /tracez renders the hierarchy from the explicit parent ids
        tz = sp.server.tracez()
        tree = [r["tree"] for r in tz["requests"]
                if r["trace_id"] == tid][0]
        roots = {n["name"] for n in tree}
        assert "serving/client_infer" in roots

        def find(nodes, name):
            for n in nodes:
                if n["name"] == name:
                    return n
                hit = find(n["children"], name)
                if hit:
                    return hit
            return None

        assert find(tree, "serving/queue_wait") is not None
    finally:
        fr.close()
        cli.close()
        sp.stop()


# ---------------------------------------------------------------------------
# fleet balancer over in-process wire servers (fast failure-path tests)
# ---------------------------------------------------------------------------
def test_fleet_requeues_off_dead_backend_without_losing_requests():
    sps = [_stub_wire_server("fb%d" % i, delay_s=0.002) for i in range(2)]
    fleet = wire.FleetBalancer(
        [sp.address for sp in sps], name="stubfleet",
        health_interval_s=0.2)
    errs, done = [], [0]
    stop = threading.Event()

    def storm(t):
        rng = np.random.RandomState(t)
        while not stop.is_set():
            try:
                fleet.infer(
                    {"x": rng.rand(1 + t % 3, IN_DIM).astype("float32")},
                    timeout_ms=5000)
                done[0] += 1
            except Exception as e:  # noqa: BLE001 — the assertion target
                errs.append(repr(e))
                return

    threads = [threading.Thread(target=storm, args=(t,),
                                daemon=True) for t in range(4)]
    for t in threads:
        t.start()
    time.sleep(0.25)
    req0 = monitor.counter_value("serving_requeued_total", server="stubfleet")
    sps[0].stop(drain=False)  # the "process died" event
    time.sleep(0.5)
    stop.set()
    for t in threads:
        t.join(WAIT)
    assert not any(t.is_alive() for t in threads)
    try:
        assert errs == []  # no accepted request was lost
        assert done[0] > 0
        requeued = monitor.counter_value(
            "serving_requeued_total", server="stubfleet") - req0
        assert requeued >= 1
        assert monitor.counter_value(
            "wire_backend_retired_total", fleet="stubfleet") >= 1
        stats = fleet.backend_stats()
        assert sum(1 for b in stats.values() if b["alive"]) == 1
        # traffic still flows on the survivor
        fleet.infer({"x": _rows(1)})
    finally:
        fleet.stop()
        sps[1].stop()


def test_fleet_all_backends_dead_fails_typed():
    from paddle_tpu.serving.errors import ServingError

    sp = _stub_wire_server("lone")
    fleet = wire.FleetBalancer(
        [sp.address], name="lonefleet", health_interval_s=None)
    fleet.infer({"x": _rows(1)})  # discover shape while alive
    sp.stop(drain=False)
    # failures retire the only backend; requests fail TYPED throughout
    # (BackendUnavailable while it is still routable, then the fleet's
    # no-live-backends ServingError) — never a hang or a bare socket error
    for _ in range(_stub_fail_limit() + 1):
        with pytest.raises(ServingError):
            fleet.infer({"x": _rows(1)})
    assert fleet.num_backends == 0
    with pytest.raises(ServingError, match="no live backends"):
        fleet.infer({"x": _rows(1)})
    fleet.stop()


def _stub_fail_limit():
    from paddle_tpu.serving.wire import fleet as fleet_mod

    return fleet_mod._BACKEND_FAIL_LIMIT


# ---------------------------------------------------------------------------
# acceptance: a real 2-child-process fleet over loopback TCP
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def mlp_model_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("wire") / "mlp")
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
    return d


def _backend_statusz(be):
    host, port = be.transport.address
    return json.load(urllib.request.urlopen(
        "http://%s:%d/statusz" % (host, port), timeout=WAIT))


def test_process_fleet_end_to_end(mlp_model_dir):
    """The PR's acceptance path, one fleet lifetime: 2 ServingProcess
    children over loopback TCP behind the balancer; fleet-wide warmup
    then ZERO recompiles under mixed-size concurrent traffic; one child
    hard-killed mid-traffic with no accepted request lost (requeue to
    the survivor, counter asserted); and one merged span tree per
    request spanning client -> wire hop -> replica -> executor under a
    single traceparent-carried trace id."""
    fleet = wire.FleetBalancer.from_launch(
        mlp_model_dir, n=2, name="acceptfleet",
        launch_kwargs=dict(max_batch_size=4, batch_timeout_ms=2,
                           flight_slow_ms=0.0, queue_capacity=256),
        health_interval_s=0.5)
    try:
        compiles = fleet.warmup()
        assert compiles >= 0 and fleet.metrics()["warmed_up"]

        # --- merged cross-process trace, BEFORE the storm ------------
        fr = monitor.flight_recorder(slow_ms=0.0)
        try:
            x = _rows(3, seed=9)
            out, = fleet.infer({"x": x})
            assert out.shape == (3, OUT_DIM)
            tid = fleet.last_trace_id
            rec = fr.get_record(tid)
            assert rec is not None, "request not retained client-side"
            spans = rec["spans"]
            names = {s["name"] for s in spans}
            for want in ("serving/client_infer", "wire/request",
                         "wire/server_request", "serving/queue_wait",
                         "predictor/run_padded",
                         "executor/device_execute"):
                assert want in names, (want, sorted(names))
            # every span carries THE one trace id
            for s in spans:
                assert s.get("trace_ids") == [tid], s
            # the cross-process edge is a real parent link: the server's
            # request span names the client's wire span as its parent
            by_id = {s["id"]: s for s in spans if s.get("id")}
            ws = next(s for s in spans
                      if s["name"] == "wire/server_request")
            assert by_id[ws["parent"]]["name"] == "wire/request"
            wr = by_id[ws["parent"]]
            assert by_id[wr["parent"]]["name"] == "serving/client_infer"
            qw = next(s for s in spans
                      if s["name"] == "serving/queue_wait")
            assert qw["parent"] == ws["id"]
        finally:
            fr.close()

        # --- mixed-size concurrent storm + mid-traffic child kill ----
        errs, completed = [], [0]
        stop_flag = threading.Event()
        lock = threading.Lock()

        def storm(t):
            rng = np.random.RandomState(300 + t)
            i = 0
            while not stop_flag.is_set():
                n = 1 + (t + i) % 3
                i += 1
                try:
                    out, = fleet.infer(
                        {"x": rng.rand(n, IN_DIM).astype("float32")},
                        timeout_ms=15000)
                    assert out.shape == (n, OUT_DIM)
                    with lock:
                        completed[0] += 1
                except Exception as e:  # noqa: BLE001 — assertion target
                    errs.append(repr(e))
                    return

        threads = [threading.Thread(target=storm, args=(t,), daemon=True)
                   for t in range(4)]
        for t in threads:
            t.start()
        time.sleep(0.5)
        req0 = monitor.counter_value(
            "serving_requeued_total", server="acceptfleet")
        victim = next(be for be in fleet._backends if be.handle)
        victim.handle.kill()  # SIGKILL: the real lost-process event
        time.sleep(1.5)
        stop_flag.set()
        for t in threads:
            t.join(WAIT)
        assert not any(t.is_alive() for t in threads)
        assert errs == [], "accepted requests were lost: %s" % errs[:3]
        assert completed[0] > 20
        requeued = monitor.counter_value(
            "serving_requeued_total", server="acceptfleet") - req0
        assert requeued >= 1, "kill produced no requeue"
        stats = fleet.backend_stats()
        assert sum(1 for b in stats.values() if b["alive"]) == 1, stats

        # --- zero recompiles fleet-wide after warmup ------------------
        survivor = next(
            be for be in fleet._backends
            if be.alive and be.handle and be.handle.poll() is None)
        doc = _backend_statusz(survivor)
        assert doc["metrics"]["recompiles"] == 0, doc["metrics"]
        assert doc["metrics"]["completed"] > 0
        # the child's own /tracez carries hierarchical trees too
        host, port = survivor.transport.address
        tz = json.load(urllib.request.urlopen(
            "http://%s:%d/tracez" % (host, port), timeout=WAIT))
        assert tz["retained"] > 0
        assert any(r.get("tree") for r in tz["requests"])
    finally:
        fleet.stop(shutdown_backends=True)
    # the flight recorder in this test is closed; no global leak
    assert _flight.get() is None


# ---------------------------------------------------------------------------
# review regressions: deadline typing, keep-alive hygiene, cycle trees
# ---------------------------------------------------------------------------
def test_fleet_expired_deadline_stays_typed_and_does_not_retire():
    """A deadline that expires before the wire exchange must surface as
    DeadlineExceeded — NOT reach the socket as a 0s (non-blocking)
    timeout that reads as a dead backend and retires a healthy fleet."""
    sp = _stub_wire_server("dl")
    fleet = wire.FleetBalancer(
        [sp.address], name="dlfleet", health_interval_s=None)
    try:
        fleet.infer({"x": _rows(1)})  # shape discovery + health
        for _ in range(_stub_fail_limit() + 1):
            with pytest.raises(DeadlineExceeded):
                fleet.infer({"x": _rows(1)}, timeout_ms=0.0001)
        stats = fleet.backend_stats()
        assert all(b["alive"] for b in stats.values()), stats
        assert all(b["failed"] == 0 for b in stats.values()), stats
        fleet.infer({"x": _rows(1)})  # still serving
    finally:
        fleet.stop()
        sp.stop()


def test_warmup_then_infer_on_one_keepalive_connection():
    """Control POSTs (/warmup, /quitquitquit) must drain their request
    bodies: an unread body on the pooled HTTP/1.1 connection would be
    parsed as the next request line and fail the following infer."""
    sp = _stub_wire_server("ka")
    cli = wire.RemoteClient(sp.address)
    try:
        # same thread => same pooled connection for every call
        assert cli.warmup() == 0  # stub predictor: no compiles
        out, = cli.infer({"x": _rows(2, seed=3)})
        assert out.shape == (2, 1)
        assert cli.warmup() == 0
        out, = cli.infer({"x": _rows(1, seed=4)})
        assert out.shape == (1, 1)
    finally:
        cli.close()
        sp.stop()


def test_span_tree_breaks_parent_cycles():
    """A malformed peer's parent cycle degrades to a root with the
    back-edge cut — every span appears exactly once and the forest
    still JSON-serializes (no circular reference)."""
    from paddle_tpu.monitor.flight import span_tree

    roots = span_tree([
        {"name": "a", "id": "a1", "parent": "b1", "dur": 0.0},
        {"name": "b", "id": "b1", "parent": "a1", "dur": 0.0},
        {"name": "ok", "id": "c1", "dur": 0.0},
    ])
    names = sorted(n["name"] for n in roots)
    assert "ok" in names and ("a" in names or "b" in names)

    def count(nodes):
        return sum(1 + count(n["children"]) for n in nodes)

    assert count(roots) == 3  # nothing dropped, nothing duplicated
    json.dumps(roots)  # and no circular reference
