"""Fleet observability control tower (PR 17): the severity-tagged event
ring (``/eventz``), the SLO burn-rate engine (``/sloz`` +
``slo_burn_rate`` gauges, multi-window multi-burn-rate fire/clear), the
exposition federation pipeline (parse -> relabel -> merge -> render ->
aggregate), the FleetBalancer's federated admin tier over live stub
children (including a concurrent hammer of every surface under
traffic), and the cross-process acceptance path: a deadline-missed
request over the wire retained by the CHILD's flight recorder and
surfaced in the BALANCER's federated ``/tracez``.
"""
import json
import threading
import time
import urllib.error
import urllib.request

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import framework, monitor
from paddle_tpu.monitor import events as events_mod
from paddle_tpu.monitor import slo as slo_mod
from paddle_tpu.monitor.registry import (
    REGISTRY,
    MetricsRegistry,
    aggregate_families,
    merge_expositions,
    parse_exposition,
    relabel_exposition,
    render_exposition,
)
from paddle_tpu.serving import wire
from paddle_tpu.serving.errors import DeadlineExceeded
from paddle_tpu.serving.server import InferenceServer

IN_DIM, OUT_DIM = 16, 4


# ---------------------------------------------------------------------------
# event ring
# ---------------------------------------------------------------------------
def test_event_ring_bounded_severity_filter_and_counter():
    ring = events_mod.EventRing(capacity=4)
    for i in range(6):
        ring.emit("test/tick", severity="info", i=i)
    assert ring.dropped == 2
    snap = ring.snapshot()
    assert [e["i"] for e in snap] == [2, 3, 4, 5]  # oldest -> newest
    assert [e["seq"] for e in snap] == sorted(e["seq"] for e in snap)
    ring.emit("test/bad", severity="error", what="boom")
    assert [e["kind"] for e in ring.snapshot(min_severity="warning")] == [
        "test/bad"]
    assert len(ring.snapshot(limit=2)) == 2
    doc = ring.eventz(limit=3)
    assert doc["capacity"] == 4 and doc["retained"] == 3
    assert doc["dropped"] == 3
    with pytest.raises(ValueError):
        ring.emit("test/nope", severity="fatal")
    with pytest.raises(ValueError):
        events_mod.EventRing(capacity=0)
    ring.clear()
    assert ring.snapshot() == [] and ring.dropped == 0


def test_module_emit_counts_and_mirrors_span_instant():
    """``monitor.emit_event`` hits all three sinks: the process ring,
    ``serving_events_total{severity}``, and an instant in any active
    span stream (the pre-ring behavior of these call sites)."""
    ring = events_mod.install(capacity=16)
    try:
        before = monitor.counter_value(
            "serving_events_total", severity="warning")
        with monitor.trace_session() as sess:
            rec = monitor.emit_event(
                "test/obs_marker", severity="warning", cat="test",
                server="obstest", detail=7)
        assert rec["kind"] == "test/obs_marker" and rec["detail"] == 7
        assert monitor.counter_value(
            "serving_events_total", severity="warning") == before + 1
        assert any(e["kind"] == "test/obs_marker"
                   for e in ring.snapshot())
        markers = [s for s in sess.spans
                   if s.get("args", {}).get("instant")
                   and s["name"] == "test/obs_marker"]
        assert markers and markers[0]["args"]["severity"] == "warning"
    finally:
        events_mod.uninstall()
    # the default ring is always present — emitting needs no setup
    assert events_mod.get() is not None


# ---------------------------------------------------------------------------
# SLO engine: deterministic fire-and-clear with an injected clock
# ---------------------------------------------------------------------------
def test_slo_engine_multiwindow_burn_fires_and_clears():
    reg = MetricsRegistry()
    good = reg.counter("obs_good_total", "test good events")
    bad = reg.counter("obs_bad_total", "test bad events")
    fake = [0.0]
    ring = events_mod.install(capacity=64)
    # window_scale 0.01 -> 5m=3s, 1h=36s, 6h=216s, 3d=2592s of fake time
    engine = slo_mod.SloEngine(
        [slo_mod.availability("obs-avail", good="obs_good_total",
                              bad="obs_bad_total", target=0.99)],
        interval_s=1.0, window_scale=0.01, registry=reg,
        clock=lambda: fake[0])
    try:
        good.inc(100)
        engine.evaluate_once()
        doc = engine.evaluate_once()
        assert doc["ok"] and doc["objectives"][0]["ok"]

        # 40 fake seconds of pure failure: error rate 1.0, budget 0.01
        # -> burn 100 in BOTH fast windows (5m and 1h) => fast fires
        for t in range(1, 41):
            fake[0] = float(t)
            bad.inc(10)
            doc = engine.evaluate_once()
        obj = doc["objectives"][0]
        fast = next(a for a in obj["alerts"] if a["pair"] == "fast")
        assert fast["firing"] and fast["severity"] == "critical"
        assert not doc["ok"] and not obj["ok"]
        assert obj["windows"]["5m"]["burn"] >= 14.4
        fired = [e for e in ring.snapshot()
                 if e["kind"] == "slo/fired" and e["slo"] == "obs-avail"]
        assert fired and fired[0]["severity"] == "critical"
        # verdicts export as gauges for dashboards
        snap = REGISTRY.snapshot()
        firing_series = {
            (s["labels"]["slo"], s["labels"]["pair"]): s["value"]
            for s in snap["slo_alert_firing"]["series"]}
        assert firing_series[("obs-avail", "fast")] == 1.0
        assert any(s["labels"] == {"slo": "obs-avail", "window": "5m"}
                   and s["value"] >= 14.4
                   for s in snap["slo_burn_rate"]["series"])

        # recovery: pure good for > the 5m window -> the SHORT window
        # drops below threshold, the pair needs both => cleared
        for t in range(41, 51):
            fake[0] = float(t)
            good.inc(1000)
            doc = engine.evaluate_once()
        obj = doc["objectives"][0]
        fast = next(a for a in obj["alerts"] if a["pair"] == "fast")
        assert not fast["firing"]
        cleared = [e for e in ring.snapshot()
                   if e["kind"] == "slo/cleared"
                   and e["slo"] == "obs-avail"]
        assert cleared and cleared[0]["severity"] == "info"
    finally:
        engine.stop()
        events_mod.uninstall()
    # stop() retires this engine's gauge series from the exposition
    snap = REGISTRY.snapshot()
    assert not any(s["labels"].get("slo") == "obs-avail"
                   for s in snap["slo_burn_rate"]["series"])
    assert not any(s["labels"].get("slo") == "obs-avail"
                   for s in snap["slo_alert_firing"]["series"])


def test_slo_latency_objective_and_module_slot():
    reg = MetricsRegistry()
    h = reg.histogram("obs_lat_seconds", "test latency",
                      buckets=(0.01, 0.1, 1.0))
    for _ in range(90):
        h.observe(0.005)
    for _ in range(10):
        h.observe(0.5)
    obj = slo_mod.latency("obs-lat", "obs_lat_seconds",
                          threshold_s=0.1, target=0.95)
    good, total = obj.sample(reg.snapshot())
    assert (good, total) == (90.0, 100.0)
    with pytest.raises(ValueError):
        slo_mod.availability("bad", good="a", bad="b", target=1.5)
    with pytest.raises(ValueError):
        slo_mod.SloEngine([obj, slo_mod.latency(
            "obs-lat", "obs_lat_seconds", threshold_s=0.2)])

    # module slot: /sloz stays total with no engine installed
    assert slo_mod.get() is None
    doc = slo_mod.sloz()
    assert doc == {"installed": False, "ok": True, "objectives": []}
    eng = slo_mod.install([obj], interval_s=60.0, start=False,
                          registry=reg)
    try:
        eng.evaluate_once()
        doc = slo_mod.sloz()
        assert doc["installed"] and doc["objectives"][0]["name"] == "obs-lat"
    finally:
        slo_mod.uninstall()
    assert slo_mod.get() is None


# ---------------------------------------------------------------------------
# exposition federation pipeline
# ---------------------------------------------------------------------------
def _child_registry(tag: str, n: int) -> MetricsRegistry:
    reg = MetricsRegistry()
    c = reg.counter("obs_requests_total", "requests", ("verb",))
    c.labels(verb="infer").inc(n)
    reg.gauge("obs_depth", "queue depth").set(n)
    h = reg.histogram("obs_wait_seconds", "queue wait",
                      buckets=(0.1, 1.0))
    h.observe(0.05 * n)
    h.observe(0.5)
    reg.counter("obs_%s_only_total" % tag, "child-unique family").inc()
    return reg


def test_parse_relabel_merge_render_roundtrip_and_aggregate():
    a, b = _child_registry("a", 3), _child_registry("b", 7)
    fa = relabel_exposition(parse_exposition(a.render_text()),
                            "backend", "b0")
    fb = relabel_exposition(parse_exposition(b.render_text()),
                            "backend", "b1")
    for fams, want in ((fa, "b0"), (fb, "b1")):
        for fam in fams.values():
            for _, labels, _ in fam["samples"]:
                assert labels["backend"] == want
    merged = merge_expositions([fa, fb])
    text = render_exposition(merged)
    reparsed = parse_exposition(text)
    # stable: rendering the parse renders back identically
    assert render_exposition(reparsed) == text
    fam = reparsed["obs_requests_total"]
    assert fam["type"] == "counter"
    vals = {s[1]["backend"]: s[2] for s in fam["samples"]}
    assert vals == {"b0": 3.0, "b1": 7.0}
    # histogram series survive with bucket/sum/count structure intact
    hb = [s for s in reparsed["obs_wait_seconds"]["samples"]
          if s[0].endswith("_bucket")]
    assert {s[1]["le"] for s in hb} == {"0.1", "1", "+Inf"}

    agg = aggregate_families(merged)
    assert agg["counters"]["obs_requests_total"] == 10.0
    assert agg["gauges"]["obs_depth"] == 7.0  # worst-case across fleet
    hist = agg["histograms"]["obs_wait_seconds"]
    assert hist["count"] == 4 and 0.0 < hist["p50_est"] <= 1.0
    assert hist["p99_est"] >= hist["p50_est"]

    # transitive federation: an upstream balancer PREFIXES an existing
    # backend label instead of clobbering it
    again = relabel_exposition(fa, "backend", "edge")
    for fam in again.values():
        for _, labels, _ in fam["samples"]:
            assert labels["backend"] == "edge/b0"


def test_parse_exposition_handles_escapes_and_untyped():
    text = (
        "# HELP weird a \"help\" line\n"
        "# TYPE weird counter\n"
        'weird{path="C:\\\\x\\n",q="a\\"b"} 2\n'
        "loose_metric 1.5\n")
    fams = parse_exposition(text)
    _, labels, v = fams["weird"]["samples"][0]
    assert labels == {"path": "C:\\x\n", "q": 'a"b'} and v == 2.0
    assert fams["loose_metric"]["type"] == "untyped"


# ---------------------------------------------------------------------------
# fleet admin tier over live stub children
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


def _stub_wire_server(name, **kw):
    srv = InferenceServer(StubPredictor(), max_batch_size=8,
                          batch_timeout_ms=1, name=name, **kw)
    sp = wire.ServingProcess(srv)
    sp.start()
    return sp


def _rows(n, seed=0):
    return np.random.RandomState(seed).uniform(
        -1, 1, (n, IN_DIM)).astype("float32")


def _admin_get(addr, path, timeout_s=5.0):
    """(status, body_bytes) — never raises on HTTP error statuses."""
    try:
        with urllib.request.urlopen(
                "http://%s:%d%s" % (addr[0], addr[1], path),
                timeout=timeout_s) as resp:
            return resp.status, resp.read()
    except urllib.error.HTTPError as e:
        return e.code, e.read()


def test_fleet_admin_tier_federates_stub_children():
    sps = [_stub_wire_server("obsfed-%d" % i) for i in range(2)]
    fleet = wire.FleetBalancer(
        [sp.address for sp in sps], name="obsfed",
        health_interval_s=0.2, admin_port=0, scrape_interval_s=0.1)
    try:
        for i in range(6):
            fleet.infer({"x": _rows(1 + i % 3, seed=i)})
        fleet.scrape_once()
        addr = fleet.admin_address
        assert addr is not None

        st, body = _admin_get(addr, "/healthz")
        h = json.loads(body)
        assert st == 200 and h["ok"] and h["role"] == "balancer"
        assert h["backends_alive"] == 2

        st, body = _admin_get(addr, "/metrics")
        assert st == 200
        fams = parse_exposition(body.decode("utf-8"))
        backends = {
            labels.get("backend")
            for fam in fams.values()
            for _, labels, _ in fam["samples"]}
        # every child's series arrive under its own backend label, and
        # the balancer's own series stay unlabeled
        names = {be.name for be in fleet._backends}
        assert names <= backends and None in backends
        assert "wire_federation_scrapes_total" in fams

        st, body = _admin_get(addr, "/statusz")
        doc = json.loads(body)
        assert st == 200 and doc["role"] == "balancer"
        assert set(doc["backends"]) == names
        for be_doc in doc["backends"].values():
            assert be_doc["statusz"]["metrics"]["completed"] >= 0
        assert "counters" in doc["aggregate"]

        # exact federation, checked while the fleet is idle: every
        # child ``serving_*`` counter series appears verbatim under
        # that child's backend label, and the aggregate is their sum
        children = {
            be.name: parse_exposition(be.transport.get_text("/metrics"))
            for be in fleet._backends}
        fleet.scrape_once()
        fed = parse_exposition(
            _admin_get(addr, "/metrics")[1].decode("utf-8"))
        agg = json.loads(
            _admin_get(addr, "/statusz")[1])["aggregate"]["counters"]
        fed_index = {
            (name, tuple(sorted(labels.items()))): value
            for fam in fed.values() if fam["type"] == "counter"
            for name, labels, value in fam["samples"]}
        sums = {}
        for backend, child_fams in children.items():
            for fam_name, fam in child_fams.items():
                if (fam["type"] != "counter"
                        or not fam_name.startswith("serving_")):
                    continue
                for name, labels, value in fam["samples"]:
                    key = (name, tuple(sorted(
                        dict(labels, backend=backend).items())))
                    assert fed_index.get(key) == value, (key, value)
                    sums[fam_name] = sums.get(fam_name, 0.0) + value
        assert sums.get("serving_completed_total", 0.0) > 0, sums
        for fam_name, want in sums.items():
            # to the last digit for the counts; a counter of SECONDS
            # (serving_pool_state_seconds_total) sums floats, and the
            # balancer adds the children in an order of its own
            assert agg.get(fam_name) == pytest.approx(
                want, rel=1e-12), fam_name

        st, body = _admin_get(addr, "/tracez")
        doc = json.loads(body)
        assert st == 200 and doc["role"] == "balancer"
        st, body = _admin_get(addr, "/sloz")
        assert st == 200 and "installed" in json.loads(body)
        st, body = _admin_get(addr, "/eventz")
        doc = json.loads(body)
        assert st == 200 and isinstance(doc["events"], list)
        st, body = _admin_get(addr, "/nope")
        assert st == 404

        # federation health families export under the fleet label
        assert monitor.counter_value(
            "wire_federation_scrapes_total",
            fleet="obsfed", status="ok") > 0
    finally:
        fleet.stop()
        for sp in sps:
            sp.stop()
    # stop() retires the fleet's federation series and admin socket
    assert fleet.admin_address is None
    snap = monitor.snapshot()
    fam = snap.get("wire_federation_staleness_seconds")
    assert not any(s["labels"].get("fleet") == "obsfed"
                   for s in (fam["series"] if fam else ()))


def test_admin_surfaces_survive_concurrent_hammering():
    """The ISSUE's torture test: hammer /metrics + /tracez + /sloz (and
    /statusz, /eventz) while the fleet serves traffic — every response
    is a 200 and every exposition parses (no torn writes, no 500s)."""
    sps = [_stub_wire_server("obshammer-%d" % i) for i in range(2)]
    fleet = wire.FleetBalancer(
        [sp.address for sp in sps], name="obshammer",
        health_interval_s=0.2, admin_port=0, scrape_interval_s=0.05)
    eng = slo_mod.install(
        [slo_mod.availability(
            "hammer-avail", good="wire_requests_total",
            bad="wire_backend_retired_total", target=0.999)],
        interval_s=0.05, window_scale=0.001)
    addr = fleet.admin_address
    errors = []
    stop = threading.Event()

    def traffic():
        i = 0
        while not stop.is_set():
            try:
                fleet.infer({"x": _rows(1 + i % 3, seed=i)},
                            timeout_ms=10000)
            except Exception as e:  # noqa: BLE001 — assertion target
                errors.append("traffic: %r" % e)
                return
            i += 1

    def hammer(path):
        while not stop.is_set():
            try:
                st, body = _admin_get(addr, path)
                if st != 200:
                    errors.append("%s -> HTTP %d" % (path, st))
                    return
                if path == "/metrics":
                    parse_exposition(body.decode("utf-8"))
                else:
                    json.loads(body)
            except Exception as e:  # noqa: BLE001 — assertion target
                errors.append("%s: %r" % (path, e))
                return

    threads = [threading.Thread(target=traffic, daemon=True) for _ in range(2)]
    threads += [threading.Thread(target=hammer, args=(p,), daemon=True)
                for p in ("/metrics", "/tracez", "/sloz",
                          "/statusz", "/eventz")]
    try:
        for t in threads:
            t.start()
        time.sleep(2.0)
    finally:
        stop.set()
        for t in threads:
            t.join(timeout=10)
        slo_mod.uninstall()
        fleet.stop()
        for sp in sps:
            sp.stop()
    assert errors == [], errors[:5]
    assert eng._ticks > 0  # the evaluator actually ran during the storm


def test_injected_dispatch_delay_fires_and_clears_the_fast_burn_alert():
    """The fire/clear drill end to end, over the admin tier's own HTTP
    surfaces: a delay fault armed on the balancer's dispatch makes
    every routed request miss a latency objective's threshold, the
    fast-burn pair fires in ``/sloz``; disarmed, clean completions
    drain the short window and it clears; both transitions land in
    ``/eventz``, the firing one critical."""
    from paddle_tpu import faults

    slo_name = "drill-p99-latency"
    sps = [_stub_wire_server("obsdrill-%d" % i) for i in range(2)]
    fleet = wire.FleetBalancer(
        [sp.address for sp in sps], name="obsdrill",
        health_interval_s=0.2, admin_port=0, scrape_interval_s=0.1)
    # window_scale 0.001 -> 5m = 0.3 s, 1h = 3.6 s of real time
    slo_mod.install(
        [slo_mod.latency(
            slo_name, histogram="serving_request_latency_seconds",
            threshold_s=0.1, target=0.99, server="obsdrill")],
        interval_s=0.05, window_scale=0.001)
    addr = fleet.admin_address
    stop = threading.Event()

    def fast_alert():
        doc = json.loads(_admin_get(addr, "/sloz")[1])
        for obj in doc.get("objectives") or ():
            if obj.get("name") == slo_name:
                return next(a for a in obj["alerts"] if a["pair"] == "fast")
        return None

    def wait_for(firing):
        deadline = time.monotonic() + 30.0
        while time.monotonic() < deadline:
            alert = fast_alert()
            if alert is not None and bool(alert["firing"]) == firing:
                return True
            time.sleep(0.05)
        return False

    def injector(seed):
        # continuous completions keep the scaled short window
        # populated: an empty window reads as burn 0
        i = 0
        while not stop.is_set():
            try:
                fleet.infer({"x": _rows(1, seed=seed + i)},
                            timeout_ms=30000)
            except Exception:  # noqa: BLE001 — only completions matter
                pass
            i += 1

    injectors = [threading.Thread(target=injector, args=(100 * i,),
                                  daemon=True)
                 for i in range(4)]
    try:
        with faults.armed("fleet.dispatch=delay:0.3"):
            for t in injectors:
                t.start()
            assert wait_for(True), "fast-burn alert never fired in /sloz"
        assert wait_for(False), "fast-burn alert never cleared in /sloz"
    finally:
        stop.set()
        for t in injectors:
            t.join(timeout=30)
        events = json.loads(_admin_get(addr, "/eventz")[1])["events"]
        slo_mod.uninstall()
        fleet.stop()
        for sp in sps:
            sp.stop()
    transitions = {
        e["kind"]: e for e in events
        if e.get("kind") in ("slo/fired", "slo/cleared")
        and e.get("slo") == slo_name and e.get("pair") == "fast"}
    assert set(transitions) == {"slo/fired", "slo/cleared"}, events
    assert transitions["slo/fired"]["severity"] == "critical"


# ---------------------------------------------------------------------------
# acceptance: deadline-missed request over the wire -> child flight
# recorder -> balancer's federated /tracez (REAL child process)
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def mlp_model_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("obs") / "mlp")
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


def test_deadline_miss_lands_in_child_and_federated_tracez(mlp_model_dir):
    """One launched child (its own process, flight recorder installed
    via ``--flight-slow-ms``, every dispatch delayed 300ms by an armed
    fault point): a 120ms-deadline request fails typed at the client,
    the CHILD's recorder retains it with status ``deadline``, and the
    balancer's federated ``/tracez`` surfaces that record tagged with
    the backend's name — the cross-process debugging loop the control
    tower exists for."""
    fleet = wire.FleetBalancer.from_launch(
        mlp_model_dir, n=1, name="obse2e",
        launch_kwargs=dict(
            max_batch_size=4, batch_timeout_ms=2, queue_capacity=64,
            flight_slow_ms=1e9,  # retain ONLY errored/deadline-missed
            env={"PADDLE_TPU_FAULTS": "replica.dispatch=delay:0.3"}),
        health_interval_s=0.5, admin_port=0, scrape_interval_s=0.2)
    try:
        # a generously-deadlined request completes (0.3s dispatch delay)
        out, = fleet.infer({"x": _rows(2, seed=3)}, timeout_ms=30000)
        assert out.shape == (2, OUT_DIM)

        # occupy the child's one replica with a blocker batch, then send
        # a victim whose deadline expires while it waits in the replica
        # queue — the child re-checks deadlines at the replica and marks
        # the miss (status "deadline") into its flight recorder.  The
        # balancer-side recorder is what makes the client send the
        # traceparent header, so both processes key the SAME trace id.
        with monitor.flight_recorder(slow_ms=1e9):
            blocker = threading.Thread(
                target=lambda: fleet.infer(
                    {"x": _rows(1, seed=5)}, timeout_ms=30000), daemon=True)
            blocker.start()
            time.sleep(0.08)
            with pytest.raises(DeadlineExceeded):
                fleet.infer({"x": _rows(1, seed=4)}, timeout_ms=150)
            tid = fleet.last_trace_id
            blocker.join(timeout=30)

        # the child process's own recorder retains the miss
        be = fleet._backends[0]
        host, port = be.transport.address
        deadline = time.monotonic() + 10
        rec = None
        while rec is None and time.monotonic() < deadline:
            tz = json.load(urllib.request.urlopen(
                "http://%s:%d/tracez" % (host, port), timeout=5))
            rec = next((r for r in tz["requests"]
                        if r["trace_id"] == tid), None)
            if rec is None:
                time.sleep(0.1)
        assert rec is not None, "child recorder never retained the miss"
        assert rec["status"] == "deadline"

        # ... and the balancer's federated /tracez carries the same
        # record, trace tree intact, tagged with the backend name
        fleet.scrape_once()
        addr = fleet.admin_address
        st, body = _admin_get(addr, "/tracez", timeout_s=10)
        fed = json.loads(body)
        assert st == 200
        mine = [r for r in fed["requests"] if r.get("trace_id") == tid]
        assert mine, "federated /tracez lost the deadline miss"
        assert mine[0]["backend"] == be.name
        assert mine[0]["status"] == "deadline"
    finally:
        fleet.stop(shutdown_backends=True)
