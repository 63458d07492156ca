"""Chaos drills for the serving stack: injected frame corruption on the
wire hop (``wire.send``), replica failures and half-open re-admission
(``replica.dispatch``), expired-deadline fail-fast at requeue sites,
circuit-breaker re-admission of a retired wire backend, and the
acceptance storm — a real 2-child process fleet under corruption +
delays + a SIGKILL (``fleet.dispatch`` kill mode) that loses zero
accepted requests and re-admits the killed backend via supervisor
relaunch + half-open probe, without manual intervention.
"""
import threading
import time

import numpy as np
import pytest

from conftest import WAIT

import paddle_tpu as fluid
from paddle_tpu import decoding, faults, framework, monitor
from paddle_tpu.serving import InferenceServer, wire
from paddle_tpu.serving.errors import DeadlineExceeded, ServingError

IN_DIM = 16


@pytest.fixture(autouse=True)
def _always_disarm():
    yield
    faults.disarm()


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


def _rows(n, seed=0):
    return np.random.RandomState(seed).uniform(
        -1, 1, (n, IN_DIM)).astype("float32")


def _stub_wire_server(name, delay_s=0.0, **kw):
    srv = InferenceServer(StubPredictor(delay_s=delay_s), max_batch_size=8,
                          batch_timeout_ms=1, name=name, **kw)
    sp = wire.ServingProcess(srv)
    sp.start()
    return sp


# ---------------------------------------------------------------------------
# wire.send: frame corruption requeues to a survivor, nothing is lost
# ---------------------------------------------------------------------------
def test_wire_send_corruption_requeues_and_completes():
    sps = [_stub_wire_server("cor%d" % i) for i in range(2)]
    fleet = wire.FleetBalancer([sp.address for sp in sps],
                               name="corruptfleet", health_interval_s=None)
    try:
        fleet.infer({"x": _rows(1)})  # shape discovery, clean
        req0 = monitor.counter_value(
            "serving_requeued_total", server="corruptfleet")
        f0 = monitor.counter_value("faults_injected_total",
                                   point="wire.send")
        # corrupt the next TWO outbound frames: each surfaces as a typed
        # WireProtocolError on the hop and the request re-sends — an
        # accepted request never drops on in-flight corruption
        with faults.armed("wire.send=corrupt,times=2"):
            x = _rows(3, seed=1)
            out, = fleet.infer({"x": x}, timeout_ms=15000)
        np.testing.assert_allclose(out, x.sum(axis=1, keepdims=True),
                                   rtol=1e-6)
        assert monitor.counter_value(
            "faults_injected_total", point="wire.send") - f0 == 2
        assert monitor.counter_value(
            "serving_requeued_total", server="corruptfleet") - req0 >= 1
    finally:
        fleet.stop()
        for sp in sps:
            sp.stop()


# ---------------------------------------------------------------------------
# circuit breaker: a retired wire backend comes back via half-open probe
# ---------------------------------------------------------------------------
def test_fleet_dispatch_error_injection_never_leaks_inflight_slot():
    """Review regression: an error-mode injection at fleet.dispatch (or
    any non-serving exception mid-route) must release the backend's
    in-flight slot — with max_in_flight=1 a leaked slot would wedge the
    backend forever."""
    sp = _stub_wire_server("slot")
    fleet = wire.FleetBalancer([sp.address], name="slotfleet",
                               health_interval_s=None, max_in_flight=1)
    try:
        fleet.infer({"x": _rows(1)})  # shape discovery, clean
        with faults.armed("fleet.dispatch=error:ConnectionError,times=2"):
            for _ in range(2):
                with pytest.raises(ConnectionError):
                    fleet.infer({"x": _rows(1)}, timeout_ms=5000)
        # both slots released: the sole max_in_flight=1 backend routes
        out, = fleet.infer({"x": _rows(2, seed=9)}, timeout_ms=5000)
        assert out.shape == (2, 1)
        with fleet._route_cv:
            assert all(b.in_flight == 0 for b in fleet._backends)
    finally:
        fleet.stop()
        sp.stop()


def test_retired_wire_backend_readmitted_after_heal():
    sp = _stub_wire_server("ho")
    fleet = wire.FleetBalancer([sp.address], name="halfopen",
                               health_interval_s=0.1, cooldown_s=0.3)
    try:
        fleet.infer({"x": _rows(1)})  # discover shape while healthy
        h0 = monitor.counter_value(
            "backend_halfopen_probes_total", pool="fleet/halfopen")
        # three injected transport failures retire the only backend
        # (drop-N-then-heal: the server itself stays healthy throughout)
        with faults.armed(
                "wire.send=error:BackendUnavailable,times=3"):
            while fleet.num_backends:
                with pytest.raises(ServingError):
                    fleet.infer({"x": _rows(1)}, timeout_ms=2000)
        assert monitor.counter_value(
            "wire_backend_retired_total", fleet="halfopen") >= 1
        # cooldown passes -> the health loop's half-open /healthz probe
        # re-admits it, no manual intervention
        deadline = time.monotonic() + 10
        while fleet.num_backends == 0 and time.monotonic() < deadline:
            time.sleep(0.05)
        assert fleet.num_backends == 1, "backend was never re-admitted"
        assert monitor.counter_value(
            "backend_halfopen_probes_total", pool="fleet/halfopen") > h0
        out, = fleet.infer({"x": _rows(2, seed=4)})  # serving again
        assert out.shape == (2, 1)
    finally:
        fleet.stop()
        sp.stop()


# ---------------------------------------------------------------------------
# replica.dispatch: injected replica failures requeue, then re-admit
# ---------------------------------------------------------------------------
def test_replica_dispatch_fault_requeues_without_losing_requests():
    srv = InferenceServer([StubPredictor(), StubPredictor()],
                          max_batch_size=8, batch_timeout_ms=1,
                          name="repfault")
    try:
        req0 = monitor.counter_value(
            "serving_requeued_total", server="repfault")
        # the first two dispatch attempts fail injected (one per
        # replica), the third heals — the request completes via requeue
        with faults.armed("replica.dispatch=error:RuntimeError,times=2"):
            x = _rows(2, seed=2)
            out, = srv.submit({"x": x}, timeout_ms=15000).result(timeout=WAIT)
        np.testing.assert_allclose(out, x.sum(axis=1, keepdims=True),
                                   rtol=1e-6)
        assert monitor.counter_value(
            "serving_requeued_total", server="repfault") - req0 == 2
        stats = srv.replica_stats()
        assert all(s["alive"] for s in stats.values()), stats
    finally:
        srv.stop()


def test_retired_replica_readmitted_half_open():
    srv = InferenceServer(StubPredictor(), max_batch_size=8,
                          batch_timeout_ms=1, name="repho",
                          readmit_cooldown_s=0.3)
    try:
        h0 = monitor.counter_value(
            "backend_halfopen_probes_total", pool="server/repho")
        # three consecutive injected failures retire the sole replica
        with faults.armed("replica.dispatch=error:RuntimeError,times=3"):
            for _ in range(3):
                with pytest.raises(RuntimeError, match="injected fault"):
                    srv.submit({"x": _rows(1)},
                               timeout_ms=5000).result(timeout=WAIT)
        assert srv.num_replicas == 0
        time.sleep(0.4)  # cooldown
        # the next submitted request IS the half-open probe (the fault
        # healed, so it succeeds and fully re-admits the replica)
        out, = srv.submit({"x": _rows(1, seed=6)},
                          timeout_ms=5000).result(timeout=WAIT)
        assert out.shape == (1, 1)
        assert srv.num_replicas == 1
        assert monitor.counter_value(
            "backend_halfopen_probes_total", pool="server/repho") - h0 == 1
    finally:
        srv.stop()


def test_requeue_expired_deadline_fails_fast_without_burning_slots():
    """Satellite regression: a request whose deadline expired during a
    failed dispatch must fail typed at the requeue site — not re-route
    to a survivor just to be shed there."""
    srv = InferenceServer([StubPredictor(), StubPredictor()],
                          max_batch_size=8, batch_timeout_ms=1,
                          name="dlreq")
    try:
        req0 = monitor.counter_value(
            "serving_requeued_total", server="dlreq")
        exp0 = monitor.counter_value(
            "serving_expired_total", server="dlreq")
        # the dispatch burns 80ms then fails; the 50ms deadline is gone
        # by the requeue decision
        with faults.armed("replica.dispatch=delay:0.08;"
                          "replica.dispatch=error:RuntimeError,times=1"):
            with pytest.raises(DeadlineExceeded):
                # no timeout of the wait's own: the request's 50 ms bound it
                srv.submit({"x": _rows(1)},
                           timeout_ms=50).result(timeout=None)
            # the future raises at ITS deadline; the server reaches the
            # requeue decision ~30ms later — wait for it to land
            deadline = time.monotonic() + 5
            while (monitor.counter_value(
                    "serving_expired_total", server="dlreq") - exp0 < 1
                   and time.monotonic() < deadline):
                time.sleep(0.01)
        assert monitor.counter_value(
            "serving_expired_total", server="dlreq") - exp0 == 1
        assert monitor.counter_value(
            "serving_requeued_total", server="dlreq") - req0 == 0
    finally:
        srv.stop()


# ---------------------------------------------------------------------------
# acceptance: 2-child process fleet under corruption + delays + SIGKILL
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def mlp_model_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("chaos") / "mlp")
    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 7
    with framework.program_guard(prog, startup):
        x = fluid.layers.data("x", [IN_DIM])
        h = fluid.layers.fc(x, 32, act="relu")
        pred = fluid.layers.fc(h, 4, act="softmax")
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.save_inference_model(d, ["x"], [pred], exe, prog)
    return d


def test_chaos_fleet_storm_corruption_delay_kill_readmission(mlp_model_dir):
    """The PR's serving acceptance path: a 2-child wire fleet under a
    mixed-size storm with injected frame corruption + delays and ONE
    SIGKILLed child (the ``fleet.dispatch`` kill fault, fired
    deterministically mid-storm) loses zero accepted requests; the
    killed backend is revived by the supervisor and re-admitted through
    the half-open probe without manual intervention."""
    fleet = wire.FleetBalancer.from_launch(
        mlp_model_dir, n=2, name="chaosfleet",
        launch_kwargs=dict(max_batch_size=4, batch_timeout_ms=2,
                           queue_capacity=256),
        health_interval_s=0.25, cooldown_s=0.5,
        supervisor=wire.launch.Supervisor(
            max_attempts=2, base_delay_s=0.2, fleet="chaosfleet"))
    plan = faults.arm(
        # 2 corrupted frames + 3 delayed sends early in the storm, and
        # one SIGKILL of whichever child the 25th routed request picks
        "wire.send=corrupt,times=2,after=2;"
        "wire.send=delay:0.02,times=3,after=4;"
        "fleet.dispatch=kill,after=24,times=1",
        seed=11)
    errs, completed = [], [0]
    lock = threading.Lock()
    try:
        def storm(t):
            rng = np.random.RandomState(300 + t)
            for i in range(16):
                n = 1 + (t + i) % 3
                try:
                    out, = fleet.infer(
                        {"x": rng.rand(n, IN_DIM).astype("float32")},
                        timeout_ms=30000)
                    assert out.shape == (n, 4)
                    with lock:
                        completed[0] += 1
                except Exception as e:  # noqa: BLE001 — assertion target
                    errs.append(repr(e))
                    return

        threads = [threading.Thread(target=storm, args=(t,), daemon=True)
                   for t in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(WAIT)
        assert not any(t.is_alive() for t in threads)
        # zero lost accepted requests, every fault actually landed
        assert errs == [], "accepted requests were lost: %s" % errs[:3]
        assert completed[0] == 64
        trig = plan.triggers()
        assert trig["fleet.dispatch"] == 1, trig  # the SIGKILL fired
        assert trig["wire.send"] == 5, trig       # corruption + delays
        assert monitor.counter_value(
            "serving_requeued_total", server="chaosfleet") >= 1
        # the killed child's process is really gone
        dead = [be for be in fleet._backends
                if be.handle and be.handle.poll() is not None]
        assert dead, "kill fault fired but no child process exited"

        # ...and WITHOUT manual intervention the fleet heals: the
        # supervisor relaunches the dead child (counted), the half-open
        # probe re-admits it, and both backends route again
        deadline = time.monotonic() + 120
        while fleet.num_backends < 2 and time.monotonic() < deadline:
            time.sleep(0.25)
        assert fleet.num_backends == 2, fleet.backend_stats()
        assert monitor.counter_value(
            "wire_backend_relaunches_total", fleet="chaosfleet") >= 1
        assert monitor.counter_value(
            "backend_halfopen_probes_total", pool="fleet/chaosfleet") >= 1
        # steady traffic across the healed fleet
        for i in range(8):
            out, = fleet.infer({"x": _rows(2, seed=50 + i)},
                               timeout_ms=15000)
            assert out.shape == (2, 4)
    finally:
        faults.disarm()
        fleet.stop(shutdown_backends=True)


# ---------------------------------------------------------------------------
# decode.step: tick-loop fault injection (continuous-batching scheduler)
# ---------------------------------------------------------------------------
def _chain_decode_server(name):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.serving.decode import DecodeServer

    V, EOS = 23, 9

    def step_fn(cache, tokens, ts):
        return jax.nn.one_hot((tokens + 1) % V, V) * 10.0, cache

    def make_cache(n_rows, seq_len):
        return {"z": jnp.zeros((n_rows, seq_len), "float32")}

    decoding.declare(make_cache, decoding.CacheSpec(
        {"z": decoding.Leaf(1)}))
    srv = DecodeServer(step_fn, make_cache, eos_id=EOS, max_seq_len=16,
                       max_slots=2, steps_per_tick=2, name=name)
    srv.warmup(configure_cache=False)
    return srv


def test_decode_step_error_fails_in_flight_typed_then_heals():
    """An injected ``decode.step`` error fails every in-flight request
    TYPED (never a hang, never a half-result) and the scheduler keeps
    serving: the very next submission decodes cleanly on fresh state,
    with zero recompiles."""
    srv = _chain_decode_server("chaos-decode")
    try:
        with faults.armed("decode.step=error:RuntimeError,times=1"):
            reqs = [srv.submit({"tokens": np.array([10], np.int32)},
                               max_new_tokens=8) for _ in range(2)]
            # the first request is in the faulted tick for certain; the
            # second races admission against the one-shot error under
            # CPU contention — it either shared the tick (fails typed)
            # or was admitted after it burned (decodes cleanly).  What
            # must never happen is a hang or an untyped failure.
            with pytest.raises(RuntimeError):
                reqs[0].result(timeout=30.0)
            try:
                out = reqs[1].result(timeout=30.0)
                assert out[0].tolist() == [11, 12, 13, 14, 15, 16, 17, 18]
            except RuntimeError:
                pass
        assert srv.metrics()["failed"] >= 1
        # healed: the tick loop survives the fault and the pool state
        # rebuilds on warmed executables
        out = srv.submit({"tokens": np.array([4, 5], np.int32)}).result(
            timeout=30.0)
        assert out[0].tolist() == [6, 7, 8, 9]
        assert srv._pool.jit_cache_stats()["misses"] == 0
        assert srv.metrics().get("recompiles", 0) == 0
    finally:
        srv.stop(drain=False)


def test_decode_step_delay_stretches_ticks_but_loses_nothing():
    """``decode.step`` delay mode: every tick pays the injected stall
    (TTFT visibly degrades) but all sequences still complete exactly —
    slow is not wrong."""
    srv = _chain_decode_server("chaos-decode-delay")
    try:
        with faults.armed("decode.step=delay:0.05,times=4"):
            t0 = time.perf_counter()
            req = srv.submit({"tokens": np.array([10], np.int32)},
                             max_new_tokens=8)
            out = req.result(timeout=30.0)[0].tolist()
            assert out == [11, 12, 13, 14, 15, 16, 17, 18]
            assert time.perf_counter() - t0 >= 0.15  # the stalls landed
    finally:
        srv.stop(drain=False)


# ---------------------------------------------------------------------------
# decode.prefix_admit: shared-prefix KV admission fault injection
# ---------------------------------------------------------------------------
def _prefix_decode_server(name):
    import jax
    import jax.numpy as jnp

    from paddle_tpu.serving.decode import DecodeServer
    from paddle_tpu.serving.prefix_cache import PrefixKVCache

    V, EOS = 23, 9

    def step_fn(cache, tokens, ts):
        return jax.nn.one_hot((tokens + 1) % V, V) * 10.0, cache

    def make_cache(n_rows, seq_len):
        return {"z": jnp.zeros((n_rows, seq_len), "float32")}

    decoding.declare(make_cache, decoding.CacheSpec(
        {"z": decoding.Leaf(1)}))
    cache = PrefixKVCache(capacity_bytes=1 << 20, block_tokens=4,
                          name=name)
    srv = DecodeServer(step_fn, make_cache, eos_id=EOS, max_seq_len=16,
                       max_slots=2, steps_per_tick=2, name=name,
                       prefix_cache=cache)
    srv.warmup(configure_cache=False)
    # warm one retained entry: tokens 1..8 decode to EOS immediately and
    # the freed slot offers its block-aligned 8-token prefix
    out = srv.submit({"tokens": np.arange(1, 9, dtype=np.int32)}).result(
        timeout=30.0)
    assert out[0].tolist() == [9]
    deadline = time.monotonic() + 10.0
    while cache.stats()["entries"] == 0 and time.monotonic() < deadline:
        time.sleep(0.01)
    assert cache.stats()["entries"] == 1
    return srv, cache


def test_decode_prefix_admit_error_falls_back_to_full_prefill():
    """An injected ``decode.prefix_admit`` error (the corrupted /
    evicted-mid-admit window) DEGRADES to a full prefill — the output
    is exactly the uncached decode, the fallback is counted, and the
    next matching admission uses the cache again.  Wrong tokens are the
    one forbidden outcome; zero recompiles throughout."""
    srv, cache = _prefix_decode_server("chaos-prefix")
    prompt = np.array([1, 2, 3, 4, 5, 6, 7, 8, 10], np.int32)
    try:
        p0 = srv.metrics()["decode"]["prefill_tokens"]
        with faults.armed("decode.prefix_admit=error:RuntimeError,times=1"):
            out = srv.submit({"tokens": prompt},
                             max_new_tokens=4).result(timeout=30.0)
        assert out[0].tolist() == [11, 12, 13, 14]  # degraded, not wrong
        m = srv.metrics()
        assert m["prefix_fallback"] == 1
        assert cache.stats()["fallbacks"] == 1
        # the fallback re-ran the FULL prefill: all 9 prompt tokens
        assert m["decode"]["prefill_tokens"] - p0 == 9
        # healed: the same prompt now admits through the retained prefix
        # (only the unmatched 1-token suffix prefills)
        p1 = m["decode"]["prefill_tokens"]
        out = srv.submit({"tokens": prompt},
                         max_new_tokens=4).result(timeout=30.0)
        assert out[0].tolist() == [11, 12, 13, 14]
        assert srv.metrics()["decode"]["prefill_tokens"] - p1 == 1
        assert srv._pool.jit_cache_stats()["misses"] == 0
        assert srv.metrics().get("recompiles", 0) == 0
    finally:
        faults.disarm()
        srv.stop(drain=False)


def test_decode_prefix_admit_delay_is_slow_not_wrong():
    """``decode.prefix_admit`` delay mode: the admission stalls (the
    eviction-race window stretched wide) but the shared-prefix install
    still lands — same tokens, prefill still skipped."""
    srv, _cache = _prefix_decode_server("chaos-prefix-delay")
    prompt = np.array([1, 2, 3, 4, 5, 6, 7, 8, 10], np.int32)
    try:
        p0 = srv.metrics()["decode"]["prefill_tokens"]
        with faults.armed("decode.prefix_admit=delay:0.05,times=1"):
            t0 = time.perf_counter()
            out = srv.submit({"tokens": prompt},
                             max_new_tokens=4).result(timeout=30.0)
            assert time.perf_counter() - t0 >= 0.05  # the stall landed
        assert out[0].tolist() == [11, 12, 13, 14]
        assert srv.metrics()["decode"]["prefill_tokens"] - p0 == 1
        assert srv.metrics().get("prefix_fallback", 0) == 0
    finally:
        faults.disarm()
        srv.stop(drain=False)
