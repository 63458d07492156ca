"""Chaos drills for graceful degradation under overload.

The acceptance storm: a real 2-child wire fleet driven at ~3x its
measured saturation throughput with mixed priority classes must keep
goodput >= 70% of saturation, lose zero accepted requests (every
submission ends completed or TYPED), shed low priority before high,
and honor ``ServerOverloaded.retry_after_ms`` in the balancer's retry
pacing (paused backends observed, token-bucket denials counted in
``retry_throttled_total``).

Also here: the ``server.admit`` fault point (deterministic injection at
the admission gate) — the door where overload control lives.
"""
import threading
import time

import numpy as np
import pytest

from conftest import WAIT

import paddle_tpu as fluid
from paddle_tpu import faults, framework, monitor
from paddle_tpu.serving import (
    PRIORITY_HIGH,
    PRIORITY_LOW,
    PRIORITY_NORMAL,
    DeadlineExceeded,
    InferenceServer,
    ServerOverloaded,
    wire,
)

IN_DIM = 16


@pytest.fixture(autouse=True)
def _always_disarm():
    yield
    faults.disarm()


class StubPredictor:
    def get_input_names(self):
        return ["x"]

    def get_output_names(self):
        return ["y"]

    def input_specs(self):
        return {"x": ((IN_DIM,), np.dtype("float32"))}

    def jit_cache_stats(self):
        return {"entries": 0, "hits": 0, "misses": 0}

    def run_padded(self, feed, n_valid=None):
        return [np.asarray(feed["x"][:n_valid]).sum(axis=1, keepdims=True)]


def _rows(n, seed=0):
    return np.random.RandomState(seed).uniform(
        -1, 1, (n, IN_DIM)).astype("float32")


# ---------------------------------------------------------------------------
# server.admit: injection at the admission gate
# ---------------------------------------------------------------------------
def test_server_admit_fault_point_injects_typed_error():
    srv = InferenceServer(StubPredictor(), max_batch_size=4,
                          batch_timeout_ms=0, queue_capacity=8,
                          name="admitfault")
    try:
        plan = faults.arm("server.admit=error:ConnectionError,times=2")
        for _ in range(2):
            with pytest.raises(ConnectionError):
                srv.submit({"x": _rows(1)})
        assert plan.triggers()["server.admit"] == 2
        faults.disarm()
        # healed: admission is clean again and the request completes
        out, = srv.submit({"x": _rows(2, seed=3)}).result(timeout=WAIT)
        assert out.shape == (2, 1)
    finally:
        srv.stop(drain=True)


def test_server_admit_delay_mode_slows_not_breaks():
    srv = InferenceServer(StubPredictor(), max_batch_size=4,
                          batch_timeout_ms=0, queue_capacity=8,
                          name="admitdelay")
    try:
        with faults.armed("server.admit=delay:0.05,times=1"):
            t0 = time.perf_counter()
            req = srv.submit({"x": _rows(1)})
            assert time.perf_counter() - t0 >= 0.05
            req.result(timeout=WAIT)
    finally:
        srv.stop(drain=True)


# ---------------------------------------------------------------------------
# acceptance: 3x mixed-priority storm over a real 2-child fleet
# ---------------------------------------------------------------------------
@pytest.fixture(scope="module")
def mlp_model_dir(tmp_path_factory):
    d = str(tmp_path_factory.mktemp("overload") / "mlp")
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


def test_chaos_overload_storm_goodput_floor_and_priority_order(
        mlp_model_dir):
    """3x-capacity mixed-priority storm against a 2-child fleet:
    goodput >= 70% of saturation, zero lost accepted requests,
    low-priority shed before high, high-priority p99 inside the
    deadline, retry-after pacing engaged (paused backends observed)
    and the retry throttle exercised (``retry_throttled_total`` > 0)."""
    # the children arrive PRE-ARMED with a deterministic per-batch
    # execution delay (replica.dispatch, env plan): a known, finite
    # capacity the storm can actually drive 3x past — saturation as a
    # controlled input, not a race against how fast the CPU happens to
    # run an MLP
    import os

    os.environ["PADDLE_TPU_FAULTS"] = "replica.dispatch=delay:0.04"
    try:
        fleet = wire.FleetBalancer.from_launch(
            mlp_model_dir, n=2, name="overloadfleet",
            launch_kwargs=dict(max_batch_size=2, batch_timeout_ms=2,
                               queue_capacity=2),
            health_interval_s=None, max_in_flight=8,
            retry_rate_per_s=20.0, retry_burst=2)
    finally:
        os.environ.pop("PADDLE_TPU_FAULTS", None)
    deadline_ms = 2500.0
    try:
        fleet.warmup()
        # --- phase 1: saturation throughput, closed loop ------------
        n_sat = 8
        sat_done = [0] * n_sat
        stop = threading.Event()

        def closed(tid):
            rng = np.random.RandomState(40 + tid)
            while not stop.is_set():
                try:
                    fleet.infer({"x": rng.rand(2, IN_DIM).astype("f4")},
                                timeout_ms=5000)
                    sat_done[tid] += 1
                except (ServerOverloaded, DeadlineExceeded):
                    time.sleep(0.005)

        threads = [threading.Thread(target=closed, args=(t,), daemon=True)
                   for t in range(n_sat)]
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        time.sleep(1.5)
        stop.set()
        for t in threads:
            t.join(WAIT)
        assert not any(t.is_alive() for t in threads)
        sat_rps = sum(sat_done) / (time.perf_counter() - t0)
        assert sat_rps > 0

        # --- phase 2: the 3x mixed-priority storm -------------------
        classes = (("high", PRIORITY_HIGH), ("normal", PRIORITY_NORMAL),
                   ("low", PRIORITY_LOW))
        n_threads = 24  # 3x the saturation concurrency, 8 per class
        stats = {
            label: {"completed": 0, "shed": 0, "expired": 0, "lat": []}
            for label, _ in classes
        }
        hints = []
        errs = []
        lock = threading.Lock()
        stop = threading.Event()
        throttled0 = monitor.counter_value(
            "retry_throttled_total", default=0.0, fleet="overloadfleet")
        max_paused = [0.0]

        def sampler():
            # proof the balancer HONORS retry hints: during the storm a
            # shedding backend must show up paused (not_before in the
            # future) in the routing state
            while not stop.is_set():
                for s in fleet.backend_stats().values():
                    max_paused[0] = max(max_paused[0], s["paused_ms"])
                time.sleep(0.01)

        def storm(tid):
            label, prio = classes[tid % len(classes)]
            rng = np.random.RandomState(90 + tid)
            st = stats[label]
            while not stop.is_set():
                t_req = time.perf_counter()
                try:
                    fleet.infer({"x": rng.rand(2, IN_DIM).astype("f4")},
                                timeout_ms=deadline_ms, priority=prio)
                    with lock:
                        st["completed"] += 1
                        st["lat"].append(
                            (time.perf_counter() - t_req) * 1e3)
                except ServerOverloaded as e:
                    with lock:
                        st["shed"] += 1
                        hints.append(e.retry_after_ms)
                    # the CLIENT honors the hint too: back off before
                    # re-offering (bounded so the storm stays a storm)
                    time.sleep(min(0.1, (e.retry_after_ms or 1.0) / 1e3))
                except DeadlineExceeded:
                    with lock:
                        st["expired"] += 1
                except Exception as e:  # noqa: BLE001 — assertion target
                    with lock:
                        errs.append(repr(e))
                    return

        threads = [threading.Thread(target=storm, args=(t,), daemon=True)
                   for t in range(n_threads)]
        threads.append(threading.Thread(target=sampler, daemon=True))
        t0 = time.perf_counter()
        for t in threads:
            t.start()
        time.sleep(3.0)
        stop.set()
        for t in threads:
            t.join(WAIT)
        assert not any(t.is_alive() for t in threads)
        elapsed = time.perf_counter() - t0

        # zero lost accepted requests: every submission ended in a
        # result or a TYPED end state — never an untyped error or hang
        assert errs == [], "untyped failures under overload: %s" % errs[:3]

        # goodput floor: past saturation the fleet keeps doing the work
        goodput = sum(s["completed"] for s in stats.values()) / elapsed
        assert goodput >= 0.7 * sat_rps, (
            "goodput collapsed past saturation: %.1f rps vs saturation "
            "%.1f rps (floor 70%%); stats=%s"
            % (goodput, sat_rps,
               {k: {x: v[x] for x in ("completed", "shed", "expired")}
                for k, v in stats.items()}))

        # overload actually happened, and LOW shed before HIGH
        total_shed = sum(s["shed"] for s in stats.values())
        assert total_shed > 0, "storm never saturated the fleet"
        assert stats["low"]["shed"] >= stats["high"]["shed"]
        assert stats["low"]["shed"] > 0
        assert stats["high"]["completed"] >= stats["low"]["completed"]

        # high-priority latency stays inside the deadline envelope
        lat = sorted(stats["high"]["lat"])
        assert lat, "no high-priority request completed"
        p99 = lat[int(0.99 * (len(lat) - 1))]
        assert p99 <= deadline_ms, "high-priority p99 %.1fms" % p99

        # the retry-after contract, end to end: sheds carried hints,
        # and the balancer PAUSED shedding backends (pacing honored)
        assert any(h is not None and h >= 1.0 for h in hints), hints[:5]
        assert max_paused[0] > 0.0, (
            "no backend was ever paused by its retry-after hint")

        # the token-bucket throttle engaged under the storm
        assert monitor.counter_value(
            "retry_throttled_total", fleet="overloadfleet") > throttled0
    finally:
        fleet.stop(shutdown_backends=True)
