"""Overload-control tests (paddle_tpu/serving/admission.py and its
integration through DynamicBatcher / InferenceServer / the wire layer):
EDF ordering, expired-entry sweeps, priority shedding, the AIMD admit
limit, the brownout ladder, retry-after hints, deadline propagation
fail-fast, and the fleet balancer's load-aware routing + retry pacing.
"""
import threading
import time

import numpy as np
import pytest

from conftest import WAIT

from paddle_tpu import monitor, serving
from paddle_tpu.serving import (
    PRIORITY_HIGH,
    PRIORITY_LOW,
    PRIORITY_NORMAL,
    AdmissionQueue,
    BrownoutController,
    DeadlineExceeded,
    DynamicBatcher,
    InferenceServer,
    ServerOverloaded,
    ServingRequest,
)

IN_DIM = 16


class Req:
    """Duck-typed queue entry: just the attributes admission reads."""

    def __init__(self, deadline=None, priority=PRIORITY_NORMAL,
                 submit_t=None, tag=None):
        self.deadline = deadline
        self.priority = priority
        self.submit_t = time.perf_counter() if submit_t is None else submit_t
        self.tag = tag
        self.error = None

    def fail(self, e):
        self.error = e


def _pop(q):
    with q.cv:
        return q.pop_locked()


class SlowPredictor:
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


# ---------------------------------------------------------------------------
# AdmissionQueue: EDF ordering + sweeps
# ---------------------------------------------------------------------------
def test_edf_pop_order_and_no_deadline_fifo_tail():
    q = AdmissionQueue(16, name="edf", adaptive=False)
    now = time.monotonic()
    order = [Req(deadline=now + 30, tag="late"),
             Req(deadline=None, tag="none-a"),
             Req(deadline=now + 10, tag="soon"),
             Req(deadline=None, tag="none-b"),
             Req(deadline=now + 20, tag="mid")]
    for r in order:
        admitted, expired, shed, _ = q.offer(r)
        assert admitted and not expired and not shed
    tags = [_pop(q)[0].tag for _ in range(5)]
    # deadline order first, then the no-deadline entries FIFO
    assert tags == ["soon", "mid", "late", "none-a", "none-b"]
    q.close()


def test_expired_entries_swept_not_dispatched():
    q = AdmissionQueue(16, name="sweep", adaptive=False)
    now = time.monotonic()
    q.offer(Req(deadline=now + 0.01, tag="dying"))
    q.offer(Req(deadline=now + 30, tag="live"))
    time.sleep(0.03)
    req, expired = _pop(q)  # the pop-side sweep drops the expired top
    assert req.tag == "live"
    assert [r.tag for r in expired] == ["dying"]
    assert q.qsize() == 0
    q.close()


def test_offer_time_sweep_makes_room():
    """An expired queued entry must not hold a slot against a live
    arrival: the offer-time sweep drops it first."""
    q = AdmissionQueue(1, name="offersweep", adaptive=False)
    q.offer(Req(deadline=time.monotonic() + 0.02, tag="dying"))
    time.sleep(0.03)
    admitted, expired, shed, _ = q.offer(Req(deadline=None, tag="fresh"))
    assert admitted and not shed
    assert [r.tag for r in expired] == ["dying"]
    q.close()


# ---------------------------------------------------------------------------
# priority shedding
# ---------------------------------------------------------------------------
def test_full_queue_evicts_lowest_priority_least_urgent():
    q = AdmissionQueue(2, name="prio", adaptive=False)
    now = time.monotonic()
    low_urgent = Req(deadline=now + 5, priority=PRIORITY_LOW, tag="low-5s")
    low_lazy = Req(deadline=now + 50, priority=PRIORITY_LOW, tag="low-50s")
    q.offer(low_urgent)
    q.offer(low_lazy)
    admitted, _, shed, retry_ms = q.offer(
        Req(deadline=now + 30, priority=PRIORITY_HIGH, tag="high"))
    assert admitted
    # the LEAST urgent of the lowest class loses, and the hint is usable
    assert [r.tag for r in shed] == ["low-50s"]
    assert retry_ms >= 1.0
    q.close()


def test_equal_priority_arrival_is_shed_not_queued_work():
    q = AdmissionQueue(1, name="equal", adaptive=False)
    q.offer(Req(priority=PRIORITY_NORMAL, tag="first"))
    admitted, _, shed, retry_ms = q.offer(
        Req(priority=PRIORITY_NORMAL, tag="second"))
    assert not admitted and not shed and retry_ms >= 1.0
    # a HIGHER-priority arrival still gets in
    admitted, _, shed, _ = q.offer(Req(priority=PRIORITY_HIGH, tag="vip"))
    assert admitted and [r.tag for r in shed] == ["first"]
    q.close()


# ---------------------------------------------------------------------------
# weighted fair sharing across priority classes
# ---------------------------------------------------------------------------
def _fill_three_classes(q, n_per_class=14):
    for i in range(n_per_class):
        for prio, tag in ((PRIORITY_HIGH, "high"), (PRIORITY_NORMAL, "norm"),
                          (PRIORITY_LOW, "low")):
            admitted, _, shed, _ = q.offer(Req(priority=prio,
                                               tag="%s-%d" % (tag, i)))
            assert admitted and not shed


def test_weighted_shares_under_three_way_saturation():
    """Default 4:2:1 stride scheduling: out of every 7 pops under
    steady three-way saturation, HIGH gets 4, NORMAL 2, LOW 1 — a
    deterministic trickle instead of the starvation pure priority
    ordering produces."""
    q = AdmissionQueue(64, name="wfs", adaptive=False)
    _fill_three_classes(q)
    first14 = [_pop(q)[0].tag.split("-")[0] for _ in range(14)]
    counts = {c: first14.count(c) for c in ("high", "norm", "low")}
    assert counts == {"high": 8, "norm": 4, "low": 2}
    # LOW's trickle starts inside the first stride window, not after
    # the other classes drain
    assert "low" in set(first14[:7])
    q.close()


def test_weighted_share_preserves_edf_within_class():
    q = AdmissionQueue(16, name="wfs-edf", adaptive=False)
    now = time.monotonic()
    q.offer(Req(deadline=now + 30, priority=PRIORITY_LOW, tag="low-late"))
    q.offer(Req(deadline=now + 10, priority=PRIORITY_LOW, tag="low-soon"))
    q.offer(Req(deadline=now + 50, priority=PRIORITY_HIGH, tag="high-a"))
    popped = [_pop(q)[0].tag for _ in range(3)]
    # whatever the cross-class interleave, LOW drains soonest-first
    assert popped.index("low-soon") < popped.index("low-late")
    q.close()


def test_class_weights_none_restores_pure_edf():
    """``class_weights=None`` disables sharing: pops follow the global
    deadline order regardless of class."""
    q = AdmissionQueue(16, name="wfs-off", adaptive=False,
                       class_weights=None)
    now = time.monotonic()
    q.offer(Req(deadline=now + 30, priority=PRIORITY_HIGH, tag="high-30"))
    q.offer(Req(deadline=now + 10, priority=PRIORITY_LOW, tag="low-10"))
    q.offer(Req(deadline=now + 20, priority=PRIORITY_NORMAL, tag="norm-20"))
    assert [_pop(q)[0].tag for _ in range(3)] == [
        "low-10", "norm-20", "high-30"]
    q.close()


def test_idle_class_cannot_bank_credit():
    """A class waking from empty joins at the CURRENT virtual time: a
    long-idle LOW must not monopolize the queue to 'catch up'."""
    q = AdmissionQueue(64, name="wfs-bank", adaptive=False)
    # drain a long HIGH-only phase (advances HIGH's pass well past 0)
    for i in range(12):
        q.offer(Req(priority=PRIORITY_HIGH, tag="h%d" % i))
    for _ in range(12):
        _pop(q)
    # LOW wakes now; under mixed load it still gets only its 1-in-5
    # share vs HIGH (4:_:1), never a burst of back-credit
    for i in range(10):
        q.offer(Req(priority=PRIORITY_HIGH, tag="high-%d" % i))
        q.offer(Req(priority=PRIORITY_LOW, tag="low-%d" % i))
    first5 = [_pop(q)[0].tag.split("-")[0] for _ in range(5)]
    assert first5.count("low") == 1
    q.close()


def test_custom_and_invalid_class_weights():
    q = AdmissionQueue(16, name="wfs-custom", adaptive=False,
                       class_weights={PRIORITY_HIGH: 1.0,
                                      PRIORITY_LOW: 1.0})
    for i in range(4):
        q.offer(Req(priority=PRIORITY_HIGH, tag="high-%d" % i))
        q.offer(Req(priority=PRIORITY_LOW, tag="low-%d" % i))
    first4 = [_pop(q)[0].tag.split("-")[0] for _ in range(4)]
    # equal weights: strict alternation between the two classes
    assert first4.count("high") == 2 and first4.count("low") == 2
    q.close()
    with pytest.raises(ValueError):
        AdmissionQueue(16, name="wfs-bad",
                       class_weights={PRIORITY_HIGH: 0.0})


def test_weighted_share_flows_through_batcher_pops():
    """The batcher pops through the same stride scheduler, so a
    saturated server's batches carry the LOW trickle."""
    b = DynamicBatcher(1, 0.0, 64, name="wfs-batcher")
    for i in range(7):
        for prio in (PRIORITY_HIGH, PRIORITY_NORMAL, PRIORITY_LOW):
            b.offer(ServingRequest({"x": _rows(1)}, 1, None, priority=prio))
    stop = threading.Event()
    popped = []
    for _ in range(7):
        batch = b.next_batch(stop, lambda r: None, block=False)
        popped.extend(r.priority for r in batch)
    assert popped.count(PRIORITY_LOW) == 1
    assert popped.count(PRIORITY_HIGH) == 4
    b.close()


# ---------------------------------------------------------------------------
# AIMD admit limit
# ---------------------------------------------------------------------------
def test_aimd_halves_on_overshoot_and_regrows_additively():
    q = AdmissionQueue(64, target_wait_ms=10.0, min_limit=2, name="aimd")
    assert q.limit == 64
    now = time.monotonic()
    with q.cv:
        # overshoot: one observation per adjustment window (now steps
        # past _ADJUST_INTERVAL_S each time) -> multiplicative decrease
        q._observe_locked(1.0, now)
        q._observe_locked(1.0, now + 0.3)
    assert q.limit == 32
    with q.cv:
        q._observe_locked(1.0, now + 0.6)
    assert q.limit == 16
    with q.cv:
        # EWMA back under target -> +1 per window (additive increase);
        # reset the EWMA so every window below is under-target
        q._wait_ewma = 0.0
        for k in range(5):
            q._observe_locked(0.0, now + 1.0 + 0.3 * k)
    assert q.limit == 16 + 5
    gauge = monitor.snapshot()["serving_admit_limit"]
    vals = {tuple(sorted(s["labels"].items())): s["value"]
            for s in gauge["series"]}
    assert vals[(("server", "aimd"),)] == q.limit
    q.close()


def test_aimd_floor_never_exceeds_capacity():
    q = AdmissionQueue(2, target_wait_ms=1.0, min_limit=8, name="floor")
    assert q.limit == 2
    now = time.monotonic()
    with q.cv:
        q._observe_locked(5.0, now)
        q._observe_locked(5.0, now + 0.3)
    assert q.limit <= 2  # a decrease must never grow past capacity
    q.close()


def test_unbounded_queue_never_sheds():
    q = AdmissionQueue(0, name="unbounded")
    for i in range(100):
        admitted, _, shed, _ = q.offer(Req(tag=i))
        assert admitted and not shed
    assert q.qsize() == 100
    assert q.depth_ratio() == 0.0


# ---------------------------------------------------------------------------
# brownout ladder
# ---------------------------------------------------------------------------
def test_brownout_ladder_climbs_one_rung_after_hold():
    clk = [100.0]
    b = BrownoutController("ladder", hold_s=1.0, clock=lambda: clk[0])
    assert b.update(0.95) == 0          # pending, not yet held
    clk[0] += 0.5
    assert b.update(0.95) == 0          # still inside hold_s
    clk[0] += 0.6
    assert b.update(0.95) == 1          # held >= hold_s: ONE rung
    assert b.update(0.95) == 1          # transition re-arms the hold
    clk[0] += 1.1
    assert b.update(0.95) == 2          # next rung needed its own hold
    assert b.update(0.95) == 2
    clk[0] += 1.1
    assert b.update(0.95) == 3
    clk[0] += 1.1
    assert b.update(0.95) == 3          # MAX_LEVEL caps the ladder
    b.close()


def test_brownout_descends_slower_than_it_climbs():
    clk = [0.0]
    b = BrownoutController("hyst", hold_s=1.0, clock=lambda: clk[0])
    b.update(0.95)
    clk[0] += 1.1
    assert b.update(0.95) == 1
    # pressure clears: descent requires 4x the hold (hysteresis)
    assert b.update(0.0) == 1
    clk[0] += 2.0
    assert b.update(0.0) == 1
    clk[0] += 2.5
    assert b.update(0.0) == 0
    b.close()


def test_brownout_blip_does_not_flap():
    clk = [0.0]
    b = BrownoutController("blip", hold_s=1.0, clock=lambda: clk[0])
    b.update(0.95)
    clk[0] += 0.5
    b.update(0.0)   # pressure blip ends: pending ascent resets
    clk[0] += 0.6
    assert b.update(0.95) == 0  # the climb clock restarted
    b.close()


# ---------------------------------------------------------------------------
# DynamicBatcher integration
# ---------------------------------------------------------------------------
def _sreq(n=1, deadline_ms=None, priority=PRIORITY_NORMAL):
    deadline = (time.monotonic() + deadline_ms / 1e3
                if deadline_ms is not None else None)
    return ServingRequest({"x": np.zeros((n, 4), np.float32)}, n,
                          deadline, priority=priority)


def test_batcher_coalesces_in_deadline_order():
    b = DynamicBatcher(8, 0.0, 16, name="edfbatch")
    late, soon, mid = (_sreq(deadline_ms=30000), _sreq(deadline_ms=10000),
                       _sreq(deadline_ms=20000))
    for r in (late, soon, mid):
        b.offer(r)
    batch = b.next_batch(threading.Event(), lambda r: None)
    assert batch == [soon, mid, late]
    b.close()


def test_eager_mode_skips_the_coalescing_window():
    b = DynamicBatcher(8, 5000.0, 16, name="eager")  # 5s window!
    b.eager = True
    b.offer(_sreq())
    t0 = time.perf_counter()
    batch = b.next_batch(threading.Event(), lambda r: None)
    assert len(batch) == 1
    assert time.perf_counter() - t0 < 1.0  # did not wait the window
    b.close()


def test_batcher_default_hooks_fail_typed():
    b = DynamicBatcher(8, 0.0, 1, name="hooks")
    first = _sreq(priority=PRIORITY_LOW)
    b.offer(first)
    b.offer(_sreq(priority=PRIORITY_HIGH))  # evicts `first`
    with pytest.raises(ServerOverloaded) as ei:
        first.result(timeout=WAIT)
    assert ei.value.retry_after_ms is not None
    b.close()


# ---------------------------------------------------------------------------
# InferenceServer: priority shedding, fail-fast, brownout behaviors
# ---------------------------------------------------------------------------
def test_server_sheds_low_priority_for_high_under_pressure():
    srv = InferenceServer(SlowPredictor(delay_s=0.25), max_batch_size=1,
                          batch_timeout_ms=0, queue_capacity=2,
                          name="prioserver")
    try:
        # saturate the dispatch pipeline (dispatcher holds batches while
        # the replica's bounded in-flight is full), waiting for the
        # dispatcher to absorb EACH submit — a burst can overflow the
        # 2-slot queue itself when the dispatcher thread is starved
        # under CPU contention — THEN fill the queue
        pipelined = []
        for _ in range(3):
            pipelined.append(
                srv.submit({"x": _rows(1)}, priority=PRIORITY_LOW))
            wait_until = time.monotonic() + 5.0
            while (srv._batcher.qsize() > 0
                   and time.monotonic() < wait_until):
                time.sleep(0.01)
        assert srv._batcher.qsize() == 0
        queued = [srv.submit({"x": _rows(1)}, priority=PRIORITY_LOW)
                  for _ in range(2)]  # fills the 2-slot queue
        vip = srv.submit({"x": _rows(1)}, priority=PRIORITY_HIGH)
        outcomes = []
        for r in queued:
            try:
                r.result(timeout=WAIT)
                outcomes.append("ok")
            except ServerOverloaded as e:
                outcomes.append("shed")
                assert e.retry_after_ms is not None and e.retry_after_ms >= 1
        assert outcomes.count("shed") == 1  # exactly one low evicted
        vip.result(timeout=WAIT)      # the high-priority request completed
        for r in pipelined:
            r.result(timeout=WAIT)
        m = srv.metrics()
        assert m["shed"] == 1
    finally:
        srv.stop(drain=True)


def test_expired_deadline_fails_fast_at_admission():
    srv = InferenceServer(SlowPredictor(), max_batch_size=4,
                          batch_timeout_ms=0, queue_capacity=8,
                          name="expsrv")
    try:
        before = monitor.counter_value(
            "admission_expired_total", default=0.0, server="expsrv")
        with pytest.raises(DeadlineExceeded):
            srv.submit({"x": _rows(1)}, timeout_ms=-5.0)
        assert monitor.counter_value(
            "admission_expired_total", server="expsrv") == before + 1
        assert srv.metrics()["expired"] >= 1
    finally:
        srv.stop(drain=True)


def test_brownout_level3_sheds_lowest_class_at_the_door():
    srv = InferenceServer(SlowPredictor(), max_batch_size=4,
                          batch_timeout_ms=0, queue_capacity=8,
                          name="l3srv")
    try:
        srv._brownout.level = 3
        with pytest.raises(ServerOverloaded) as ei:
            srv.submit({"x": _rows(1)}, priority=PRIORITY_LOW)
        assert ei.value.retry_after_ms is not None
        # normal and high still pass at L3 (only the lowest class sheds)
        for priority in (PRIORITY_NORMAL, PRIORITY_HIGH):
            srv.submit({"x": _rows(1)},
                       priority=priority).result(timeout=WAIT)
    finally:
        srv.stop(drain=True)


def test_brownout_descends_under_low_priority_only_traffic():
    """Regression: at L3 the door sheds low priority before anything
    enqueues, so the parked dispatcher never samples pressure again —
    the submit path must drive the ladder too, or an idle server sheds
    100%% of low-priority traffic forever."""
    srv = InferenceServer(SlowPredictor(), max_batch_size=4,
                          batch_timeout_ms=0, queue_capacity=8,
                          name="l3descend", brownout_hold_s=0.05)
    try:
        srv._brownout.level = 3
        deadline = time.monotonic() + 5.0
        accepted = False
        while time.monotonic() < deadline:
            try:
                srv.submit({"x": _rows(1)},
                           priority=PRIORITY_LOW).result(timeout=WAIT)
                accepted = True
                break
            except ServerOverloaded:
                time.sleep(0.02)  # only LOW traffic arrives, ever
        assert accepted, "brownout latched at L3 under low-only traffic"
        assert srv._brownout.level < 3
    finally:
        srv.stop(drain=True)


def test_server_load_report_shape():
    srv = InferenceServer(SlowPredictor(), max_batch_size=4,
                          batch_timeout_ms=0, queue_capacity=8,
                          name="loadsrv")
    try:
        load = srv.load()
        assert set(load) == {"queue_depth", "admit_limit", "brownout_level"}
        assert load["admit_limit"] == 8
        assert load["brownout_level"] == 0
        m = srv.metrics()
        assert m["admit_limit"] == 8 and m["brownout_level"] == 0
    finally:
        srv.stop(drain=True)


def test_client_priority_plumbs_through():
    srv = InferenceServer(SlowPredictor(), max_batch_size=4,
                          batch_timeout_ms=0, queue_capacity=8,
                          name="cliprio")
    try:
        cli = serving.Client(srv)
        out, = cli.infer({"x": _rows(2)}, priority=PRIORITY_HIGH)
        assert out.shape == (2, 1)
        outs = cli.infer_many([{"x": _rows(1)}, {"x": _rows(1, seed=1)}],
                              priority=PRIORITY_LOW)
        assert len(outs) == 2
    finally:
        srv.stop(drain=True)


# ---------------------------------------------------------------------------
# wire layer: retry-after + load over the hop, fleet pacing
# ---------------------------------------------------------------------------
def _stub_wire_server(name, delay_s=0.0, max_batch_size=8, **kw):
    from paddle_tpu.serving import wire

    srv = InferenceServer(SlowPredictor(delay_s=delay_s),
                          max_batch_size=max_batch_size,
                          batch_timeout_ms=1, name=name, **kw)
    sp = wire.ServingProcess(srv)
    sp.start()
    return sp


def test_wire_carries_retry_after_and_load_report():
    from paddle_tpu.serving import wire
    from paddle_tpu.serving.wire.client import raise_in_band_error

    sp = _stub_wire_server("wireload", queue_capacity=4)
    try:
        cli = wire.RemoteClient(sp.address)
        out, = cli.infer({"x": _rows(2)}, priority=PRIORITY_HIGH)
        assert out.shape == (2, 1)
        # the admin surface reports the overload-control state
        doc = cli.healthz()
        assert doc["admit_limit"] == 4
        assert doc["brownout_level"] == 0
        # a synthesized overload answer re-attaches hint AND load
        with pytest.raises(ServerOverloaded) as ei:
            raise_in_band_error({
                "error": "ServerOverloaded", "message": "shed",
                "retry_after_ms": 12.5,
                "load": {"queue_depth": 3, "admit_limit": 4,
                         "brownout_level": 1}})
        assert ei.value.retry_after_ms == 12.5
        assert ei.value.load["queue_depth"] == 3
        cli.close()
    finally:
        sp.stop()


def test_wire_server_sheds_expired_deadline_at_admission():
    from paddle_tpu.serving import wire
    from paddle_tpu.serving.wire.client import raise_in_band_error
    from paddle_tpu.serving.wire.http import HttpTransport

    sp = _stub_wire_server("wireexp", queue_capacity=4)
    try:
        before = monitor.counter_value(
            "admission_expired_total", default=0.0, server="wireexp")
        t = HttpTransport(*sp.address)
        meta, _ = t.request("/infer", {
            "feed_names": ["x"], "timeout_ms": -10.0}, [_rows(1)])
        with pytest.raises(DeadlineExceeded):
            raise_in_band_error(meta)
        assert monitor.counter_value(
            "admission_expired_total", server="wireexp") == before + 1
        t.close()
    finally:
        sp.stop()


def test_remote_client_fails_fast_when_deadline_already_gone():
    from paddle_tpu.serving.wire.client import RemoteClient

    with pytest.raises(DeadlineExceeded):
        RemoteClient._remaining_ms(time.monotonic() - 1.0)
    assert RemoteClient._remaining_ms(None) is None
    assert RemoteClient._remaining_ms(time.monotonic() + 1.0) > 0


def test_fleet_folds_reported_load_into_routing():
    from paddle_tpu.serving import wire

    sps = [_stub_wire_server("fold%d" % i, queue_capacity=16)
           for i in range(2)]
    fleet = wire.FleetBalancer([sp.address for sp in sps],
                               name="foldfleet", health_interval_s=None)
    try:
        out, = fleet.infer({"x": _rows(2)}, timeout_ms=10000)
        assert out.shape == (2, 1)
        stats = fleet.backend_stats()
        served = [s for s in stats.values() if s["executed"] == 1]
        assert len(served) == 1
        assert served[0]["load_fresh"]
        assert served[0]["reported_limit"] == 16
        # routing prefers the quiet backend over a backlogged one
        now = time.monotonic()
        with fleet._route_cv:
            busy, idle = fleet._backends
            busy.reported_depth, busy.load_ts = 50, now
            idle.reported_depth, idle.load_ts = 0, now
        assert fleet._pick(None, now) is idle
        # ...unless the report has gone stale
        with fleet._route_cv:
            busy.load_ts = now - 60.0
            idle.in_flight = 1
        assert fleet._pick(None, now) is busy
    finally:
        fleet.stop()
        for sp in sps:
            sp.stop()


def test_fleet_pacing_honors_not_before_pause():
    from paddle_tpu.serving import wire

    sp = _stub_wire_server("pace", queue_capacity=16)
    fleet = wire.FleetBalancer([sp.address], name="pacefleet",
                               health_interval_s=None)
    try:
        fleet.infer({"x": _rows(1)})  # shape discovery
        pause_s = 0.3
        with fleet._route_cv:
            fleet._backends[0].not_before = time.monotonic() + pause_s
        t0 = time.perf_counter()
        out, = fleet.infer({"x": _rows(1, seed=1)}, timeout_ms=10000)
        waited = time.perf_counter() - t0
        assert out.shape == (1, 1)
        assert waited >= pause_s * 0.8, (
            "dispatch did not wait out the retry-after pause: %.3fs"
            % waited)
    finally:
        fleet.stop()
        sp.stop()


def test_fleet_retry_throttle_denial_counts_and_propagates():
    from paddle_tpu.serving import wire

    # a saturated backend: 1-slot queue behind a slow single-row worker
    # (max_batch_size=1 defeats coalescing so the pipeline really fills)
    sp = _stub_wire_server("throt", delay_s=0.4, queue_capacity=1,
                           max_batch_size=1)
    fleet = wire.FleetBalancer([sp.address], name="throtfleet",
                               health_interval_s=None, max_in_flight=16,
                               retry_rate_per_s=0.001, retry_burst=0)
    try:
        before = monitor.counter_value(
            "retry_throttled_total", default=0.0, fleet="throtfleet")
        results = []
        lock = threading.Lock()

        def one(i):
            try:
                fleet.infer({"x": _rows(1, seed=i)}, timeout_ms=8000)
                with lock:
                    results.append("ok")
            except ServerOverloaded as e:
                assert e.retry_after_ms is not None
                with lock:
                    results.append("shed")

        threads = [threading.Thread(target=one, args=(i,), daemon=True)
                   for i in range(8)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(WAIT)
        assert not any(t.is_alive() for t in threads)
        assert "shed" in results, results
        assert "ok" in results, results
        # a burst-0 bucket denies every paced retry: the shed propagated
        # with its hint instead of re-storming the backend
        assert monitor.counter_value(
            "retry_throttled_total", fleet="throtfleet") > before
    finally:
        fleet.stop()
        sp.stop()
