"""Training control tower (ISSUE 20): the step-phase ledger (every
wall-clock second of a ``train_from_dataset`` epoch attributed to a
phase, summing to elapsed within 1%), the EWMA/z-score anomaly
watchdog with its typed halt, the ``/trainz`` admin surface + JSONL
step log, and fleet federation of a trainer next to serving backends.
"""
import json
import math
import os
import sys
import urllib.request

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import framework, monitor
from paddle_tpu.monitor import events as mon_events
from paddle_tpu.monitor import train as mtrain

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _fc_model(dim=8, hidden=4, seed=7):
    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = seed
    with framework.program_guard(prog, startup):
        x = fluid.layers.data("x", [dim])
        y = fluid.layers.data("y", [1])
        h = fluid.layers.fc(x, hidden, act="relu")
        pred = fluid.layers.fc(h, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        opt = fluid.optimizer.SGDOptimizer(0.05)
        opt.minimize(loss)
    return prog, startup, loss, opt


def _feeds(dim=8, batch=4, n=10, seed=3):
    rng = np.random.RandomState(seed)
    return [
        {"x": rng.randn(batch, dim).astype("float32"),
         "y": rng.randn(batch, 1).astype("float32")}
        for _ in range(n)
    ]


def _get_json(addr, path):
    host, port = addr
    with urllib.request.urlopen(
            "http://%s:%d%s" % (host, port, path), timeout=5) as r:
        return json.loads(r.read().decode("utf-8"))


# ---------------------------------------------------------------------------
# StepPhaseLedger accounting contract
# ---------------------------------------------------------------------------
def test_ledger_phases_sum_exactly_to_wall():
    """Direct charges + the closing remainder: phases sum to the epoch
    wall-clock, with the unattributed part landing in ``other``."""
    import time as _time

    led = mtrain.StepPhaseLedger(metrics=False)
    led.begin_epoch()
    _time.sleep(0.03)
    led.charge("h2d", 0.010)
    led.charge("ps_wait", 0.005)
    led.finish_epoch()
    snap = led.snapshot()
    assert snap["finished"]
    total = sum(snap["phases"].values())
    # snapshot() rounds each phase and the wall to 1e-6 s separately, so
    # the rounded parts may miss the rounded whole by a few 1e-6
    assert total == pytest.approx(snap["wall_s"], abs=1e-5)
    assert snap["phases"]["other"] >= 0.01  # the unattributed sleep


def test_ledger_window_excludes_nested_charges():
    """Window-exclusive nesting: a charge made inside an open window is
    subtracted from what the window's own phase receives — no second is
    ever booked twice."""
    import time as _time

    led = mtrain.StepPhaseLedger(metrics=False)
    led.begin_epoch()
    tok = led.window_begin()
    _time.sleep(0.02)
    led.charge("ps_wait", 0.015)  # nested: claimed by ps_wait
    dt = led.window_end(tok, "device_execute")
    assert led.seconds["ps_wait"] == pytest.approx(0.015)
    # the window charged only elapsed - 15ms, never the full 20ms+
    assert dt == pytest.approx(led.seconds["device_execute"])
    assert led.seconds["device_execute"] < 0.02
    led.finish_epoch()
    snap = led.snapshot()
    assert sum(snap["phases"].values()) == pytest.approx(
        snap["wall_s"], abs=1e-5)  # per-phase 1e-6 rounding, as above


def test_ledger_overcount_fails_loudly():
    """Charging more seconds than elapsed is a double-charge bug; the
    strict finish asserts, the non-strict path (exceptional exits)
    keeps the partial ledger readable."""
    led = mtrain.StepPhaseLedger(metrics=False)
    led.begin_epoch()
    led.charge("device_execute", 100.0)  # obviously more than elapsed
    with pytest.raises(AssertionError, match="charged twice"):
        led.finish_epoch(strict=True)
    led2 = mtrain.StepPhaseLedger(metrics=False)
    led2.begin_epoch()
    led2.charge("device_execute", 100.0)
    led2.finish_epoch(strict=False)  # no raise
    assert led2.snapshot()["finished"]


def test_ledger_timed_iter_charges_data_wait_and_closes_source():
    import time as _time

    closed = []

    def slow_src():
        try:
            for i in range(3):
                _time.sleep(0.005)
                yield i
        finally:
            closed.append(True)

    led = mtrain.StepPhaseLedger(metrics=False)
    led.begin_epoch()
    got = list(led.timed_iter(slow_src()))
    assert got == [0, 1, 2] and closed == [True]
    assert led.seconds["data_wait"] >= 0.012

    # early exit still closes the wrapped source (prefetch shutdown)
    closed2 = []

    def src2():
        try:
            while True:
                yield 0
        finally:
            closed2.append(True)

    it = led.timed_iter(src2())
    next(it)
    it.close()
    assert closed2 == [True]


def test_step_done_rows_and_counter_flush():
    led = mtrain.StepPhaseLedger()
    base = monitor.counter_value("train_phase_seconds_total", phase="h2d")
    led.begin_epoch()
    led.charge("h2d", 0.25)
    row = led.step_done(0, 0.3, examples=16, loss=1.5)
    assert row["phases"]["h2d"] == pytest.approx(0.25)
    assert row["examples"] == 16 and row["loss"] == 1.5
    # flushed to the labeled counter exactly once
    assert monitor.counter_value(
        "train_phase_seconds_total", phase="h2d") - base == pytest.approx(
            0.25, abs=1e-6)
    row2 = led.step_done(1, 0.01, examples=16)
    assert "h2d" not in row2["phases"]  # per-step delta, not cumulative


def test_estimate_block_flops_counts_mul_and_grads():
    """fc(8->4) + fc(4->1) at batch 4: forward muls are 2*B*K*N each,
    every ``*_grad`` op counts double its forward — the static MFU
    numerator is hand-checkable."""
    prog, _, _, _ = _fc_model(dim=8, hidden=4)
    fwd = 2.0 * 4 * 8 * 4 + 2.0 * 4 * 4 * 1
    want = fwd * 3.0  # forward + mul_grad at 2x
    got = mtrain.estimate_block_flops(prog, batch=4)
    assert got == pytest.approx(want)


def test_batch_examples_reads_leading_dim():
    assert mtrain.batch_examples({"x": np.zeros((7, 3))}) == 7
    assert mtrain.batch_examples({"x": [1, 2, 3]}) == 3
    assert mtrain.batch_examples({}) == 0
    assert mtrain.batch_examples(None) == 0


# ---------------------------------------------------------------------------
# TrainWatchdog
# ---------------------------------------------------------------------------
def test_watchdog_nan_loss_halts_typed_and_emits_critical():
    wd = mtrain.TrainWatchdog(clock=lambda: 123.5)
    mark = mon_events.eventz()["retained"]
    for i in range(3):
        assert wd.observe_step(i, loss=1.0, step_time_s=0.01) == []
    found = wd.observe_step(3, loss=float("nan"), step_time_s=0.01)
    assert [f["kind"] for f in found] == ["nan_loss"]
    assert found[0]["severity"] == "critical"
    assert found[0]["ts"] == 123.5  # injectable clock stamped it
    with pytest.raises(mtrain.TrainAnomalyError) as ei:
        wd.raise_if_halt(found)
    assert ei.value.kind == "nan_loss" and ei.value.step == 3
    assert wd.halted is not None and wd.state()["halted"]["kind"] == "nan_loss"
    evs = mon_events.eventz()["events"]
    mine = [e for e in evs if e.get("kind") == "train/anomaly"
            and e.get("anomaly") == "nan_loss" and e.get("step") == 3]
    assert mine and mine[-1]["severity"] == "critical"
    assert mon_events.eventz()["retained"] > mark


def test_watchdog_loss_spike_after_warmup_only():
    wd = mtrain.TrainWatchdog(warmup_steps=8, z_threshold=6.0,
                              clock=lambda: 0.0)
    # a wild value DURING warmup is not flagged (EWMA still settling)
    assert wd.observe_step(0, loss=500.0) == []
    wd2 = mtrain.TrainWatchdog(warmup_steps=8, z_threshold=6.0,
                               clock=lambda: 0.0)
    rng = np.random.RandomState(0)
    for i in range(20):
        assert wd2.observe_step(i, loss=1.0 + 0.01 * rng.randn()) == []
    found = wd2.observe_step(20, loss=50.0)
    assert [f["kind"] for f in found] == ["loss_spike"]
    assert found[0]["severity"] == "error"
    wd2.raise_if_halt(found)  # loss_spike not in halt_on -> no raise


def test_watchdog_step_time_regression_needs_z_and_ratio():
    wd = mtrain.TrainWatchdog(warmup_steps=8, z_threshold=6.0,
                              clock=lambda: 0.0)
    rng = np.random.RandomState(1)
    for i in range(20):
        assert wd.observe_step(
            i, step_time_s=0.010 + 0.0001 * rng.randn()) == []
    found = wd.observe_step(20, step_time_s=0.100)  # 10x straggler
    assert [f["kind"] for f in found] == ["step_time_regression"]
    assert found[0]["severity"] == "warning"


def test_watchdog_grad_norm_blowup_and_nonfinite():
    wd = mtrain.TrainWatchdog(warmup_steps=4, z_threshold=6.0,
                              clock=lambda: 0.0)
    for i in range(10):
        assert wd.observe_step(i, grad_norm=1.0) == []
    found = wd.observe_step(10, grad_norm=float("inf"))
    assert [f["kind"] for f in found] == ["grad_norm_blowup"]
    assert found[0]["severity"] == "critical"  # non-finite escalates


# ---------------------------------------------------------------------------
# the peaks table: unknown device = error, explicit peak = MFU
# ---------------------------------------------------------------------------
def test_peaks_table_rejects_unknown_device_and_cpu():
    import types

    import jax

    from paddle_tpu import device_peaks

    v5e = types.SimpleNamespace(platform="tpu", device_kind="TPU v5 lite")
    assert device_peaks.peak_flops(v5e) == 197e12
    unknown = types.SimpleNamespace(platform="tpu", device_kind="TPU v99")
    with pytest.raises(ValueError, match="TPU v99"):
        device_peaks.peak_flops(unknown)
    with pytest.raises(ValueError, match="no published peak"):
        device_peaks.peak_flops(jax.devices()[0])  # the CPU testbed


def test_ledger_reports_mfu_only_against_a_stated_peak():
    led = mtrain.StepPhaseLedger(metrics=False, flops_per_step=2e6)
    assert led.peak_flops is None  # CPU default: no peak, no MFU
    led2 = mtrain.StepPhaseLedger(metrics=False, flops_per_step=2e6,
                                  peak_flops=1e9)
    for one in (led, led2):
        one.begin_epoch()
        one.charge("device_execute", 0.001)
        one.step_done(0, 0.001, examples=4)
    assert led.snapshot()["mfu_ratio"] is None
    assert led2.snapshot()["mfu_ratio"] > 0.0


# ---------------------------------------------------------------------------
# train_from_dataset end to end
# ---------------------------------------------------------------------------
def test_train_epoch_ledger_watchdog_steplog_end_to_end(tmp_path):
    """One armed epoch: ledger books balance within 1%, throughput
    gauges land (and NO MFU: this runs on a CPU, which has no published
    peak), the step log replays to the same totals, and
    ``exe.trainz()`` composes it all."""
    prog, startup, loss, _ = _fc_model()
    feeds = _feeds(n=12)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    log = str(tmp_path / "steps.jsonl")
    with fluid.scope_guard(scope):
        exe.run(startup)
        out = exe.train_from_dataset(
            program=prog, dataset=feeds, scope=scope, fetch_list=[loss],
            phase_ledger=True, watchdog=True, train_log=log)
    assert len(out) == 12
    led = exe.last_train_ledger
    snap = led.snapshot()
    assert snap["finished"] and snap["n_steps"] == 12
    assert snap["examples"] == 12 * 4
    total = sum(snap["phases"].values())
    assert abs(total - snap["wall_s"]) <= 0.01 * snap["wall_s"] + 1e-6
    assert snap["phases"]["device_execute"] > 0.0
    assert snap["phases"]["h2d"] > 0.0
    assert snap["steps_per_second"] > 0.0
    assert snap["examples_per_second"] > 0.0
    # static FLOPs resolved on the first step from the block shapes;
    # a CPU has no row in the peaks table, so no MFU is reported
    assert snap["flops_per_step"] == pytest.approx(
        mtrain.estimate_block_flops(prog, batch=4))
    assert snap["peak_flops"] is None and snap["mfu_ratio"] is None
    # registry surfaces
    assert monitor.counter_value("train_phase_seconds_total",
                                 phase="device_execute") > 0.0
    assert monitor.counter_value("train_steps_per_second") > 0.0
    cnt = [l for l in monitor.render_openmetrics().splitlines()
           if l.startswith("executor_train_step_seconds_count")]
    assert cnt and float(cnt[0].split()[-1]) >= 12
    # the per-step JSONL stream replays to the same books
    rep = mtrain.replay_step_log(log)
    assert rep["n_steps"] == 12 and rep["examples"] == 48
    assert rep["phases"]["device_execute"] == pytest.approx(
        snap["phases"]["device_execute"], abs=0.05)
    rows = [json.loads(l) for l in open(log) if l.strip()]
    assert all(r["trace_id"] == exe.last_train_trace_id for r in rows)
    assert all(math.isfinite(r["loss"]) for r in rows)
    # the composed /trainz document
    doc = exe.trainz()
    assert doc["role"] == "trainer"
    assert doc["ledger"]["n_steps"] == 12
    assert doc["watchdog"]["steps_observed"] == 12
    assert doc["train_log"] == log
    assert doc["trace_id"] == exe.last_train_trace_id


def test_disarmed_loop_leaves_no_ledger_state():
    prog, startup, loss, _ = _fc_model(seed=9)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.train_from_dataset(program=prog, dataset=_feeds(n=3),
                               scope=scope, fetch_list=[loss])
    assert exe._train_ledger is None  # run()'s gate stays one None-check


def test_train_step_histogram_carries_trace_exemplar():
    prog, startup, loss, _ = _fc_model(seed=11)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.train_from_dataset(program=prog, dataset=_feeds(n=3),
                               scope=scope, fetch_list=[loss],
                               trace_id="traintrace42")
    text = monitor.render_openmetrics()
    lines = [l for l in text.splitlines()
             if l.startswith("executor_train_step_seconds_bucket")
             and "traintrace42" in l]
    assert lines, "no executor_train_step_seconds exemplar with the epoch id"


def test_watchdog_halt_is_typed_from_train_loop(tmp_path):
    """A NaN batch mid-epoch: the typed halt propagates, the fatal step
    is in the step log BEFORE the raise, and the partial ledger stays
    readable (non-strict close on the exceptional exit)."""
    prog, startup, loss, _ = _fc_model(seed=13)
    feeds = _feeds(n=8)
    feeds[5]["x"][:] = np.nan
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    log = str(tmp_path / "halt.jsonl")
    with fluid.scope_guard(scope):
        exe.run(startup)
        with pytest.raises(mtrain.TrainAnomalyError) as ei:
            exe.train_from_dataset(
                program=prog, dataset=feeds, scope=scope,
                fetch_list=[loss], phase_ledger=True, watchdog=True,
                train_log=log)
    assert ei.value.kind == "nan_loss" and ei.value.step == 5
    rows = [json.loads(l) for l in open(log) if l.strip()]
    assert rows[-1]["step"] == 5
    assert rows[-1]["anomalies"][0]["kind"] == "nan_loss"
    assert exe.last_train_watchdog.halted["kind"] == "nan_loss"
    assert exe.last_train_ledger.snapshot()["finished"]
    assert exe._train_ledger is None  # disarm even on the raise path


# ---------------------------------------------------------------------------
# Admin surface + federation
# ---------------------------------------------------------------------------
def test_train_admin_serves_all_surfaces():
    prog, startup, loss, _ = _fc_model(seed=17)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.train_from_dataset(program=prog, dataset=_feeds(n=4),
                               scope=scope, fetch_list=[loss],
                               phase_ledger=True, watchdog=True)
    addr = exe.start_train_admin(port=0)
    try:
        assert exe.start_train_admin() == addr  # repeat call reuses
        assert exe.train_admin_address == addr
        tz = _get_json(addr, "/trainz")
        assert tz["role"] == "trainer" and tz["ledger"]["n_steps"] == 4
        sz = _get_json(addr, "/statusz")
        assert sz["role"] == "trainer" and "jit_cache" in sz
        assert sz["trainz"]["ledger"]["n_steps"] == 4
        hz = _get_json(addr, "/healthz")
        assert hz == {"ok": True, "role": "trainer"}
        ez = _get_json(addr, "/eventz")
        assert "events" in ez
        trz = _get_json(addr, "/tracez")
        assert "recorder" in trz
        host, port = addr
        with urllib.request.urlopen(
                "http://%s:%d/metrics" % (host, port), timeout=5) as r:
            text = r.read().decode("utf-8")
        assert "train_phase_seconds_total" in text
        assert "executor_train_step_seconds" in text
        req = urllib.request.Request(
            "http://%s:%d/metrics" % (host, port),
            headers={"Accept": "application/openmetrics-text"})
        with urllib.request.urlopen(req, timeout=5) as r:
            assert r.headers.get_content_type() == (
                "application/openmetrics-text")
    finally:
        exe.stop_train_admin()
    assert exe.train_admin_address is None


def test_fleet_federates_trainer_next_to_serving_backends():
    """``FleetBalancer.add_scrape_target`` folds a trainer's admin into
    the fleet documents: its metrics re-serve under its backend label,
    its statusz/eventz join the federated docs."""
    from paddle_tpu.serving.wire.fleet import FleetBalancer

    prog, startup, loss, _ = _fc_model(seed=19)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.train_from_dataset(program=prog, dataset=_feeds(n=4),
                               scope=scope, fetch_list=[loss],
                               phase_ledger=True, watchdog=True)
    addr = exe.start_train_admin(port=0)
    fleet = FleetBalancer([addr], health_interval_s=None)
    try:
        fleet.add_scrape_target("trainer-0", addr)
        fleet.scrape_once()
        fed = fleet.federated_metrics()
        rows = [l for l in fed.splitlines()
                if l.startswith("train_phase_seconds_total")
                and 'backend="trainer-0"' in l]
        assert rows, "trainer metrics not re-served under its label"
        assert any('phase="device_execute"' in l for l in rows)
        statusz = fleet.federated_statusz()
        assert "trainer-0" in statusz["backends"]
        assert statusz["backends"]["trainer-0"]["statusz"]["role"] == (
            "trainer")
        fleet.federated_eventz()  # shape-only: must not raise
    finally:
        fleet.stop()
        exe.stop_train_admin()


# ---------------------------------------------------------------------------
# fsdp-2 + async checkpointing acceptance
# ---------------------------------------------------------------------------
def test_fsdp2_async_checkpoint_epoch_books_balance(tmp_path):
    """The ISSUE acceptance cut: an fsdp-2 sharded training epoch with
    async checkpointing, ledger armed — books balance within 1%, the
    checkpoint phase records the commit join, and a resumed second
    epoch attributes its restore to restore_fallback and reports the
    resume in /trainz."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu import sharding
    from paddle_tpu.sharding.rules import PartitionRules
    from paddle_tpu.sharding.train import retire_state_bytes

    dim = 8
    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 21
    with framework.program_guard(prog, startup):
        x = fluid.layers.data("x", [dim])
        y = fluid.layers.data("y", [1])
        h = fluid.layers.fc(x, 4, act="relu")
        pred = fluid.layers.fc(h, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        opt = fluid.optimizer.AdamOptimizer(0.01)
        opt.minimize(loss)
    compiled = sharding.sharded_train_program(
        prog, PartitionRules([(r".", P("fsdp"))], name="trainobs/fsdp"),
        optimizer=opt, mesh_axes={"fsdp": 2})
    ckpt_dir = str(tmp_path / "ckpt")
    feeds = _feeds(dim=dim, batch=4, n=8)
    exe = fluid.Executor(fluid.CPUPlace())
    try:
        scope = fluid.Scope()
        with fluid.scope_guard(scope):
            exe.run(startup)
            exe.train_from_dataset(
                program=compiled, dataset=feeds, scope=scope,
                fetch_list=[loss], phase_ledger=True, watchdog=True,
                checkpoint_dir=ckpt_dir, checkpoint_every=4,
                checkpoint_async=True)
        snap = exe.last_train_ledger.snapshot()
        total = sum(snap["phases"].values())
        assert abs(total - snap["wall_s"]) <= 0.01 * snap["wall_s"] + 1e-6
        assert snap["phases"]["checkpoint"] > 0.0
        assert (snap["checkpoint"]["sync_s"] > 0.0
                or snap["checkpoint"]["commit_s"] > 0.0)
        assert monitor.counter_value("train_checkpoints_total") > 0.0

        # resume: the restore cost is its own phase, not device_execute
        scope2 = fluid.Scope()
        with fluid.scope_guard(scope2):
            exe.run(startup)
            exe.train_from_dataset(
                program=compiled, dataset=feeds, scope=scope2,
                fetch_list=[loss], phase_ledger=True,
                resume_from=ckpt_dir)
        snap2 = exe.last_train_ledger.snapshot()
        assert snap2["phases"]["restore_fallback"] > 0.0
        total2 = sum(snap2["phases"].values())
        assert abs(total2 - snap2["wall_s"]) <= (
            0.01 * snap2["wall_s"] + 1e-6)
        doc = exe.trainz()
        assert doc["checkpoint"]["last_resume_step"] == 8
        assert doc["checkpoint"]["last_restore_path"]
        # the resume event landed in the ring for /eventz
        evs = mon_events.eventz()["events"]
        assert any(e.get("kind") == "train/resume" and e.get("step") == 8
                   for e in evs)
    finally:
        retire_state_bytes()
