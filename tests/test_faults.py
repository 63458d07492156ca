"""Unit tests for the robustness layer (paddle_tpu/faults/): the
fault-injection registry's arming/determinism/modes, RetryPolicy's
backoff/jitter/deadline-budget semantics, the relaunch Supervisor's
crash-loop give-up, the atomic TrainCheckpoint layout, the PS table
assign/restore path, and the socket-hygiene contracts of the background
PS helper threads.  End-to-end failure drills live in tests/chaos/.
"""
import os
import subprocess
import sys
import time

import numpy as np
import pytest

from conftest import WAIT

import paddle_tpu as fluid
from paddle_tpu import faults, framework, monitor
from paddle_tpu.faults.retry import RetryPolicy


@pytest.fixture(autouse=True)
def _always_disarm():
    yield
    faults.disarm()


# ---------------------------------------------------------------------------
# registry semantics
# ---------------------------------------------------------------------------
def test_disarmed_by_default_and_armed_scope():
    assert faults.active is None
    with faults.armed("executor.run=delay:0.0") as plan:
        assert faults.active is plan
    assert faults.active is None


def test_unknown_point_never_fires():
    with faults.armed("wire.send=error:RuntimeError"):
        assert faults.active.faultpoint("no.such.point") is None


def test_after_times_and_heal():
    """drop-N-then-heal: skip `after` hits, fire `times`, then pass."""
    with faults.armed("ps.pull=error:ConnectionError,after=2,times=2") as p:
        fp = faults.active.faultpoint
        fp("ps.pull")
        fp("ps.pull")  # the first two hits pass (after=2)
        for _ in range(2):
            with pytest.raises(ConnectionError):
                fp("ps.pull")
        fp("ps.pull")  # healed
        assert p.triggers() == {"ps.pull": 2}


def test_seeded_probability_is_deterministic():
    def run(seed):
        plan = faults.arm("a.b=error:RuntimeError,prob=0.5,times=100",
                         seed=seed)
        fired = []
        for _ in range(40):
            try:
                plan.faultpoint("a.b")
                fired.append(0)
            except RuntimeError:
                fired.append(1)
        faults.disarm()
        return fired

    a, b, c = run(7), run(7), run(8)
    assert a == b          # same seed -> identical decisions
    assert a != c          # different seed -> different stream
    assert 0 < sum(a) < 40  # actually probabilistic


def test_corrupt_action_mangles_bytes():
    with faults.armed("wire.send=corrupt,times=1"):
        act = faults.active.faultpoint("wire.send")
        data = bytes(range(256)) * 4
        assert act.corrupt(data) != data
        assert faults.active.faultpoint("wire.send") is None  # healed


def test_delay_mode_sleeps():
    with faults.armed("x.y=delay:0.05,times=1"):
        t0 = time.perf_counter()
        faults.active.faultpoint("x.y")
        assert time.perf_counter() - t0 >= 0.045


def test_kill_mode_kills_ctx_pid():
    proc = subprocess.Popen([sys.executable, "-c",
                             "import time; time.sleep(60)"])
    try:
        with faults.armed("fleet.dispatch=kill,times=1"):
            faults.active.faultpoint("fleet.dispatch", pid=proc.pid)
        assert proc.wait(timeout=10) == -9  # SIGKILL
    finally:
        if proc.poll() is None:
            proc.kill()


def test_env_arming_and_seed():
    plan = faults.arm_from_env(
        {"PADDLE_TPU_FAULTS":
             "wire.send=corrupt,times=1; ps.push=delay:0.001 ;seed=42"})
    assert plan is not None and plan.seed == 42
    assert plan.points == ["ps.push", "wire.send"]
    assert faults.arm_from_env({}) is None


def test_bad_specs_are_loud():
    with pytest.raises(ValueError):
        faults.parse_plan("BadName=error")
    with pytest.raises(ValueError):
        faults.parse_plan("a.b=explode")
    with pytest.raises(ValueError):
        faults.parse_plan("a.b=error:NoSuchError")
    with pytest.raises(ValueError):
        faults.parse_plan("a.b=corrupt:arg")
    with pytest.raises(ValueError):
        faults.parse_plan("a.b=delay:0.1,wat=1")


def test_injection_counter_in_registry():
    c0 = monitor.counter_value("faults_injected_total", point="m.n")
    with faults.armed("m.n=delay:0.0,times=3"):
        for _ in range(5):
            faults.active.faultpoint("m.n")
    assert monitor.counter_value(
        "faults_injected_total", point="m.n") - c0 == 3


# ---------------------------------------------------------------------------
# RetryPolicy
# ---------------------------------------------------------------------------
def test_backoff_delays_grow_and_cap():
    sleeps = []
    p = RetryPolicy(max_attempts=6, base_delay_s=0.1, multiplier=2.0,
                    max_delay_s=0.3, jitter=False, sleep=sleeps.append)
    b = p.budget(op="t")
    while b.backoff():
        pass
    assert sleeps == [0.1, 0.2, 0.3, 0.3, 0.3]  # exp growth, capped


def test_full_jitter_bounds_and_determinism():
    def delays(seed):
        out = []
        p = RetryPolicy(max_attempts=8, base_delay_s=0.2, multiplier=2.0,
                        max_delay_s=1.0, seed=seed, sleep=out.append)
        b = p.budget(op="t")
        while b.backoff():
            pass
        return out

    a, b_, c = delays(3), delays(3), delays(4)
    assert a == b_ and a != c
    for i, d in enumerate(a):
        assert 0.0 <= d <= min(1.0, 0.2 * 2 ** i)


def test_deadline_debits_the_budget():
    """A retry whose backoff cannot finish before the deadline is
    refused — the budget never sleeps the caller past its deadline."""
    sleeps = []
    p = RetryPolicy(max_attempts=100, base_delay_s=10.0, jitter=False,
                    sleep=sleeps.append)
    b = p.budget(deadline=time.monotonic() + 0.2, op="t")
    assert not b.backoff()   # 10s backoff >> 0.2s remaining
    assert sleeps == []
    # and with room, the retry is granted
    p2 = RetryPolicy(max_attempts=2, base_delay_s=0.001, jitter=False,
                     sleep=sleeps.append)
    b2 = p2.budget(deadline=time.monotonic() + 5.0, op="t")
    assert b2.backoff() and not b2.backoff()


def test_retry_counter_and_call_helper():
    c0 = monitor.counter_value("retry_attempts_total", op="unit.test")
    calls = [0]

    def flaky():
        calls[0] += 1
        if calls[0] < 3:
            raise ConnectionError("blip")
        return "ok"

    p = RetryPolicy(max_attempts=4, base_delay_s=0.0, jitter=False,
                    sleep=lambda s: None)
    assert p.budget(op="unit.test").call(
        flaky, retryable=(ConnectionError,)) == "ok"
    assert monitor.counter_value(
        "retry_attempts_total", op="unit.test") - c0 == 2
    # non-retryable errors pass straight through
    with pytest.raises(ValueError):
        p.budget(op="unit.test").call(
            lambda: (_ for _ in ()).throw(ValueError("no")),
            retryable=(ConnectionError,))


# ---------------------------------------------------------------------------
# Supervisor: crash-looping child
# ---------------------------------------------------------------------------
def test_supervisor_gives_up_typed_with_capped_backoff(monkeypatch):
    from paddle_tpu.serving.errors import RelaunchFailed
    from paddle_tpu.serving.wire import launch as launch_mod

    boots = [0]

    def always_dies(handle, port=0):
        boots[0] += 1
        raise RuntimeError("child died before READY (boot %d)" % boots[0])

    monkeypatch.setattr(launch_mod, "relaunch", always_dies)
    sleeps = []
    sup = launch_mod.Supervisor(
        max_attempts=4, base_delay_s=0.1, multiplier=10.0, max_delay_s=0.5,
        fleet="crashloop", sleep=sleeps.append)
    r0 = monitor.counter_value(
        "wire_backend_relaunches_total", fleet="crashloop")

    class H:  # the only attrs revive touches besides relaunch()
        name = "victim"

    with pytest.raises(RelaunchFailed, match="after 4 relaunch"):
        sup.revive(H())
    assert boots[0] == 4  # every budgeted attempt was used
    # the counter matches the attempts exactly
    assert monitor.counter_value(
        "wire_backend_relaunches_total", fleet="crashloop") - r0 == 4
    # backoff capped at max_delay_s (jittered below the cap, never above)
    assert len(sleeps) == 3 and all(0 <= s <= 0.5 for s in sleeps)


def test_supervisor_succeeds_midway(monkeypatch):
    from paddle_tpu.serving.wire import launch as launch_mod

    calls = [0]

    def flaky(handle, port=0):
        calls[0] += 1
        if calls[0] < 3:
            raise RuntimeError("boot flop")
        return "newhandle"

    monkeypatch.setattr(launch_mod, "relaunch", flaky)
    sup = launch_mod.Supervisor(max_attempts=5, base_delay_s=0.0,
                                fleet="flaky", sleep=lambda s: None)
    assert sup.revive(object()) == "newhandle"
    assert calls[0] == 3


# ---------------------------------------------------------------------------
# health-probe jitter (thundering-herd satellite)
# ---------------------------------------------------------------------------
def test_probe_jitter_spreads_backend_clocks():
    import random

    from paddle_tpu.serving.wire.fleet import _probe_jitter

    rng = random.Random(5)
    delays = [_probe_jitter(1.0, rng) for _ in range(32)]
    assert all(0.85 <= d <= 1.15 for d in delays)
    assert len(set(round(d, 6) for d in delays)) > 16  # actually spread


# ---------------------------------------------------------------------------
# TrainCheckpoint: atomic layout + roundtrip
# ---------------------------------------------------------------------------
def _tiny_model(seed=3):
    from paddle_tpu import unique_name

    with unique_name.guard():
        prog, startup = framework.Program(), framework.Program()
        prog.random_seed = startup.random_seed = seed
        with framework.program_guard(prog, startup):
            x = fluid.layers.data("x", [4])
            y = fluid.layers.data("y", [1])
            pred = fluid.layers.fc(x, 1)
            loss = fluid.layers.mean(
                fluid.layers.square_error_cost(pred, y))
            fluid.optimizer.SGDOptimizer(0.1).minimize(loss)
        return prog, startup, loss


def test_checkpoint_atomic_roundtrip(tmp_path):
    from paddle_tpu.faults.checkpoint import TrainCheckpoint

    prog, startup, loss = _tiny_model()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    run_dir = str(tmp_path / "run")
    ck = TrainCheckpoint(run_dir, every_n_steps=5, keep=2)
    assert ck.latest() is None and ck.restore(prog, scope) is None
    assert ck.should_save(5) and not ck.should_save(4)

    rng = np.random.RandomState(0)
    feed = {"x": rng.rand(8, 4).astype("float32"),
            "y": rng.rand(8, 1).astype("float32")}
    c0 = monitor.counter_value("train_checkpoints_total")
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.run(prog, feed=feed, fetch_list=[loss])
        # a stale tmp dir from a "crashed" previous attempt is cleaned
        os.makedirs(os.path.join(run_dir, ".tmp-ckpt-000005"))
        ck.save(prog, scope, step=5)
        saved = {v.name: np.asarray(scope.get(v.name))
                 for v in prog.all_parameters()}
        exe.run(prog, feed=feed, fetch_list=[loss])  # mutate past it
    assert monitor.counter_value("train_checkpoints_total") - c0 == 1
    # committed layout, no tmp residue, LATEST points at it
    assert sorted(d for d in os.listdir(run_dir)
                  if not d.startswith(".")) == ["LATEST", "ckpt-000005"]
    assert not [d for d in os.listdir(run_dir) if d.startswith(".tmp")]

    # restore into a FRESH scope: params match the step-5 snapshot
    scope2 = fluid.Scope()
    cursor = ck.restore(prog, scope2)
    assert cursor == {"step": 5, "epoch": 0}
    for name, val in saved.items():
        np.testing.assert_array_equal(np.asarray(scope2.get(name)), val)


def test_checkpoint_prunes_but_keeps_latest(tmp_path):
    from paddle_tpu.faults.checkpoint import TrainCheckpoint

    prog, startup, _ = _tiny_model()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    ck = TrainCheckpoint(str(tmp_path), keep=2)
    with fluid.scope_guard(scope):
        exe.run(startup)
        for step in (5, 10, 15, 20):
            ck.save(prog, scope, step=step)
    kept = sorted(d for d in os.listdir(str(tmp_path))
                  if d.startswith("ckpt-"))
    assert kept == ["ckpt-000015", "ckpt-000020"]
    assert ck.latest().endswith("ckpt-000020")


def test_checkpoint_prune_orders_numerically_past_padding(tmp_path):
    """Steps past the %06d padding must prune by STEP, not by string —
    lexicographic order would delete a newer checkpoint as 'oldest'."""
    from paddle_tpu.faults.checkpoint import TrainCheckpoint

    prog, startup, _ = _tiny_model()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    ck = TrainCheckpoint(str(tmp_path), keep=2)
    with fluid.scope_guard(scope):
        exe.run(startup)
        for step in (500000, 1000000, 1500000):
            ck.save(prog, scope, step=step)
    kept = sorted(d for d in os.listdir(str(tmp_path))
                  if d.startswith("ckpt-"))
    assert kept == ["ckpt-1000000", "ckpt-1500000"]
    assert ck.latest().endswith("ckpt-1500000")


def test_dangling_latest_falls_back_to_remaining_checkpoints(tmp_path):
    """Regression (ISSUE 15 small fix): a LATEST pointer naming a
    pruned/missing checkpoint must fall back typed+counted through the
    remaining complete checkpoints — not fail on the dangling pointer,
    and not silently fresh-start while committed state exists."""
    from paddle_tpu.faults.checkpoint import TrainCheckpoint

    prog, startup, loss = _tiny_model()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    ck = TrainCheckpoint(str(tmp_path), keep=3)
    with fluid.scope_guard(scope):
        exe.run(startup)
        for step in (5, 10):
            ck.save(prog, scope, step=step)
    # simulate a lost/pruned pointer target
    import shutil

    shutil.rmtree(str(tmp_path / "ckpt-000010"))
    assert ck.latest() is None  # the pointer dangles...
    f0 = monitor.counter_value("train_checkpoint_fallback_total")
    r0 = monitor.counter_value("train_checkpoint_restore_total")
    scope2 = fluid.Scope()
    cursor = ck.restore(prog, scope2)  # ...but restore finds ckpt-000005
    assert cursor["step"] == 5
    assert ck.last_restore_path.endswith("ckpt-000005")
    assert ck.last_restore_fallbacks == 1
    assert monitor.counter_value("train_checkpoint_fallback_total") == f0 + 1
    assert monitor.counter_value("train_checkpoint_restore_total") == r0 + 1

    # with EVERY checkpoint dir gone but the pointer still there, the
    # run's state was lost — typed, never a silent step-0 fresh start
    from paddle_tpu.faults.checkpoint import CheckpointCorruptionError

    shutil.rmtree(str(tmp_path / "ckpt-000005"))
    with pytest.raises(CheckpointCorruptionError, match="no committed"):
        ck.restore(prog, fluid.Scope())
    # a genuinely fresh dir (no pointer, no checkpoints) stays None
    os.remove(str(tmp_path / "LATEST"))
    assert ck.restore(prog, fluid.Scope()) is None


def test_integrity_manifest_covers_every_file_and_detects_tamper(
        tmp_path):
    """Every committed checkpoint carries integrity.json listing every
    other file with size + sha256; verify_checkpoint_dir passes on a
    clean dir and types a flipped byte, a truncation, a deleted file,
    and an unlisted extra file as CheckpointCorruptionError."""
    import json as _json

    from paddle_tpu.faults.checkpoint import (
        CheckpointCorruptionError,
        TrainCheckpoint,
        verify_checkpoint_dir,
    )

    prog, startup, _ = _tiny_model()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    ck = TrainCheckpoint(str(tmp_path))
    with fluid.scope_guard(scope):
        exe.run(startup)
        path = ck.save(prog, scope, step=5)
    with open(os.path.join(path, "integrity.json")) as f:
        doc = _json.load(f)
    on_disk = set()
    for dirpath, _, fns in os.walk(path):
        for fn in fns:
            rel = os.path.relpath(os.path.join(dirpath, fn), path)
            if rel != "integrity.json":
                on_disk.add(rel.replace(os.sep, "/"))
    assert set(doc["files"]) == on_disk and on_disk  # complete, both ways
    verify_checkpoint_dir(path)  # clean: no raise
    # the bytes gauge published the checkpoint's size at commit
    total = sum(e["bytes"] for e in doc["files"].values())
    got = monitor.counter_value("train_checkpoint_bytes")
    assert got >= total  # + integrity.json itself

    # flipped byte
    victim = os.path.join(path, "cursor.json")
    raw = open(victim, "rb").read()
    with open(victim, "wb") as f:
        f.write(bytes([raw[0] ^ 0xFF]) + raw[1:])
    with pytest.raises(CheckpointCorruptionError, match="hash"):
        verify_checkpoint_dir(path)
    with open(victim, "wb") as f:
        f.write(raw)  # heal
    # truncation
    with open(victim, "wb") as f:
        f.write(raw[:-1])
    with pytest.raises(CheckpointCorruptionError, match="bytes"):
        verify_checkpoint_dir(path)
    with open(victim, "wb") as f:
        f.write(raw)
    # deleted file
    os.rename(victim, victim + ".bak")
    with pytest.raises(CheckpointCorruptionError, match="missing"):
        verify_checkpoint_dir(path)
    os.rename(victim + ".bak", victim)
    # unlisted extra file (post-commit tamper)
    extra = os.path.join(path, "params", "smuggled.npy")
    with open(extra, "w") as f:
        f.write("x")
    with pytest.raises(CheckpointCorruptionError, match="not in"):
        verify_checkpoint_dir(path)
    os.remove(extra)
    verify_checkpoint_dir(path)

    # a STRUCTURALLY malformed manifest (valid JSON, wrong shape) is
    # the typed corruption too — an untyped KeyError/TypeError here
    # would defeat the fallback chain
    integ = os.path.join(path, "integrity.json")
    good = open(integ).read()
    for bad in ('{"algo": "sha256"}',
                '{"algo": "sha256", "files": "nope"}',
                '{"algo": "sha256", "files": {"cursor.json": {}}}',
                '{"algo": "sha256", "files": {"cursor.json": 3}}'):
        with open(integ, "w") as f:
            f.write(bad)
        with pytest.raises(CheckpointCorruptionError, match="malformed"):
            verify_checkpoint_dir(path)
    with open(integ, "w") as f:
        f.write(good)
    verify_checkpoint_dir(path)

    # pre-integrity checkpoints (no manifest) pass unverified
    os.remove(integ)
    verify_checkpoint_dir(path)
    cursor = ck.restore(prog, fluid.Scope())
    assert cursor["step"] == 5


def test_pre_integrity_load_failure_falls_back_typed(tmp_path):
    """A checkpoint from before the integrity manifest existed has
    nothing for the hash gate to check — but a damaged file in it must
    STILL engage the fallback chain at load time (typed + counted),
    never an untyped np.load crash over a half-restored scope."""
    from paddle_tpu.faults.checkpoint import TrainCheckpoint

    prog, startup, loss = _tiny_model()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    ck = TrainCheckpoint(str(tmp_path), keep=3)
    with fluid.scope_guard(scope):
        exe.run(startup)
        ck.save(prog, scope, step=5)
        path10 = ck.save(prog, scope, step=10)
    # make ckpt-000010 look pre-integrity, then truncate a params file
    os.remove(os.path.join(path10, "integrity.json"))
    victim = next(os.path.join(path10, "params", f)
                  for f in sorted(os.listdir(os.path.join(path10, "params")))
                  if f.endswith(".npy"))
    with open(victim, "r+b") as f:
        f.truncate(10)
    c0 = monitor.counter_value("train_checkpoint_corruption_total")
    scope2 = fluid.Scope()
    cursor = ck.restore(prog, scope2)
    assert cursor["step"] == 5  # fell back past the damaged newest
    assert ck.last_restore_fallbacks == 1
    assert monitor.counter_value(
        "train_checkpoint_corruption_total") == c0 + 1


def test_executor_restore_bookkeeping_defaults_and_resets(tmp_path):
    """A fresh Executor answers the restore-bookkeeping reads before
    any epoch ran, and a plain (non-resume) run RESETS them — it must
    not keep reporting a previous run's restore/fallbacks."""
    exe = fluid.Executor(fluid.CPUPlace())
    assert exe.last_resume_step is None
    assert exe.last_restore_path is None
    assert exe.last_restore_fallbacks == 0
    assert exe.last_restore_stats is None

    prog, startup, loss = _tiny_model()
    run_dir = str(tmp_path / "run")
    rng = np.random.RandomState(0)
    feeds = [{"x": rng.rand(8, 4).astype("float32"),
              "y": rng.rand(8, 1).astype("float32")} for _ in range(2)]
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.train_from_dataset(program=prog, dataset=feeds, scope=scope,
                               fetch_list=[loss], checkpoint_dir=run_dir,
                               checkpoint_every=2)
        exe.train_from_dataset(program=prog, dataset=feeds, scope=scope,
                               fetch_list=[loss], resume_from=run_dir)
        assert exe.last_resume_step == 2
        assert exe.last_restore_path.endswith("ckpt-000002")
        # a plain run afterwards clears the stale restore report
        exe.train_from_dataset(program=prog, dataset=feeds, scope=scope,
                               fetch_list=[loss])
        assert exe.last_resume_step is None
        assert exe.last_restore_path is None
        assert exe.last_restore_fallbacks == 0


def test_restore_fault_point_arms_the_restore_path(tmp_path):
    """checkpoint.restore mirrors checkpoint.commit on the read side:
    an armed error fires out of restore() typed; healed, the same
    restore succeeds."""
    from paddle_tpu.faults.checkpoint import TrainCheckpoint

    prog, startup, _ = _tiny_model()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    ck = TrainCheckpoint(str(tmp_path))
    with fluid.scope_guard(scope):
        exe.run(startup)
        ck.save(prog, scope, step=5)
    with faults.armed("checkpoint.restore=error:RuntimeError,times=1"):
        with pytest.raises(RuntimeError, match="injected fault"):
            ck.restore(prog, fluid.Scope())
        # healed after times=1: the very next restore works
        assert ck.restore(prog, fluid.Scope())["step"] == 5


def test_checkpoint_ps_tables_roundtrip(tmp_path):
    """PS rows restore by VALUE through the assign op — not replayed
    through the optimizer — into a fresh server."""
    from paddle_tpu.distributed.ps import ParameterServer, PSClient
    from paddle_tpu.faults.checkpoint import TrainCheckpoint

    prog, startup, _ = _tiny_model()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    s1 = ParameterServer().start()
    s2 = ParameterServer().start()
    cli = PSClient([s1.endpoint, s2.endpoint])
    try:
        cli.create_table("emb", 4, initializer="zeros")
        ids = np.arange(23, dtype=np.int64)
        cli.push_sparse("emb", ids, -np.tile(
            np.arange(4, dtype=np.float32) + 1, (23, 1)))  # rows = lr*(i+1)
        want = cli.pull_sparse("emb", ids)
        ck = TrainCheckpoint(str(tmp_path))
        with fluid.scope_guard(scope):
            exe.run(startup)
            path = ck.save(prog, scope, step=7, ps_client=cli)
        assert os.path.isdir(os.path.join(path, "ps"))
    finally:
        cli.close()
        s1.stop()
        s2.stop()

    # fresh servers, fresh client: restore and compare rows exactly
    s3 = ParameterServer().start()
    s4 = ParameterServer().start()
    cli2 = PSClient([s3.endpoint, s4.endpoint])
    try:
        scope2 = fluid.Scope()
        cursor = ck.restore(prog, scope2, ps_client=cli2)
        assert cursor["step"] == 7
        np.testing.assert_array_equal(
            cli2.pull_sparse("emb", ids), want)
    finally:
        cli2.close()
        s3.stop()
        s4.stop()


def test_checkpoint_restores_adagrad_moments_exactly(tmp_path):
    """Optimizer-moment checkpointing: after restore, the SAME gradient
    applied to the original and the resumed table lands the SAME rows —
    the adagrad accumulators were restored by value, so per-row step
    sizes continue instead of restarting at their largest (which would
    diverge the loss trajectory on resume)."""
    from paddle_tpu.distributed.ps import ParameterServer, PSClient
    from paddle_tpu.faults.checkpoint import TrainCheckpoint

    prog, startup, _ = _tiny_model()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    ids = np.arange(17, dtype=np.int64)
    rng = np.random.RandomState(3)
    g1, g2, g3 = (rng.uniform(-1, 1, (17, 4)).astype(np.float32)
                  for _ in range(3))
    s1 = ParameterServer().start()
    s2 = ParameterServer().start()
    cli = PSClient([s1.endpoint, s2.endpoint])
    ck = TrainCheckpoint(str(tmp_path))
    try:
        cli.create_table("emb", 4, initializer="zeros",
                         optimizer="adagrad", lr=0.1)
        cli.push_sparse("emb", ids, g1)
        cli.push_sparse("emb", ids, g2)  # moments now hold g1^2 + g2^2
        want = cli.pull_sparse("emb", ids)
        with fluid.scope_guard(scope):
            exe.run(startup)
            path = ck.save(prog, scope, step=5, ps_client=cli)
        # the moment dump is really on disk, flagged in the manifest
        assert os.path.exists(os.path.join(path, "ps", "t000_moments.npy"))
        cli.push_sparse("emb", ids, g3)  # the original run continues
        want_after = cli.pull_sparse("emb", ids)
    finally:
        cli.close()
        s1.stop()
        s2.stop()

    s3 = ParameterServer().start()
    s4 = ParameterServer().start()
    cli2 = PSClient([s3.endpoint, s4.endpoint])
    try:
        # the resumed run binds its tables first (optimizer config comes
        # from the program binding, not the checkpoint)
        cli2.create_table("emb", 4, initializer="zeros",
                          optimizer="adagrad", lr=0.1)
        scope2 = fluid.Scope()
        ck.restore(prog, scope2, ps_client=cli2)
        np.testing.assert_array_equal(cli2.pull_sparse("emb", ids), want)
        # the SAME next gradient must produce the SAME next rows:
        # bitwise, because the accumulators resumed by value
        cli2.push_sparse("emb", ids, g3)
        np.testing.assert_array_equal(
            cli2.pull_sparse("emb", ids), want_after)
    finally:
        cli2.close()
        s3.stop()
        s4.stop()


def test_checkpoint_with_ps_tables_requires_client(tmp_path):
    from paddle_tpu.distributed.ps import ParameterServer, PSClient
    from paddle_tpu.faults.checkpoint import TrainCheckpoint

    prog, startup, _ = _tiny_model()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    srv = ParameterServer().start()
    cli = PSClient([srv.endpoint])
    try:
        cli.create_table("t", 2)
        cli.push_sparse("t", np.array([1]), np.ones((1, 2), np.float32))
        ck = TrainCheckpoint(str(tmp_path))
        with fluid.scope_guard(scope):
            exe.run(startup)
            ck.save(prog, scope, step=1, ps_client=cli)
        with pytest.raises(ValueError, match="ps_client"):
            ck.restore(prog, fluid.Scope())
    finally:
        cli.close()
        srv.stop()


# ---------------------------------------------------------------------------
# PS helper-thread socket hygiene (leak-check satellites)
# ---------------------------------------------------------------------------
def test_executor_pull_thread_closes_client_on_error():
    """The overlapped dense-PS pull thread must close its dedicated
    PSClient's sockets on every exit path — forced via the ps.pull
    fault point (no server needed: the fault fires pre-socket)."""
    exe = fluid.Executor(fluid.CPUPlace())
    ctx = {"endpoints": ["127.0.0.1:1"]}
    with faults.armed("ps.pull=error:ConnectionError"):
        exe._dense_ps_spawn_pull(ctx, ["w"])
        with pytest.raises(ConnectionError):
            exe._dense_ps_join_pending(ctx, fluid.Scope())
    # the erroring client was closed and dropped: a later spawn redials
    assert "_pull_client" not in ctx
    # retries were granted (and each one closed the previous client)
    assert monitor.counter_value("retry_attempts_total", op="ps.pull") >= 3


def test_communicator_send_thread_owns_and_closes_its_client():
    from paddle_tpu.distributed.communicator import Communicator
    from paddle_tpu.distributed.ps import ParameterServer, PSClient

    srv = ParameterServer().start()
    cli = PSClient([srv.endpoint])
    try:
        cli.create_table("g", 3)
        comm = Communicator(cli, max_retries=2).start()
        comm.push("g", np.array([4, 4, 9]), np.ones((3, 3), np.float32))
        comm.flush()
        comm.stop()
        # the send thread used its OWN client and closed it on exit
        assert comm._send_client is not cli
        assert comm._send_client._socks == [None]
        # the caller's client is untouched and still usable
        rows = cli.pull_sparse("g", np.array([4, 9]))
        assert rows.shape == (2, 3)
    finally:
        cli.close()
        srv.stop()


# ---------------------------------------------------------------------------
# async background checkpointing
# ---------------------------------------------------------------------------
def test_checkpoint_save_async_hides_write_cost(tmp_path):
    """save_async returns before the commit happens (the write stalls
    inside an injected checkpoint.commit delay) and wait() delivers the
    committed path; the layout is byte-identical to a sync save."""
    from paddle_tpu.faults.checkpoint import TrainCheckpoint

    prog, startup, loss = _tiny_model()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    ck = TrainCheckpoint(str(tmp_path), keep=2)
    with fluid.scope_guard(scope):
        exe.run(startup)
        with faults.armed("checkpoint.commit=delay:0.4"):
            t0 = time.perf_counter()
            ck.save_async(prog, scope, step=5)
            returned_in = time.perf_counter() - t0
            assert returned_in < 0.3, returned_in  # write cost hidden
            assert ck.in_flight
            assert ck.latest() is None  # not committed yet
            path = ck.wait(WAIT)
        assert path.endswith("ckpt-000005")
        assert ck.latest() == path
        scope2 = fluid.Scope()
        assert ck.restore(prog, scope2) == {"step": 5, "epoch": 0}
        for v in prog.all_parameters():
            np.testing.assert_array_equal(
                np.asarray(scope2.get(v.name)),
                np.asarray(scope.get(v.name)))


def test_checkpoint_async_snapshot_is_copy_on_write(tmp_path):
    """Values are captured AT save_async time: training that mutates
    the live scope while the background writer is still serializing
    must not leak into the checkpoint."""
    from paddle_tpu.faults.checkpoint import TrainCheckpoint

    prog, startup, loss = _tiny_model()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    ck = TrainCheckpoint(str(tmp_path))
    rng = np.random.RandomState(1)
    feed = {"x": rng.rand(8, 4).astype("float32"),
            "y": rng.rand(8, 1).astype("float32")}
    with fluid.scope_guard(scope):
        exe.run(startup)
        at_snapshot = {v.name: np.array(np.asarray(scope.get(v.name)))
                       for v in prog.all_parameters()}
        with faults.armed("checkpoint.commit=delay:0.3"):
            ck.save_async(prog, scope, step=1)
            # mutate the live scope while the writer is mid-save
            exe.run(prog, feed=feed, fetch_list=[loss])
            ck.wait(WAIT)
    scope2 = fluid.Scope()
    ck.restore(prog, scope2)
    for name, val in at_snapshot.items():
        np.testing.assert_array_equal(np.asarray(scope2.get(name)), val)


def test_checkpoint_async_write_error_reraises_at_wait(tmp_path):
    from paddle_tpu.faults.checkpoint import TrainCheckpoint

    prog, startup, _ = _tiny_model()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    ck = TrainCheckpoint(str(tmp_path))
    with fluid.scope_guard(scope):
        exe.run(startup)
        with faults.armed("checkpoint.commit=error:OSError"):
            ck.save_async(prog, scope, step=1)
            with pytest.raises(OSError):
                ck.wait(WAIT)
        # the failed attempt committed nothing; a clean retry succeeds
        assert ck.latest() is None
        ck.save_async(prog, scope, step=1)
        assert ck.wait(WAIT).endswith("ckpt-000001")


def test_checkpoint_async_serializes_with_next_save(tmp_path):
    """A second save (sync or async) joins the in-flight writer first:
    commits land in order, LATEST ends at the newest step."""
    from paddle_tpu.faults.checkpoint import TrainCheckpoint

    prog, startup, _ = _tiny_model()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    ck = TrainCheckpoint(str(tmp_path), keep=3)
    with fluid.scope_guard(scope):
        exe.run(startup)
        with faults.armed("checkpoint.commit=delay:0.2,times=1"):
            ck.save_async(prog, scope, step=1)
            ck.save_async(prog, scope, step=2)  # joins step-1 first
            ck.wait(WAIT)
    names = sorted(d for d in os.listdir(str(tmp_path))
                   if d.startswith("ckpt-"))
    assert names == ["ckpt-000001", "ckpt-000002"]
    assert ck.latest().endswith("ckpt-000002")


def test_train_from_dataset_async_checkpoint_resumes_exact(tmp_path):
    """checkpoint_async=True through the executor: same commits, same
    resume semantics as the sync path (loss-exact against a golden
    uninterrupted run is covered by the chaos drill; here the cursor
    and params roundtrip)."""
    prog, startup, loss = _tiny_model(seed=11)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(2)

    def batches(n):
        for i in range(n):
            r = np.random.RandomState(100 + i)
            yield {"x": r.rand(8, 4).astype("float32"),
                   "y": r.rand(8, 1).astype("float32")}

    run_dir = str(tmp_path / "run")
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.train_from_dataset(
            program=prog, dataset=batches(7), scope=scope,
            fetch_list=[loss], checkpoint_dir=run_dir,
            checkpoint_every=3, checkpoint_async=True)
    from paddle_tpu.faults.checkpoint import TrainCheckpoint

    ck = TrainCheckpoint(run_dir)
    assert not ck.in_flight  # the epoch joined the tail save
    assert ck.latest().endswith("ckpt-000006")
    scope2 = fluid.Scope()
    assert ck.restore(prog, scope2)["step"] == 6
