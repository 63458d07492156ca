"""Sharded FSDP/TP training through the rules surface (ISSUE 12
acceptance):

* fsdp-2 AND tp-2 training parity vs the replicated trainer — per-step
  loss within rtol 2e-4 over >= 10 steps on the in-tree transformer LM,
  with Adam moments deriving their placement from their param's matched
  rule (``paddle_tpu.sharding.train``),
* per-device param+moment bytes <= 0.6x replicated, and ZERO recompiles
  after warmup (jit-cache ground truth) — sharded optimizer state stays
  sharded across steps via the pinned out shardings,
* shard-wise checkpoints: saving never gathers a full tensor to host
  (per-shard file shapes prove it), resume is loss-exact, resuming on a
  DIFFERENT mesh shape is a typed ``CheckpointMeshMismatchError``,
* the train→export→serve round-trip: ``save_inference_model`` accepts
  the TRAINING layout, and the trained sharded model serves behind
  ``InferenceServer`` with zero recompiles,
* the ``sharding_train_state_bytes{kind}`` gauges publish at restage
  and retire on teardown.
"""
import json
import os

import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import framework, models, monitor, serving, sharding
from paddle_tpu.faults.checkpoint import (
    CheckpointMeshMismatchError,
    TrainCheckpoint,
)
from paddle_tpu.inference import AnalysisConfig, create_paddle_predictor

SEQ = 16
D_MODEL = 32
VOCAB = 128
BATCH = 4
STEPS = 12  # >= 10 per the acceptance bar


def _build_lm():
    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 23
    with framework.program_guard(prog, startup):
        ids = fluid.layers.data("src_ids", [SEQ], dtype="int64")
        lbl = fluid.layers.data("lbl", [SEQ, 1], dtype="int64")
        loss, logits = models.transformer_lm(
            ids, lbl, vocab_size=VOCAB, d_model=D_MODEL, n_layer=1,
            n_head=4, d_inner=64, seq_len=SEQ, max_pos=64)
        opt = fluid.optimizer.AdamOptimizer(1e-3)
        opt.minimize(loss)
    return {"prog": prog, "startup": startup, "loss": loss,
            "logits": logits, "opt": opt}


def _batches(n, start=0):
    for i in range(start, n):
        rng = np.random.RandomState(500 + i)  # keyed by GLOBAL step
        yield {
            "src_ids": rng.randint(1, VOCAB, (BATCH, SEQ)).astype(np.int64),
            "lbl": rng.randint(0, VOCAB, (BATCH, SEQ, 1)).astype(np.int64),
        }


@pytest.fixture(scope="module")
def lm():
    return _build_lm()


@pytest.fixture(scope="module")
def golden(lm):
    """The replicated trainer's per-step losses — the parity yardstick."""
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(lm["startup"])
        out = exe.train_from_dataset(
            program=lm["prog"], dataset=_batches(STEPS), scope=scope,
            fetch_list=[lm["loss"]])
    return [float(np.asarray(o[0])) for o in out]


def _state_names(lm):
    accs = set(lm["opt"].accumulator_map())
    params = {p.name for p in lm["prog"].global_block().all_parameters()}
    return params, accs


def _per_device_bytes(scope, names):
    from paddle_tpu.sharding.train import per_device_bytes

    return sum(per_device_bytes(scope.get(n)) for n in names)


def _acc_name(lm, param, kind):
    """The accumulator var name for (param, kind) — looked up through
    the optimizer's map, never hard-coded (unique_name suffixes depend
    on how many programs this process built before the fixture)."""
    return next(a for a, (p, k) in lm["opt"].accumulator_map().items()
                if p == param and k == kind)


def _replicated_bytes(lm, names):
    block = lm["prog"].global_block()
    total = 0
    for n in names:
        var = block._find_var_recursive(n)
        total += int(np.prod(var.shape or (1,))) * 4  # float32 state
    return total


def _run_sharded(lm, mode, mesh_axes):
    compiled = sharding.sharded_train_program(
        lm["prog"], sharding.transformer_lm_rules(mode),
        optimizer=lm["opt"], mesh_axes=mesh_axes)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    losses = []
    with fluid.scope_guard(scope):
        exe.run(lm["startup"])
        it = _batches(STEPS)
        # warmup: 2 steps settle the state avals (2 compiles), then the
        # remaining steps must hit the cache — the zero-recompile claim
        for feed in (next(it), next(it)):
            l, = exe.run(compiled, feed=feed, fetch_list=[lm["loss"]])
            losses.append(float(l))
        misses0 = exe.jit_cache_stats()["misses"]
        for feed in it:
            l, = exe.run(compiled, feed=feed, fetch_list=[lm["loss"]])
            losses.append(float(l))
        recompiles = exe.jit_cache_stats()["misses"] - misses0
    return compiled, scope, losses, recompiles


@pytest.mark.parametrize("mode,mesh_axes", [
    ("fsdp", {"fsdp": 2}),
    ("tp", {"tp": 2}),
])
def test_sharded_training_parity(lm, golden, mode, mesh_axes):
    compiled, scope, losses, recompiles = _run_sharded(lm, mode, mesh_axes)
    # per-step loss parity with the replicated trainer over all STEPS
    np.testing.assert_allclose(losses, golden, rtol=2e-4)
    # zero recompiles after warmup — jit-cache ground truth
    assert recompiles == 0

    params, accs = _state_names(lm)
    # every param and moment is mesh-committed (the one layout covers
    # optimizer state too — no accumulator was left on host)
    for n in list(params) + list(accs):
        v = scope.get(n)
        assert len(getattr(v.sharding, "device_set", ())) == 2, n
    # the capacity claim: per-device param+moment bytes <= 0.6x the
    # replicated footprint
    sharded = _per_device_bytes(scope, params | accs)
    replicated = _replicated_bytes(lm, params | accs)
    assert sharded <= 0.6 * replicated, (mode, sharded, replicated)

    # a moment's shard mirrors its param's placement (rule inheritance);
    # accumulator names come from the map — unique_name suffixes depend
    # on what ran earlier in the process
    emb = scope.get("lm_word_emb")
    m1 = scope.get(_acc_name(lm, "lm_word_emb", "moment1"))
    assert (tuple(emb.addressable_shards[0].data.shape)
            == tuple(m1.addressable_shards[0].data.shape))

    # the state-bytes gauges published at restage, by kind
    for kind in ("param", "grad", "moment"):
        assert monitor.counter_value(
            "sharding_train_state_bytes", default=-1.0, kind=kind) > 0
    # moments outweigh params (Adam: two moments + beta pows per param)
    assert monitor.counter_value(
        "sharding_train_state_bytes", kind="moment") > monitor.counter_value(
        "sharding_train_state_bytes", kind="param")


def test_accumulators_require_coverage(lm):
    """No default= escape hatch: an accumulator whose param no rule
    covers is a typed error naming the param — not a silent replicate."""
    from paddle_tpu.sharding.rules import PartitionRules, ShardingRuleError
    from paddle_tpu.sharding.train import train_rules

    base = sharding.transformer_lm_rules("tp")
    doctored = PartitionRules(
        [(p, s) for p, s in base.rules if "head" not in p],
        name="doctored")
    tr = train_rules(doctored, optimizer=lm["opt"])
    acc = _acc_name(lm, "lm_head_w", "moment1")
    with pytest.raises(ShardingRuleError) as ei:
        tr.spec_for(acc, (D_MODEL, VOCAB))
    msg = str(ei.value)
    assert acc in msg and "inherits" in msg and "lm_head_w" in msg


def test_shard_wise_checkpoint_resume_and_teardown(lm, golden, tmp_path):
    """Shard-wise save: per-shard files only (never a gathered full
    tensor), loss-exact resume through train_from_dataset, gauges
    retired on teardown."""
    compiled = sharding.sharded_train_program(
        lm["prog"], sharding.transformer_lm_rules("fsdp"),
        optimizer=lm["opt"], mesh_axes={"fsdp": 2})
    exe = fluid.Executor(fluid.CPUPlace())
    run_dir = str(tmp_path / "run")

    s1 = fluid.Scope()
    with fluid.scope_guard(s1):
        exe.run(lm["startup"])
        out = exe.train_from_dataset(
            program=compiled, dataset=_batches(8), scope=s1,
            fetch_list=[lm["loss"]], checkpoint_dir=run_dir,
            checkpoint_every=4)
    first8 = [float(np.asarray(o[0])) for o in out]
    np.testing.assert_allclose(first8, golden[:8], rtol=2e-4)

    ck = os.path.join(run_dir, "ckpt-000008")
    sdir = os.path.join(ck, "shards")
    assert os.path.isdir(sdir)
    with open(os.path.join(sdir, "manifest.json")) as f:
        man = json.load(f)
    assert man["mesh_axes"] == {"fsdp": 2}
    # per-shard FILE shapes are shard shapes — the on-disk proof no
    # full tensor was gathered: (VOCAB, D) saved as two (VOCAB/2, D)
    for name in ("lm_word_emb",
                 _acc_name(lm, "lm_word_emb", "moment1"),
                 _acc_name(lm, "lm_word_emb", "moment2")):
        ent = man["vars"][name]
        assert ent["shape"] == [VOCAB, D_MODEL]
        assert len(ent["shards"]) == 2
        for doc in ent["shards"]:
            arr = np.load(os.path.join(sdir, doc["file"]))
            assert arr.shape == (VOCAB // 2, D_MODEL), (name, arr.shape)
    # ...and the host-side params dir holds NO entry for sharded vars
    with open(os.path.join(ck, "params", "__manifest__.json")) as f:
        host_names = {e["name"] for e in json.load(f)["vars"]}
    assert "lm_word_emb" not in host_names
    assert not (host_names & set(man["vars"]))

    # resume in a FRESH scope: steps 8..12 must equal the golden tail
    # exactly (moments included — a moment-less restore would diverge)
    s2 = fluid.Scope()
    with fluid.scope_guard(s2):
        exe.run(lm["startup"])
        out = exe.train_from_dataset(
            program=compiled, dataset=_batches(STEPS), scope=s2,
            fetch_list=[lm["loss"]], checkpoint_dir=run_dir,
            checkpoint_every=4, resume_from=run_dir)
        assert exe.last_resume_step == 8
        # the same mesh re-places each shard directly: no exchange
        assert exe.last_restore_stats["exchanged"] == 0
    resumed = [float(np.asarray(o[0])) for o in out]
    assert len(resumed) == STEPS - 8
    np.testing.assert_allclose(resumed, golden[8:], rtol=2e-4)

    # teardown retires the state-bytes series
    from paddle_tpu.sharding.train import retire_state_bytes

    retire_state_bytes()
    assert monitor.counter_value(
        "sharding_train_state_bytes", default=-1.0, kind="param") == -1.0


def _compiled_for(lm, n):
    return sharding.sharded_train_program(
        lm["prog"], sharding.transformer_lm_rules("fsdp"),
        optimizer=lm["opt"], mesh_axes={"fsdp": n})


def test_cross_mesh_restore_chain(lm, golden, tmp_path, monkeypatch):
    """ISSUE 15 acceptance: the fsdp-2 → fsdp-4 → fsdp-2 restore chain
    is loss-exact vs the uninterrupted golden run (asserted per step),
    with no full-tensor host materialization on either side — every
    read out of shards/ is a per-shard file, and the shard-exchange
    host buffer high-water stays below the biggest var's full size."""
    run_dir = str(tmp_path / "run")
    exe = fluid.Executor(fluid.CPUPlace())

    # spy every np.load out of a shards/ dir: the on-disk proof that
    # restore only ever touches per-shard files, never a gathered dump
    shard_reads = []
    orig_load = np.load

    def spy(path, *a, **k):
        arr = orig_load(path, *a, **k)
        p = str(path)
        if os.sep + "shards" + os.sep in p and p.endswith(".npy"):
            shard_reads.append(int(arr.nbytes))
        return arr

    monkeypatch.setattr(np, "load", spy)

    losses = []
    # leg 1: fsdp-2, steps 0..4, checkpoint at 4
    c2 = _compiled_for(lm, 2)
    s1 = fluid.Scope()
    with fluid.scope_guard(s1):
        exe.run(lm["startup"])
        out = exe.train_from_dataset(
            program=c2, dataset=_batches(4), scope=s1,
            fetch_list=[lm["loss"]], checkpoint_dir=run_dir,
            checkpoint_every=4)
    losses += [float(np.asarray(o[0])) for o in out]

    # leg 2: resume the fsdp-2 checkpoint on an fsdp-4 mesh — the
    # shard-exchange path re-slices the saved halves into quarters
    c4 = _compiled_for(lm, 4)
    s2 = fluid.Scope()
    with fluid.scope_guard(s2):
        exe.run(lm["startup"])
        out = exe.train_from_dataset(
            program=c4, dataset=_batches(8), scope=s2,
            fetch_list=[lm["loss"]], checkpoint_dir=run_dir,
            checkpoint_every=4, resume_from=run_dir)
    assert exe.last_resume_step == 4
    stats = exe.last_restore_stats
    assert stats["exchanged"] > 0  # topologies differ: real exchange
    losses += [float(np.asarray(o[0])) for o in out]

    # biggest sharded var is (VOCAB, D) fp32: its full size is the
    # never-materialized bar for both buffers and file reads
    full = VOCAB * D_MODEL * 4
    assert 0 < stats["max_region_bytes"] < full
    assert shard_reads and max(shard_reads) <= full // 2

    # leg 3: resume the fsdp-4 checkpoint back on fsdp-2
    c2b = _compiled_for(lm, 2)
    s3 = fluid.Scope()
    with fluid.scope_guard(s3):
        exe.run(lm["startup"])
        out = exe.train_from_dataset(
            program=c2b, dataset=_batches(STEPS), scope=s3,
            fetch_list=[lm["loss"]], checkpoint_dir=run_dir,
            checkpoint_every=4, resume_from=run_dir)
    assert exe.last_resume_step == 8
    assert exe.last_restore_stats["exchanged"] > 0
    assert exe.last_restore_stats["max_region_bytes"] < full
    losses += [float(np.asarray(o[0])) for o in out]

    # the whole chain IS the uninterrupted trajectory, step for step
    assert len(losses) == STEPS
    np.testing.assert_allclose(losses, golden, rtol=2e-4)

    # restores were counted, none fell back
    assert exe.last_restore_fallbacks == 0
    assert monitor.counter_value("train_checkpoint_restore_total") >= 2


def test_incompatible_restore_is_typed(lm, tmp_path):
    """CheckpointMeshMismatchError remains for the GENUINELY
    incompatible: a layout that cannot resolve on the new mesh (axis
    divisibility), a shard set that no longer tiles a target region
    (doctored manifest), and shard-wise state without the layout at
    all — never silent mis-placement, never a fallback (these are
    configuration errors, not corruption)."""
    run_dir = str(tmp_path / "run")
    compiled2 = _compiled_for(lm, 2)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(lm["startup"])
        exe.train_from_dataset(
            program=compiled2, dataset=_batches(4), scope=scope,
            fetch_list=[lm["loss"]], checkpoint_dir=run_dir,
            checkpoint_every=4)

    # fsdp-3: VOCAB=128 does not divide by 3 — the layout itself is
    # unresolvable on this mesh, typed with the var named
    compiled3 = _compiled_for(lm, 3)
    fresh = fluid.Scope()
    with fluid.scope_guard(fresh):
        exe.run(lm["startup"])
        with pytest.raises(CheckpointMeshMismatchError) as ei:
            TrainCheckpoint(run_dir).restore(
                lm["prog"], fresh, compiled=compiled3)
        assert "cannot resolve" in str(ei.value)
        # ...and shard-wise state without the layout is typed too
        with pytest.raises(ValueError) as ei:
            TrainCheckpoint(run_dir).restore(lm["prog"], fresh)
        assert "compiled" in str(ei.value)

    # doctor the shard manifest: drop one of the embedding's shards —
    # the survivors cannot tile a target region anymore.  (integrity
    # is removed so the INCOMPATIBILITY surfaces, not the tamper: with
    # it left in place the corruption gate would fall back instead.)
    sdir = os.path.join(run_dir, "ckpt-000004", "shards")
    with open(os.path.join(sdir, "manifest.json")) as f:
        man = json.load(f)
    man["vars"]["lm_word_emb"]["shards"] = (
        man["vars"]["lm_word_emb"]["shards"][:1])
    with open(os.path.join(sdir, "manifest.json"), "w") as f:
        json.dump(man, f)
    os.remove(os.path.join(run_dir, "ckpt-000004", "integrity.json"))
    fresh2 = fluid.Scope()
    with fluid.scope_guard(fresh2):
        exe.run(lm["startup"])
        with pytest.raises(CheckpointMeshMismatchError) as ei:
            TrainCheckpoint(run_dir).restore(
                lm["prog"], fresh2, compiled=_compiled_for(lm, 4))
        assert "lm_word_emb" in str(ei.value)
        assert "cover" in str(ei.value)


def test_overlapping_shard_manifest_is_typed(lm, tmp_path):
    """Coverage is checked by overlap-VOLUME summation, which is exact
    only over a disjoint shard grid — a doctored manifest listing the
    same shard twice could otherwise fake full coverage while leaving
    zero-filled holes.  Overlapping indexes are typed before assembly."""
    run_dir = str(tmp_path / "run")
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(lm["startup"])
        exe.train_from_dataset(
            program=_compiled_for(lm, 2), dataset=_batches(4),
            scope=scope, fetch_list=[lm["loss"]],
            checkpoint_dir=run_dir, checkpoint_every=4)
    ck = os.path.join(run_dir, "ckpt-000004")
    mpath = os.path.join(ck, "shards", "manifest.json")
    with open(mpath) as f:
        man = json.load(f)
    docs = man["vars"]["lm_word_emb"]["shards"]
    man["vars"]["lm_word_emb"]["shards"] = [docs[0], dict(docs[0])]
    with open(mpath, "w") as f:
        json.dump(man, f)
    os.remove(os.path.join(ck, "integrity.json"))
    fresh = fluid.Scope()
    with fluid.scope_guard(fresh):
        exe.run(lm["startup"])
        with pytest.raises(CheckpointMeshMismatchError, match="overlap"):
            TrainCheckpoint(run_dir).restore(
                lm["prog"], fresh, compiled=_compiled_for(lm, 2))


def test_corrupt_shard_falls_back_to_previous_checkpoint(lm, golden,
                                                         tmp_path):
    """A flipped byte in any shard file of the newest checkpoint is a
    detected corruption: restore falls back to the previous complete
    checkpoint (counted), and training resumes loss-exact from IT."""
    run_dir = str(tmp_path / "run")
    c2 = _compiled_for(lm, 2)
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(lm["startup"])
        exe.train_from_dataset(
            program=c2, dataset=_batches(8), scope=scope,
            fetch_list=[lm["loss"]], checkpoint_dir=run_dir,
            checkpoint_every=4)
    # both checkpoints committed (keep=2); flip one byte in a shard
    # file of the NEWEST one
    sdir = os.path.join(run_dir, "ckpt-000008", "shards")
    victim = next(os.path.join(sdir, f) for f in sorted(os.listdir(sdir))
                  if f.endswith(".npy"))
    with open(victim, "r+b") as f:
        f.seek(128)
        b = f.read(1)
        f.seek(128)
        f.write(bytes([b[0] ^ 0xFF]))

    c0 = monitor.counter_value("train_checkpoint_corruption_total")
    f0 = monitor.counter_value("train_checkpoint_fallback_total")
    fresh = fluid.Scope()
    with fluid.scope_guard(fresh):
        exe.run(lm["startup"])
        out = exe.train_from_dataset(
            program=_compiled_for(lm, 2), dataset=_batches(STEPS),
            scope=fresh, fetch_list=[lm["loss"]],
            checkpoint_dir=run_dir, checkpoint_every=0,
            resume_from=run_dir)
    # the corrupt ckpt-000008 was skipped — training resumed from 4
    assert exe.last_resume_step == 4
    assert exe.last_restore_path.endswith("ckpt-000004")
    assert exe.last_restore_fallbacks == 1
    assert monitor.counter_value("train_checkpoint_corruption_total") == c0 + 1
    assert monitor.counter_value("train_checkpoint_fallback_total") == f0 + 1
    resumed = [float(np.asarray(o[0])) for o in out]
    np.testing.assert_allclose(resumed, golden[4:], rtol=2e-4)

    # with the corrupt one ALSO flipped in ckpt-000004, nothing
    # verifies: the typed corruption error surfaces (never silent)
    sdir4 = os.path.join(run_dir, "ckpt-000004", "shards")
    victim4 = next(os.path.join(sdir4, f)
                   for f in sorted(os.listdir(sdir4))
                   if f.endswith(".npy"))
    with open(victim4, "r+b") as f:
        f.seek(64)
        b = f.read(1)
        f.seek(64)
        f.write(bytes([b[0] ^ 0xFF]))
    from paddle_tpu.faults.checkpoint import CheckpointCorruptionError

    fresh2 = fluid.Scope()
    with fluid.scope_guard(fresh2):
        exe.run(lm["startup"])
        with pytest.raises(CheckpointCorruptionError, match="hash"):
            TrainCheckpoint(run_dir).restore(
                lm["prog"], fresh2, compiled=_compiled_for(lm, 2))


def test_replicated_dp_checkpoint_stays_portable(tmp_path):
    """A plain data-parallel run's state is mesh-committed but FULLY
    replicated — its checkpoint must stay on the portable params/ path
    (no shards/ dir), resume without compiled=, and not pin the run to
    this host's device count."""
    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 3
    with framework.program_guard(prog, startup):
        x = fluid.layers.data("x", [8])
        y = fluid.layers.data("y", [1])
        out = fluid.layers.fc(x, 1)
        loss = fluid.layers.mean(
            fluid.layers.square_error_cost(out, y))
        fluid.optimizer.AdamOptimizer(0.05).minimize(loss)
    compiled = fluid.CompiledProgram(prog).with_data_parallel()
    exe = fluid.Executor(fluid.CPUPlace())
    run_dir = str(tmp_path / "dp")

    def feeds(n):
        for i in range(n):
            r = np.random.RandomState(i)
            xv = r.rand(8, 8).astype(np.float32)
            yield {"x": xv, "y": xv.sum(1, keepdims=True)}

    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.train_from_dataset(
            program=compiled, dataset=feeds(4), scope=scope,
            fetch_list=[loss], checkpoint_dir=run_dir, checkpoint_every=4)
    ck = os.path.join(run_dir, "ckpt-000004")
    assert not os.path.isdir(os.path.join(ck, "shards"))
    # ...and the portable checkpoint restores with NO compiled= at all
    fresh = fluid.Scope()
    with fluid.scope_guard(fresh):
        exe.run(startup)
        cursor = TrainCheckpoint(run_dir).restore(prog, fresh)
    assert cursor["step"] == 4


def test_with_default_keeps_accumulator_map(lm):
    """with_sharding_rules(default=...) must not demote a
    TrainPartitionRules to plain rules — the accumulator map (and with
    it the typed-inheritance semantics and the export unwrap) survives
    the default rebind."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.sharding.train import TrainPartitionRules, train_rules

    tr = train_rules(sharding.transformer_lm_rules("tp"),
                     optimizer=lm["opt"])
    compiled = fluid.CompiledProgram(lm["prog"]).with_sharding_rules(
        tr, mesh_axes={"tp": 2}, default=P())
    rebound = compiled.sharding_rules
    assert isinstance(rebound, TrainPartitionRules)
    assert rebound.accumulators == tr.accumulators
    # the serving rules survive with the default baked in (an export of
    # this layout resolves unmatched names the same way training does)
    assert rebound.serving_rules.rules == tr.serving_rules.rules
    assert tuple(rebound.serving_rules.default) == ()
    # a moment still inherits its param's spec (not the default)
    acc = _acc_name(lm, "lm_word_emb", "moment1")
    assert tuple(rebound.spec_for(acc, (VOCAB, D_MODEL))) == ("tp", None)


def test_train_export_serve_round_trip(lm, tmp_path):
    """save_inference_model accepts the TRAINING layout (unwrapping to
    the serving rules), and the trained sharded model serves behind
    InferenceServer with zero recompiles."""
    from paddle_tpu.sharding.train import train_rules

    tr = train_rules(sharding.transformer_lm_rules("tp"),
                     optimizer=lm["opt"])
    compiled = sharding.sharded_train_program(
        lm["prog"], tr, mesh_axes={"tp": 2})
    exe = fluid.Executor(fluid.CPUPlace())
    export_dir = str(tmp_path / "lm_tp2")
    rep_dir = str(tmp_path / "lm_rep")
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        exe.run(lm["startup"])
        for feed in _batches(4):
            exe.run(compiled, feed=feed, fetch_list=[lm["loss"]])
        # export WITH the training layout: the manifest carries the
        # serving rules (accumulators are pruned with the backward
        # pass).  A second, replicated export of the SAME trained scope
        # is the parity yardstick below.
        fluid.save_inference_model(
            export_dir, ["src_ids"], [lm["logits"]], exe, lm["prog"],
            sharding_rules=tr, sharding_mesh={"tp": 2})
        fluid.save_inference_model(
            rep_dir, ["src_ids"], [lm["logits"]], exe, lm["prog"])

    with open(os.path.join(export_dir, "__model__")) as f:
        manifest = json.load(f)["sharding"]
    assert manifest["mesh_axes"] == {"tp": 2}
    pats = [p for p, _ in manifest["rules"]["rules"]]
    assert not any("moment" in p for p in pats)  # serving rules only

    pred = create_paddle_predictor(AnalysisConfig(export_dir))
    assert pred.sharded
    rep = create_paddle_predictor(AnalysisConfig(rep_dir))
    assert not rep.sharded
    # the sharded predictor serves the SAME trained weights: parity
    # against the replicated predictor exported from the same scope
    probe = next(_batches(1))
    out, = pred.run({"src_ids": probe["src_ids"]})
    ref, = rep.run({"src_ids": probe["src_ids"]})
    np.testing.assert_allclose(out, ref, rtol=2e-4, atol=2e-4)

    server = serving.InferenceServer(
        pred, max_batch_size=4, batch_timeout_ms=2, name="trainedlm")
    try:
        server.warmup()
        misses0 = pred.jit_cache_stats()["misses"]
        cli = serving.Client(server)
        for n in (1, 3, 2):
            res, = cli.infer(
                {"src_ids": np.random.RandomState(n).randint(
                    1, VOCAB, (n, SEQ)).astype(np.int64)})
            assert res.shape == (n, SEQ, VOCAB)
        assert pred.jit_cache_stats()["misses"] == misses0
        assert server.statusz()["metrics"]["recompiles"] == 0
    finally:
        server.stop(drain=True)
