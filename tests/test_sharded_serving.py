"""Sharded-model serving end to end (ISSUE 10 acceptance):

* a transformer-LM predictor sharded 2-way (tp) across the virtual CPU
  mesh serves a mixed-size storm behind ``InferenceServer`` with ZERO
  recompiles after warmup (asserted via ``jit_cache_stats``/statusz),
* every parameter is verifiably placed per its rule — addressable
  shard shapes checked against the canonical tp layout — and each
  sharded parameter's per-device HBM footprint is half the replicated
  baseline,
* sharded and replicated predictors agree numerically,
* the layout rides ``save_inference_model``'s manifest so launched
  ``ServingProcess`` children reconstruct it and a ``FleetBalancer``
  routes to model-parallel GROUPS,
* the known interop gap is closed both ways: an uncompiled run over
  mesh-committed state raises a typed ``MeshCommittedStateError``
  naming the variable and mesh, or reshard-on-gathers when opted in.
"""
import os
import tempfile
import threading

import numpy as np
import pytest

from conftest import WAIT

import paddle_tpu as fluid
from paddle_tpu import framework, models, monitor, serving, sharding
from paddle_tpu.inference import AnalysisConfig, create_paddle_predictor
from paddle_tpu.sharding import MeshCommittedStateError

SEQ = 16
D_MODEL = 32
VOCAB = 256
TP = 2


def _save_lm(dirname: str, sharded: bool) -> str:
    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 21  # identical weights both ways
    with framework.program_guard(prog, startup):
        ids = fluid.layers.data("src_ids", [SEQ], dtype="int64")
        _, logits = models.transformer_lm(
            ids, None, vocab_size=VOCAB, d_model=D_MODEL, n_layer=2,
            n_head=4, d_inner=64, seq_len=SEQ, max_pos=64)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        kw = {}
        if sharded:
            kw = dict(
                sharding_rules=sharding.transformer_lm_rules("tp"),
                sharding_mesh={"tp": TP})
        fluid.save_inference_model(
            dirname, ["src_ids"], [logits], exe, prog, **kw)
    return dirname


@pytest.fixture(scope="module")
def lm_dirs():
    with tempfile.TemporaryDirectory() as tmp:
        yield {
            "replicated": _save_lm(os.path.join(tmp, "rep"), sharded=False),
            "sharded": _save_lm(os.path.join(tmp, "tp2"), sharded=True),
        }


def _ids(n, seed=0):
    return np.random.RandomState(seed).randint(
        1, VOCAB, (n, SEQ)).astype(np.int64)


# ---------------------------------------------------------------------------
# placement + parity
# ---------------------------------------------------------------------------
def test_sharded_predictor_places_params_per_rule(lm_dirs):
    sharded0 = monitor.counter_value(
        "sharding_params_sharded_total", default=0.0)
    pred = create_paddle_predictor(AnalysisConfig(lm_dirs["sharded"]))
    assert pred.sharded
    rep = create_paddle_predictor(AnalysisConfig(lm_dirs["replicated"]))
    assert not rep.sharded

    x = _ids(3, seed=5)
    out_s, = pred.run({"src_ids": x})
    out_r, = rep.run({"src_ids": x})
    # one predictor now spans a 2-device tp group; the math is the same
    np.testing.assert_allclose(out_s, out_r, rtol=2e-4, atol=2e-4)

    placements = pred.param_placements()
    # column-parallel q/k/v: output dim sharded -> shard (D, D/2)
    qw = placements["lm_dec_0_att_q_w"]
    assert qw["spec"] == [None, "tp"] and qw["placed"] and qw["sharded"]
    assert tuple(qw["shard_shape"]) == (D_MODEL, D_MODEL // TP)
    # row-parallel attention output: input dim sharded -> (D/2, D)
    ow = placements["lm_dec_1_att_out_w"]
    assert tuple(ow["shard_shape"]) == (D_MODEL // TP, D_MODEL)
    # vocab-sharded embedding and head
    emb = placements["lm_word_emb"]
    assert tuple(emb["shard_shape"]) == (VOCAB // TP, D_MODEL)
    hw = placements["lm_head_w"]
    assert tuple(hw["shard_shape"]) == (D_MODEL, VOCAB // TP)
    # norms replicate (placed on the mesh, but whole per device)
    ln = placements["lm_dec_0_ln1_scale"]
    assert not ln["sharded"] and tuple(ln["shard_shape"]) == (D_MODEL,)

    # per-param HBM: every sharded param's per-device bytes is HALF the
    # replicated baseline (tp=2) — the acceptance capacity claim
    for name, p in placements.items():
        full = int(np.prod(p["shape"])) * 4  # float32 params
        if p["sharded"]:
            assert p["bytes_per_device"] * TP <= full + 4, (name, p)

    stats = pred.sharding_stats()
    assert stats["n_sharded"] >= 20  # qkv/out/ffn/emb/head across 2 layers
    assert stats["hbm_bytes_per_device"] < stats["replicated_bytes"]
    # placement moved the process-wide sharded-params counter
    assert monitor.counter_value(
        "sharding_params_sharded_total", default=0.0) >= (
            sharded0 + stats["n_sharded"])


def test_manifest_survives_save_load(lm_dirs):
    import json

    with open(os.path.join(lm_dirs["sharded"], "__model__")) as f:
        model = json.load(f)
    man = model["sharding"]
    assert man["mesh_axes"] == {"tp": TP}
    rules = sharding.PartitionRules.from_manifest(man["rules"])
    assert rules.spec_for("lm_head_b", (VOCAB,)) is not None
    # the replicated dir carries no manifest
    with open(os.path.join(lm_dirs["replicated"], "__model__")) as f:
        assert "sharding" not in json.load(f)


def test_export_validates_rules_against_program():
    """A layout that misses a param fails at EXPORT, not in a child."""
    prog, startup = framework.Program(), framework.Program()
    with framework.program_guard(prog, startup):
        ids = fluid.layers.data("src_ids", [SEQ], dtype="int64")
        _, logits = models.transformer_lm(
            ids, None, vocab_size=VOCAB, d_model=D_MODEL, n_layer=1,
            n_head=4, d_inner=64, seq_len=SEQ, max_pos=64)
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        with tempfile.TemporaryDirectory() as tmp:
            with pytest.raises(sharding.ShardingRuleError):
                fluid.save_inference_model(
                    tmp, ["src_ids"], [logits], exe, prog,
                    sharding_rules=[(r"_att_", (None, "tp"))],
                    sharding_mesh={"tp": TP})
            # a mesh missing the rules' axes fails at export too — not
            # in the serving child that would otherwise load it
            rules = sharding.transformer_lm_rules("tp")
            with pytest.raises(sharding.ShardingRuleError) as ei:
                fluid.save_inference_model(
                    tmp, ["src_ids"], [logits], exe, prog,
                    sharding_rules=rules, sharding_mesh={"dp": 2})
            assert "tp" in str(ei.value)
            # ...and a multi-axis rule set with no mesh is ambiguous
            with pytest.raises(sharding.ShardingRuleError):
                fluid.save_inference_model(
                    tmp, ["src_ids"], [logits], exe, prog,
                    sharding_rules=sharding.transformer_lm_rules(
                        "fsdp_tp"))
            # ...and a mesh size the param dims don't divide by fails
            # at export too (not as a raw device_put ValueError in the
            # loader): d_model=32 is not divisible by tp=3
            with pytest.raises(sharding.ShardingRuleError) as ei:
                fluid.save_inference_model(
                    tmp, ["src_ids"], [logits], exe, prog,
                    sharding_rules=rules, sharding_mesh={"tp": 3})
            assert "divisible" in str(ei.value)


# ---------------------------------------------------------------------------
# the serving acceptance: mixed-size storm, zero recompiles, group stats
# ---------------------------------------------------------------------------
def test_sharded_server_storm_zero_recompiles(lm_dirs):
    pred = create_paddle_predictor(AnalysisConfig(lm_dirs["sharded"]))
    server = serving.InferenceServer(
        pred, max_batch_size=8, batch_timeout_ms=2, queue_capacity=128,
        name="shardlm")
    try:
        server.warmup()
        misses0 = pred.jit_cache_stats()["misses"]

        cli = serving.Client(server)
        errs = []

        def storm(t):
            rng = np.random.RandomState(40 + t)
            for i in range(10):
                n = 1 + (t + i) % 4
                try:
                    out, = cli.infer(
                        {"src_ids": rng.randint(1, VOCAB, (n, SEQ))
                         .astype(np.int64)})
                    assert out.shape == (n, SEQ, VOCAB)
                except Exception as e:  # noqa: BLE001
                    errs.append(e)

        threads = [threading.Thread(target=storm, args=(t,), daemon=True)
                   for t in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(WAIT)
        assert not any(t.is_alive() for t in threads)
        assert errs == []

        # the zero-recompile guarantee holds for a mesh-spanning group
        assert pred.jit_cache_stats()["misses"] == misses0
        doc = server.statusz()
        assert doc["metrics"]["recompiles"] == 0
        # statusz surfaces the group placement accounting
        sh = doc["sharding"]["r0"]
        assert sh["sharded"] and sh["mesh_axes"] == {"tp": TP}
        assert sh["hbm_bytes_per_device"] < sh["replicated_bytes"]
        # warmup published the per-group HBM gauge
        assert monitor.counter_value(
            "sharding_group_hbm_bytes", default=-1.0,
            group="shardlm/r0") > 0
    finally:
        server.stop(drain=True)


# ---------------------------------------------------------------------------
# fleet: mesh-spanning predictors as wire backends
# ---------------------------------------------------------------------------
def test_sharded_fleet_serves_groups(lm_dirs):
    """Two launched children, each ONE model-parallel tp group spanning
    its own mesh, behind the balancer: routing/warmup/in-flight
    accounting work unchanged, recompiles stay zero fleet-wide, and
    /healthz advertises the group."""
    import json
    import urllib.request

    from paddle_tpu.serving import wire

    fleet = wire.FleetBalancer.from_launch(
        lm_dirs["sharded"], n=2, name="shardfleet",
        launch_kwargs=dict(max_batch_size=8, batch_timeout_ms=2,
                           queue_capacity=128),
        health_interval_s=None)
    try:
        fleet.warmup()
        for be in fleet._backends:
            hz = be.transport.get_json("/healthz")
            assert hz["sharded"] is True and hz["ok"]

        errs = []

        def storm(t):
            rng = np.random.RandomState(70 + t)
            for i in range(8):
                n = 1 + (t + i) % 4
                try:
                    out, = fleet.infer(
                        {"src_ids": rng.randint(1, VOCAB, (n, SEQ))
                         .astype(np.int64)},
                        timeout_ms=60000)
                    assert out.shape == (n, SEQ, VOCAB)
                except Exception as e:  # noqa: BLE001
                    errs.append(e)

        threads = [threading.Thread(target=storm, args=(t,), daemon=True)
                   for t in range(3)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(WAIT)
        assert not any(t.is_alive() for t in threads)
        assert errs == []

        for be in fleet._backends:
            host, port = be.transport.address
            doc = json.load(urllib.request.urlopen(
                "http://%s:%d/statusz" % (host, port), timeout=WAIT))
            assert doc["metrics"]["recompiles"] == 0
            sh = doc["sharding"]["r0"]
            assert sh["sharded"] and sh["n_sharded"] >= 20
    finally:
        fleet.stop(shutdown_backends=True)


# ---------------------------------------------------------------------------
# the interop gap, pinned both ways
# ---------------------------------------------------------------------------
def _fc_prog():
    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 3
    with framework.program_guard(prog, startup):
        x = fluid.layers.data("x", [16])
        y = fluid.layers.fc(x, 8, act="softmax",
                            param_attr=fluid.ParamAttr(name="gap_w"))
    return prog, startup, y


def test_uncompiled_after_compiled_raises_typed():
    prog, startup, y = _fc_prog()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    x = np.random.RandomState(0).randn(8, 16).astype(np.float32)
    with fluid.scope_guard(scope):
        exe.run(startup)
        compiled = fluid.CompiledProgram(prog).with_data_parallel()
        exe.run(compiled, feed={"x": x}, fetch_list=[y])
        # the scope's params are now committed to the dp mesh; an
        # uncompiled run must fail LOUDLY naming the var and mesh, not
        # deep inside jit
        with pytest.raises(MeshCommittedStateError) as ei:
            exe.run(prog, feed={"x": x}, fetch_list=[y])
        msg = str(ei.value)
        assert "gap_w" in msg and "dp" in msg and "reshard_on_gather" in msg


def test_uncompiled_after_compiled_reshards_when_opted_in():
    prog, startup, y = _fc_prog()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    x = np.random.RandomState(1).randn(8, 16).astype(np.float32)
    with fluid.scope_guard(scope):
        exe.run(startup)
        compiled = fluid.CompiledProgram(prog).with_data_parallel()
        ref, = exe.run(compiled, feed={"x": x}, fetch_list=[y])
        # opt-in: gather the committed state back to host once...
        exe2 = fluid.Executor(fluid.CPUPlace(), reshard_on_gather=True)
        out, = exe2.run(prog, feed={"x": x}, fetch_list=[y])
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
        # ...after which the PLAIN executor runs clean (state is host)
        out2, = exe.run(prog, feed={"x": x}, fetch_list=[y])
        np.testing.assert_allclose(out2, ref, rtol=1e-5, atol=1e-5)


def test_env_opt_in_reshards(monkeypatch):
    monkeypatch.setenv("PADDLE_TPU_RESHARD_ON_GATHER", "1")
    prog, startup, y = _fc_prog()
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    x = np.random.RandomState(2).randn(8, 16).astype(np.float32)
    with fluid.scope_guard(scope):
        exe.run(startup)
        compiled = fluid.CompiledProgram(prog).with_data_parallel()
        ref, = exe.run(compiled, feed={"x": x}, fetch_list=[y])
        out, = exe.run(prog, feed={"x": x}, fetch_list=[y])
        np.testing.assert_allclose(out, ref, rtol=1e-5, atol=1e-5)
