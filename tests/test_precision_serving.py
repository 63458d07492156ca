"""Mixed-precision serving path (contrib/mixed_precision → inference →
serving): the bf16/int8 predictor variants, the export parity gate, the
manifest ride, per-request fp32 opt-out, and the zero-recompile
guarantee across both compiled ladders.
"""
import os
import threading

import numpy as np
import pytest

from conftest import WAIT

import paddle_tpu as fluid
from paddle_tpu import framework, models, serving
from paddle_tpu.contrib.mixed_precision import inference as mp_inf
from paddle_tpu.inference import AnalysisConfig, create_paddle_predictor

RTOL_BF16 = mp_inf.DEFAULT_RTOL["bf16"]


# ---------------------------------------------------------------------------
# endpoint builders (the three families the tentpole names)
# ---------------------------------------------------------------------------
def _export(dirname, build, precision=None, **save_kw):
    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 29
    with framework.program_guard(prog, startup):
        feed_names, targets = build()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        fluid.save_inference_model(
            str(dirname), feed_names, targets, exe, prog,
            precision_policy=precision, **save_kw)
    return str(dirname)


def _build_lenet():
    img = fluid.layers.data("img", [1, 28, 28])
    lbl = fluid.layers.data("lbl", [1], dtype="int64")
    _, _, pred = models.lenet5(img, lbl)
    return ["img"], [pred]


def _lenet_feed(n=4, seed=0):
    rng = np.random.RandomState(seed)
    return {"img": rng.uniform(-1, 1, (n, 1, 28, 28)).astype(np.float32)}


def _build_deepfm(num_features=512, num_fields=8):
    ids = fluid.layers.data("feat_ids", [num_fields, 1], dtype="int64")
    vals = fluid.layers.data("feat_vals", [num_fields])
    lbl = fluid.layers.data("lbl", [1], dtype="int64")
    _, prob = models.deepfm_ctr(
        ids, vals, lbl, num_features=num_features, num_fields=num_fields,
        embed_dim=4, deep_layers=(16, 16))
    return ["feat_ids", "feat_vals"], [prob]


def _deepfm_feed(n=4, seed=0, num_features=512, num_fields=8):
    rng = np.random.RandomState(seed)
    return {
        "feat_ids": rng.randint(
            0, num_features, (n, num_fields, 1)).astype(np.int64),
        "feat_vals": rng.uniform(0, 1, (n, num_fields)).astype(np.float32),
    }


_LM_V, _LM_D, _LM_S = 128, 16, 8


def _build_lm():
    """The transformer-LM decode endpoint's logits program (the same
    family tests/test_sharded_serving.py serves as a tp group)."""
    ids = fluid.layers.data("src_ids", [_LM_S], dtype="int64")
    _, logits = models.transformer_lm(
        ids, None, vocab_size=_LM_V, d_model=_LM_D, n_layer=1, n_head=2,
        d_inner=32, seq_len=_LM_S, max_pos=2 * _LM_S)
    return ["src_ids"], [logits]


def _lm_feed(n=4, seed=0):
    rng = np.random.RandomState(seed)
    return {"src_ids": rng.randint(1, _LM_V, (n, _LM_S)).astype(np.int64)}


def _rel_err(ref, out):
    return mp_inf.max_rel_err(ref, out)


# ---------------------------------------------------------------------------
# rewrite_program on pruned inference programs: parity + cast census
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("build,feed_fn", [
    (_build_lenet, _lenet_feed),
    (_build_deepfm, _deepfm_feed),
    (_build_lm, _lm_feed),
], ids=["lenet", "deepfm", "transformer-lm"])
def test_bf16_variant_parity(build, feed_fn, tmp_path):
    """bf16 vs fp32 within rtol on all three endpoint families, via the
    full export → manifest → loader → per-request-opt-out path."""
    d = _export(tmp_path / "ep", build, precision={"dtype": "bf16"})
    pred = create_paddle_predictor(AnalysisConfig(d))
    policy = pred.precision_policy
    assert policy["dtype"] == "bf16"
    assert policy["max_rel_err"] <= policy["rtol"]
    assert pred.precision_dtypes() == ["bf16", "fp32"]
    feed = feed_fn(n=4, seed=3)
    out_low = pred.run(feed)
    out_fp32 = pred.run(feed, precision="fp32")
    # fetch pinning: bf16 never leaves the predictor
    assert all(np.asarray(o).dtype != np.dtype("bfloat16") for o in out_low)
    assert _rel_err(out_fp32, out_low) <= policy["rtol"]
    # the manifest-declared bound holds at runtime, and the variants
    # genuinely differ (the bf16 path is not silently serving fp32)
    assert any(
        not np.array_equal(np.asarray(a), np.asarray(b))
        for a, b in zip(out_fp32, out_low))


def test_gray_chains_stay_bf16_no_bounce_casts(tmp_path):
    """The rewritten LeNet program's cast census: ONE cast down (the
    image input feeding the first conv — every weight cast is hoisted
    into the variant scope) and ONE cast up (feeding the black-listed
    softmax).  The conv→pool→relu→fc gray chain carries no
    intermediate fp32 bounce-casts."""
    d = _export(tmp_path / "lenet", _build_lenet)
    pred = create_paddle_predictor(AnalysisConfig(d))
    variant, info = mp_inf.build_bf16_variant(pred._program,
                                              pred._fetch_names)
    counts = info["cast_ops"]
    assert counts == {"to_low": 1, "to_fp32": 1}, counts
    # every float parameter was hoisted to a load-time bf16 cast
    assert len(info["cast_params"]) == 8  # 2 conv + 2 fc, w + b each
    # structural no-bounce check: no cast-to-fp32 output feeds a
    # white/gray op (fp32 may only flow into black ops or fetches)
    lists = mp_inf.AutoMixedPrecisionLists()
    block = variant.global_block()
    fp32_outs = {
        op.outputs["Out"][0] for op in block.ops
        if op.type == "cast" and op.attrs.get("out_dtype") == "float32"}
    for op in block.ops:
        if op.type in lists.white_list or op.type in lists.gray_list:
            for names in op.inputs.values():
                assert not (set(names) & fp32_outs), (
                    "fp32 bounce-cast feeds %s" % op.type)


def test_parity_gate_refuses_impossible_rtol(tmp_path):
    with pytest.raises(mp_inf.PrecisionParityError):
        _export(tmp_path / "ep", _build_lenet,
                precision={"dtype": "bf16", "rtol": 1e-7})


def test_unknown_policy_dtype_and_keys_typed(tmp_path):
    with pytest.raises(mp_inf.PrecisionPolicyError):
        _export(tmp_path / "a", _build_lenet, precision={"dtype": "fp8"})
    with pytest.raises(mp_inf.PrecisionPolicyError):
        _export(tmp_path / "b", _build_lenet,
                precision={"dtype": "bf16", "typo_knob": 1})
    # validation is symmetric across dtypes: a known key the chosen
    # dtype cannot honor is refused, never silently discarded
    with pytest.raises(mp_inf.PrecisionPolicyError):
        _export(tmp_path / "c", _build_lenet,
                precision={"dtype": "bf16",
                           "calibration": [_lenet_feed(n=2)]})
    with pytest.raises(mp_inf.PrecisionPolicyError):
        _export(tmp_path / "d", _build_lenet,
                precision={"dtype": "int8",
                           "calibration": [_lenet_feed(n=2)],
                           "custom_black_list": ["softmax"]})


def test_int8_precision_and_sharding_not_composable(tmp_path):
    """bf16 composes with sharding (tests/test_precision_sharding.py);
    int8's frozen sub-model carries its own param set and stays typed-
    refused when combined with a layout."""
    from paddle_tpu import sharding

    cal = [_lenet_feed(n=2, seed=100)]
    with pytest.raises(mp_inf.PrecisionPolicyError):
        _export(tmp_path / "ep", _build_lm,
                precision={"dtype": "int8", "calibration": cal},
                sharding_rules=sharding.transformer_lm_rules("tp"),
                sharding_mesh={"tp": 2})


# ---------------------------------------------------------------------------
# int8 via the contrib/quantize seam
# ---------------------------------------------------------------------------
def test_int8_calibrated_roundtrip(tmp_path):
    cal = [_lenet_feed(n=4, seed=100 + i) for i in range(3)]
    d = _export(tmp_path / "ep", _build_lenet,
                precision={"dtype": "int8", "calibration": cal})
    assert os.path.isdir(os.path.join(d, "__int8__"))
    pred = create_paddle_predictor(AnalysisConfig(d))
    policy = pred.precision_policy
    assert policy["dtype"] == "int8"
    assert policy["variant_dir"] == "__int8__"
    assert policy["max_rel_err"] <= policy["rtol"]
    feed = _lenet_feed(n=2, seed=5)
    out_i8 = pred.run(feed)
    out_fp = pred.run(feed, precision="fp32")
    assert _rel_err(out_fp, out_i8) <= policy["rtol"]
    # the frozen sub-model really holds int8 weights, not fp32 copies
    files = os.listdir(os.path.join(d, "__int8__"))
    assert any(".int8" in f for f in files)
    assert "conv2d_0.w_0.npy" not in files


def test_int8_without_calibration_typed(tmp_path):
    with pytest.raises(mp_inf.PrecisionPolicyError):
        _export(tmp_path / "ep", _build_lenet, precision={"dtype": "int8"})


# ---------------------------------------------------------------------------
# serving: mixed-precision dispatch, zero recompiles, wire loopback
# ---------------------------------------------------------------------------
def test_serving_mixed_precision_zero_recompiles(tmp_path):
    """The serving acceptance core: warmup compiles BOTH ladders, a
    storm mixing policy-default and fp32-opt-out requests never
    recompiles, batches never mix precisions, and the per-dtype
    request counter accounts for every completion."""
    d = _export(tmp_path / "ep", _build_lenet, precision={"dtype": "bf16"})
    pred = create_paddle_predictor(AnalysisConfig(d))
    srv = serving.InferenceServer(
        pred, max_batch_size=8, batch_timeout_ms=2, queue_capacity=64,
        name="prec-srv")
    try:
        compiles = srv.warmup()
        # both ladders warmed: one compiled signature per (rung, dtype)
        assert compiles == 2 * len(srv.bucket_ladder)
        misses0 = pred.jit_cache_stats()["misses"]
        cli = serving.Client(srv)
        rng = np.random.RandomState(0)
        n_fp32 = 0
        for i in range(40):
            n = 1 + i % 3
            feed = {"img": rng.uniform(
                -1, 1, (n, 1, 28, 28)).astype(np.float32)}
            if i % 5 == 0:
                cli.infer(feed, precision="fp32")
                n_fp32 += 1
            else:
                cli.infer(feed)
        m = srv.metrics()
        assert m["recompiles"] == 0
        assert pred.jit_cache_stats()["misses"] == misses0
        assert m["completed"] == 40
        assert m["precision_requests"]["fp32"] == n_fp32
        assert m["precision_requests"]["bf16"] == 40 - n_fp32
        assert m["precision_dtypes"] == ["bf16", "fp32"]
        # unknown dtype fails typed at submit, before anything enqueues
        with pytest.raises(ValueError):
            srv.submit(_lenet_feed(n=1), precision="fp8")
    finally:
        srv.stop(drain=True)


def test_precision_alias_accepted(tmp_path):
    d = _export(tmp_path / "ep", _build_lenet, precision={"dtype": "bf16"})
    pred = create_paddle_predictor(AnalysisConfig(d))
    srv = serving.InferenceServer(
        pred, max_batch_size=4, batch_timeout_ms=1, name="prec-alias")
    try:
        srv.warmup()
        cli = serving.Client(srv)
        out = cli.infer(_lenet_feed(n=1), precision="float32")
        ref = pred.run(_lenet_feed(n=1), precision="fp32")
        np.testing.assert_allclose(np.asarray(out[0]),
                                   np.asarray(ref[0]), rtol=1e-6)
    finally:
        srv.stop(drain=True)


def test_wire_loopback_precision(tmp_path):
    """Precision rides the wire: /healthz advertises the policy, the
    remote fp32 opt-out serves the base program, and an unknown dtype
    comes back as the typed in-band ValueError."""
    from paddle_tpu.serving.wire import RemoteClient
    from paddle_tpu.serving.wire.server import ServingProcess

    d = _export(tmp_path / "ep", _build_lenet, precision={"dtype": "bf16"})
    pred = create_paddle_predictor(AnalysisConfig(d))
    srv = serving.InferenceServer(
        pred, max_batch_size=4, batch_timeout_ms=1, name="prec-wire")
    srv.warmup()
    sp = ServingProcess(srv)
    sp.start()
    cli = RemoteClient(sp.address)
    try:
        h = cli.healthz()
        assert h["precision"] == "bf16"
        assert h["precision_dtypes"] == ["bf16", "fp32"]
        feed = _lenet_feed(n=2, seed=8)
        out_low = cli.infer(feed)
        out_fp32 = cli.infer(feed, precision="fp32")
        ref_low = pred.run(feed)
        ref_fp32 = pred.run(feed, precision="fp32")
        np.testing.assert_allclose(
            np.asarray(out_low[0]), np.asarray(ref_low[0]), rtol=1e-6)
        np.testing.assert_allclose(
            np.asarray(out_fp32[0]), np.asarray(ref_fp32[0]), rtol=1e-6)
        misses0 = pred.jit_cache_stats()["misses"]
        for i in range(6):
            cli.infer(_lenet_feed(n=1 + i % 2, seed=i),
                      precision="fp32" if i % 2 else None)
        assert pred.jit_cache_stats()["misses"] == misses0
        with pytest.raises(ValueError):
            cli.infer(feed, precision="fp8")
    finally:
        cli.close()
        sp.stop(drain=True)


def test_fleet_children_rebuild_the_variant_and_never_recompile(tmp_path):
    """A REAL 2-child wire fleet over one bf16-manifest endpoint dir:
    every child reconstructs the variant from the manifest (its
    ``/healthz`` advertises it), the fleet-wide warmup compiles both
    ladders in both processes, and after a storm mixing policy-default
    and fp32-opt-out requests each child's ``/statusz`` counts zero
    recompiles."""
    from paddle_tpu.serving import wire

    d = _export(tmp_path / "ep", _build_lenet, precision={"dtype": "bf16"})
    fleet = wire.FleetBalancer.from_launch(
        d, 2, name="prec-fleet",
        launch_kwargs={"max_batch_size": 4, "batch_timeout_ms": 2})
    try:
        fleet.warmup()
        for be in fleet._backends:
            h = be.transport.get_json("/healthz")
            assert h["precision"] == "bf16"
            assert h["precision_dtypes"] == ["bf16", "fp32"]
        errors = []

        def storm(t):
            try:
                for i in range(8):
                    n = 1 + (t + i) % 4
                    kw = {"precision": "fp32"} if (t + i) % 4 == 0 else {}
                    (out,) = fleet.infer(
                        _lenet_feed(n=n, seed=10 * t + i), **kw)
                    assert out.shape[0] == n
            except Exception as e:  # noqa: BLE001 — assertion target
                errors.append(e)

        threads = [threading.Thread(target=storm, args=(t,), daemon=True)
                   for t in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(WAIT)
        assert not any(t.is_alive() for t in threads)
        assert errors == []
        for be in fleet._backends:
            status = be.transport.get_json("/statusz")
            assert status["metrics"]["completed"] > 0  # both children served
            assert status["metrics"]["recompiles"] == 0, status["metrics"]
    finally:
        fleet.stop(shutdown_backends=True)
