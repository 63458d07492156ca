"""Kimi Delta Attention beside gated position-free GQA and routed experts
(``model_type: solar_open2``) on the pooled decode path:
``decoding.make_kda_routed_lm_pooled_step_fn`` -> ``KVSlotPool`` ->
``DecodeServer``, at tiny sizes on the CPU, against the benchmark's plain
reference (``benchmark/configs/solar_open2_250b_reference.py``: float32,
full forward, no cache, the rule a scan over time with ``Diag(alpha)``).

What is new under the pool: a delta rule whose decay is one factor a KEY
CHANNEL of a head (``alpha [N, H, dk]``; the kernel takes it as a third
column beside k and q), two low-rank gate pairs, a sigmoid output gate, a
G layer with a sigmoid gate and no positions, and routed experts beside a
shared expert after EVERY mixer, a share of them held.
"""
import importlib.util
import os

import numpy as np
import pytest

from conftest import WAIT
from test_delta_hybrid_lm import _serve
from test_routed_conv_lm import _staggered

from paddle_tpu import decoding
from paddle_tpu import delta_hybrid_lm as dh
from paddle_tpu import routed_experts as rx
from paddle_tpu.serving.decode import DecodeServer
from paddle_tpu.serving.kv_pool import KVSlotPool

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(ROOT, "benchmark", "configs",
                         "solar_open2_250b_reference.py"), "solar_reference")

V = 193
N_ALL = 16


def tiny_cfg(held=(4, 8), **over):
    """One period G, K, K, K at tiny widths: 3 linear heads of 16 lanes
    (a head count that is no power of two), 4 query heads over 2 K/V
    heads, 16 experts routed 4 a token of which ``held`` are computed,
    one shared expert."""
    cfg = dict(
        model_type="solar_open2", vocab_size=V, hidden_size=64,
        num_hidden_layers=4, gqa_layers=[0], gqa_interval=3,
        num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        linear_attn_config={"short_conv_kernel_size": 4, "head_dim": 16,
                            "num_heads": 3, "num_kv_heads": None},
        use_rope=False, rope_theta=10000, partial_rotary_factor=1,
        use_gqa_gate=True, kda_use_full_proj=False, kda_allow_neg_eigval=True,
        rms_norm_eps=1e-5, first_k_dense_replace=0, intermediate_size=128,
        moe_intermediate_size=32, n_routed_experts=N_ALL, n_shared_experts=1,
        num_experts_per_tok=4, norm_topk_prob=True, routed_scaling_factor=1,
        tie_word_embeddings=False)
    if held is not None:
        cfg.update(n_routed_experts=held[1] - held[0],
                   n_routed_experts_all=N_ALL)
    cfg.update(over)
    return cfg


def weights(cfg, seed=0, dtype="float32", held=(4, 8)):
    return dh.kda_random_state(np.random.RandomState(seed), cfg, std=0.1,
                               dtype=dtype, held=held, gate_std=0.3)


def _build(cfg, w, held=(4, 8), kv_dtype="fp32"):
    return decoding.make_kda_routed_lm_pooled_step_fn(
        w, cfg, kv_dtype=kv_dtype, held=held)


# fp32: the step and the reference differ in the order of float32 sums.
# bf16: bf16 weights against activations rounded to bf16, K/V in bf16;
# the reference upcasts the same weights and keeps the rest float32.
@pytest.mark.parametrize("dtype,kv_dtype,worst,mean", [
    ("float32", "fp32", 3e-5, 5e-6), ("bfloat16", "bf16", 6e-2, 1e-2)])
def test_prefill_then_decode_equals_the_full_forward(dtype, kv_dtype, worst,
                                                     mean):
    import jax.numpy as jnp

    cfg = tiny_cfg()
    d = dh.kda_dims(cfg)
    assert d.kinds == (dh.FULL,) + (dh.LINEAR,) * 3
    w = weights(cfg, seed=3, dtype=dtype)
    step, make_cache = _build(cfg, w, kv_dtype=kv_dtype)
    toks = np.random.RandomState(5).randint(0, V, (3, 12)).astype(np.int32)
    want = np.asarray(ref.forward(w, jnp.asarray(toks), cfg, held=(4, 8)))
    got, cache = _staggered(step, make_cache, toks)
    gap = np.abs(got - want).max(-1) / (want.max() - want.min())
    assert gap.max() <= worst and gap.mean() <= mean
    # the row that was idle throughout was neither written nor started
    for layer in cache["layers"]:
        for leaf in layer.values():
            assert float(jnp.abs(leaf[3].astype("float32")).max()) == 0.0
    assert [sorted(layer) for layer in cache["layers"]] == (
        [["k", "v"]] + [["conv", "state"]] * 3)
    assert cache["layers"][0]["k"].dtype == jnp.dtype(
        {"fp32": "float32", "bf16": "bfloat16"}[kv_dtype])
    assert cache["layers"][1]["state"].shape == (4,) + d.state_shape
    assert cache["layers"][1]["state"].dtype == jnp.float32
    # every layer counted every live row's pairs routed to a held expert
    stats = np.asarray(cache["expert_stats"])
    assert stats.shape == (4, len(rx.STAT_NAMES))
    assert (stats[:, 3] == 12 + 2).all() and (stats[:, 0] > 0).all()
    assert decoding.spec_of(make_cache).n_expert == 4


def test_the_decay_of_these_weights_differs_across_the_channels_of_a_head():
    """Else nothing here could tell a decay a channel from a decay a
    head: the spread of alpha INSIDE a head, and beta past 1."""
    import jax.numpy as jnp

    cfg = tiny_cfg()
    d = dh.kda_dims(cfg)
    w = weights(cfg, seed=3)
    x = jnp.asarray(np.random.RandomState(0).randn(64, 64), jnp.float32)
    alpha, beta = dh.channel_decay(x, w, "lm_l1_", d)
    assert alpha.shape == (64, 3, 16) and beta.shape == (64, 3)
    alpha, beta = np.asarray(alpha), np.asarray(beta)
    assert (alpha > 0).all() and (alpha < 1).all()
    assert np.median(alpha.max(-1) - alpha.min(-1)) > 0.05
    assert 0.2 < (beta > 1).mean() < 0.8 and beta.max() <= 2


@pytest.mark.parametrize("harm,told", [
    ("decay_averaged_over_a_head", True), ("beta_without_its_two", True),
    ("silu_output_gate", True), ("no_gqa_gate", True),
    ("rotary_in_the_g_layer", True)])
def test_a_harmed_rule_is_not_the_reference(harm, told, monkeypatch):
    """The variants the chip check exists to tell, at tiny sizes: each
    moves the logits far past the fp32 tolerance."""
    import jax
    import jax.numpy as jnp

    cfg = tiny_cfg()
    w = weights(cfg, seed=3)
    toks = np.random.RandomState(5).randint(0, V, (3, 12)).astype(np.int32)
    want = np.asarray(ref.forward(w, jnp.asarray(toks), cfg, held=(4, 8)))
    served = dict(cfg)
    if harm == "decay_averaged_over_a_head":
        real = dh.channel_decay

        def per_head(x, w_, p, d):
            alpha, beta = real(x, w_, p, d)
            return jnp.broadcast_to(alpha.mean(-1, keepdims=True),
                                    alpha.shape), beta
        monkeypatch.setattr(dh, "channel_decay", per_head)
    elif harm == "beta_without_its_two":
        served["kda_allow_neg_eigval"] = False
    elif harm == "silu_output_gate":
        real = dh.gated_output_norm
        monkeypatch.setattr(
            dh, "gated_output_norm",
            lambda o, gate, w_norm, eps, act=None: real(o, gate, w_norm, eps))
    elif harm == "no_gqa_gate":
        served["use_gqa_gate"] = False
    else:
        served["use_rope"] = True
    step, make_cache = _build(served, w)
    got, _ = _staggered(step, make_cache, toks)
    gap = np.abs(got - want).max(-1) / (want.max() - want.min())
    assert (gap.max() > 1e-3) == told


def test_the_eight_shares_and_one_shared_term_add_up_to_the_uncut_layer():
    """The share test: routed over all 16 experts, the parts that the
    disjoint held ranges give (no shared expert) plus ONE shared term are
    what the layer holding every expert gives — in the program's expert
    layer and in the reference's alike."""
    import jax.numpy as jnp

    cfg_all = tiny_cfg(held=None)
    d = dh.kda_dims(cfg_all)
    w = weights(cfg_all, seed=7, held=None)
    p = "lm_l1_"
    rng = np.random.RandomState(1)
    f = jnp.asarray(rng.randn(10, 64), jnp.float32)
    ts = jnp.asarray([0, 1, 2, 3, -1, 5, 6, 7, 8, 9], jnp.int32)
    whole, stats = rx.expert_layer(f, w, p, ts, d)
    live = np.asarray(ts) >= 0
    assert int(stats[0]) == live.sum() * d.top_k
    shares = jnp.zeros_like(whole)
    n_shares = 8
    per = N_ALL // n_shares
    pairs = 0
    for c in range(n_shares):
        held = (c * per, (c + 1) * per)
        wc = dict(w)
        for k in ("experts_w13", "experts_w2"):
            wc[p + k] = w[p + k][held[0]:held[1]]
        part, st = rx.expert_layer(f, wc, p, ts, d, held, shared=False)
        shares = shares + part
        pairs += int(st[0])
    assert pairs == live.sum() * d.top_k       # every pair in ONE share
    total = shares + rx.shared_expert(f, w, p, d)
    np.testing.assert_allclose(np.asarray(total)[live],
                               np.asarray(whole)[live], rtol=0, atol=2e-6)
    # and the reference's own shares, against its uncut layer
    sel, gate = ref.routing(w, p, f[None], cfg_all)
    r_whole = ref.experts(w, p, f[None], sel, gate, cfg_all)
    parts = sum(ref.experts(
        {p + "experts_w13": w[p + "experts_w13"][c * per:(c + 1) * per],
         p + "experts_w2": w[p + "experts_w2"][c * per:(c + 1) * per]},
        p, f[None], sel, gate, cfg_all, held=(c * per, (c + 1) * per),
        shared=False) for c in range(n_shares))
    parts = parts + ref._gated(f[None], w[p + "shared_w13"],
                               w[p + "shared_w2"], cfg_all)
    np.testing.assert_allclose(np.asarray(parts), np.asarray(r_whole),
                               rtol=0, atol=2e-6)
    np.testing.assert_allclose(np.asarray(r_whole)[0][live],
                               np.asarray(whole)[live], rtol=0, atol=5e-6)


# ---------------------------------------------------------------------------
# the pool: a reused slot, an idle row, refused tiers
# ---------------------------------------------------------------------------
def _pool(cfg, w, len_ladder, **kw):
    step, make_cache = _build(cfg, w)
    return KVSlotPool(step, make_cache, eos_id=V, max_slots=2,
                      max_seq_len=len_ladder[-1], slot_ladder=[2],
                      len_ladder=len_ladder, steps=2, kv_dtype="fp32", **kw)


def _recurrent(state):
    return [(layer[name], name) for layer in state["cache"]["layers"]
            for name in ("state", "conv") if name in layer]


@pytest.mark.parametrize("reset", [True, False])
def test_a_reused_slot_starts_its_state_and_conv_window_from_zero(
        reset, monkeypatch):
    import jax.numpy as jnp

    if not reset:
        monkeypatch.setattr(dh, "starts_fresh",
                            lambda ts: jnp.zeros(ts.shape, bool))
    cfg = tiny_cfg()
    w = weights(cfg, seed=11)
    rng = np.random.RandomState(2)
    a, b = (rng.randint(0, V, n).astype(np.int32) for n in (9, 4))
    pool = _pool(cfg, w, [16])
    virgin, want_toks = _serve(pool, pool.alloc(2, 16), 0, b, 8)
    used, _ = _serve(pool, pool.alloc(2, 16), 0, a, 7)
    assert all(np.abs(np.asarray(leaf)[0]).max() > 0
               for leaf, _ in _recurrent(used))
    used, got_toks = _serve(pool, used, 0, b, 8)
    same = all(np.array_equal(np.asarray(u)[0], np.asarray(v)[0])
               for (u, _), (v, _) in zip(_recurrent(used),
                                         _recurrent(virgin)))
    if reset:
        assert same and np.array_equal(got_toks, want_toks)
    else:
        assert not same


def test_an_idle_row_keeps_its_state_and_its_conv_window():
    cfg = tiny_cfg()
    w = weights(cfg, seed=11)
    rng = np.random.RandomState(4)
    a, b = (rng.randint(0, V, n).astype(np.int32) for n in (5, 6))
    pool = _pool(cfg, w, [32])
    state, _ = _serve(pool, pool.alloc(2, 32), 0, a, 4)
    before = [np.asarray(leaf)[0].copy() for leaf, _ in _recurrent(state)]
    assert np.abs(before[0]).max() > 0
    state, _ = _serve(pool, state, 1, b, 20)
    for (leaf, _), was in zip(_recurrent(state), before):
        assert np.array_equal(np.asarray(leaf)[0], was)


def test_the_leaves_are_declared_leaf_by_leaf():
    cfg = tiny_cfg()
    step, make_cache = _build(cfg, weights(cfg))
    spec = decoding.spec_of(make_cache)
    names = spec.names(lambda leaf: leaf.seq_axis is None)
    assert len(names) == 3 * 2 + 1         # state + conv a K layer, the counts
    assert len(spec.names(lambda leaf: leaf.seq_axis is None
                          and leaf.slot)) == 6
    assert not any("['layers'][0]" in n for n in names)    # the G layer


@pytest.mark.parametrize("tier", ["prefix", "speculative"])
def test_prefix_and_speculation_are_refused_over_this_builder(tier):
    cfg = tiny_cfg()
    w = weights(cfg)
    if tier == "prefix":
        kw = {"prefix": True}
    else:
        from paddle_tpu.serving.speculative import SpeculativeConfig

        step, make_cache = _build(cfg, w)
        kw = {"speculative": SpeculativeConfig(
            lambda c, t, ts: (None, c), step, make_cache, k=2)}
    with pytest.raises(ValueError, match=r"recurrent leaves .*a recurrent "
                       r"state has no"):
        _pool(cfg, w, [16], **kw)


@pytest.mark.parametrize("key,value,match", [
    ("kda_use_full_proj", True, "kda_use_full_proj"),
    ("first_k_dense_replace", 1, "first_k_dense_replace"),
    ("gqa_layers", [0, 9], "gqa_layers"),
    ("tie_word_embeddings", True, "tied head")])
def test_a_config_this_builder_cannot_serve_is_refused_by_name(key, value,
                                                               match):
    cfg = tiny_cfg(**{key: value})
    with pytest.raises(ValueError, match=match):
        if key == "first_k_dense_replace":
            # the sizes take leading dense layers (kda_latent_lm serves
            # them); THIS builder follows every mixer by experts
            assert dh.kda_dims(cfg).dense == (True, False, False, False)
            _build(cfg, weights(cfg))
        dh.kda_dims(cfg)


@pytest.mark.parametrize("backend,path", [("cpu", "xla"), ("tpu", "kernel")])
def test_a_traced_step_counts_the_decay_a_channel_and_the_form(backend, path,
                                                               monkeypatch):
    """``delta_update_decay_total{decay="channel"}`` once a K layer, and
    on a TPU at whole tiles the traced program holds the kernel's call."""
    import jax

    cfg = tiny_cfg(linear_attn_config={
        "short_conv_kernel_size": 4, "head_dim": 128, "num_heads": 2,
        "num_kv_heads": None})
    step, make_cache = _build(cfg, weights(cfg))
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    channel = lambda: dh.DECAY.labels(decay="channel").value
    head = lambda: dh.DECAY.labels(decay="head").value
    lowered = lambda: dh.LOWERED.labels(path=path).value
    before = channel(), head(), lowered()
    jaxpr = jax.make_jaxpr(step)(make_cache(8, 16), np.zeros(8, np.int32),
                                 np.zeros(8, np.int32))
    assert (channel() - before[0], head() - before[1],
            lowered() - before[2]) == (3, 0, 3)
    assert (dh.KERNEL_NAME in str(jaxpr)) == (path == "kernel")


# ---------------------------------------------------------------------------
# the server
# ---------------------------------------------------------------------------
def test_decode_server_end_to_end_with_slot_reuse_and_expert_counts():
    """Six requests through two slots: every one gets the tokens the
    reference's full forward ranks first (greedy, fp32), the server
    counted one reset an admission, and the builder's device counts
    reached the four expert counters."""
    import jax.numpy as jnp

    cfg = tiny_cfg()
    w = weights(cfg, seed=21)
    step, make_cache = _build(cfg, w)
    srv = DecodeServer(step, make_cache, eos_id=V, max_seq_len=32,
                       max_slots=2, slot_ladder=[2], len_ladder=[32],
                       steps_per_tick=2, kv_dtype="fp32", name="kda-e2e")
    try:
        srv.warmup()
        rng = np.random.RandomState(8)
        prompts = [rng.randint(0, V, n).astype(np.int32)
                   for n in (5, 9, 3, 7, 4, 6)]
        reqs = [srv.submit({"tokens": p}, max_new_tokens=6 + i)
                for i, p in enumerate(prompts)]
        outs = [r.result(timeout=WAIT)[0] for r in reqs]
        m = srv.metrics()["decode"]
    finally:
        srv.stop(drain=False, timeout=30)
    for i, (p, out) in enumerate(zip(prompts, outs)):
        assert len(out) == 6 + i
        full = np.concatenate([p, out])[None, :]
        logits = np.asarray(ref.forward(w, jnp.asarray(full), cfg,
                                        held=(4, 8)))[0]
        for j, tok in enumerate(out):
            row = logits[len(p) + j - 1]
            assert row.max() - row[tok] <= 1e-5 * (row.max() - row.min())
    assert m["state_resets"] == len(prompts)
    row_steps = sum(len(p) + len(o) - 1 for p, o in zip(prompts, outs))
    assert 0 < m["expert_assignments"] <= row_steps * 4 * 4
    assert m["expert_layer_steps"] > 0 and m["experts_touched"] > 0
