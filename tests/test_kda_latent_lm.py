"""The decoder of Kimi Delta Attention beside position-free multi-head
LATENT attention, a leading dense FFN and routed experts (``model_type:
kimi_linear``) on the pooled decode path:
``decoding.make_kda_latent_lm_pooled_step_fn`` at a small size on the CPU
(seeded), against the benchmark's plain reference
(``benchmark/configs/kimi_linear_48b_a3b_reference.py``: float32, full
forward, the rule a scan over positions, attention expanded, no cache).

What is new: the delta rule's CHUNKWISE form
(``delta_hybrid_lm.gated_delta_chunk``) against the rule walked token by
token, for a decay a head and a decay a channel; a chunked prefill over
RECURRENT leaves beside latent sequence leaves in one ``CacheSpec``, so
that the pool serves ``prefix=True`` by whole-row snapshots that carry
the delta state, the conv window and the latent rows together; one query
matrix (``q_lora_rank`` null) and no rotary (``mla_use_nope``) in
``latent_sparse_lm.latent_inputs``.
"""
import importlib.util
import os

import numpy as np
import pytest

from conftest import WAIT

from paddle_tpu import decoding, monitor
from paddle_tpu import delta_hybrid_lm as dh
from paddle_tpu import kda_latent_lm as kl
from paddle_tpu import latent_sparse_lm as ls
from paddle_tpu import routed_experts as rx
from paddle_tpu.serving.decode import DecodeServer
from paddle_tpu.serving.kv_pool import KVSlotPool

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V, CHUNK, N_ALL = 97, 8, 16


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(ROOT, "benchmark", "configs",
                         "kimi_linear_48b_a3b_reference.py"),
            "kimi_linear_reference")


def tiny_cfg(held=(4, 8), **over):
    """K, K, M, K: a dense FFN then three expert layers (16 experts, 2 a
    token, one shared), 2 delta heads of 8 x 8, a latent of 16 + 4 lanes
    under 4 heads, under the release's own key names."""
    cfg = dict(
        model_type="kimi_linear", vocab_size=V, hidden_size=32,
        num_hidden_layers=4, first_k_dense_replace=1,
        num_attention_heads=4, num_key_value_heads=4, head_dim=8,
        linear_attn_config={"kda_layers": [1, 2, 4], "full_attn_layers": [3],
                            "head_dim": 8, "num_heads": 2,
                            "short_conv_kernel_size": 4},
        q_lora_rank=None, kv_lora_rank=16, qk_nope_head_dim=8,
        qk_rope_head_dim=4, v_head_dim=8, mla_use_nope=True,
        intermediate_size=48, moe_intermediate_size=16,
        num_experts=N_ALL, num_experts_per_token=2, num_shared_experts=1,
        moe_renormalize=True, moe_router_activation_func="sigmoid",
        num_expert_group=1, topk_group=1, routed_scaling_factor=2.446,
        rms_norm_eps=1e-5, rope_theta=10000.0, rope_scaling=None,
        tie_word_embeddings=False, num_nextn_predict_layers=0)
    if held is not None:
        cfg.update(num_experts=held[1] - held[0], num_experts_all=N_ALL)
    cfg.update(over)
    return cfg


def weights(cfg, seed=0, held=(4, 8)):
    return kl.random_state(np.random.RandomState(seed), cfg, std=0.3,
                           held=held)


def _build(cfg, w, held=(4, 8), chunk=CHUNK, kv_dtype="fp32"):
    return decoding.make_kda_latent_lm_pooled_step_fn(
        w, cfg, kv_dtype=kv_dtype, held=held, prefill_tokens=chunk)


# ---------------------------------------------------------------------------
# the chunkwise form against the rule walked token by token
# ---------------------------------------------------------------------------
def _rule_inputs(rng, c, h, dk, dv, channel, low=0.9):
    import jax.numpy as jnp

    f32 = jnp.float32
    q = dh.l2_norm(jnp.asarray(rng.randn(c, h, dk), f32)) * dk ** -0.5
    k = dh.l2_norm(jnp.asarray(rng.randn(c, h, dk), f32))
    v = jnp.asarray(rng.randn(c, h, dv), f32)
    alpha = jnp.asarray(rng.uniform(
        low, 1.0, (c, h, dk) if channel else (c, h)), f32)
    beta = jnp.asarray(rng.uniform(0.0, 1.0, (c, h)), f32)
    return q, k, v, alpha, beta


def _walk(q, k, v, alpha, beta, s0, start, n):
    """``n`` calls of the one-token rule over ONE row."""
    import jax
    import jax.numpy as jnp

    def one(s, xs):
        t, row = xs[0], [x[None] for x in xs[1:]]
        o, s = dh.xla_gated_delta_step(*row, s, (start + t)[None])
        return s, o[0]

    s, o = jax.jit(lambda s, xs: jax.lax.scan(one, s, xs))(
        s0[None], (jnp.arange(n),) + tuple(
            x[:n] for x in (q, k, v, alpha, beta)))
    return o, s[0]


@pytest.mark.parametrize("carried", [False, True])
@pytest.mark.parametrize("c", [1, 63, 64, 65, 512])
@pytest.mark.parametrize("channel", [False, True])
def test_the_chunk_form_equals_the_token_walk(channel, c, carried):
    """``alpha [C, H]`` and ``[C, H, dk]`` under ONE contract, sub-chunks
    that end inside, on and past a boundary, from zero at position 0 and
    from a carried state: outputs and the state at float32 rounding."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(c + 7 * channel + 13 * carried)
    h, dk, dv = 3, 8, 16
    args = _rule_inputs(rng, c, h, dk, dv, channel)
    s0 = jnp.asarray(rng.randn(h, dk, dv) if carried
                     else rng.randn(h, dk, dv) * 0 + 5.0, jnp.float32)
    start = jnp.int32(11 if carried else 0)     # position 0: read as zero
    want_o, want_s = _walk(*args, s0, start, c)
    got_o, got_s = jax.jit(dh.gated_delta_chunk)(*args, s0, start,
                                                 jnp.int32(c))
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               rtol=0, atol=3e-6)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               rtol=0, atol=5e-6)


@pytest.mark.parametrize("channel", [False, True])
def test_a_chunk_of_strong_decays_neither_overflows_nor_drifts(channel):
    """Decays down to 0.2 a channel and position over 64 positions (a
    cumulative decay of 1e-45: no float32 holds its inverse): every
    exponent is ``g_r - g_s`` with ``s <= r``, so nothing is inf or nan
    and the gap stays at float32 rounding."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(5)
    h, dk, dv = 2, 8, 8
    args = _rule_inputs(rng, 64, h, dk, dv, channel, low=0.2)
    s0 = jnp.asarray(rng.randn(h, dk, dv), jnp.float32)
    want_o, want_s = _walk(*args, s0, jnp.int32(3), 64)
    got_o, got_s = jax.jit(dh.gated_delta_chunk)(*args, s0, jnp.int32(3),
                                                 jnp.int32(64))
    assert np.isfinite(np.asarray(got_o)).all()
    assert np.isfinite(np.asarray(got_s)).all()
    np.testing.assert_allclose(np.asarray(got_o), np.asarray(want_o),
                               rtol=0, atol=3e-6)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               rtol=0, atol=3e-6)


def test_a_partial_chunk_leaves_the_state_of_its_valid_positions():
    """``n_valid`` of ``C``, heads laid two a lane tile in the leaf
    (``heads_per_tile``): the rows past ``n_valid`` neither decay nor
    write."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(9)
    h, dk, dv, g = 4, 8, 64, 2
    assert dh.heads_per_tile(h, dv) == g
    args = _rule_inputs(rng, 70, h, dk, dv, True)
    leaf = jnp.asarray(rng.randn(h // g, dk, g * dv), jnp.float32)
    want_o, want_s = _walk(*args, leaf, jnp.int32(2), 37)
    got_o, got_s = jax.jit(dh.gated_delta_chunk)(*args, leaf, jnp.int32(2),
                                                 jnp.int32(37))
    np.testing.assert_allclose(np.asarray(got_o)[:37], np.asarray(want_o),
                               rtol=0, atol=3e-6)
    np.testing.assert_allclose(np.asarray(got_s), np.asarray(want_s),
                               rtol=0, atol=5e-6)


def test_the_chunk_form_counts_a_path_of_its_own():
    import jax

    chunk = lambda: dh.LOWERED.labels(path="chunk").value
    channel = lambda: dh.DECAY.labels(decay="channel").value
    before = chunk(), channel()
    args = _rule_inputs(np.random.RandomState(0), 4, 2, 8, 8, True)
    jaxpr = jax.make_jaxpr(dh.gated_delta_chunk)(
        *args, np.zeros((2, 8, 8), np.float32), np.int32(0), np.int32(4))
    assert (chunk(), channel()) == (before[0] + 1, before[1] + 1)
    assert "triangular_solve" in str(jaxpr)
    assert dh.DELTA_CHUNK_SCOPE == "delta_chunk_prefill"


# ---------------------------------------------------------------------------
# the sizes and the schema
# ---------------------------------------------------------------------------
def test_the_release_s_key_names_give_the_layers_and_the_experts():
    d = kl.dims(tiny_cfg())
    assert d.kinds == (kl.KDA, kl.KDA, kl.LATENT, kl.KDA)   # 1-indexed lists
    assert d.dense == (True, False, False, False)
    assert d.expert_layers == (1, 2, 3)
    assert (d.n_expert, d.top_k, d.n_shared, d.norm_topk) == (16, 2, 1, True)
    assert d.routed_scale == 2.446 and not d.neg_eigval
    assert d.q_rank is None and not d.rotary
    assert (d.d_latent, d.d_qk, d.scale) == (20, 12, 12 ** -0.5)
    shapes = kl.param_shapes(tiny_cfg(), held=(4, 8))
    assert shapes["lm_l0_ffn_gate"] == (32, 48)
    assert "lm_l0_router" not in shapes and "lm_l1_ffn_gate" not in shapes
    assert shapes["lm_l1_router"] == (32, 16)
    assert shapes["lm_l1_experts_w13"] == (4, 32, 32)
    assert shapes["lm_l2_attn_q"] == (32, 4 * 12)
    assert shapes["lm_l2_attn_uk"] == (4, 8, 16)
    assert not any(k.endswith(("q_a_norm", "attn_q_a", "attn_q_b"))
                   for k in shapes)
    assert shapes["lm_l3_lin_fb"] == (8, 16)
    assert shapes["lm_l3_lin_dt_bias"] == (16,)


@pytest.mark.parametrize("over,match", [
    (dict(linear_attn_config={"kda_layers": [1, 2], "full_attn_layers": [3],
                              "head_dim": 8, "num_heads": 2,
                              "short_conv_kernel_size": 4}), "1-indexed"),
    (dict(q_lora_rank=16), "q_lora_rank"),
    (dict(num_expert_group=2), "ungrouped"),
    (dict(kda_allow_neg_eigval=True), "factor 2"),
    (dict(num_nextn_predict_layers=1), "num_nextn_predict_layers")])
def test_a_config_this_builder_cannot_serve_is_refused_by_name(over, match):
    with pytest.raises(ValueError, match=match):
        kl.dims(tiny_cfg(**over))


def test_the_latent_inputs_take_one_query_matrix_and_no_rotary():
    """``q_lora_rank`` null: ONE product, no ``q_a_norm``;
    ``mla_use_nope``: the shared lanes are what was projected, whatever
    the position — and rotated where the key is false."""
    import jax.numpy as jnp

    cfg = tiny_cfg()
    w = weights(cfg, seed=2)
    d = kl.dims(cfg)
    x = jnp.asarray(np.random.RandomState(1).randn(5, 32), jnp.float32)
    p = "lm_l2_"
    cq, qc, qr, row = ls.latent_inputs(x, w, p, jnp.arange(5) + 40, d)
    assert cq is None
    q = np.asarray(x @ w[p + "attn_q"]).reshape(5, 4, 12)
    np.testing.assert_allclose(np.asarray(qc), q[..., :8], atol=1e-6)
    np.testing.assert_allclose(np.asarray(qr), q[..., 8:], atol=1e-6)
    np.testing.assert_allclose(np.asarray(row)[:, 16:],
                               np.asarray(x @ w[p + "attn_kv_a"])[:, 16:],
                               atol=1e-6)
    rotated = ls.latent_inputs(x, w, p, jnp.arange(5) + 40,
                               kl.dims(tiny_cfg(mla_use_nope=False)))
    assert np.abs(np.asarray(rotated[2]) - np.asarray(qr)).max() > 0.1
    assert np.abs(np.asarray(rotated[3]) - np.asarray(row)).max() > 0.1
    np.testing.assert_array_equal(np.asarray(rotated[1]), np.asarray(qc))


def test_the_cache_declares_recurrent_leaves_beside_latent_rows():
    import jax

    cfg = tiny_cfg()
    _, make_cache, prefill = _build(cfg, weights(cfg))
    cache = jax.eval_shape(lambda: make_cache(3, 32))
    assert [sorted(c) for c in cache["layers"]] == [
        ["conv", "state"], ["conv", "state"], ["latent"], ["conv", "state"]]
    assert cache["layers"][2]["latent"].shape == (3, 32, 128)
    assert cache["layers"][0]["state"].shape == (3, 2, 8, 8)
    assert cache["layers"][0]["conv"].shape == (3, 3, 48)
    assert cache["expert_stats"].shape == (3, 4)
    spec = decoding.spec_of(make_cache)
    assert len(spec.names(lambda leaf: leaf.seq_axis is None
                          and leaf.slot)) == 6
    assert spec.names(lambda leaf: leaf.seq_axis is not None) == [
        "['layers'][2]['latent']"]
    (read,) = [r for r in spec.reads if r.kind == "latent"]
    assert read.layers == 1 and read.rule(7) == 7
    assert prefill.chunk_tokens == CHUNK
    assert not getattr(prefill, "lookahead", 0)
    assert spec.verify_fn is None and spec.mtp_fn is None


# ---------------------------------------------------------------------------
# step and prefill against the reference's full forward
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("held", [None, (4, 8)])
def test_steps_through_the_cache_equal_the_full_forward(held):
    """One token a step, an idle row beside the live ones, against the
    reference's forward (the rule a scan, attention expanded): logits."""
    import jax
    import jax.numpy as jnp

    cfg = tiny_cfg(held)
    w = weights(cfg, held=held)
    toks = np.random.RandomState(1).randint(0, V, (2, 20)).astype(np.int32)
    want = np.stack([np.asarray(ref.forward(w, jnp.asarray(t), cfg,
                                            held=held)) for t in toks])
    step, make_cache, _ = _build(cfg, w, held)
    cache, jstep = make_cache(3, 32), jax.jit(step)
    for t in range(toks.shape[1]):
        logits, cache = jstep(cache, jnp.asarray(np.append(toks[:, t], 0)),
                              jnp.asarray([t, t, -1], jnp.int32))
        np.testing.assert_allclose(np.asarray(logits)[:2], want[:, t],
                                   atol=3e-4, rtol=3e-4)
    stats = np.asarray(cache["expert_stats"])
    assert stats.shape == (3, 4) and (stats[:, 3] == 20).all()
    for leaf in jax.tree.leaves(cache["layers"]):
        assert not np.asarray(leaf)[2].any()        # the idle row


@pytest.mark.parametrize("chunk,fed", [(8, 16), (4, 14)])
def test_chunked_prefill_equals_steps_leaf_for_leaf_then_decodes(chunk, fed):
    """Whole chunks and a partial last one through every layer (the rule
    in its chunkwise form, attention expanded), then steps: every leaf
    equals what the steps write for the same positions, and the logits
    after the prefill equal the reference's full forward."""
    import jax
    import jax.numpy as jnp

    cfg = tiny_cfg()
    w = weights(cfg, seed=5)
    toks = np.random.RandomState(4).randint(0, V, 24).astype(np.int32)
    want = np.asarray(ref.forward(w, jnp.asarray(toks), cfg, held=(4, 8)))
    step, make_cache, prefill = _build(cfg, w, chunk=chunk)
    jstep, jpre = jax.jit(step), jax.jit(prefill)
    cache = make_cache(2, 32)
    for at in range(0, fed, chunk):
        rows = np.zeros(chunk, np.int32)
        n = min(chunk, fed - at)
        rows[:n] = toks[at:at + n]
        cache = jpre(cache, jnp.int32(1), jnp.asarray(rows), jnp.int32(at),
                     jnp.int32(n))
    by_steps = make_cache(2, 32)
    for t in range(fed):
        _, by_steps = jstep(by_steps, jnp.asarray([0, toks[t]]),
                            jnp.asarray([-1, t], jnp.int32))
    for a, b in zip(jax.tree.leaves(cache["layers"]),
                    jax.tree.leaves(by_steps["layers"])):
        np.testing.assert_allclose(np.asarray(a)[1], np.asarray(b)[1],
                                   atol=2e-5, rtol=2e-5)
        assert not np.asarray(a)[0].any()       # the other slot untouched
    # the prefill counts no expert rows; its steps do
    assert not np.asarray(cache["expert_stats"]).any()
    for t in range(fed, 24):
        logits, cache = jstep(cache, jnp.asarray([0, toks[t]]),
                              jnp.asarray([-1, t], jnp.int32))
        np.testing.assert_allclose(np.asarray(logits)[1], want[t],
                                   atol=3e-4, rtol=3e-4)


# ---------------------------------------------------------------------------
# under the pool and the server: snapshots over recurrent leaves
# ---------------------------------------------------------------------------
def _pool(cfg, w, len_ladder, slots=2, **kw):
    step, make_cache, _ = _build(cfg, w)
    return KVSlotPool(step, make_cache, eos_id=V, max_slots=slots,
                      max_seq_len=len_ladder[-1], slot_ladder=[slots],
                      len_ladder=len_ladder, steps=4, kv_dtype="fp32", **kw)


def test_a_request_over_a_snapshot_in_a_reused_slot_equals_it_prefilled():
    """``snapshot`` -> ``admit_prefix`` over recurrent AND sequence
    leaves: the slot's delta state, conv window and latent rows installed
    together into a slot ANOTHER request used, which then decodes what
    the same request prefilled whole decodes."""
    cfg = tiny_cfg()
    w = weights(cfg, seed=5)
    pool = _pool(cfg, w, [64], prefix=True)
    assert pool.snapshots and pool.prefill_tokens == CHUNK
    assert len(pool.recurrent_leaves) == 7      # 3 x (state, conv), counts
    rng = np.random.RandomState(3)
    doc = rng.randint(0, V, 24).astype(np.int32)
    prompt = np.concatenate([doc, rng.randint(0, V, 4).astype(np.int32)])
    other = rng.randint(0, V, 9).astype(np.int32)
    # slot 0: the whole prompt by chunks and steps
    state = pool.admit(pool.alloc(2, 64), 0, prompt, len(prompt), 40)
    state = pool.release(state, [0])
    for c in range(3):
        state = pool.prefill(state, 0, c * CHUNK, c == 2)
    snap = pool.snapshot(state, 0)
    # slot 1: another request first, so the slot is USED when seated
    state = pool.admit(state, 1, other, len(other), 20)
    for _ in range(10):
        state = pool.chunk(state)
    want = np.asarray(state["tokens"])[0, :40]
    assert all(np.abs(np.asarray(c[name])[1]).max() > 0
               for c in state["cache"]["layers"] for name in c)
    state = pool.release(state, [1])
    counts = np.asarray(state["cache"]["expert_stats"]).copy()
    state = pool.admit_prefix(state, 1, prompt, len(prompt), 40, snap, 24)
    assert np.array_equal(np.asarray(state["cache"]["expert_stats"]), counts)
    assert int(np.asarray(state["pos"])[1]) == 24
    for _ in range(4):
        state = pool.chunk(state)
    assert np.array_equal(np.asarray(state["tokens"])[1, :40], want)


def test_speculation_is_refused_with_the_pools_reason():
    from paddle_tpu.serving.speculative import SpeculativeConfig

    cfg = tiny_cfg()
    w = weights(cfg)
    step, make_cache, _ = _build(cfg, w)
    with pytest.raises(ValueError, match=r"speculative=.*recurrent leaves "
                       r".*a recurrent state has no"):
        _pool(cfg, w, [16], speculative=SpeculativeConfig(
            lambda c, t, ts: (None, c), step, make_cache, k=2))


def test_decode_server_end_to_end_with_snapshots_over_the_delta_state():
    """A document prefilled once in chunks, then requests seated over
    its snapshot in reused slots: every one gets the tokens the
    reference's full forward picks, and the server's prefill, snapshot
    and prefix-hit series count this builder's set-up."""
    import jax.numpy as jnp

    cfg = tiny_cfg()
    w = weights(cfg, seed=7)
    step, make_cache, _ = _build(cfg, w)
    name = "kda-latent-e2e"
    srv = DecodeServer(step, make_cache, eos_id=V, max_seq_len=64,
                       max_slots=2, slot_ladder=(2,), len_ladder=(64,),
                       steps_per_tick=4, prefix_cache=1 << 22,
                       kv_dtype="fp32", name=name)
    rng = np.random.RandomState(11)
    doc = rng.randint(0, V, 32).astype(np.int32)
    try:
        srv.warmup()
        first = srv.submit({"tokens": np.concatenate([doc, doc[:2]])},
                           max_new_tokens=2)
        first.result(WAIT)
        asked = []
        for n_q, n_new in ((3, 9), (5, 12), (2, 7), (4, 10)):
            p = np.concatenate([doc, rng.randint(0, V, n_q)]).astype(np.int32)
            asked.append((p, srv.submit({"tokens": p}, max_new_tokens=n_new)))
        for p, req in asked:
            out = np.asarray(req.result(WAIT)[0])
            full = np.concatenate([p, out])
            lg = np.asarray(ref.forward(w, jnp.asarray(full), cfg,
                                        held=(4, 8)))
            want = lg[len(p) - 1:len(p) - 1 + len(out)].argmax(-1)
            assert np.array_equal(out, want)
        m = srv.metrics()["decode"]
        assert m["prefix_cache"]["hits"] == 4 and m["prefill_chunks"] == 4
        assert monitor.counter_value("serving_decode_prefill_tokens_total",
                                     server=name) >= 32
        assert monitor.counter_value("serving_prefix_snapshots_total",
                                     cache=name) == 1
        live = monitor.counter_value(
            "serving_decode_kv_positions_live_total", server=name)
        assert m["latent_positions_selected"] == live   # ONE latent layer
        assert m["expert_assignments"] > 0
    finally:
        srv.stop(drain=False, timeout=30.0)


# ---------------------------------------------------------------------------
# the cut: sixteen chips a layer
# ---------------------------------------------------------------------------
def test_the_sixteen_shares_and_the_parts_counted_once_add_up():
    """The share test: routed over all 16 experts, the parts that sixteen
    disjoint held ranges give (no shared expert) plus ONE shared term are
    what the layer holding every expert gives — in the program's expert
    layer and in the reference's alike — and with the mixer counted once
    that is the uncut reference's layer output."""
    import jax.numpy as jnp

    cfg_all = tiny_cfg(held=None)
    d = kl.dims(cfg_all)
    w = weights(cfg_all, seed=7, held=None)
    p = "lm_l1_"
    rng = np.random.RandomState(1)
    f = jnp.asarray(rng.randn(10, 32), jnp.float32)
    ts = jnp.asarray([0, 1, 2, 3, -1, 5, 6, 7, 8, 9], jnp.int32)
    whole, stats = rx.expert_layer(f, w, p, ts, d)
    live = np.asarray(ts) >= 0
    assert int(stats[0]) == live.sum() * d.top_k
    shares, pairs = jnp.zeros_like(whole), 0
    for c in range(N_ALL):
        wc = dict(w)
        for k in ("experts_w13", "experts_w2"):
            wc[p + k] = w[p + k][c:c + 1]
        part, st = rx.expert_layer(f, wc, p, ts, d, (c, c + 1), shared=False)
        shares = shares + part
        pairs += int(st[0])
    assert pairs == live.sum() * d.top_k       # every pair in ONE share
    total = shares + rx.shared_expert(f, w, p, d)
    np.testing.assert_allclose(np.asarray(total)[live],
                               np.asarray(whole)[live], rtol=2e-6, atol=3e-6)
    # the reference's own shares, against its uncut BLOCK: the mixer and
    # the norms (every chip holds them whole) counted once
    h = jnp.asarray(rng.randn(12, 32), jnp.float32)
    uncut, _ = ref.block(w, p, h, cfg_all, (ref.K_LAYER, False))
    by_share = [ref.block(
        {**w, p + "experts_w13": w[p + "experts_w13"][c:c + 1],
         p + "experts_w2": w[p + "experts_w2"][c:c + 1]},
        p, h, cfg_all, (ref.K_LAYER, False), held=(c, c + 1),
        shared=(c == 0))[0] for c in range(N_ALL)]
    # a share's block output is the residual after the mixer + its part:
    # the parts summed over ONE residual
    dense_part = ref.block(
        {**w, p + "experts_w13": w[p + "experts_w13"][:0],
         p + "experts_w2": w[p + "experts_w2"][:0]},
        p, h, cfg_all, (ref.K_LAYER, False), held=(0, 0), shared=False)[0]
    total = dense_part + sum(b - dense_part for b in by_share)
    np.testing.assert_allclose(np.asarray(total), np.asarray(uncut),
                               rtol=1e-5, atol=1e-5)
