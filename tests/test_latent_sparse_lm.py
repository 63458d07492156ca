"""The latent sparse decoder (``model_type: deepseek_v32``) on the pooled
decode path: ``decoding.make_latent_sparse_lm_pooled_step_fn`` ->
``KVSlotPool`` -> ``DecodeServer``, at the sizes of the benchmark
configuration's ``rehearse`` group on the CPU (seeded), against the
benchmark's plain reference (``benchmark/configs/
deepseek_v3_2_reference.py``: float32, full forward, expanded, no cache).

What is new under the pool: a fourth kind of leaf (ONE latent row and ONE
index key a position, no heads), a read of the positions a learned
scorer names for each row, an absorbed step beside an expanded prefill,
YaRN rotary, and group-limited routing over a held share.
"""
import importlib.util
import json
import os

import numpy as np
import pytest

from paddle_tpu import decoding, monitor
from paddle_tpu import latent_sparse_lm as ls
from paddle_tpu.serving.decode import DecodeServer
from paddle_tpu.serving.kv_pool import KVSlotPool

from conftest import WAIT

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V, TOPK, CHUNK = 211, 16, 8


def _lanes(width):
    """A leaf's lanes: the width rounded up to whole 128-lane tiles."""
    return -(-width // 128) * 128


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(ROOT, "benchmark", "configs",
                         "deepseek_v3_2_reference.py"), "deepseek_reference")


def rehearse_cfg(**over):
    """The configuration file at its ``rehearse`` sizes (hidden 64, 4
    heads over a latent of 32 + 8 lanes, 4 index heads of 16, top 16; 16
    experts in 4 groups, 4 a token; dense then two sparse layers) with
    ALL the experts held unless ``over`` says otherwise."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "deepseek_v3_2.json")) as f:
        cfg = json.load(f)
    tiny = cfg.pop("rehearse")
    cfg.update({k: v for k, v in tiny.items() if not isinstance(v, dict)})
    cfg["rope_scaling"] = dict(cfg["rope_scaling"], **tiny["rope_scaling"])
    cfg.update(vocab_size=V, n_routed_experts=16)
    cfg.update(over)
    assert cfg["index_topk"] == TOPK
    return cfg


def weights(cfg, seed=0, dtype="float32", held=None):
    return ls.random_state(np.random.RandomState(seed), cfg, std=0.15,
                           dtype=dtype, held=held)


def _prefill_then_decode(step, make_cache, prefill, toks, n_chunks,
                         seq_len=64):
    """Every row: ``n_chunks`` prefill chunks, then one token a step to
    the end; one more row idle throughout.  Returns the logits per (row,
    position past the prefill) and the final cache."""
    import jax
    import jax.numpy as jnp

    B, S = toks.shape
    cache = make_cache(B + 1, seq_len)
    jstep, jpre = jax.jit(step), jax.jit(prefill)
    for b in range(B):
        for c in range(n_chunks):
            cache = jpre(cache, jnp.int32(b),
                         jnp.asarray(toks[b, c * CHUNK:(c + 1) * CHUNK]),
                         jnp.int32(c * CHUNK), jnp.int32(CHUNK))
    start, got = n_chunks * CHUNK, []
    for t in range(start, S):
        lg, cache = jstep(cache, np.append(toks[:, t], 0).astype(np.int32),
                          np.asarray([t] * B + [-1], np.int32))
        got.append(np.asarray(lg)[:B])
    return np.stack(got, axis=1), cache


def _reference_logits(w, toks, cfg, **kw):
    import jax.numpy as jnp

    return np.stack([np.asarray(ref.forward(w, jnp.asarray(row), cfg, **kw))
                     for row in toks])


# fp32: the step (absorbed) and the reference (expanded) differ in the
# order of float32 sums.  bf16: weights multiplied as stored, latent rows
# and index keys in bf16; a marginal position near rank 16 or a marginal
# expert may go the other way: the MEAN gap is held, the worst loosely.
# ``n_chunks`` 0: every position rides the step, contexts 1 .. 48 pass
# index_topk = 16 on the way; 3: the prefill's chunks cross it.
@pytest.mark.parametrize("dtype,kv_dtype,n_chunks,worst,mean", [
    ("float32", "fp32", 0, 5e-5, 5e-6), ("float32", "fp32", 3, 5e-5, 5e-6),
    ("bfloat16", "bf16", 3, 0.25, 2e-2)])
def test_steps_and_prefill_equal_the_reference_forward(dtype, kv_dtype,
                                                       n_chunks, worst, mean):
    import jax.numpy as jnp

    cfg = rehearse_cfg()
    w = weights(cfg, seed=3, dtype=dtype)
    step, make_cache, prefill = decoding.make_latent_sparse_lm_pooled_step_fn(
        w, cfg, kv_dtype=kv_dtype, prefill_tokens=CHUNK)
    assert prefill.chunk_tokens == CHUNK
    assert decoding.spec_of(make_cache).prefill_fn is prefill
    toks = np.random.RandomState(5).randint(0, V, (2, 48)).astype(np.int32)
    # the reference rounds the operands a bf16 run states as rounded
    rcfg = dict(cfg, matmul_inputs=None if dtype == "float32" else dtype)
    want = _reference_logits(w, toks, rcfg)[:, n_chunks * CHUNK:]
    got, cache = _prefill_then_decode(step, make_cache, prefill, toks,
                                      n_chunks)
    gap = np.abs(got - want).max(-1) / (want.max() - want.min())
    assert gap.max() <= worst and gap.mean() <= mean
    d = ls.dims(cfg)
    # one latent row and one index key a position; the idle row never
    # written
    for layer in cache["layers"]:
        assert layer["latent"].shape == (3, 64, _lanes(d.d_c + d.d_rope))
        assert layer["index_k"].shape == (3, 64, _lanes(d.d_index))
        # the lanes past the row's width are padding: never written
        assert not np.asarray(layer["latent"][..., d.d_c + d.d_rope:]).any()
        for leaf in layer.values():
            assert float(jnp.abs(leaf[2].astype("float32")).max()) == 0.0
            assert leaf.dtype == jnp.dtype(
                {"fp32": "float32", "bf16": "bfloat16"}[kv_dtype])
    # the counts are of steps: rows x top 4, no chunk among them
    steps = 48 - n_chunks * CHUNK
    assert np.asarray(cache["expert_stats"])[:, 0].tolist() == [
        steps * 2 * 4] * 2


def _harmed_reference(harm):
    """The reference with one mechanism changed, as a module."""
    mod = _load(os.path.join(ROOT, "benchmark", "configs",
                             "deepseek_v3_2_reference.py"),
                "deepseek_reference_" + harm)
    if harm == "dense_read":
        mod.selected = lambda scores, at, top_k: (
            np.arange(scores.shape[1])[None, :] <= at[:, None])
    elif harm == "no_index_rotary":
        mod._rope_head = lambda x, cfg, at=None: x
    elif harm == "no_relu":
        mod.index_scores = lambda qi, wi, ki: mod.jnp.einsum(
            "qhs,qh->qs", mod.jnp.einsum("qhd,sd->qhs", qi, ki), wi)
    elif harm == "no_yarn_scale":
        mod.softmax_scale = lambda cfg: (
            int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
        ) ** -0.5
    return mod


@pytest.mark.parametrize("harm,over", [
    ("dense_read", {}), ("no_index_rotary", {}), ("no_relu", {}),
    ("no_yarn_scale", {}), ("topk_half", {"index_topk": TOPK // 2}),
    ("one_group_more", {"topk_group": 3})])
def test_a_harmed_mechanism_shows_at_the_fp32_tolerance(harm, over):
    """The tolerance of the test above fails a reference that reads
    every position, scores without the indexer's rotary or ReLU, drops
    YaRN's scale, selects half as many, or keeps one group more."""
    cfg = rehearse_cfg()
    w = weights(cfg, seed=3)
    toks = np.random.RandomState(5).randint(0, V, (1, 48)).astype(np.int32)
    want = _reference_logits(w, toks, cfg)[:, 24:]
    mod = _harmed_reference(harm)
    import jax.numpy as jnp

    off = np.asarray(mod.forward(w, jnp.asarray(toks[0]),
                                 dict(cfg, **over)))[None, 24:]
    gap = np.abs(off - want).max(-1) / (want.max() - want.min())
    assert gap.max() > 1e-3 > 5e-5


def test_a_prefill_chunk_equals_its_steps_leaf_for_leaf():
    """Expanded (the chunk) and absorbed (the step) write the same
    leaves: 37 positions by steps, and by four chunks and a short one."""
    import jax
    import jax.numpy as jnp

    cfg = rehearse_cfg()
    w = weights(cfg, seed=6)
    step, make_cache, prefill = decoding.make_latent_sparse_lm_pooled_step_fn(
        w, cfg, kv_dtype="fp32", prefill_tokens=CHUNK)
    toks = np.random.RandomState(2).randint(0, V, (40,)).astype(np.int32)
    a, b = make_cache(2, 64), make_cache(2, 64)
    jstep, jpre = jax.jit(step), jax.jit(prefill)
    for t in range(37):
        _, a = jstep(a, np.asarray([0, toks[t]], np.int32),
                     np.asarray([-1, t], np.int32))
    for c in range(5):      # the last chunk short by three
        n = CHUNK if c < 4 else CHUNK - 3
        b = jpre(b, jnp.int32(1), jnp.asarray(toks[c * 8:c * 8 + 8]),
                 jnp.int32(c * 8), jnp.int32(n))
    for la, lb in zip(a["layers"], b["layers"]):
        for leaf in ("latent", "index_k"):
            np.testing.assert_allclose(np.asarray(la[leaf][1]),
                                       np.asarray(lb[leaf][1]), atol=5e-5)
            assert np.asarray(la[leaf][1, :37]).any()
            assert not np.asarray(lb[leaf][1, 37:]).any()   # short chunk
            assert not np.asarray(lb[leaf][0]).any()        # the other slot
    # the next step reads either cache alike
    la, _ = jstep(a, np.asarray([0, toks[37]], np.int32),
                  np.asarray([-1, 37], np.int32))
    lb, _ = jstep(b, np.asarray([0, toks[37]], np.int32),
                  np.asarray([-1, 37], np.int32))
    np.testing.assert_allclose(np.asarray(la)[1], np.asarray(lb)[1],
                               atol=5e-5)


def _plain_latent_inputs(x, w, p, pos, d):
    """``latent_inputs`` as the module's docstring writes it: no barrier."""
    import jax.numpy as jnp

    if d.q_rank is None:
        cq, q = None, ls.linear(x, w[p + "attn_q"])
    else:
        cq = ls.rms_norm(ls.linear(x, w[p + "attn_q_a"]), w[p + "q_a_norm"],
                         d.eps)
        q = ls.linear(cq, w[p + "attn_q_b"])
    q = q.reshape(x.shape[0], d.n_head, d.d_qk)
    ckr = ls.linear(x, w[p + "attn_kv_a"])
    row = jnp.concatenate(
        [ls.rms_norm(ckr[:, :d.d_c], w[p + "kv_a_norm"], d.eps),
         ls.rotate(ckr[:, d.d_c:], pos, d.inv_freq)], axis=-1)
    return cq, q[..., :d.d_nope], ls.rotate(q[..., d.d_nope:], pos,
                                            d.inv_freq), row


def _plain_index_inputs(x, cq, w, p, pos, d):
    """``index_inputs`` as the module's docstring writes it: no barrier."""
    import jax.numpy as jnp

    def turned(t):
        return jnp.concatenate([ls.rotate(t[..., :d.d_rope], pos, d.inv_freq),
                                t[..., d.d_rope:]], axis=-1)

    q = ls.linear(cq, w[p + "index_q"]).reshape(
        x.shape[0], d.n_index_head, d.d_index)
    k = ls.linear(x, w[p + "index_k"])
    mu = jnp.mean(k, axis=-1, keepdims=True)
    var = jnp.mean((k - mu) ** 2, axis=-1, keepdims=True)
    k = ((k - mu) / jnp.sqrt(var + d.ln_eps) * w[p + "index_k_norm"]
         + w[p + "index_k_norm_bias"])
    wi = ls.linear(x, w[p + "index_w"]) * float(
        d.n_index_head ** -0.5 * d.d_index ** -0.5)
    return turned(q), turned(k), wi


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("low_rank", [True, False],
                         ids=["q_lora_rank", "one_query_matrix"])
def test_the_projections_equal_the_plain_formula_bit_for_bit(low_rank, dtype):
    """A projection's product is COMPLETE before the reshape by heads
    sees it (``optimization_barrier`` behind ``attn_q_b`` and
    ``index_q``: the chip then reads both matrices as stored, PR 64): the
    identity on values — ``latent_inputs`` and ``index_inputs``, eager
    and jitted, are bit-equal to the formula written without it, with a
    query low rank and (``d.q_rank`` None: ONE ``attn_q`` product, no
    barrier taken) without."""
    import jax
    import jax.numpy as jnp

    cfg = rehearse_cfg()
    w = weights(cfg, seed=11, dtype=dtype)
    d = ls.dims(cfg)
    p = "lm_l1_"
    rng = np.random.RandomState(5)
    x = jnp.asarray(rng.randn(6, d.d_model), jnp.float32)
    pos = jnp.arange(6) + 17
    if not low_rank:
        d.q_rank = None
        w[p + "attn_q"] = jnp.asarray(
            0.15 * rng.randn(d.d_model, d.n_head * d.d_qk), dtype)

    def both(fn_latent, fn_index):
        def run(x, w):
            cq, qc, qr, row = fn_latent(x, w, p, pos, d)
            out = (qc, qr, row)
            if low_rank:
                out += (cq,) + tuple(fn_index(x, cq, w, p, pos, d))
            return out
        return run

    plain = both(_plain_latent_inputs, _plain_index_inputs)
    built = both(ls.latent_inputs, ls.index_inputs)
    for run in (lambda f: f(x, w), lambda f: jax.jit(f)(x, w)):
        want, got = run(plain), run(built)
        assert len(got) == (7 if low_rank else 3)
        for a, b in zip(want, got):
            assert a.dtype == b.dtype == jnp.float32
            np.testing.assert_array_equal(np.asarray(a), np.asarray(b))


def test_the_chunks_selection_is_the_steps_list_as_a_mask():
    """``chunk_select`` (a threshold and the tie rule) marks exactly the
    positions ``select_positions`` lists, ties included."""
    import jax.numpy as jnp

    rng = np.random.RandomState(4)
    c, t, heads, dim = 8, 32, 4, 16
    qi = jnp.asarray(rng.randn(c, heads, dim).astype("float32"))
    wi = jnp.asarray(rng.randn(c, heads).astype("float32"))
    keys = rng.randn(t, dim).astype("float32")
    keys[5] = keys[3]           # exact ties
    keys[20] = keys[3]
    keys = jnp.asarray(keys)
    q_pos = jnp.asarray([3, 6, 9, 14, 21, 27, 31, -1], jnp.int32)
    member = np.asarray(ls.chunk_select(qi, wi, keys, q_pos, 32, 6,
                                        key_block=8))
    scores = ls.index_scores(qi, wi, keys)
    sel, valid = (np.asarray(x) for x in ls.select_positions(scores, q_pos,
                                                             6))
    for i in range(c):
        assert sorted(np.flatnonzero(member[i])) == sorted(sel[i][valid[i]])
    assert member.sum(-1).tolist() == [4, 6, 6, 6, 6, 6, 6, 0]


def _scores(kind, n, t, rng):
    x = rng.randn(n, t)
    if kind == "halves":            # thousands of ties at the threshold
        x = np.round(x * 2) / 2
    elif kind == "few_values":
        x = rng.randint(0, 3, (n, t)) - 1.0
    elif kind == "all_equal":
        x = np.full((n, t), 0.25)
    elif kind == "negative":
        x = -np.abs(x) - 0.5
    elif kind == "mixed_sign":      # ties on both sides of zero
        x = np.round(x * 3) * 1e3
    elif kind == "signed_zeros":    # the floats' total order: -0.0 < 0.0
        x = np.where(rng.rand(n, t) < 0.5, 0.0, -0.0) * (
            rng.rand(n, t) < 0.7) + (rng.rand(n, t) < 0.05) * x
    return x.astype("float32")


@pytest.mark.parametrize("kind", ["random", "halves", "few_values",
                                  "all_equal", "negative", "mixed_sign",
                                  "signed_zeros"])
@pytest.mark.parametrize("t,k", [(256, 32), (200, 24), (40, 6), (96, 200)],
                         ids=["rung256", "rung200_no_power_of_two",
                              "rung40_one_block", "top_k_over_the_rung"])
def test_the_selection_is_lax_top_ks_set_in_position_order(kind, t, k):
    """``select_positions`` against ``lax.top_k`` of the masked scores
    (ties: the lowest position first): the same SET a row — rows idle, at
    ``ts`` 0, one under ``top_k`` live, exactly ``top_k`` live, one over,
    mid-rung and at the rung's end —; ``sel[valid]`` strictly ascending,
    the WHOLE list ascending (so unique) and in range, ``valid`` the first
    ``min(k, ts + 1)`` places; and the counter of its lowering moves."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import decode_attention as da

    kk = min(k, t)
    ts = np.minimum(np.asarray([-1, 0, kk - 2, kk - 1, kk, t // 2 + 3, t - 1],
                               np.int32), t - 1)
    rng = np.random.RandomState(len(kind) * 1000 + t)
    scores = jnp.asarray(_scores(kind, len(ts), t, rng))
    before = da.INDEX_SELECT_LOWERED.labels(path="threshold").value
    sel, valid = (np.asarray(x) for x in ls.select_positions(
        scores, jnp.asarray(ts), k))
    assert da.INDEX_SELECT_LOWERED.labels(
        path="threshold").value == before + 1
    live = np.arange(t)[None, :] <= ts[:, None]
    top, want = jax.lax.top_k(jnp.where(live, scores, -jnp.inf), kk)
    top, want = np.asarray(top), np.asarray(want)
    assert sel.shape == valid.shape == (len(ts), kk)
    assert sel.dtype == np.int32 and valid.dtype == bool
    for r in range(len(ts)):
        n_live = min(kk, max(int(ts[r]) + 1, 0))
        assert valid[r].sum() == n_live and valid[r, :n_live].all()
        assert sel[r][valid[r]].tolist() == sorted(
            want[r][top[r] > -np.inf].tolist())
        assert (np.diff(sel[r]) > 0).all(), sel[r]
        assert sel[r, 0] >= 0 and sel[r, -1] < t


def test_the_shares_of_the_grouped_layer_add_up():
    """model-configs section 4's test at this family's routing: the
    shares four chips holding 4 experts each give, the shared expert
    counted once, add up to what the uncut reference gives for the whole
    layer WITH groups."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import routed_experts as rx

    cfg = rehearse_cfg()
    d = ls.dims(cfg)
    w = weights(cfg, seed=4)
    p = "lm_l1_"
    rng = np.random.RandomState(6)
    f = jnp.asarray(rng.randn(24, cfg["hidden_size"]).astype("float32"))
    ts = jnp.asarray(rng.randint(0, 9, 24).astype(np.int32))
    with jax.default_matmul_precision("highest"):
        sel, gate = ref.routing(w, p, f, cfg)
        uncut = np.asarray(ref.experts(w, p, f, sel, gate, cfg))
    whole, stats = rx.expert_layer(f, w, p, ts, d)
    np.testing.assert_allclose(np.asarray(whole), uncut, atol=2e-5)
    parts, pairs = rx.shared_expert(f, w, p, d), 0
    for lo in range(0, 16, 4):
        held = dict(w, **{p + "experts_w13": w[p + "experts_w13"][lo:lo + 4],
                          p + "experts_w2": w[p + "experts_w2"][lo:lo + 4]})
        share, st = rx.expert_layer(f, held, p, ts, d, held=(lo, lo + 4),
                                    shared=False)
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref.experts(held, p, f, sel, gate, cfg,
                                          held=(lo, lo + 4), shared=False))
        np.testing.assert_allclose(np.asarray(share), want, atol=2e-5)
        parts, pairs = parts + share, pairs + int(st[0])
    np.testing.assert_allclose(np.asarray(parts), uncut, atol=2e-5)
    assert pairs == int(stats[0]) == 24 * d.top_k
    # the choice stayed inside two groups of four experts
    assert all(len({int(e) // 4 for e in row}) <= 2
               for row in np.asarray(sel))


def test_a_held_share_serves_what_the_references_share_gives():
    """The builder over experts 4-7 of 16 equals the reference's forward
    with the same held range (the rest's part left out, the shared
    expert in)."""
    cfg = rehearse_cfg(n_routed_experts=4)
    w = weights(cfg, seed=8, held=(4, 8))
    step, make_cache, prefill = decoding.make_latent_sparse_lm_pooled_step_fn(
        w, cfg, kv_dtype="fp32", held=(4, 8), prefill_tokens=CHUNK)
    assert decoding.spec_of(make_cache).n_expert == 4
    toks = np.random.RandomState(9).randint(0, V, (2, 32)).astype(np.int32)
    want = _reference_logits(w, toks, cfg, held=(4, 8))[:, 16:]
    got, _ = _prefill_then_decode(step, make_cache, prefill, toks, 2)
    gap = np.abs(got - want).max(-1) / (want.max() - want.min())
    assert gap.max() <= 5e-5


def test_yarn_blends_the_frequencies_and_scales_the_softmax():
    cfg = rehearse_cfg()
    inv = ls.yarn_inv_freq(cfg)
    plain = 10000.0 ** (-np.arange(0, 8, 2) / 8)
    # the fastest lane extrapolates, the slow ones are interpolated / 4
    np.testing.assert_allclose(inv, [plain[0]] + list(plain[1:] / 4),
                               rtol=1e-6)
    np.testing.assert_allclose(inv, np.asarray(ref.inv_freq(cfg)), rtol=1e-6)
    m = 0.1 * np.log(4) + 1
    assert ls.softmax_scale(cfg) == pytest.approx(24 ** -0.5 * m * m)
    # the published values: 192^-0.5 * 1.36889^2
    pub = dict(cfg, qk_nope_head_dim=128, qk_rope_head_dim=64,
               rope_scaling=dict(cfg["rope_scaling"], factor=40,
                                 original_max_position_embeddings=4096))
    assert ls.softmax_scale(pub) == pytest.approx(0.135234, rel=1e-5)
    assert ref.softmax_scale(pub) == pytest.approx(0.135234, rel=1e-5)
    assert ls.yarn_inv_freq(dict(pub, rope_scaling=None)).shape == (32,)


def test_dims_refuses_what_the_step_does_not_compute():
    cfg = rehearse_cfg()
    for over in ({"scoring_func": "softmax"}, {"tie_word_embeddings": True},
                 {"num_nextn_predict_layers": 1}, {"n_group": 3},
                 {"topk_group": 5}, {"attention_bias": True},
                 {"moe_layer_freq": 2}, {"hidden_act": "gelu"}):
        with pytest.raises(ValueError):
            ls.dims(dict(cfg, **over))
    with pytest.raises(ValueError):
        ls.yarn_inv_freq(dict(cfg, rope_scaling={"type": "linear",
                                                 "factor": 2}))
    shapes = ls.param_shapes(cfg, held=(4, 8))
    assert shapes["lm_l1_experts_w13"] == (4, 64, 64)
    assert shapes["lm_l1_router"] == (64, 16)
    assert shapes["lm_l0_attn_uk"] == (4, 16, 32)
    assert "lm_l0_router" not in shapes and "lm_l1_ffn_gate" not in shapes


# ---------------------------------------------------------------------------
# under the pool and the server
# ---------------------------------------------------------------------------
def _pool(cfg, w, len_ladder, slots=2, **kw):
    step, make_cache, _ = decoding.make_latent_sparse_lm_pooled_step_fn(
        w, cfg, kv_dtype="fp32", prefill_tokens=CHUNK)
    return KVSlotPool(step, make_cache, eos_id=V, max_slots=slots,
                      max_seq_len=len_ladder[-1], slot_ladder=[slots],
                      len_ladder=len_ladder, steps=4, kv_dtype="fp32",
                      **kw), make_cache


def test_the_pool_counts_and_declares_the_new_leaves():
    cfg = rehearse_cfg()
    d = ls.dims(cfg)
    pool, make_cache = _pool(cfg, weights(cfg), [64], prefix=True)
    assert pool.snapshots and pool.prefill_tokens == CHUNK
    # every cache leaf has positions but the counts (no slot axis)
    assert pool.recurrent_leaves == ["['expert_stats']"]
    assert not pool.ring_leaves
    per_position = d.n_layer * (_lanes(d.d_c + d.d_rope)
                                + _lanes(d.d_index)) * 4
    assert pool.kv_rung_bytes(2, 64) == 2 * 64 * per_position
    kv, latent = decoding.spec_of(make_cache).reads
    assert (kv.kind, latent.kind, latent.layers) == (
        "kv", "latent", d.n_layer)
    assert latent.rule(np.asarray([3, 16, 40])).tolist() == [3, 16, 16]


def test_a_snapshot_taken_and_seated_equals_the_prefilled_slot():
    """``snapshot`` -> ``admit_prefix`` over latent leaves: the slot's
    row of both leaves installed into another slot, which then decodes
    what the first did; the counts (no slot axis) are neither copied nor
    overwritten."""
    cfg = rehearse_cfg()
    w = weights(cfg, seed=5)
    pool, _ = _pool(cfg, w, [64], prefix=True)
    d = ls.dims(cfg)
    rng = np.random.RandomState(3)
    doc = rng.randint(0, V, 24).astype(np.int32)
    tail = rng.randint(0, V, 4).astype(np.int32)
    prompt = np.concatenate([doc, tail])
    # slot 0: the whole prompt by chunks (3, past index_topk) and steps
    state = pool.admit(pool.alloc(2, 64), 0, prompt, len(prompt), 40)
    state = pool.release(state, [0])
    for c in range(3):
        state = pool.prefill(state, 0, c * CHUNK, c == 2)
    snap = pool.snapshot(state, 0)
    assert [tuple(x.shape) for x in snap] == [(1,)] + [
        (64, _lanes(d.d_index)), (64, _lanes(d.d_c + d.d_rope))] * d.n_layer
    for _ in range(4):
        state = pool.chunk(state)
    counts = np.asarray(state["cache"]["expert_stats"]).copy()
    want = np.asarray(state["tokens"])[0, :40]
    # slot 1: seated over the snapshot
    state = pool.admit_prefix(state, 1, prompt, len(prompt), 40, snap, 24)
    assert np.array_equal(np.asarray(state["cache"]["expert_stats"]), counts)
    assert int(np.asarray(state["pos"])[1]) == 24
    for leaf in ("latent", "index_k"):
        np.testing.assert_array_equal(
            np.asarray(state["cache"]["layers"][1][leaf][1, :24]),
            np.asarray(state["cache"]["layers"][1][leaf][0, :24]))
    for _ in range(4):
        state = pool.chunk(state)
    assert np.array_equal(np.asarray(state["tokens"])[1, :40], want)


def test_decode_server_end_to_end_with_snapshots_and_the_selection_counters():
    """A document prefilled once in chunks, then requests seated over
    its snapshot in reused slots: every one gets the tokens the
    reference's full forward picks; the two selection counters say what
    was scored and what was read."""
    import jax.numpy as jnp

    cfg = rehearse_cfg()
    w = weights(cfg, seed=7)
    d = ls.dims(cfg)
    step, make_cache, _ = decoding.make_latent_sparse_lm_pooled_step_fn(
        w, cfg, kv_dtype="fp32", prefill_tokens=CHUNK)
    name = "latent-e2e"
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
            lg = np.asarray(ref.forward(w, jnp.asarray(full), cfg))
            want = lg[len(p) - 1:len(p) - 1 + len(out)].argmax(-1)
            assert np.array_equal(out, want)
        m = srv.metrics()["decode"]
        assert m["prefix_cache"]["hits"] == 4 and m["prefill_chunks"] == 4
        live = monitor.counter_value(
            "serving_decode_kv_positions_live_total", server=name)
        assert m["index_positions_scored"] == live * d.n_layer
        # every step past 16 positions read 16 of them in every layer
        assert 0 < m["latent_positions_selected"] < m["index_positions_scored"]
        assert m["latent_positions_selected"] % d.n_layer == 0
        assert m["expert_assignments"] > 0
        for key in ("index_positions_scored", "latent_positions_selected"):
            assert key in srv.statusz()["metrics"]["decode"]
    finally:
        srv.stop(drain=False, timeout=30.0)
