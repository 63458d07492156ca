"""paddle_tpu.routed_experts: the router, the dispatch by expert, the
held experts' share of a mixture layer, and the gated short convolution,
at tiny widths on the CPU (seeded), against a per-token loop and the
benchmark's plain reference (``benchmark/configs/
lfm2_24b_a2b_reference.py``: float32, every expert applied to every
token).

Tolerance: float32 weights, so both sides compute every product and sum
in float32 and differ in the order of sums of at most 64 terms of O(1):
2e-5 absolute.
"""
import importlib.util
import os

import numpy as np
import pytest

from paddle_tpu import routed_experts as rx

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ATOL = 2e-5


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(ROOT, "benchmark", "configs",
                         "lfm2_24b_a2b_reference.py"), "lfm2_reference")


def tiny_cfg(n_expert=64, top_k=4, layers=("conv", "full_attention", "conv")):
    """LFM2-MoE's shape of block at tiny widths: the published expert
    count and experts per token, one dense layer, then expert layers."""
    return dict(
        vocab_size=97, hidden_size=32, num_hidden_layers=len(layers),
        layer_types=list(layers), num_dense_layers=1,
        num_attention_heads=4, num_key_value_heads=2, intermediate_size=48,
        moe_intermediate_size=16, num_experts=n_expert,
        num_experts_per_tok=top_k, conv_L_cache=3, conv_bias=False,
        norm_eps=1e-5, norm_topk_prob=True, routed_scaling_factor=1,
        use_expert_bias=True,
        rope_parameters={"rope_theta": 1000000, "rope_type": "default"})


def weights(cfg, seed=0, dtype="float32", **kw):
    return rx.random_state(np.random.RandomState(seed), cfg, std=0.3,
                           dtype=dtype, **kw)


def _layer_inputs(cfg, n=12, seed=1):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    f = jnp.asarray(rng.randn(n, cfg["hidden_size"]).astype("float32"))
    ts = jnp.asarray(rng.randint(0, 9, n).astype(np.int32))
    return f, ts


def _held(w, p, lo, hi):
    """The weights a chip that holds experts ``lo..hi - 1`` has."""
    return dict(w, **{p + "experts_w13": w[p + "experts_w13"][lo:hi],
                      p + "experts_w2": w[p + "experts_w2"][lo:hi]})


P = "lm_l1_"


# ---------------------------------------------------------------------------
# the router
# ---------------------------------------------------------------------------
def test_the_bias_changes_the_choice_and_never_the_weights():
    import jax.numpy as jnp

    cfg = tiny_cfg()
    d = rx.dims(cfg)
    w = weights(cfg, bias_range=0.3)
    f, _ = _layer_inputs(cfg, n=40)
    sel, gate = (np.asarray(a) for a in rx.route(
        f, w[P + "router"], w[P + "expert_bias"], d))
    sel0, gate0 = (np.asarray(a) for a in rx.route(
        f, w[P + "router"], jnp.zeros_like(w[P + "expert_bias"]), d))
    # it chooses: other experts than an unbiased router picks
    assert (np.sort(sel, -1) != np.sort(sel0, -1)).any()
    # it does not weigh: the gates are the normalised UNBIASED scores of
    # whatever was chosen
    s = 1.0 / (1.0 + np.exp(-(np.asarray(f, np.float64)
                              @ np.asarray(w[P + "router"], np.float64))))
    picked = np.take_along_axis(s, sel, -1)
    np.testing.assert_allclose(
        gate, picked / (picked.sum(-1, keepdims=True) + 1e-6), atol=1e-6)
    # and the choice is the top-k of score PLUS bias
    want = np.argsort(-(s + np.asarray(w[P + "expert_bias"])), -1)[:, :d.top_k]
    assert (np.sort(sel, -1) == np.sort(want, -1)).all()
    assert gate.dtype == np.float32 and sel.dtype == np.int32


def test_no_normalisation_and_a_scaling_factor_are_honoured():
    cfg = dict(tiny_cfg(), norm_topk_prob=False, routed_scaling_factor=2.5)
    d = rx.dims(cfg)
    w = weights(cfg)
    f, _ = _layer_inputs(cfg)
    sel, gate = (np.asarray(a) for a in rx.route(
        f, w[P + "router"], w[P + "expert_bias"], d))
    s = 1.0 / (1.0 + np.exp(-(np.asarray(f, np.float64)
                              @ np.asarray(w[P + "router"], np.float64))))
    np.testing.assert_allclose(gate, 2.5 * np.take_along_axis(s, sel, -1),
                               atol=1e-5)


def test_dispatch_sorts_pairs_by_expert_and_drops_idle_and_absent():
    import jax.numpy as jnp

    sel = jnp.asarray([[3, 0], [1, 3], [2, 1], [0, 2]], jnp.int32)
    live = jnp.asarray([True, True, False, True])
    order, sizes, kept = (np.asarray(a) for a in rx.dispatch(
        sel, live, (1, 4), 4))
    # held experts 1..3: row 0 -> 3; row 1 -> 1, 3; row 2 idle; row 3 -> 2
    assert sizes.tolist() == [1, 1, 2]
    assert kept.tolist() == [[True, False], [True, True],
                             [False, False], [False, True]]
    # the first sum(sizes) sorted places are the kept pairs, by expert,
    # in (row, choice) order inside an expert
    assert order[:4].tolist() == [2, 7, 0, 3]


# ---------------------------------------------------------------------------
# the layer
# ---------------------------------------------------------------------------
def _per_token_loop(f, w, p, ts, d, lo=0, hi=None):
    """Each live row against each of its chosen, held experts, one at a
    time, in float64."""
    hi = d.n_expert if hi is None else hi
    sel, gate = (np.asarray(a) for a in rx.route(
        f, w[p + "router"], w[p + "expert_bias"], d))
    w13 = np.asarray(w[p + "experts_w13"], np.float64)
    w2 = np.asarray(w[p + "experts_w2"], np.float64)
    f64 = np.asarray(f, np.float64)
    out = np.zeros_like(f64)
    for n in range(f64.shape[0]):
        if int(ts[n]) < 0:
            continue
        for e, g in zip(sel[n], gate[n]):
            if lo <= e < hi:
                gu = f64[n] @ w13[e - lo]
                a = gu[:d.d_expert]
                out[n] += g * ((a / (1 + np.exp(-a)) * gu[d.d_expert:])
                               @ w2[e - lo])
    return out, sel


def test_layer_equals_a_per_token_loop_over_its_chosen_experts():
    cfg = tiny_cfg()
    d = rx.dims(cfg)
    w = weights(cfg)
    f, ts = _layer_inputs(cfg)
    got, stats = rx.expert_layer(f, w, P, ts, d)
    want, sel = _per_token_loop(f, w, P, np.asarray(ts), d)
    np.testing.assert_allclose(np.asarray(got), want, atol=ATOL)
    counts = np.bincount(sel.reshape(-1), minlength=d.n_expert)
    assert np.asarray(stats).tolist() == [
        sel.size, int((counts > 0).sum()), int(counts.max()), 1]


def test_the_shares_add_up():
    """model-configs section 4's test: the parts of the result that four
    chips holding 16 experts each give add up to what one chip holding
    all 64 gives, which is what the uncut reference gives for the whole
    layer; and each share equals the reference's share."""
    import jax
    import jax.numpy as jnp

    cfg = tiny_cfg()
    d = rx.dims(cfg)
    w = weights(cfg, seed=4)
    f, ts = _layer_inputs(cfg, n=24, seed=6)
    whole, stats = rx.expert_layer(f, w, P, ts, d)
    with jax.default_matmul_precision("highest"):
        uncut = np.asarray(ref.experts(w, P, f[None], cfg)[0])
    np.testing.assert_allclose(np.asarray(whole), uncut, atol=ATOL)
    parts, pairs = jnp.zeros_like(whole), 0
    for lo in range(0, 64, 16):
        held = _held(w, P, lo, lo + 16)
        share, st = rx.expert_layer(f, held, P, ts, d, held=(lo, lo + 16))
        with jax.default_matmul_precision("highest"):
            want = np.asarray(ref.experts(held, P, f[None], cfg,
                                          held=(lo, lo + 16))[0])
        np.testing.assert_allclose(np.asarray(share), want, atol=ATOL)
        parts, pairs = parts + share, pairs + int(st[0])
    np.testing.assert_allclose(np.asarray(parts), uncut, atol=ATOL)
    # every (row, choice) pair was some share's, once
    assert pairs == int(stats[0]) == 24 * d.top_k


@pytest.mark.parametrize("n_expert,top_k,held,rows", [
    (320, 8, (0, 40), 24),       # solar_open2_250b's router: ~0.6 rows a group
    (320, 8, (280, 320), 24),    # the last chip's range
    (128, 8, (16, 32), 6),       # more held experts than pairs that reach them
    (64, 4, (60, 64), 3)])
def test_a_held_eighth_of_a_wide_router_at_a_few_rows_a_group(n_expert, top_k,
                                                              held, rows):
    """A router far wider than the held range, groups far under a row
    tile (most of them empty): the share is the per-token loop over the
    held experts alone, and the counts are the held groups' own."""
    cfg = tiny_cfg(n_expert=n_expert, top_k=top_k)
    d = rx.dims(cfg)
    w = weights(cfg, seed=9)
    f, ts = _layer_inputs(cfg, n=rows, seed=3)
    lo, hi = held
    got, stats = rx.expert_layer(f, _held(w, P, lo, hi), P, ts, d, held=held)
    want, sel = _per_token_loop(f, _held(w, P, lo, hi), P, np.asarray(ts), d,
                                lo, hi)
    np.testing.assert_allclose(np.asarray(got), want, atol=ATOL)
    counts = np.bincount(sel.reshape(-1), minlength=n_expert)[lo:hi]
    assert np.asarray(stats).tolist() == [
        int(counts.sum()), int((counts > 0).sum()), int(counts.max()), 1]
    assert counts.sum() < rows * top_k          # most pairs go elsewhere


def test_an_idle_row_is_routed_nowhere_and_counted_nowhere():
    import jax.numpy as jnp

    cfg = tiny_cfg(n_expert=8, top_k=2)
    d = rx.dims(cfg)
    w = weights(cfg)
    f, _ = _layer_inputs(cfg, n=6)
    ts = jnp.asarray([0, -1, 5, -1, -1, 2], jnp.int32)
    got, stats = rx.expert_layer(f, w, P, ts, d)
    got = np.asarray(got)
    assert not got[[1, 3, 4]].any()
    assert got[[0, 2, 5]].any(axis=1).all()
    live_only, stats_live = rx.expert_layer(f[jnp.asarray([0, 2, 5])], w, P,
                                            ts[jnp.asarray([0, 2, 5])], d)
    np.testing.assert_allclose(got[[0, 2, 5]], np.asarray(live_only),
                               atol=ATOL)
    assert np.asarray(stats).tolist() == np.asarray(stats_live).tolist()
    assert int(stats[0]) == 3 * d.top_k
    # nobody live: nothing counted, not even the step
    _, none = rx.expert_layer(f, w, P, jnp.full((6,), -1, jnp.int32), d)
    assert np.asarray(none).tolist() == [0, 0, 0, 0]


def test_bf16_weights_are_multiplied_as_stored_and_the_router_stays_float32():
    import jax
    import jax.numpy as jnp

    cfg = tiny_cfg(n_expert=8, top_k=2)
    d = rx.dims(cfg)
    w = weights(cfg, dtype="bfloat16")
    assert w[P + "router"].dtype == np.float32
    assert w[P + "experts_w13"].dtype == jnp.bfloat16
    f, ts = _layer_inputs(cfg)
    jaxpr = jax.make_jaxpr(lambda f: rx.expert_layer(f, w, P, ts, d))(f)
    # no expert matrix is converted: only activations are
    stacked = {w[P + "experts_w13"].shape, w[P + "experts_w2"].shape}
    assert not [e for e in jaxpr.jaxpr.eqns
                if e.primitive.name == "convert_element_type"
                and e.outvars[0].aval.shape in stacked]
    got, _ = rx.expert_layer(f, w, P, ts, d)
    want, _ = _per_token_loop(f, {k: np.asarray(v, np.float32)
                                  for k, v in w.items()}, P,
                              np.asarray(ts), d)
    assert np.abs(np.asarray(got) - want).max() < 0.02 * np.abs(want).max()


# ---------------------------------------------------------------------------
# the gated short convolution
# ---------------------------------------------------------------------------
def _conv_rows(cfg, w, r, staggered=False):
    """``r`` [B, S, D] through ``short_conv_step`` one position a step;
    with ``staggered`` row b starts b steps late and idles before."""
    import jax
    import jax.numpy as jnp

    d = rx.dims(cfg)
    b, s, _ = r.shape
    conv = jnp.asarray(np.random.RandomState(9).randn(
        b, d.conv_len - 1, d.d_model).astype("float32"))  # a past occupant
    step = jax.jit(lambda x, c, ts: rx.short_conv_step(x, w, "lm_l0_", c,
                                                       ts, d))
    out = np.zeros(r.shape, "float32")
    for t in range(s + (b - 1 if staggered else 0)):
        ts = np.array([t - (i if staggered else 0) for i in range(b)])
        ts = np.where((ts >= 0) & (ts < s), ts, -1).astype(np.int32)
        x = np.stack([r[i, max(ts[i], 0)] for i in range(b)])
        o, conv_new = step(jnp.asarray(x), conv, jnp.asarray(ts))
        for i in range(b):
            if ts[i] >= 0:
                out[i, ts[i]] = np.asarray(o)[i]
            else:   # an idle row's state is kept as it was
                np.testing.assert_array_equal(np.asarray(conv_new)[i],
                                              np.asarray(conv)[i])
        conv = conv_new
    return out


@pytest.mark.parametrize("staggered", [False, True])
def test_short_conv_steps_equal_the_causal_convolution(staggered):
    """T steps from whatever a slot held before equal the reference's
    depthwise convolution over the whole sequence: the state is read as
    zero at position 0."""
    import jax
    import jax.numpy as jnp

    cfg = tiny_cfg()
    w = weights(cfg, seed=2)
    r = np.random.RandomState(3).randn(3, 7, 32).astype("float32")
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.short_conv(w, "lm_l0_", jnp.asarray(r), cfg))
    np.testing.assert_allclose(_conv_rows(cfg, w, r, staggered), want,
                               atol=ATOL)


def test_short_conv_without_the_reset_starts_from_the_past_occupant(
        monkeypatch):
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(rx, "starts_fresh",
                        lambda ts: jnp.zeros(ts.shape, bool))
    cfg = tiny_cfg()
    w = weights(cfg, seed=2)
    r = np.random.RandomState(3).randn(2, 5, 32).astype("float32")
    with jax.default_matmul_precision("highest"):
        want = np.asarray(ref.short_conv(w, "lm_l0_", jnp.asarray(r), cfg))
    got = _conv_rows(cfg, w, r)
    assert np.abs(got[:, 0] - want[:, 0]).max() > 1e-2
    # the window is conv_L_cache - 1 long: from position 2 on it holds
    # only the row's own inputs
    np.testing.assert_allclose(got[:, 2:], want[:, 2:], atol=ATOL)


def test_dims_refuses_what_the_step_does_not_compute():
    with pytest.raises(ValueError, match="layer_types"):
        rx.dims(dict(tiny_cfg(), layer_types=["conv", "sliding_attention",
                                              "conv"]))
    with pytest.raises(ValueError, match="layer_types"):
        rx.dims(dict(tiny_cfg(), num_hidden_layers=2))
    with pytest.raises(ValueError, match="conv_bias"):
        rx.dims(dict(tiny_cfg(), conv_bias=True))


def test_param_shapes_hold_gate_and_up_as_one_matrix():
    cfg = tiny_cfg()
    shapes = rx.param_shapes(cfg)
    assert shapes["lm_l1_experts_w13"] == (64, 32, 32)
    assert shapes["lm_l1_experts_w2"] == (64, 16, 32)
    assert "lm_l0_experts_w13" not in shapes and "lm_l0_ffn_gate" in shapes
    assert "lm_l1_attn_q" in shapes and "lm_l1_conv_in" not in shapes
    assert "lm_head" not in shapes      # tied to lm_emb


# ---------------------------------------------------------------------------
# group-limited selection (``d.n_group`` > 1: ``latent_sparse_lm``)
# ---------------------------------------------------------------------------
def _grouped(d, n_group, topk_group):
    from types import SimpleNamespace

    return SimpleNamespace(**dict(vars(d), n_group=n_group,
                                  topk_group=topk_group))


@pytest.mark.parametrize("n_group,topk_group", [(8, 4), (4, 1), (16, 3)])
def test_group_limited_route_equals_a_plain_loop(n_group, topk_group):
    """Row by row in numpy: a group's score is the sum of its two largest
    ``s + b``, the best ``topk_group`` groups stay, the top-k runs inside
    them, and the weights are the bare sigmoids of the chosen."""
    import jax.numpy as jnp

    cfg = tiny_cfg()
    d = _grouped(rx.dims(cfg), n_group, topk_group)
    w = weights(cfg, seed=2, bias_range=0.3)
    f, _ = _layer_inputs(cfg, n=16, seed=3)
    sel, gate = (np.asarray(a) for a in rx.route(
        f, w[P + "router"], w[P + "expert_bias"], d))
    s = 1 / (1 + np.exp(-(np.asarray(f, np.float64)
                          @ np.asarray(w[P + "router"], np.float64))))
    z = s + np.asarray(w[P + "expert_bias"], np.float64)
    size = d.n_expert // n_group
    for n in range(16):
        score = [np.sort(z[n, g * size:(g + 1) * size])[-2:].sum()
                 for g in range(n_group)]
        best = set(np.argsort(score)[::-1][:topk_group].tolist())
        inside = [e for e in range(d.n_expert) if e // size in best]
        want = sorted(inside, key=lambda e: -z[n, e])[:d.top_k]
        assert sel[n].tolist() == want
        np.testing.assert_allclose(gate[n], s[n, want] / (s[n, want].sum()
                                                          + 1e-6), rtol=1e-5)
    assert {int(e) // size for e in sel.reshape(-1)} <= set(range(n_group))
    assert all(len({int(e) // size for e in row}) <= topk_group
               for row in sel)


def test_one_group_is_todays_route_bit_for_bit():
    """``n_group`` 1 (or absent) leaves the function as it was: the same
    choices and the same float32 weights, bit for bit."""
    cfg = tiny_cfg()
    d = rx.dims(cfg)
    w = weights(cfg, seed=5, bias_range=0.3)
    f, _ = _layer_inputs(cfg, n=32, seed=7)
    assert not hasattr(d, "n_group")
    sel0, gate0 = rx.route(f, w[P + "router"], w[P + "expert_bias"], d)
    sel1, gate1 = rx.route(f, w[P + "router"], w[P + "expert_bias"],
                           _grouped(d, 1, 1))
    assert np.array_equal(np.asarray(sel0), np.asarray(sel1))
    assert np.asarray(gate0).tobytes() == np.asarray(gate1).tobytes()
    # ... and all the groups kept is no limit at all
    sel8, gate8 = rx.route(f, w[P + "router"], w[P + "expert_bias"],
                           _grouped(d, 8, 8))
    assert np.array_equal(np.asarray(sel0), np.asarray(sel8))
    assert np.asarray(gate0).tobytes() == np.asarray(gate8).tobytes()
