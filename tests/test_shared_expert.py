"""A SHARED expert beside the routed ones (``routed_experts.
shared_expert`` / ``expert_layer(..., shared=)``; ``model_type:
exaone_moe``), at tiny widths on the CPU (seeded), against the
benchmark's plain reference (``benchmark/configs/
k_exaone_236b_a23b_reference.py``: float32, every held expert applied to
every token).

The tie between a chip's share and the model: the routed parts of the
eight ``held`` ranges of an expert-parallel deployment, plus the shared
expert counted ONCE, equal the uncut layer.
"""
import importlib.util
import os

import numpy as np
import pytest

from paddle_tpu import mtp_routed_lm as mr
from paddle_tpu import routed_experts as rx

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
ATOL = 3e-5


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(ROOT, "benchmark", "configs",
                         "k_exaone_236b_a23b_reference.py"),
            "k_exaone_reference")


def tiny_cfg(**over):
    """The published expert count and experts per token (128, 8 a token,
    one shared) at width 16 over a hidden size of 32."""
    cfg = dict(
        vocab_size=61, hidden_size=32, num_hidden_layers=2,
        layer_types=["sliding_attention", "full_attention"],
        mlp_layer_types=["dense", "sparse"],
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        intermediate_size=48, moe_intermediate_size=16, num_experts=128,
        num_experts_per_tok=8, num_shared_experts=1, sliding_window=4,
        rms_norm_eps=1e-5, rope_parameters={"rope_theta": 1e4},
        routed_scaling_factor=2.5, norm_topk_prob=True,
        num_nextn_predict_layers=0)
    cfg.update(over)
    return cfg


def _layer(cfg, seed=0, n=24):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    w = mr.random_state(rng, cfg, std=0.3)
    f = jnp.asarray(rng.randn(n, cfg["hidden_size"]).astype("float32"))
    ts = jnp.asarray(rng.randint(0, 9, n).astype(np.int32))
    return w, f, ts


def _held(w, p, lo, hi):
    out = dict(w)
    out[p + "experts_w13"] = w[p + "experts_w13"][lo:hi]
    out[p + "experts_w2"] = w[p + "experts_w2"][lo:hi]
    return out


def test_the_layer_with_its_shared_expert_equals_the_reference():
    cfg = tiny_cfg()
    w, f, ts = _layer(cfg)
    d, p = mr.dims(cfg), "lm_l1_"
    out, _ = rx.expert_layer(f, w, p, ts, d)
    sel, gate = ref.routing(w, p, f[None], cfg)
    want = ref.experts(w, p, f[None], sel, gate, cfg)[0]
    np.testing.assert_allclose(np.asarray(out), np.asarray(want), atol=ATOL)
    # the shared term is what ``shared=False`` leaves out, and it is the
    # plain gated FFN of every row
    routed, _ = rx.expert_layer(f, w, p, ts, d, shared=False)
    np.testing.assert_allclose(
        np.asarray(out - routed), np.asarray(rx.shared_expert(f, w, p, d)),
        atol=ATOL)
    assert float(np.abs(np.asarray(out - routed)).mean()) > 1e-2


@pytest.mark.parametrize("chips", [8, 2])
def test_the_shares_add_up_with_the_shared_expert_counted_once(chips):
    """Eight chips of 16 experts each: the routed parts of all the held
    ranges plus ONE shared term are the uncut layer; summing shares that
    each added the shared term would count it ``chips`` times."""
    cfg = tiny_cfg()
    w, f, ts = _layer(cfg, seed=3)
    d, p = mr.dims(cfg), "lm_l1_"
    whole, whole_stats = rx.expert_layer(f, w, p, ts, d)
    per = d.n_expert // chips
    parts, pairs = [], 0
    for c in range(chips):
        held = (c * per, (c + 1) * per)
        y, st = rx.expert_layer(f, _held(w, p, *held), p, ts, d, held,
                                shared=False)
        parts.append(np.asarray(y))
        pairs += int(st[0])
    total = sum(parts) + np.asarray(rx.shared_expert(f, w, p, d))
    np.testing.assert_allclose(total, np.asarray(whole), atol=ATOL)
    assert pairs == int(whole_stats[0]) == f.shape[0] * d.top_k
    # the same against the reference's shares
    sel, gate = ref.routing(w, p, f[None], cfg)
    held = (per, 2 * per)
    want = ref.experts(_held(w, p, *held), p, f[None], sel, gate, cfg, held,
                       shared=False)[0]
    np.testing.assert_allclose(parts[1], np.asarray(want), atol=ATOL)
    twice = sum(parts) + chips * np.asarray(rx.shared_expert(f, w, p, d))
    assert np.abs(twice - np.asarray(whole)).max() > 1e-2


def test_a_layer_without_a_shared_expert_is_what_it_was():
    """``lfm2_moe`` / ``smallthinker`` dims have no ``n_shared``: the
    flag changes nothing there."""
    cfg = tiny_cfg(num_shared_experts=0)
    w, f, ts = _layer(cfg, seed=5)
    d, p = mr.dims(cfg), "lm_l1_"
    assert p + "shared_w13" not in w
    a, _ = rx.expert_layer(f, w, p, ts, d)
    b, _ = rx.expert_layer(f, w, p, ts, d, shared=False)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(b))
    del d.n_shared
    c, _ = rx.expert_layer(f, w, p, ts, d)
    np.testing.assert_array_equal(np.asarray(a), np.asarray(c))


# ---------------------------------------------------------------------------
# the same tie under an UNGROUPED, BIAS-FREE router (``pangu_ultra_moe``)
# ---------------------------------------------------------------------------
def _latent_mtp_layer(seed=5, n=24):
    """The published expert count and experts per token (256, 8 a token,
    one shared, no bias, one group) at width 16 over a hidden size of
    32, under ``latent_mtp_lm``'s names and its reference."""
    import jax.numpy as jnp

    from paddle_tpu import latent_mtp_lm as lm

    cfg = dict(
        vocab_size=61, hidden_size=32, num_hidden_layers=2,
        first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=16,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, intermediate_size=48, moe_intermediate_size=16,
        n_routed_experts=256, num_experts_per_tok=8, n_shared_experts=1,
        rms_norm_eps=1e-5, rope_theta=25600000.0, sandwich_norm=True,
        routed_scaling_factor=2.5, norm_topk_prob=True,
        num_nextn_predict_layers=1)
    rng = np.random.RandomState(seed)
    w = lm.random_state(rng, cfg, std=0.3)
    f = jnp.asarray(rng.randn(n, cfg["hidden_size"]).astype("float32"))
    ts = jnp.asarray(rng.randint(0, 9, n).astype(np.int32))
    pangu = _load(os.path.join(ROOT, "benchmark", "configs",
                               "openpangu_ultra_moe_718b_reference.py"),
                  "openpangu_reference_shares")
    return cfg, lm.dims(cfg), w, f, ts, pangu


@pytest.mark.parametrize("chips,p", [(32, "lm_l1_"), (32, "lm_mtp_"),
                                     (8, "lm_l1_")])
def test_the_shares_add_up_under_an_ungrouped_bias_free_router(chips, p):
    """Thirty-two chips of 8 experts each (the ``openpangu_ultra_moe_718b``
    deployment), in a layer and in the module's block: the routed parts
    of all the held ranges plus ONE shared term are the uncut layer, the
    choice made on the bare sigmoid scores over all 256."""
    cfg, d, w, f, ts, pangu = _latent_mtp_layer()
    assert not d.expert_bias and d.n_group == 1
    assert p + "expert_bias" not in w
    whole, whole_stats = rx.expert_layer(f, w, p, ts, d)
    per = d.n_expert // chips
    parts, pairs = [], 0
    for c in range(chips):
        held = (c * per, (c + 1) * per)
        y, st = rx.expert_layer(f, _held(w, p, *held), p, ts, d, held,
                                shared=False)
        parts.append(np.asarray(y))
        pairs += int(st[0])
    total = sum(parts) + np.asarray(rx.shared_expert(f, w, p, d))
    np.testing.assert_allclose(total, np.asarray(whole), atol=ATOL)
    assert pairs == int(whole_stats[0]) == f.shape[0] * d.top_k
    # the uncut layer and one share against the reference's
    sel, gate = pangu.routing(w, p, f, cfg)
    np.testing.assert_allclose(
        np.asarray(whole), np.asarray(pangu.experts(w, p, f, sel, gate, cfg)),
        atol=ATOL)
    held = (per, 2 * per)
    want = pangu.experts(_held(w, p, *held), p, f, sel, gate, cfg, held,
                         shared=False)
    np.testing.assert_allclose(parts[1], np.asarray(want), atol=ATOL)
    # the choice is the top-8 of the scores themselves
    scores = 1 / (1 + np.exp(-np.asarray(f) @ np.asarray(w[p + "router"])))
    np.testing.assert_array_equal(
        np.sort(np.asarray(sel), axis=-1),
        np.sort(np.argsort(-scores, axis=-1)[:, :d.top_k], axis=-1))
