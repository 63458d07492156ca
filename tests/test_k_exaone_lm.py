"""The decoder with a shared expert, closing norms and a multi-token-
prediction module (``model_type: exaone_moe``) on the pooled decode
path: ``decoding.make_mtp_routed_lm_pooled_step_fn`` at a small size on
the CPU (seeded), against the benchmark's plain reference
(``benchmark/configs/k_exaone_236b_a23b_reference.py``: float32, full
forward, no cache and no ring).

What is new: q/k norms and branch-closing norms, a leading dense layer,
the router on the FFN's own input, a shared expert beside a held range
of the experts, a window SMALLER than the prefill chunk, a K-wide verify
that yields hidden states, and the module's K-wide pass over leaves of
its own.
"""
import importlib.util
import os

import numpy as np
import pytest

from paddle_tpu import decoding
from paddle_tpu import mtp_routed_lm as mr

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V, WINDOW, CHUNK = 97, 4, 8


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(ROOT, "benchmark", "configs",
                         "k_exaone_236b_a23b_reference.py"),
            "k_exaone_reference")


def tiny_cfg(**over):
    """Dense L, then L L G L (the cut's own pattern), 8 experts of which
    2 a token, one shared, a window of 4, one module."""
    cfg = dict(
        vocab_size=V, hidden_size=32, num_hidden_layers=5,
        layer_types=["sliding_attention"] * 3
        + ["full_attention", "sliding_attention"],
        mlp_layer_types=["dense"] + ["sparse"] * 4,
        num_attention_heads=4, num_key_value_heads=2, head_dim=8,
        intermediate_size=48, moe_intermediate_size=16, num_experts=8,
        num_experts_per_tok=2, num_shared_experts=1, sliding_window=WINDOW,
        rms_norm_eps=1e-5, rope_parameters={"rope_theta": 1e4},
        routed_scaling_factor=2.5, norm_topk_prob=True,
        scoring_func="sigmoid", n_group=1, topk_group=1,
        num_nextn_predict_layers=1, mtp_layer_types=["full_attention"],
        tie_word_embeddings=False)
    cfg.update(over)
    return cfg


def weights(cfg, seed=0, held=None):
    return mr.random_state(np.random.RandomState(seed), cfg, std=0.3,
                           held=held)


def _build(cfg, w, held=None, chunk=CHUNK):
    return decoding.make_mtp_routed_lm_pooled_step_fn(
        w, cfg, kv_dtype="fp32", held=held, prefill_tokens=chunk)


def test_param_shapes_name_the_shared_expert_and_the_module():
    shapes = mr.param_shapes(tiny_cfg(), held=(2, 6))
    assert shapes["lm_l0_ffn_gate"] == (32, 48)
    assert "lm_l0_router" not in shapes
    assert shapes["lm_l1_experts_w13"] == (4, 32, 32)
    assert shapes["lm_l1_router"] == (32, 8)
    assert shapes["lm_l1_shared_w13"] == (32, 32)
    assert shapes["lm_mtp_eh"] == (64, 32)
    assert shapes["lm_mtp_shared_w2"] == (16, 32)
    assert shapes["lm_head"] == (32, V)


@pytest.mark.parametrize("held", [None, (2, 6)])
def test_steps_through_the_cache_equal_the_full_forward(held):
    """One token a step over rings that wrap five times, with an idle
    row beside the live ones, against the reference's banded masks."""
    import jax
    import jax.numpy as jnp

    cfg = tiny_cfg()
    w = weights(cfg, held=held)
    toks = np.random.RandomState(1).randint(0, V, (2, 24)).astype(np.int32)
    want = np.asarray(ref.forward(w, jnp.asarray(toks), cfg, held=held))
    step, make_cache, _ = _build(cfg, w, held)
    cache, jstep = make_cache(3, 32), jax.jit(step)
    for t in range(toks.shape[1]):
        logits, cache = jstep(cache, jnp.asarray(np.append(toks[:, t], 0)),
                              jnp.asarray([t, t, -1], jnp.int32))
        np.testing.assert_allclose(np.asarray(logits)[:2], want[:, t],
                                   atol=2e-4, rtol=2e-4)
    # every sparse layer counted its rows; the module's row stayed zero
    stats = np.asarray(cache["expert_stats"])
    assert stats.shape == (5, 4) and (stats[:4, 3] == 24).all()
    assert (stats[4] == 0).all()


def test_verify_rows_and_the_module_equal_the_full_forward():
    """K = 2 fresh rows a slot through the cache (rings of 4 rows that
    wrap), hidden states beside the logits, then the module's pass over
    its own leaves: logits and module logits of every position equal the
    reference's."""
    import jax
    import jax.numpy as jnp

    cfg = tiny_cfg()
    w = weights(cfg, seed=3)
    toks = np.random.RandomState(2).randint(0, V, (2, 22)).astype(np.int32)
    jt = jnp.asarray(toks)
    want = np.asarray(ref.forward(w, jt, cfg))
    want_mtp = np.asarray(ref.mtp_logits(w, jt, cfg))
    _, make_cache, _ = _build(cfg, w)
    spec = decoding.spec_of(make_cache)
    verify, module = jax.jit(spec.verify_fn), jax.jit(spec.mtp_fn)
    cache = make_cache(3, 32)
    for t in range(0, 20, 2):
        ts = jnp.asarray([t, t, -1], jnp.int32)
        pair = np.zeros((3, 2), np.int32)
        pair[:2] = toks[:, t:t + 2]
        nxt = np.zeros((3, 2), np.int32)
        nxt[:2] = toks[:, t + 1:t + 3]
        logits, hidden, cache = verify(cache, jnp.asarray(pair), ts)
        np.testing.assert_allclose(np.asarray(logits)[:2], want[:, t:t + 2],
                                   atol=3e-4, rtol=3e-4)
        mlogits, cache = module(cache, hidden, jnp.asarray(nxt), ts)
        np.testing.assert_allclose(np.asarray(mlogits)[:2],
                                   want_mtp[:, t:t + 2], atol=3e-4,
                                   rtol=3e-4)
    stats = np.asarray(cache["expert_stats"])
    assert (stats[:, 3] == 10).all()        # the module's layer counted too
    assert stats[4, 0] == 10 * 2 * 2 * 2    # rounds x rows x slots x top_k


@pytest.mark.parametrize("chunk", [8, 2])
def test_chunked_prefill_equals_steps_with_a_window_under_the_chunk(chunk):
    """Two chunks of 8 through rings of 4 rows (the window is SMALLER
    than the chunk; with chunks of 2 it is larger), the module's leaves
    fed too, then steps: the logits after the prefill equal the
    reference's, and every leaf equals what steps alone would have
    written."""
    import jax
    import jax.numpy as jnp

    cfg = tiny_cfg()
    w = weights(cfg, seed=5)
    toks = np.random.RandomState(4).randint(0, V, (1, 24)).astype(np.int32)
    want = np.asarray(ref.forward(w, jnp.asarray(toks), cfg))
    step, make_cache, prefill = _build(cfg, w, chunk=chunk)
    assert prefill.lookahead == 1 and prefill.chunk_tokens == chunk
    jstep, jpre = jax.jit(step), jax.jit(prefill)
    fed = 16
    cache = make_cache(2, 32)
    for at in range(0, fed, chunk):
        cache = jpre(cache, jnp.int32(1), jnp.asarray(
            toks[0, at:at + chunk + 1]), jnp.int32(at), jnp.int32(chunk))
    # what verify + module rounds write for the same positions
    spec = decoding.spec_of(make_cache)
    verify, module = jax.jit(spec.verify_fn), jax.jit(spec.mtp_fn)
    by_rounds = make_cache(2, 32)
    for t in range(0, fed, 2):
        ts = jnp.asarray([-1, t], jnp.int32)
        pair = jnp.asarray(np.stack([[0, 0], toks[0, t:t + 2]]))
        nxt = jnp.asarray(np.stack([[0, 0], toks[0, t + 1:t + 3]]))
        _, hidden, by_rounds = verify(by_rounds, pair, ts)
        _, by_rounds = module(by_rounds, hidden, nxt, ts)
    for a, b in zip(jax.tree.leaves(cache["layers"] + [cache["mtp"]]),
                    jax.tree.leaves(by_rounds["layers"]
                                    + [by_rounds["mtp"]])):
        np.testing.assert_allclose(np.asarray(a)[1], np.asarray(b)[1],
                                   atol=2e-4, rtol=2e-4)
    for t in range(fed, 24):
        logits, cache = jstep(cache, jnp.asarray([0, toks[0, t]]),
                              jnp.asarray([-1, t], jnp.int32))
        np.testing.assert_allclose(np.asarray(logits)[1], want[0, t],
                                   atol=3e-4, rtol=3e-4)


def test_a_partial_chunk_keeps_the_ring_rows_it_does_not_reach():
    """``n_valid`` < C: the ring keeps what the chunk's valid rows do not
    overwrite, so steps after it still read the window whole."""
    import jax
    import jax.numpy as jnp

    cfg = tiny_cfg()
    w = weights(cfg, seed=6)
    toks = np.random.RandomState(8).randint(0, V, (1, 20)).astype(np.int32)
    want = np.asarray(ref.forward(w, jnp.asarray(toks), cfg))
    step, make_cache, prefill = _build(cfg, w)
    jstep, jpre = jax.jit(step), jax.jit(prefill)
    cache = make_cache(1, 32)
    cache = jpre(cache, jnp.int32(0), jnp.asarray(toks[0, :9]),
                 jnp.int32(0), jnp.int32(8))
    cache = jpre(cache, jnp.int32(0), jnp.asarray(toks[0, 8:17]),
                 jnp.int32(8), jnp.int32(2))      # two valid rows of eight
    for t in range(10, 20):
        logits, cache = jstep(cache, jnp.asarray([toks[0, t]]),
                              jnp.asarray([t], jnp.int32))
        np.testing.assert_allclose(np.asarray(logits)[0], want[0, t],
                                   atol=3e-4, rtol=3e-4)


def test_dims_refuses_what_the_block_does_not_compute():
    with pytest.raises(ValueError, match="group-limited"):
        mr.dims(tiny_cfg(n_group=2))
    with pytest.raises(ValueError, match="chain"):
        mr.dims(tiny_cfg(num_nextn_predict_layers=2))
    with pytest.raises(ValueError, match="sigmoid"):
        mr.dims(tiny_cfg(scoring_func="softmax"))
