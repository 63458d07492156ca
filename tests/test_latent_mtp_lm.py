"""The decoder of DENSE latent attention between sandwich norms, an
ungrouped bias-free router and a multi-token-prediction module
(``model_type: pangu_ultra_moe``) on the pooled decode path:
``decoding.make_latent_mtp_lm_pooled_step_fn`` at a small size on the CPU
(seeded), against the benchmark's plain reference
(``benchmark/configs/openpangu_ultra_moe_718b_reference.py``: float32,
full forward, expanded, no cache and no round).

What is new: ONE latent leaf a layer and one for the module (no index
key), K fresh rows appended and read DENSELY through one read
(``decode_attention.dense_latent_attention``) against the masked
reference, two norms a branch, a verify that yields hidden states, the
module's pass over a latent leaf of its own, a chunked prefill that feeds
it (``lookahead`` = 1), and a self-drafting round seated over an
installed snapshot.
"""
import importlib.util
import os

import numpy as np
import pytest

from conftest import WAIT

from paddle_tpu import decode_attention as da
from paddle_tpu import decoding
from paddle_tpu import latent_mtp_lm as lm

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V, CHUNK = 97, 8


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(ROOT, "benchmark", "configs",
                         "openpangu_ultra_moe_718b_reference.py"),
            "openpangu_reference")


def tiny_cfg(**over):
    """A dense layer then two sparse ones (8 experts, 2 a token, one
    shared), a latent of 16 + 4 lanes under 4 heads, one module."""
    cfg = dict(
        vocab_size=V, hidden_size=32, num_hidden_layers=3,
        first_k_dense_replace=1, num_attention_heads=4, q_lora_rank=16,
        kv_lora_rank=16, qk_nope_head_dim=8, qk_rope_head_dim=4,
        v_head_dim=8, intermediate_size=48, moe_intermediate_size=16,
        n_routed_experts=8, num_experts_per_tok=2, n_shared_experts=1,
        rms_norm_eps=1e-5, rope_theta=25600000.0, sandwich_norm=True,
        routed_scaling_factor=2.5, norm_topk_prob=True,
        scoring_func="sigmoid", num_nextn_predict_layers=1,
        tie_word_embeddings=False)
    cfg.update(over)
    return cfg


def weights(cfg, seed=0, held=None):
    return lm.random_state(np.random.RandomState(seed), cfg, std=0.3,
                           held=held)


def _build(cfg, w, held=None, chunk=CHUNK, kv_dtype="fp32"):
    return decoding.make_latent_mtp_lm_pooled_step_fn(
        w, cfg, kv_dtype=kv_dtype, held=held, prefill_tokens=chunk)


def test_param_shapes_name_four_norms_no_bias_and_the_module():
    shapes = lm.param_shapes(tiny_cfg(), held=(2, 6))
    assert shapes["lm_l0_ffn_gate"] == (32, 48)
    assert "lm_l0_router" not in shapes
    for norm in ("input_norm", "post_attn_norm", "pre_mlp_norm",
                 "post_mlp_norm"):
        assert shapes["lm_l1_" + norm] == (32,)
        assert shapes["lm_mtp_" + norm] == (32,)
    assert shapes["lm_l1_experts_w13"] == (4, 32, 32)
    assert shapes["lm_l1_router"] == (32, 8)
    assert not any(k.endswith(("expert_bias", "index_k", "index_q"))
                   for k in shapes)
    assert shapes["lm_l2_attn_uk"] == (4, 8, 16)
    assert shapes["lm_mtp_eh"] == (64, 32)
    assert shapes["lm_mtp_shared_w2"] == (16, 32)
    assert shapes["lm_head"] == (32, V)


def test_the_cache_holds_one_latent_leaf_a_layer_and_the_module():
    import jax

    cfg = tiny_cfg()
    _, make_cache, prefill = _build(cfg, weights(cfg))
    cache = jax.eval_shape(lambda: make_cache(3, 32))
    assert [sorted(c) for c in cache["layers"]] == [["latent"]] * 3
    assert sorted(cache["mtp"]) == ["latent"]
    # 20 lanes held in one whole 128-lane tile
    assert cache["mtp"]["latent"].shape == (3, 32, 128)
    assert cache["expert_stats"].shape == (3, 4)
    spec = decoding.spec_of(make_cache)
    (read,) = [r for r in spec.reads if r.kind == "latent"]
    assert read.kind == "latent" and read.layers == 4 and read.rule(7) == 7
    assert prefill.lookahead == 1


@pytest.mark.parametrize("held", [None, (2, 6)])
def test_steps_through_the_cache_equal_the_full_forward(held):
    """One token a step, an idle row beside the live ones, against the
    reference's expanded causal forward: logits, not tokens."""
    import jax
    import jax.numpy as jnp

    cfg = tiny_cfg()
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
    # every sparse layer counted its rows; the module's row stayed zero
    stats = np.asarray(cache["expert_stats"])
    assert stats.shape == (3, 4) and (stats[:2, 3] == 20).all()
    assert (stats[2] == 0).all()
    assert not np.asarray(cache["mtp"]["latent"]).any()


def test_verify_rows_and_the_module_equal_the_full_forward():
    """K = 2 fresh rows a slot through the cache, hidden states beside
    the logits, then the module's pass over its own leaf: logits and
    module logits of every position equal the reference's, and the two
    rows write what two steps write."""
    import jax
    import jax.numpy as jnp

    cfg = tiny_cfg()
    w = weights(cfg, seed=3)
    toks = np.random.RandomState(2).randint(0, V, (2, 22)).astype(np.int32)
    want = np.stack([np.asarray(ref.forward(w, jnp.asarray(t), cfg))
                     for t in toks])
    want_mtp = np.stack([np.asarray(ref.mtp_logits(w, jnp.asarray(t), cfg))
                         for t in toks])
    step, make_cache, _ = _build(cfg, w)
    spec = decoding.spec_of(make_cache)
    verify, module = jax.jit(spec.verify_fn), jax.jit(spec.mtp_fn)
    cache, by_steps, jstep = make_cache(3, 32), make_cache(3, 32), jax.jit(
        step)
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
        for j in range(2):
            _, by_steps = jstep(by_steps, jnp.asarray(pair[:, j]),
                                jnp.where(ts >= 0, ts + j, -1))
    for a, b in zip(jax.tree.leaves(cache["layers"]),
                    jax.tree.leaves(by_steps["layers"])):
        np.testing.assert_allclose(np.asarray(a), np.asarray(b), atol=2e-5)
    stats = np.asarray(cache["expert_stats"])
    assert (stats[:, 3] == 10).all()        # the module's layer counted too
    assert stats[2, 0] == 10 * 2 * 2 * 2    # rounds x rows x slots x top_k


@pytest.mark.parametrize("chunk", [8, 4])
def test_chunked_prefill_equals_steps_leaf_for_leaf_then_decodes(chunk):
    """Two (four) chunks through every layer AND the module's leaf, then
    steps: every leaf equals what verify + module rounds write for the
    same positions, and the logits after the prefill equal the
    reference's full forward."""
    import jax
    import jax.numpy as jnp

    cfg = tiny_cfg()
    w = weights(cfg, seed=5)
    toks = np.random.RandomState(4).randint(0, V, 24).astype(np.int32)
    want = np.asarray(ref.forward(w, jnp.asarray(toks), cfg))
    want_mtp = np.asarray(ref.mtp_logits(w, jnp.asarray(toks), cfg))
    step, make_cache, prefill = _build(cfg, w, chunk=chunk)
    assert prefill.lookahead == 1 and prefill.chunk_tokens == chunk
    jpre = jax.jit(prefill)
    fed = 16
    cache = make_cache(2, 32)
    for at in range(0, fed, chunk):
        cache = jpre(cache, jnp.int32(1), jnp.asarray(
            toks[at:at + chunk + 1]), jnp.int32(at), jnp.int32(chunk))
    spec = decoding.spec_of(make_cache)
    verify, module = jax.jit(spec.verify_fn), jax.jit(spec.mtp_fn)
    by_rounds = make_cache(2, 32)
    for t in range(0, fed, 2):
        ts = jnp.asarray([-1, t], jnp.int32)
        pair = jnp.asarray(np.stack([[0, 0], toks[t:t + 2]]))
        nxt = jnp.asarray(np.stack([[0, 0], toks[t + 1:t + 3]]))
        _, hidden, by_rounds = verify(by_rounds, pair, ts)
        _, by_rounds = module(by_rounds, hidden, nxt, ts)
    for a, b in zip(jax.tree.leaves(cache["layers"] + [cache["mtp"]]),
                    jax.tree.leaves(by_rounds["layers"]
                                    + [by_rounds["mtp"]])):
        np.testing.assert_allclose(np.asarray(a)[1], np.asarray(b)[1],
                                   atol=2e-4, rtol=2e-4)
        assert not np.asarray(a)[0].any()       # the other slot untouched
    # decode through the cache the prefill left: model and module
    for t in range(fed, 22, 2):
        ts = jnp.asarray([-1, t], jnp.int32)
        pair = jnp.asarray(np.stack([[0, 0], toks[t:t + 2]]))
        nxt = jnp.asarray(np.stack([[0, 0], toks[t + 1:t + 3]]))
        logits, hidden, cache = verify(cache, pair, ts)
        np.testing.assert_allclose(np.asarray(logits)[1], want[t:t + 2],
                                   atol=3e-4, rtol=3e-4)
        mlogits, cache = module(cache, hidden, nxt, ts)
        np.testing.assert_allclose(np.asarray(mlogits)[1],
                                   want_mtp[t:t + 2], atol=3e-4, rtol=3e-4)


def test_a_partial_chunk_writes_its_valid_rows_alone():
    import jax
    import jax.numpy as jnp

    cfg = tiny_cfg()
    w = weights(cfg, seed=6)
    toks = np.random.RandomState(8).randint(0, V, 20).astype(np.int32)
    want = np.asarray(ref.forward(w, jnp.asarray(toks), cfg))
    step, make_cache, prefill = _build(cfg, w)
    jstep, jpre = jax.jit(step), jax.jit(prefill)
    cache = make_cache(1, 32)
    cache = jpre(cache, jnp.int32(0), jnp.asarray(toks[:9]), jnp.int32(0),
                 jnp.int32(8))
    cache = jpre(cache, jnp.int32(0), jnp.asarray(toks[8:17]), jnp.int32(8),
                 jnp.int32(2))      # two valid rows of eight
    for leaf in jax.tree.leaves(cache["layers"] + [cache["mtp"]]):
        assert not np.asarray(leaf)[0, 10:].any()
    for t in range(10, 20):
        logits, cache = jstep(cache, jnp.asarray([toks[t]]),
                              jnp.asarray([t], jnp.int32))
        np.testing.assert_allclose(np.asarray(logits)[0], want[t],
                                   atol=3e-4, rtol=3e-4)


# ---------------------------------------------------------------------------
# the dense read against the masked reference
# ---------------------------------------------------------------------------
def _leaf(rng, s, t, lanes, dtype):
    import jax.numpy as jnp

    leaf = np.zeros((s, t, da._whole_tiles(lanes)), np.float32)
    leaf[..., :lanes] = rng.randn(s, t, lanes)
    return {"latent": jnp.asarray(leaf, dtype)}


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("k,rung,block,ts", [
    (1, 64, 16, [0, 5, -1, 63, 31]),     # a first position, idle, full rung
    (2, 64, 16, [0, 30, -1, 62, 15]),    # two rows end ON the rung's end
    (2, 48, 512, [7, -1, -1, 3, 46]),    # one block holds the rung
    (1, 40, 16, [-1, -1, -1, -1, -1]),   # nothing live: no block walked
    (2, 96, 32, [31, 32, 63, 64, 94]),   # rows either side of a boundary
])
def test_the_dense_read_equals_the_masked_reference(k, rung, block, ts,
                                                    dtype, tol):
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(k * rung + len(dtype))
    s, h, lanes, d_value = len(ts), 4, 20, 16
    kv = _leaf(rng, s, rung, lanes, dtype)
    q = jnp.asarray(rng.randn(s, k, h, lanes).astype(np.float32))
    ts = jnp.asarray(ts, jnp.int32)
    got = jax.jit(lambda q, kv, ts: da.dense_latent_attention(
        q, kv, ts, d_value=d_value, scale=0.3, key_block=block))(q, kv, ts)
    assert got.shape == (s, k, h, d_value) and got.dtype == jnp.float32
    for j in range(k):
        allowed = (jnp.arange(rung)[None, :] <= (ts + j)[:, None]) & (
            ts >= 0)[:, None]
        want = da.masked_latent_attention(q[:, j], kv, allowed,
                                          d_value=d_value, scale=0.3)
        np.testing.assert_allclose(np.asarray(got[:, j]), np.asarray(want),
                                   atol=tol, rtol=tol)
    assert not np.asarray(got)[np.asarray(ts) < 0].any()


def test_the_dense_read_walks_no_block_past_the_longest_context():
    """The host mirror of what the read touches: whole blocks up to the
    pool's longest live context, the rung at most."""
    assert da.dense_latent_positions_touched(1, 16384) == 512
    assert da.dense_latent_positions_touched(512, 16384) == 512
    assert da.dense_latent_positions_touched(513, 16384) == 1024
    assert da.dense_latent_positions_touched(16384, 16384) == 16384
    assert da.dense_latent_positions_touched(40, 48, 512) == 48


def test_appends_take_k_rows_a_slot_and_no_index_key():
    import jax.numpy as jnp

    kv = da.latent_leaves(3, 8, 20, None, jnp.float32)
    assert sorted(kv) == ["latent"]
    new = jnp.ones((3, 2, 20))
    out = da.append_latent_rows(kv, new, None, jnp.asarray([1, -1, 7]))
    got = np.asarray(out["latent"])
    assert got[0, 1:3, :20].all() and not got[0, 3:].any()
    assert not got[1].any()                     # idle: not written
    assert got[2, 7, :20].all() and got[2].sum() == 20  # past the end: dropped
    assert not got[..., 20:].any()


# ---------------------------------------------------------------------------
# a self-drafting round seated over an installed snapshot
# ---------------------------------------------------------------------------
RUNG = 64


def _server(step, make_cache, name, speculative=None, **kw):
    from paddle_tpu.serving.decode import DecodeServer

    return DecodeServer(
        step, make_cache, eos_id=V, max_seq_len=RUNG, max_slots=2,
        slot_ladder=(2,), len_ladder=(RUNG,), steps_per_tick=2,
        queue_capacity=64, target_queue_wait_ms=600000.0, kv_dtype="fp32",
        name=name, speculative=speculative, **kw)


def _serve_one(srv, prompt, n_new, **kw):
    r = srv.submit({"tokens": prompt}, max_new_tokens=n_new, **kw)
    return np.concatenate(r.result(timeout=WAIT)), r


@pytest.mark.parametrize("tail", [1, 4])
def test_a_round_over_a_snapshot_serves_the_plain_tokens_and_drafts(tail):
    """A document prefilled once leaves a snapshot whose boundary IS the
    document's end; a second request (another question of ``tail``
    tokens) is seated over it, speculative.  Its tokens are the plain
    step's (greedy-exact, whatever the pool state's stale proposal), and
    the module's proposals are those of a server that keeps no prefix
    cache: the module's row at the snapshot's last position was written
    for the FIRST request's next token, and must be written again."""
    from paddle_tpu.serving.speculative import make_self_draft

    cfg = tiny_cfg()
    step, make_cache, _ = _build(cfg, weights(cfg, seed=6))
    rng = np.random.RandomState(3)
    doc = rng.randint(0, V, 2 * CHUNK).astype(np.int32)
    first = np.concatenate([doc, rng.randint(0, V, 3).astype(np.int32)])
    second = np.concatenate([doc, rng.randint(0, V, tail).astype(np.int32)])
    n_new = 14
    plain = _server(step, make_cache, "latent-plain")
    try:
        plain.warmup()
        want = _serve_one(plain, second, n_new)[0]
    finally:
        plain.stop(drain=False, timeout=60.0)
    drafts = {}
    for name, kw in (("latent-no-prefix", {}),
                     ("latent-snap", {"prefix_cache": 1 << 24})):
        srv = _server(step, make_cache, name,
                      speculative=make_self_draft(make_cache), **kw)
        try:
            srv.warmup()
            _serve_one(srv, first, 4, speculative=True)
            got, req = _serve_one(srv, second, n_new, speculative=True,
                                  keep_drafts=True)
            m = srv.metrics()
        finally:
            srv.stop(drain=False, timeout=60.0)
        np.testing.assert_array_equal(got, want)
        assert m["recompiles"] == 0
        drafts[name] = req.draft_tokens
        if kw:
            assert m["decode"]["prefix_cache"]["hits"] == 1
            # the dense read: every live position, the module's leaf too
            d = m["decode"]
            assert d["latent_positions_selected"] \
                == d["index_positions_scored"] > 0
    assert drafts["latent-snap"].shape == (n_new,)
    # a one-token question's first answer position is proposed for by the
    # row at the document's last position: a prefill chunk's row without
    # a prefix cache (no proposal kept), a round's over the snapshot
    both = slice(1 if tail == 1 else 0, None)
    np.testing.assert_array_equal(drafts["latent-snap"][both],
                                  drafts["latent-no-prefix"][both])


def test_dims_refuses_what_the_block_does_not_compute():
    with pytest.raises(ValueError, match="ungrouped"):
        lm.dims(tiny_cfg(n_group=2, topk_group=1))
    with pytest.raises(ValueError, match="chain"):
        lm.dims(tiny_cfg(num_nextn_predict_layers=2))
    with pytest.raises(ValueError, match="sandwich_norm"):
        lm.dims(tiny_cfg(sandwich_norm=False))
    with pytest.raises(ValueError, match="rope_scaling"):
        lm.dims(tiny_cfg(rope_scaling={
            "type": "yarn", "factor": 4, "beta_fast": 32, "beta_slow": 1,
            "original_max_position_embeddings": 32}))
    with pytest.raises(ValueError, match="sigmoid"):
        lm.dims(tiny_cfg(scoring_func="softmax"))
    # the indexed family still refuses a module, and still wants its keys
    from paddle_tpu import latent_sparse_lm as ls

    with pytest.raises(KeyError):
        ls.dims(tiny_cfg())
