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


@pytest.mark.parametrize("dense", [True, False], ids=["dense", "experts"])
def test_the_closed_branches_equal_the_plain_formula_bit_for_bit(dense):
    """``close_attention`` and ``ffn_branch`` are ``h + RMS(branch;
    second norm)`` as the module's docstring writes it — whatever keeps a
    product whole before its norm on the chip (PR 64) is the identity on
    values: eager and jitted, bit-equal to the formula written plainly,
    a dense layer and an expert layer with an idle row."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import routed_experts as rx

    cfg = tiny_cfg()
    w, d = weights(cfg, seed=9), lm.dims(cfg)
    p = lm.layer_prefix("lm", 0 if dense else 2)
    rng = np.random.RandomState(4)
    h, o = (jnp.asarray(rng.randn(5, d.d_model), jnp.float32)
            for _ in range(2))
    ts = jnp.asarray([3, -1, 0, 7, 2], jnp.int32)

    def plain(h, o, w):
        h = h + lm.rms_norm(o, w[p + "post_attn_norm"], d.eps)
        f = lm.rms_norm(h, w[p + "pre_mlp_norm"], d.eps)
        y, st = ((lm.swiglu(f, w[p + "ffn_gate"], w[p + "ffn_up"],
                            w[p + "ffn_down"], 1.0, 1.0), None) if dense
                 else rx.expert_layer(f, w, p, ts, d, None))
        return h + lm.rms_norm(y, w[p + "post_mlp_norm"], d.eps), st

    def built(h, o, w):
        return lm.ffn_branch(lm.close_attention(h, o, w, p, d), w, p, dense,
                             ts, d, None)

    for run in (lambda f: f(h, o, w), lambda f: jax.jit(f)(h, o, w)):
        (want, st_want), (got, st_got) = run(plain), run(built)
        assert got.dtype == jnp.float32
        np.testing.assert_array_equal(np.asarray(want), np.asarray(got))
        assert (st_want is None) == (st_got is None) == dense
        if not dense:
            np.testing.assert_array_equal(np.asarray(st_want),
                                          np.asarray(st_got))


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


# ---------------------------------------------------------------------------
# the dense read's Pallas kernel (interpret mode) and its chooser
# ---------------------------------------------------------------------------
#: (K, rung, block, ts): what the kernel's walk must get right
_KERNEL_CASES = {
    "idle_and_inside_a_block": (1, 64, 16, [5, -1, 21, 40, -1]),
    "ends_exactly_at_a_blocks_end": (1, 64, 16, [15, 31, 47, 63, 16]),
    "one_position_and_the_rungs_last": (1, 64, 16, [0, 63, -1, 62, 1]),
    "two_rows_across_a_boundary": (2, 96, 32, [31, 32, 63, 64, 30]),
    "two_rows_on_the_rungs_end": (2, 64, 16, [0, 62, -1, 63, 14]),
    "contexts_blocks_apart": (2, 128, 16, [3, 120, 40, -1, 77]),
    "one_block_holds_the_rung": (2, 48, 48, [7, -1, -1, 3, 46]),
    "nothing_live": (1, 48, 16, [-1, -1, -1, -1, -1]),
}


@pytest.mark.parametrize("dtype,tol", [("float32", 2e-5), ("bfloat16", 2e-2)])
@pytest.mark.parametrize("case", sorted(_KERNEL_CASES))
def test_the_dense_kernel_equals_the_masked_reference(case, dtype, tol):
    """The kernel under interpret mode against the contract, a fresh row
    at a time, and against the XLA form it is chosen beside (the same
    blocks in the same order: the same sums)."""
    import jax.numpy as jnp

    k, rung, block, ts = _KERNEL_CASES[case]
    rng = np.random.RandomState(len(case) + k * rung)
    s, h, lanes, d_value = len(ts), 4, 20, 16
    kv = _leaf(rng, s, rung, lanes, dtype)
    q = jnp.asarray(rng.randn(s, k, h, lanes).astype(np.float32))
    ts = jnp.asarray(ts, jnp.int32)
    got = da.dense_latent_kernel_attention(
        q, kv, ts, d_value=d_value, scale=0.3, key_block=block,
        interpret=True)
    assert got.shape == (s, k, h, d_value) and got.dtype == jnp.float32
    for j in range(k):
        allowed = (jnp.arange(rung)[None, :] <= (ts + j)[:, None]) & (
            ts >= 0)[:, None]
        want = da.masked_latent_attention(q[:, j], kv, allowed,
                                          d_value=d_value, scale=0.3)
        np.testing.assert_allclose(np.asarray(got[:, j]), np.asarray(want),
                                   atol=tol, rtol=tol)
    assert not np.asarray(got)[np.asarray(ts) < 0].any()
    xla = da.dense_latent_attention(q, kv, ts, d_value=d_value, scale=0.3,
                                    key_block=block)
    np.testing.assert_allclose(np.asarray(got), np.asarray(xla),
                               atol=tol / 10, rtol=tol / 10)


@pytest.mark.parametrize("k", [1, 2])
def test_the_dense_kernels_work_list_stops_at_a_slots_own_last_row(k):
    """No item past the block that holds a slot's own last fresh row, so
    no dead block is read; an idle slot has none; the XLA form's mirror
    beside it walks the longest context's blocks for every slot."""
    ts = np.asarray([0, 1023, 1024, -1, 8191 - k, 16383, 16384 - k, 700],
                    np.int32)
    rung = 16384
    block = da.dense_latent_kernel_block(rung)
    assert block == 1024 and da.dense_latent_kernel_block(256) == 256
    n_items, slot, blk = (np.asarray(x) for x in da.dense_latent_work_items(
        ts, k, rung, block))
    n = int(n_items[0])
    last = np.where(ts >= 0, np.minimum(ts + k - 1, rung - 1), -1)
    want = [(i, b) for i in range(len(ts))
            for b in range(int(last[i]) // block + 1 if last[i] >= 0 else 0)]
    assert list(zip(slot[:n].tolist(), blk[:n].tolist())) == want
    assert all(b * block <= last[i] for i, b in want)
    assert 3 not in slot[:n]
    leaves = dict(lanes=640, dtype="bfloat16", n_head=128, d_value=512)
    live = last[last >= 0]
    kernel = da.dense_latent_positions_read(live, rung, backend="tpu",
                                            **leaves)
    assert kernel.tolist() == [
        (int(x) // block + 1) * block for x in live]
    assert int(kernel.sum()) == n * block
    assert ((kernel - (live + 1) >= 0) & (kernel - (live + 1) < block)).all()
    xla = da.dense_latent_positions_read(live, rung, backend="cpu", **leaves)
    assert xla.tolist() == [rung] * len(live)
    short = da.dense_latent_positions_read(np.asarray([3, 700, 40]), rung,
                                           backend="cpu", **leaves)
    assert short.tolist() == [da.dense_latent_positions_touched(
        701, rung)] * 3 == [1024] * 3
    # a chunk's steps side by side: each step its own longest context
    # (the XLA form's blocks are DENSE_LATENT_BLOCK = 512 long)
    steps = da.dense_latent_positions_read(
        np.asarray([[3, 4], [510, 511], [40, 41]]) + 1, rung, backend="cpu",
        **leaves)
    assert steps.tolist() == [[512, 1024]] * 3


@pytest.mark.parametrize("why,dtype,rung,block,backend,heads", [
    ("float32_leaves", "float32", 64, 16, "tpu", 16),
    ("a_rung_the_block_does_not_divide", "bfloat16", 1040, 16, "tpu", 16),
    ("no_tpu", "bfloat16", 64, 16, "cpu", 16),
    ("heads_no_whole_sublane_tile", "bfloat16", 64, 16, "tpu", 4),
])
def test_the_chooser_keeps_the_xla_form_where_the_kernel_is_not(
        monkeypatch, why, dtype, rung, block, backend, heads):
    """One algorithm, two lowerings, chosen by what the code can observe:
    everything the kernel does not take counts ``dense_xla`` and equals
    the contract."""
    import jax
    import jax.numpy as jnp

    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert not da.dense_latent_kernel_supported(
        rung, 128, dtype, n_head=heads, d_value=128)
    assert da.dense_latent_kernel_supported(
        64, 128, "bfloat16", n_head=16, d_value=128, backend="tpu")
    rng = np.random.RandomState(len(why))
    ts = jnp.asarray([5, -1, rung - 2], jnp.int32)
    kv = _leaf(rng, 3, rung, 128, dtype)
    q = jnp.asarray(rng.randn(3, 2, heads, 128).astype(np.float32))
    counters = {path: da.LATENT_LOWERED.labels(path=path)
                for path in ("dense_xla", "dense_kernel")}
    before = {path: c.value for path, c in counters.items()}
    got = da.dense_latent_attention(q, kv, ts, d_value=128, scale=0.1,
                                    key_block=block)
    assert counters["dense_xla"].value == before["dense_xla"] + 1
    assert counters["dense_kernel"].value == before["dense_kernel"]
    for j in range(2):
        allowed = (jnp.arange(rung)[None, :] <= (ts + j)[:, None]) & (
            ts >= 0)[:, None]
        want = da.masked_latent_attention(q[:, j], kv, allowed, d_value=128,
                                          scale=0.1)
        np.testing.assert_allclose(np.asarray(got[:, j]), np.asarray(want),
                                   atol=3e-2, rtol=3e-2)


@pytest.mark.parametrize("kv_dtype", ["bf16", "fp32"])
def test_the_builder_declares_what_the_dense_read_touches(kv_dtype):
    """Over bf16 leaves the ``"kv"`` rule is the read's own host mirror,
    counted once a round at the slot's last fresh row; float32 leaves
    keep the ragged rule, a round masked over the whole pool."""
    cfg = tiny_cfg()
    _, make_cache, _ = decoding.make_latent_mtp_lm_pooled_step_fn(
        weights(cfg, seed=3), cfg, kv_dtype=kv_dtype, prefill_tokens=CHUNK)
    reads = {r.kind: r for r in decoding.spec_of(make_cache).reads}
    assert sorted(reads) == ["kv", "latent"]
    rule, live = reads["kv"].rule, np.asarray([3, 40, 17])
    if kv_dtype == "bf16":
        assert reads["kv"].rounds
        # tiny widths: the XLA form's mirror, whatever the backend
        assert rule(live, 64).tolist() == [64] * 3
        assert rule(live, 1024).tolist() == [512] * 3
        assert rule.func is da.dense_latent_positions_read
    else:
        assert not reads["kv"].rounds
        assert rule(live, 64).tolist() == da.ragged_positions_read(
            live, 64).tolist()


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
