"""The windowed routed decoder (``model_name: smallthinker_*``) on the
pooled decode path: ``decoding.make_windowed_routed_lm_pooled_step_fn``
-> ``KVSlotPool`` -> ``DecodeServer``, at the sizes of the benchmark
configuration's ``rehearse`` group on the CPU (seeded), against the
benchmark's plain reference (``benchmark/configs/
smallthinker_21b_a3b_reference.py``: float32, full forward, no cache and
no ring).

What is new under the pool: sequence leaves of ONE rung that differ in
LENGTH (a window layer's ring of ``window`` rows beside a global layer's
rung), a chunked prefill through expert layers, a router that reads the
block's input before attention, softmax over the chosen, ReLU gates, and
whole-row snapshots over ring leaves.
"""
import copy
import importlib.util
import json
import os

import numpy as np
import pytest

from paddle_tpu import decoding, monitor
from paddle_tpu import grouped_matmul as gm
from paddle_tpu import routed_experts as rx
from paddle_tpu import windowed_routed_lm as wr
from paddle_tpu.serving.decode import DecodeServer
from paddle_tpu.serving.kv_pool import KVSlotPool

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
V, WINDOW, CHUNK = 211, 16, 8


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(ROOT, "benchmark", "configs",
                         "smallthinker_21b_a3b_reference.py"),
            "smallthinker_reference")


def rehearse_cfg(**over):
    """The configuration file at its ``rehearse`` sizes (hidden 64, 8
    experts of width 32, three a token, global / window / window /
    window, a window of 16)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "smallthinker_21b_a3b.json")) as f:
        cfg = json.load(f)
    tiny = cfg.pop("rehearse")
    cfg.update({k: v for k, v in tiny.items() if not isinstance(v, dict)})
    cfg.update(vocab_size=V, **over)
    assert cfg["sliding_window_size"] == WINDOW
    return cfg


def weights(cfg, seed=0, dtype="float32"):
    return wr.random_state(np.random.RandomState(seed), cfg, std=0.1,
                           dtype=dtype)


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


# fp32: the step and the reference differ in the order of float32 sums.
# bf16: weights multiplied as stored, K/V in bf16; a marginal third
# expert may go the other way: the MEAN gap is held as the cell's check
# holds it, the worst loosely.  Either way the prefill's chunks cross
# the window (3 chunks of 8 over a window of 16) and the decode wraps
# the ring twice more.
@pytest.mark.parametrize("dtype,kv_dtype,worst,mean", [
    ("float32", "fp32", 2e-5, 2e-6), ("bfloat16", "bf16", 8e-2, 1e-2)])
def test_prefill_then_decode_equals_the_full_forward(dtype, kv_dtype, worst,
                                                     mean):
    import jax.numpy as jnp

    cfg = rehearse_cfg()
    w = weights(cfg, seed=3, dtype=dtype)
    step, make_cache, prefill = decoding.make_windowed_routed_lm_pooled_step_fn(
        w, cfg, kv_dtype=kv_dtype, prefill_tokens=CHUNK)
    assert prefill.chunk_tokens == CHUNK
    assert decoding.spec_of(make_cache).prefill_fn is prefill
    toks = np.random.RandomState(5).randint(0, V, (2, 56)).astype(np.int32)
    want = np.asarray(ref.forward(w, jnp.asarray(toks), cfg))[:, 24:]
    got, cache = _prefill_then_decode(step, make_cache, prefill, toks, 3)
    gap = np.abs(got - want).max(-1) / (want.max() - want.min())
    assert gap.max() <= worst and gap.mean() <= mean
    # layers of two lengths in one cache; the idle row never written
    assert [c["k"].shape[1] for c in cache["layers"]] == [64, 16, 16, 16]
    for layer in cache["layers"]:
        for leaf in layer.values():
            assert float(jnp.abs(leaf[2].astype("float32")).max()) == 0.0
    assert cache["layers"][1]["k"].dtype == jnp.dtype(
        {"fp32": "float32", "bf16": "bfloat16"}[kv_dtype])
    # the counts are of steps: 32 steps x 2 rows x top 3, no chunk among them
    assert np.asarray(cache["expert_stats"])[:, 0].tolist() == [32 * 2 * 3] * 4


@pytest.mark.parametrize("harm,least", [
    ({"sliding_window_size": WINDOW - 1}, 1e-3),
    ({"sliding_window_size": WINDOW + 1}, 1e-3),
    ({"rope_layout": [1, 1, 1, 1]}, 1e-2)])
def test_a_window_one_off_or_rotary_in_a_global_layer_shows(harm, least):
    """The tolerance of the test above fails a reference whose window is
    one position off, and one that rotates the global layer."""
    import jax.numpy as jnp

    cfg = rehearse_cfg()
    w = weights(cfg, seed=3)
    toks = np.random.RandomState(5).randint(0, V, (2, 56)).astype(np.int32)
    want = np.asarray(ref.forward(w, jnp.asarray(toks), cfg))[:, 24:]
    off = np.asarray(ref.forward(w, jnp.asarray(toks),
                                 dict(cfg, **harm)))[:, 24:]
    gap = np.abs(off - want).max(-1) / (want.max() - want.min())
    assert gap.max() > least > 2e-5


def test_a_prefill_chunk_equals_its_steps_leaf_for_leaf():
    import jax
    import jax.numpy as jnp

    cfg = rehearse_cfg()
    w = weights(cfg, seed=6)
    step, make_cache, prefill = decoding.make_windowed_routed_lm_pooled_step_fn(
        w, cfg, kv_dtype="fp32", prefill_tokens=CHUNK)
    toks = np.random.RandomState(2).randint(0, V, (40,)).astype(np.int32)
    a, b = make_cache(2, 64), make_cache(2, 64)
    jstep, jpre = jax.jit(step), jax.jit(prefill)
    for t in range(40):
        _, a = jstep(a, np.asarray([0, toks[t]], np.int32),
                     np.asarray([-1, t], np.int32))
    for c in range(5):      # the last chunk short by three
        n = CHUNK if c < 4 else CHUNK - 3
        b = jpre(b, jnp.int32(1), jnp.asarray(toks[c * 8:c * 8 + 8]),
                 jnp.int32(c * 8), jnp.int32(n))
    for la, lb in zip(a["layers"], b["layers"]):
        for leaf in ("k", "v"):
            x, y = np.asarray(la[leaf][1]), np.asarray(lb[leaf][1])
            rows = x.shape[0]
            # what 37 positions leave: a global leaf's first 37 rows, a
            # ring's rows of positions 21 .. 36 (rows 37 % 16 .. hold
            # what the three last steps of path a overwrote)
            live = (np.arange(rows) < 37 if rows == 64 else
                    np.isin(np.arange(rows), np.arange(24, 37) % rows))
            np.testing.assert_allclose(x[live], y[live], atol=2e-5)
            assert not np.asarray(lb[leaf][0]).any()    # the other slot


def test_the_shares_of_the_softmax_relu_layer_add_up():
    """``held`` shares of the layer (routing over all the experts by the
    router's own input, ReLU gates) add up to the uncut layer, and the
    uncut layer is the reference's."""
    import jax.numpy as jnp

    cfg = rehearse_cfg()
    d = wr.dims(cfg)
    assert (d.scoring, d.gate_act) == (rx.SOFTMAX_CHOSEN, rx.RELU)
    w = weights(cfg, seed=8)
    rng = np.random.RandomState(4)
    f, r = (jnp.asarray(rng.randn(10, d.d_model).astype("float32"))
            for _ in range(2))
    ts = jnp.asarray([0, 3, -1, 7, 2, 5, 1, -1, 9, 4], jnp.int32)
    p = "lm_l1_"
    whole, stats = rx.expert_layer(f, w, p, ts, d, router_input=r)
    parts = []
    for lo, hi in ((0, 3), (3, 4), (4, 8)):
        held = {k: (v[lo:hi] if "experts_w" in k else v)
                for k, v in w.items()}
        parts.append(rx.expert_layer(f, held, p, ts, d, (lo, hi),
                                     router_input=r)[0])
    np.testing.assert_allclose(np.asarray(sum(parts)), np.asarray(whole),
                               atol=1e-5)
    sel, gate = ref.routing(w, p, r[None], cfg)
    want = ref.experts(w, p, f[None], sel, gate, cfg)[0]
    live = np.asarray(ts) >= 0
    np.testing.assert_allclose(np.asarray(whole)[live],
                               np.asarray(want)[live], atol=1e-5)
    assert not np.asarray(whole)[~live].any()
    assert np.asarray(stats).tolist()[0] == int(live.sum()) * d.top_k
    # the gates are a softmax over the chosen: they sum to one
    _, g = rx.route(r, w[p + "router"], None, d)
    np.testing.assert_allclose(np.asarray(g).sum(-1), 1.0, atol=1e-6)
    # the router read ITS input: routed by f instead, other experts
    other, _ = rx.expert_layer(f, w, p, ts, d)
    assert np.abs(np.asarray(other) - np.asarray(whole)).max() > 1e-3


def test_pairs_are_padded_to_a_whole_row_tile_and_equal_ragged_dot():
    """40 rows x 6 = 240 pairs go to the grouped product as 256 (pairs
    of no group); the result is the unpadded ``ragged_dot``'s."""
    import jax
    import jax.numpy as jnp

    cfg = rehearse_cfg(moe_num_active_primary_experts=6)
    d = wr.dims(cfg)
    w = weights(cfg, seed=9)
    rng = np.random.RandomState(1)
    f = jnp.asarray(rng.randn(40, d.d_model).astype("float32"))
    ts = jnp.asarray(np.where(np.arange(40) % 7 == 3, -1, np.arange(40)),
                     jnp.int32)
    seen = []
    real = gm.grouped_matmul

    def spy(lhs, rhs, plan):
        seen.append(lhs.shape[0])
        return real(lhs, rhs, plan)

    p = "lm_l0_"
    gm.grouped_matmul = spy
    try:
        got, _ = rx.expert_layer(f, w, p, ts, d)
    finally:
        gm.grouped_matmul = real
    assert seen == [256, 256] and 40 * 6 == 240
    # the same layer with no padding, by hand
    sel, gate = rx.route(f, w[p + "router"], None, d)
    order, sizes, kept = rx.dispatch(sel, ts >= 0, (0, d.n_expert),
                                     d.n_expert)
    rows = f[order // d.top_k]
    gu = jax.lax.ragged_dot(rows, w[p + "experts_w13"], sizes)
    act = jax.nn.relu(gu[:, :d.d_expert]) * gu[:, d.d_expert:]
    y = jax.lax.ragged_dot(act, w[p + "experts_w2"], sizes)
    place = jnp.zeros(240, jnp.int32).at[order].set(jnp.arange(240))
    want = jnp.sum(jnp.where(kept[..., None], gate[..., None]
                             * y[place].reshape(40, 6, -1), 0.0), axis=1)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), atol=1e-5)
    # a whole tile's worth is left alone
    seen.clear()
    gm.grouped_matmul = spy
    try:
        rx.expert_layer(jnp.concatenate([f, f, f, f[:8]]), w, p,
                        jnp.arange(128, dtype=jnp.int32), d)
    finally:
        gm.grouped_matmul = real
    assert seen == [768, 768]


def test_dims_refuses_what_the_step_does_not_compute():
    cfg = rehearse_cfg()
    for harm, msg in (
            ({"rope_layout": [1, 1, 1, 1]}, "rope_layout"),
            ({"sliding_window_layout": [0, 1, 2, 1]}, "sliding_window_layout"),
            ({"moe_primary_router_apply_softmax": False}, "softmax"),
            ({"rope_scaling": {"type": "yarn"}}, "rope_scaling"),
            ({"tie_word_embeddings": True}, "tied")):
        with pytest.raises(ValueError, match=msg):
            wr.dims(dict(cfg, **harm))
    with pytest.raises(ValueError, match="divide"):
        decoding.make_windowed_routed_lm_pooled_step_fn(
            weights(cfg), cfg, prefill_tokens=5)


# ---------------------------------------------------------------------------
# the pool over ring leaves
# ---------------------------------------------------------------------------
def _pool(cfg, w, len_ladder, slots=2, **kw):
    step, make_cache, _ = decoding.make_windowed_routed_lm_pooled_step_fn(
        w, cfg, kv_dtype="fp32", prefill_tokens=CHUNK)
    return KVSlotPool(step, make_cache, eos_id=V, max_slots=slots,
                      max_seq_len=len_ladder[-1], slot_ladder=[slots],
                      len_ladder=len_ladder, steps=4, kv_dtype="fp32",
                      **kw), make_cache


def test_sequence_leaves_of_one_rung_differ_in_length():
    """Below the window a ring leaf is the rung, at and above it the
    window; ``resize`` pads or keeps accordingly; the bytes follow, and
    what one length for every layer would hold beside them."""
    import jax

    cfg = rehearse_cfg()
    w = weights(cfg, seed=4)
    d = wr.dims(cfg)
    pool, make_cache = _pool(cfg, w, [8, 32, 64], prefix=True)
    told = decoding.spec_of(make_cache).flat
    assert len(told) == len(
        jax.tree.leaves(jax.eval_shape(lambda: make_cache(2, 32))))
    # flattened: expert_stats, then k, v of global | window x 3
    assert [leaf.window for leaf in told] == [
        None, None, None] + [WINDOW] * 6
    assert [leaf.seq_axis for leaf in told] == [None] + [1] * 8
    assert [leaf.slot for leaf in told] == [False] + [True] * 8
    assert pool.ring_leaves == [
        "['layers'][%d]['%s']" % (i, n) for i in (1, 2, 3) for n in "kv"]
    for t, ring in ((8, 8), (32, 16), (64, 16)):
        lens = [c["k"].shape[1] for c in pool._state_spec(2, t)[
            "cache"]["layers"]]
        assert lens == [t, ring, ring, ring]
        per = 2 * 2 * d.d_kv * 4           # K and V, two slots, fp32
        assert pool.kv_rung_bytes(2, t) == per * (t + 3 * ring)
        assert pool.kv_rung_bytes_one_length(2, t) == per * 4 * t
    assert pool.recurrent_rung_bytes(2, 32) == 4 * 4 * 4    # the counts
    # a declared window the leaf does not have is refused, not inferred
    step, mc, _ = decoding.make_windowed_routed_lm_pooled_step_fn(
        w, cfg, kv_dtype="fp32", prefill_tokens=CHUNK)

    def wrong(s, t):
        return mc(s, t)

    decoding.declare(wrong, decoding.CacheSpec(jax.tree.map(
        lambda leaf: decoding.Leaf(
            leaf.seq_axis, window=2 * (leaf.window or 0), slot=leaf.slot),
        decoding.spec_of(mc).leaves)))
    with pytest.raises(ValueError, match="min\\(rung, window\\)"):
        KVSlotPool(step, wrong, eos_id=V, max_slots=2, max_seq_len=64,
                   slot_ladder=[2], len_ladder=[64])

    # a request that outgrows its rung below the window: resized across
    # it mid-flight, same tokens as one that never was
    rng = np.random.RandomState(9)
    prompt = rng.randint(0, V, 5).astype(np.int32)
    want = pool.admit(pool.alloc(2, 64), 0, prompt, 5, 40)
    for _ in range(10):
        want = pool.chunk(want)
    state = pool.admit(pool.alloc(2, 8), 0, prompt, 5, 40)
    state = pool.chunk(state)                     # pos 4, rung 8
    state = pool.resize(state, 2, 32)             # ring 8 -> 16 rows
    assert state["cache"]["layers"][1]["k"].shape[1] == 16
    for _ in range(5):
        state = pool.chunk(state)                 # pos 24: wrapped
    state = pool.resize(state, 2, 64)             # ring stays 16
    for _ in range(4):
        state = pool.chunk(state)
    assert np.array_equal(np.asarray(state["tokens"])[0, :40],
                          np.asarray(want["tokens"])[0, :40])


def test_a_snapshot_carries_ring_leaves_whole_and_leaves_the_counts():
    """``snapshot`` -> ``admit_prefix`` over ring leaves: the slot's
    whole row, wrapped rows and all, installed into another slot, which
    then decodes what the first would have; the counts (no slot axis)
    are neither copied nor overwritten."""
    import jax.numpy as jnp

    cfg = rehearse_cfg()
    w = weights(cfg, seed=5)
    pool, _ = _pool(cfg, w, [64], prefix=True)
    assert pool.snapshots and pool.prefill_tokens == CHUNK
    rng = np.random.RandomState(3)
    doc = rng.randint(0, V, 24).astype(np.int32)
    tail = rng.randint(0, V, 4).astype(np.int32)
    prompt = np.concatenate([doc, tail])
    # slot 0: the whole prompt by chunks (3, past the window) and steps
    state = pool.admit(pool.alloc(2, 64), 0, prompt, len(prompt), 40)
    state = pool.release(state, [0])
    for c in range(3):
        state = pool.prefill(state, 0, c * CHUNK, c == 2)
    snap = pool.snapshot(state, 0)
    assert [tuple(x.shape) for x in snap] == [(1,)] + [
        (64, 32), (64, 32)] + [(16, 32)] * 6
    for _ in range(4):
        state = pool.chunk(state)
    counts = np.asarray(state["cache"]["expert_stats"]).copy()
    want = np.asarray(state["tokens"])[0, :40]
    # slot 1: seated over the snapshot
    state = pool.admit_prefix(state, 1, prompt, len(prompt), 40, snap, 24)
    assert np.array_equal(np.asarray(state["cache"]["expert_stats"]), counts)
    assert int(np.asarray(state["pos"])[1]) == 24
    for _ in range(4):
        state = pool.chunk(state)
    assert np.array_equal(np.asarray(state["tokens"])[1, :40], want)


@pytest.mark.parametrize("tier", ["positions", "speculative_k2",
                                  "speculative_k3"])
def test_what_would_slice_or_roll_back_a_ring_is_refused(tier):
    cfg = rehearse_cfg()
    w = weights(cfg)
    step, make_cache, _ = decoding.make_windowed_routed_lm_pooled_step_fn(
        w, cfg, kv_dtype="fp32", prefill_tokens=CHUNK)

    # the K/V leaves alone (no counts: those are refused as recurrent
    # leaves are, before a ring is looked at) and NO prefill
    def bare(s, t):
        return make_cache(s, t)["layers"]

    decoding.declare(bare, decoding.CacheSpec(
        decoding.spec_of(make_cache).leaves["layers"]))
    kw = dict(eos_id=V, max_slots=2, max_seq_len=64, slot_ladder=[2],
              len_ladder=[64])
    if tier.startswith("speculative"):
        from paddle_tpu.serving.speculative import SpeculativeConfig

        def spec(k):
            return SpeculativeConfig(lambda c, t, ts: (None, c), step, bare,
                                     k=k)

        if tier == "speculative_k2":
            # a ring of W rows carries two rows a round: nothing the
            # query after a rejection reads was overwritten
            assert KVSlotPool(step, bare, speculative=spec(2),
                              **kw).ring_leaves
            return
        with pytest.raises(ValueError, match=r"speculative=.*ring leaves.*"
                           r"rolled back.*k - 2 spare rows"):
            KVSlotPool(step, bare, speculative=spec(3), **kw)
        return
    # a builder with ring leaves and no prefill cannot keep a prefix ...
    with pytest.raises(ValueError, match=r"prefix=True.*ring leaves.*"
                       r"SNAPSHOT"):
        KVSlotPool(step, bare, prefix=True, **kw)
    # ... and one with a prefill keeps snapshots, never positions
    pool, _ = _pool(cfg, w, [64], prefix=True)
    with pytest.raises(ValueError, match=r"extract_kv over a cache with "
                       r"ring leaves.*p mod its window"):
        pool.extract_kv(pool.alloc(2, 64), 0, 8)


# ---------------------------------------------------------------------------
# the server
# ---------------------------------------------------------------------------
def test_decode_server_end_to_end_with_snapshots_and_the_window_counters():
    """A document prefilled once in chunks, then requests seated over
    its snapshot in reused slots: every one gets the tokens the
    reference's full forward picks; the window counters, the two byte
    gauges and the expert counters say what happened."""
    import jax.numpy as jnp

    cfg = rehearse_cfg()
    w = weights(cfg, seed=7)
    d = wr.dims(cfg)
    step, make_cache, _ = decoding.make_windowed_routed_lm_pooled_step_fn(
        w, cfg, kv_dtype="fp32", prefill_tokens=CHUNK)
    name = "windowed-e2e"
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
        first.result(120)
        asked = []
        for n_q, n_new in ((3, 9), (5, 12), (2, 7), (4, 10)):
            p = np.concatenate([doc, rng.randint(0, V, n_q)]).astype(np.int32)
            asked.append((p, srv.submit({"tokens": p}, max_new_tokens=n_new)))
        for p, req in asked:
            out = np.asarray(req.result(120)[0])
            full = np.concatenate([p, out])
            lg = np.asarray(ref.forward(w, jnp.asarray(full[None]), cfg))[0]
            want = lg[len(p) - 1:len(p) - 1 + len(out)].argmax(-1)
            assert np.array_equal(out, want)
        m = srv.metrics()["decode"]
        assert m["prefix_cache"]["hits"] == 4 and m["prefill_chunks"] == 4
        # every step of a window layer: min(context, 16) of its context
        assert 0 < m["window_positions_read"] < m["window_positions_live"]
        assert m["window_positions_read"] % d.window_layers == 0
        live = monitor.counter_value(
            "serving_decode_kv_positions_live_total", server=name)
        assert m["window_positions_live"] == live * d.window_layers
        assert m["expert_assignments"] > 0
        held = 2 * 2 * d.d_kv * 4 * (64 + 3 * 16)
        if m["kv_bytes_held"]:       # 0 once an idle server dropped its pool
            assert m["kv_bytes_held"] == m["kv_cache_bytes"] == held
            assert m["kv_bytes_one_length"] == 2 * 2 * d.d_kv * 4 * 4 * 64
        for key in ("window_positions_read", "window_positions_live",
                    "kv_bytes_held", "kv_bytes_one_length"):
            assert key in srv.statusz()["metrics"]["decode"]
    finally:
        srv.stop(drain=False, timeout=30.0)


def test_the_server_counts_kv_reads_by_the_builders_rule():
    """The builder of grouped heads declares what a one-row step reads
    of a slot's sequence leaves (its ``"kv"`` ``PositionRead``: the
    grouped kernel's rounding where it serves them, the whole rung off
    the TPU), and the server's read counter follows that rule, whatever
    the pool's dtype."""
    cfg = rehearse_cfg()
    step, make_cache, _ = decoding.make_windowed_routed_lm_pooled_step_fn(
        weights(cfg, seed=7), cfg, kv_dtype="fp32", prefill_tokens=CHUNK)
    spec = decoding.spec_of(make_cache)
    kv, window = spec.reads
    assert kv.rule(np.asarray([0, 40]), 64).tolist() == [64, 64]
    rungs = []
    spec = copy.copy(spec)
    spec.reads = (decoding.PositionRead("kv", lambda ts, t: (
        rungs.append(t) or (ts // 8 + 1) * 8)), window)
    decoding.declare(make_cache, spec)
    name = "windowed-kv-rule"
    srv = DecodeServer(step, make_cache, eos_id=V, max_seq_len=64,
                       max_slots=2, slot_ladder=(2,), len_ladder=(64,),
                       steps_per_tick=4, kv_dtype="fp32", name=name)
    try:
        srv.warmup()
        prompt = np.random.RandomState(3).randint(0, V, 5).astype(np.int32)
        srv.submit({"tokens": prompt}, max_new_tokens=9).result(120)
        m = srv.metrics()["decode"]
        live = monitor.counter_value(
            "serving_decode_kv_positions_live_total", server=name)
        assert set(rungs) == {64}
        assert 0 < live <= m["kv_positions_read"] < m["kv_positions_pool"]
        assert m["kv_positions_read"] % 8 == 0
    finally:
        srv.stop(drain=False, timeout=30.0)


def test_a_traced_turn_says_what_its_window_layers_read():
    """``window_rows`` on the ``deliver`` span: the positions the chunk's
    window layers read, summing to the counter."""
    from paddle_tpu.monitor import spans as mon_spans

    cfg = rehearse_cfg()
    w = weights(cfg, seed=2)
    step, make_cache, _ = decoding.make_windowed_routed_lm_pooled_step_fn(
        w, cfg, kv_dtype="fp32", prefill_tokens=CHUNK)
    srv = DecodeServer(step, make_cache, eos_id=V, max_seq_len=64,
                       max_slots=2, slot_ladder=(2,), len_ladder=(64,),
                       steps_per_tick=4, kv_dtype="fp32",
                       name="windowed-traced")
    srv.warmup()
    prompt = np.random.RandomState(1).randint(0, V, 20).astype(np.int32)
    mon_spans.start_recording()
    try:
        srv.submit({"tokens": prompt}, max_new_tokens=9).result(120)
        read = srv.metrics()["decode"]["window_positions_read"]
    finally:
        srv.stop(drain=False, timeout=30.0)
        spans = mon_spans.stop_recording()
    rows = [s["args"]["window_rows"] for s in spans
            if s["name"] == "serving/decode/deliver"
            and "window_rows" in s["args"]]
    assert rows and sum(rows) == read > 0
    # 28 consumed positions: chunks fed 16, the steps 12 at contexts
    # 17 .. 28, of which a window layer reads 16
    assert read == 12 * WINDOW * 3
