"""The hybrid SSM + attention decoder (``falcon_h1``) through the slot
pool: ``paddle_tpu.hybrid_ssm`` + ``decoding.
make_hybrid_ssm_lm_pooled_step_fn`` + ``KVSlotPool`` + ``DecodeServer``
against the benchmark's plain reference (``benchmark/configs/
falcon_h1_34b_reference.py``: float32, full forward, a scan over time)
at tiny widths on seeded weights, and what recurrent state changes in
the pool: leaves declared instead of guessed, a reused slot started from
zero, prefix reuse and speculation refused.  The last test pins the
transformer-LM pooled step (the ``gpt1_117m`` cells' step) to a
recording made with the parent commit's code: the shared ``admit`` /
pool code moved under it.
"""
import importlib.util
import os

import numpy as np
import pytest

from paddle_tpu import decoding, hybrid_ssm as hs, monitor
from paddle_tpu.serving.decode import DecodeServer
from paddle_tpu.serving.kv_pool import KVSlotPool

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(ROOT, "benchmark", "configs",
                         "falcon_h1_34b_reference.py"), "falcon_h1_reference")


def tiny_cfg(n_groups=2, d_state=16, head_dim=16, vocab=97):
    """Falcon-H1's shape of block at tiny widths; the multipliers are
    raised so that every branch is a visible share of the residual."""
    return dict(
        vocab_size=vocab, hidden_size=64, num_hidden_layers=2,
        num_attention_heads=4, num_key_value_heads=2, head_dim=head_dim,
        intermediate_size=128, mamba_d_ssm=64, mamba_n_heads=4,
        mamba_d_head=16, mamba_d_state=d_state, mamba_n_groups=n_groups,
        mamba_d_conv=4, rms_norm_eps=1e-5, rope_theta=1e11,
        embedding_multiplier=5.65, lm_head_multiplier=0.0078,
        attention_in_multiplier=1.0, attention_out_multiplier=0.3,
        key_multiplier=0.09, ssm_in_multiplier=0.25,
        ssm_out_multiplier=0.35, ssm_multipliers=[0.35, 0.25, 0.177, 0.5, 0.35],
        mlp_multipliers=[0.7, 0.09])


def weights(cfg, seed=0, dtype="float32"):
    return hs.random_state(np.random.RandomState(seed), cfg, std=0.1,
                           dtype=dtype)


# ---------------------------------------------------------------------------
# the mixer alone
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("n_groups", [1, 2])
def test_mamba2_step_run_t_times_equals_the_reference_scan(n_groups):
    import jax
    import jax.numpy as jnp

    cfg = tiny_cfg(n_groups=n_groups)
    d, w = hs.dims(cfg), weights(cfg, seed=n_groups)
    rng = np.random.RandomState(7)
    x = rng.randn(3, 9, d.d_model).astype("float32")
    want = np.asarray(ref.mamba(w, "lm_l0_", jnp.asarray(x), cfg))
    ssm = jnp.zeros((3, d.ssm_heads, d.ssm_head_dim, d.d_state), "float32")
    conv = jnp.zeros((3, d.d_conv - 1, d.d_xbc), "float32")
    step = jax.jit(lambda x_t, ssm, conv, ts: hs.mamba2_step(
        x_t, w, "lm_l0_", ssm, conv, ts, d))
    got = []
    for t in range(x.shape[1]):
        y, ssm, conv = step(x[:, t], ssm, conv, np.full(3, t, np.int32))
        got.append(np.asarray(y))
    np.testing.assert_allclose(np.stack(got, 1), want, rtol=2e-5, atol=2e-6)


# ---------------------------------------------------------------------------
# the pooled step against the full forward
# ---------------------------------------------------------------------------
def _staggered(step, make_cache, toks, seq_len=16):
    """Row b consumes ``toks[b]`` one token a step starting at step b
    (idle before and after); one more row is idle throughout.  Returns
    the logits per (row, position) and the final cache."""
    import jax

    B, S = toks.shape
    cache = make_cache(B + 1, seq_len)
    jstep = jax.jit(step)
    got = None
    for it in range(S + B):
        ts = np.array([it - b if 0 <= it - b < S else -1
                       for b in range(B)] + [-1], np.int32)
        tk = np.array([toks[b, max(ts[b], 0)] for b in range(B)] + [0],
                      np.int32)
        lg, cache = jstep(cache, tk, ts)
        lg = np.asarray(lg)
        got = np.zeros((B, S, lg.shape[-1]), "float32") if got is None else got
        for b in range(B):
            if ts[b] >= 0:
                got[b, ts[b]] = lg[b]
    return got, cache


# fp32: the step and the reference differ only in the order of float32
# sums.  bf16: the step multiplies bf16 weights by activations rounded to
# bf16 and keeps K/V in bf16, the reference upcasts the same bf16 weights
# and keeps activations in float32; 2^-9 a rounding through 2 layers stays
# under 2% of the logit range here (measured 0.6%).
@pytest.mark.parametrize("dtype,kv_dtype,tol_share", [
    ("float32", "fp32", 1e-5), ("bfloat16", "bf16", 2e-2)])
def test_prefill_then_decode_equals_the_full_forward(dtype, kv_dtype,
                                                     tol_share):
    import jax.numpy as jnp

    cfg = tiny_cfg()
    w = weights(cfg, seed=3, dtype=dtype)
    step, make_cache = decoding.make_hybrid_ssm_lm_pooled_step_fn(
        w, cfg, kv_dtype=kv_dtype)
    toks = np.random.RandomState(5).randint(0, 97, (3, 12)).astype(np.int32)
    want = np.asarray(ref.forward(w, jnp.asarray(toks), cfg))
    got, cache = _staggered(step, make_cache, toks)
    assert np.abs(got - want).max() <= tol_share * (want.max() - want.min())
    # the row that was idle throughout was neither written nor started
    for layer in cache:
        for leaf in layer.values():
            assert float(jnp.abs(leaf[3].astype("float32")).max()) == 0.0
    # weights are used as given: the step holds no converted copy
    assert make_cache(1, 4)[0]["k"].dtype == jnp.dtype(
        {"fp32": "float32", "bf16": "bfloat16"}[kv_dtype])


def test_transformer_lm_builders_refuse_bf16_kv():
    w = decoding.random_transformer_lm_state(
        np.random.RandomState(0), 31, 16, 1, 2, 32, 8)
    with pytest.raises(ValueError, match="unsupported kv_dtype"):
        decoding.make_transformer_lm_pooled_step_fn(
            w, 31, 16, 1, 2, 32, kv_dtype="bf16")
    assert decoding.normalize_kv_dtype("bfloat16") == "bf16"


# ---------------------------------------------------------------------------
# the pool: a reused slot, declared leaves, refused tiers
# ---------------------------------------------------------------------------
def _pool(cfg, w, len_ladder, **kw):
    step, make_cache = decoding.make_hybrid_ssm_lm_pooled_step_fn(
        w, cfg, kv_dtype="fp32")
    return KVSlotPool(step, make_cache, eos_id=cfg["vocab_size"],
                      max_slots=2, max_seq_len=len_ladder[-1],
                      slot_ladder=[2], len_ladder=len_ladder, steps=2,
                      kv_dtype="fp32", **kw), make_cache


def _serve(pool, state, slot, prompt, n_new):
    state = pool.admit(state, slot, prompt, len(prompt), len(prompt) + n_new)
    while not bool(np.asarray(state["finished"])[slot]):
        state = pool.chunk(state)
    toks = np.asarray(state["tokens"])[slot]
    return state, toks[len(prompt):len(prompt) + n_new].copy()


def _row0_logits(step, make_cache, seqs):
    """Feed each of ``seqs`` in turn through row 0 from position 0 (the
    other row idle); the logits of the last one."""
    import jax

    jstep, cache, out = jax.jit(step), make_cache(2, 16), []
    for seq in seqs:
        out = []
        for t, tok in enumerate(seq):
            lg, cache = jstep(cache, np.array([tok, 0], np.int32),
                              np.array([t, -1], np.int32))
            out.append(np.asarray(lg)[0])
    return np.stack(out)


@pytest.mark.parametrize("reset", [True, False])
def test_a_reused_slot_serves_as_a_virgin_pool_does(reset, monkeypatch):
    """Request B in the slot request A left gives B's logits (and,
    through the pool, B's tokens) exactly as a pool that never held A
    does; with the reset taken out of the step it starts from A's SSM
    and conv state and does not."""
    import jax.numpy as jnp

    if not reset:
        monkeypatch.setattr(hs, "starts_fresh",
                            lambda ts: jnp.zeros(ts.shape, bool))
    cfg = tiny_cfg()
    w = weights(cfg, seed=11)
    rng = np.random.RandomState(2)
    a, b = (rng.randint(0, 97, n).astype(np.int32) for n in (9, 7))
    step, make_cache = decoding.make_hybrid_ssm_lm_pooled_step_fn(
        w, cfg, kv_dtype="fp32")
    want = _row0_logits(step, make_cache, [b])
    got = _row0_logits(step, make_cache, [a, b])
    gap = np.abs(got - want).max() / (want.max() - want.min())
    pool, _ = _pool(cfg, w, [16])
    virgin, want_toks = _serve(pool, pool.alloc(2, 16), 0, b[:4], 8)
    used, _ = _serve(pool, pool.alloc(2, 16), 0, a[:6], 7)
    used, got_toks = _serve(pool, used, 0, b[:4], 8)
    same_state = np.array_equal(np.asarray(used["cache"][0]["ssm"])[0],
                                np.asarray(virgin["cache"][0]["ssm"])[0])
    if reset:
        assert gap == 0.0 and same_state
        assert np.array_equal(got_toks, want_toks)
    else:
        assert gap > 0.01 and not same_state


@pytest.mark.parametrize("rung_equals", ["mamba_d_state", "head_dim"])
def test_a_rung_equal_to_a_state_width_is_an_ordinary_rung(rung_equals):
    """Length rung 16 == d_state (and, in the other case, == head_dim;
    mamba_d_head is 16 in both): the SSM leaf [slots, heads, d_head,
    d_state] has axes as long as the rung, and nothing reads a leaf's
    kind off its shape — the builder declares it, a ``make_cache`` that
    declares nothing is refused, and resize / extract_kv / a further
    admit leave recurrent leaves alone."""
    cfg = tiny_cfg(d_state=16 if rung_equals == "mamba_d_state" else 8,
                   head_dim=16 if rung_equals == "head_dim" else 8)
    assert cfg[rung_equals] == 16
    w = weights(cfg, seed=4)
    pool, make_cache = _pool(cfg, w, [16, 32])
    d = hs.dims(cfg)
    import jax

    leaves = jax.tree.leaves(jax.eval_shape(lambda: make_cache(2, 16)))
    assert leaves[2].shape == (2, d.ssm_heads, 16, d.d_state)
    with pytest.raises(ValueError, match="make_cache declares nothing"):
        decoding.spec_of(lambda s, t: make_cache(s, t))
    spec = decoding.spec_of(make_cache)
    assert len(spec.flat) == len(leaves)
    axes = [leaf.seq_axis for leaf in spec.flat]
    assert axes == [None, 1, None, 1] * d.n_layer   # conv, k, ssm, v
    assert len(pool.recurrent_leaves) == 2 * d.n_layer

    rng = np.random.RandomState(9)
    p0, p1 = (rng.randint(0, 97, n).astype(np.int32) for n in (5, 3))
    # never resized: both requests in a pool at the long rung
    ref_state = pool.admit(pool.alloc(2, 32), 0, p0, 5, 24)
    ref_state = pool.chunk(pool.chunk(ref_state))
    ref_state = pool.admit(ref_state, 1, p1, 3, 12)
    for _ in range(12):
        ref_state = pool.chunk(ref_state)
    # resized mid-flight from the rung that equals the width
    state = pool.admit(pool.alloc(2, 16), 0, p0, 5, 24)
    state = pool.chunk(pool.chunk(state))
    before = {k: np.asarray(state["cache"][0][k]) for k in ("ssm", "conv")}
    got = pool.extract_kv(state, 0, 3)
    assert [g is None for g in got] == [True, False, True, False] * d.n_layer
    assert got[1].shape == (3, d.d_kv)
    state = pool.resize(state, 2, 32)
    for k, v in before.items():
        assert np.array_equal(np.asarray(state["cache"][0][k]), v)
    state = pool.admit(state, 1, p1, 3, 12)
    for _ in range(12):
        state = pool.chunk(state)
    assert np.array_equal(np.asarray(state["tokens"]),
                          np.asarray(ref_state["tokens"]))
    # bytes: sequence leaves scale with the rung, recurrent ones do not
    assert pool.kv_rung_bytes(2, 32) == 2 * pool.kv_rung_bytes(2, 16)
    assert pool.recurrent_rung_bytes(2, 32) == pool.recurrent_rung_bytes(
        2, 16) == d.n_layer * 2 * 4 * (
            d.ssm_heads * d.ssm_head_dim * d.d_state
            + (d.d_conv - 1) * d.d_xbc)


def test_batch_admit_into_used_slots_with_recurrent_leaves(
        check_batch_admit):
    """Both slots of a pool that has served — their SSM and conv state
    is whatever the last occupants left — re-seated by ONE admit: the
    state is leaf for leaf what two admits leave (no cache leaf is
    touched by either), and each slot then serves what a never-used
    pool serves: the step starts both states from zero."""
    import jax

    cfg = tiny_cfg()
    w = weights(cfg, seed=6)
    rng = np.random.RandomState(13)
    a, b, c, d = (rng.randint(0, 97, n).astype(np.int32)
                  for n in (6, 4, 5, 3))
    pool, _ = _pool(cfg, w, [16])
    used, _ = _serve(pool, pool.alloc(2, 16), 0, a, 7)
    used, _ = _serve(pool, used, 1, b, 9)
    before = [np.asarray(x) for x in jax.tree.leaves(used["cache"])]
    ssm = np.asarray(used["cache"][0]["ssm"])
    assert np.abs(ssm[0]).max() > 0 and np.abs(ssm[1]).max() > 0
    state = check_batch_admit(pool, used, [(1, c, 12, False),
                                           (0, d, 10, False)])
    for x, y in zip(before, jax.tree.leaves(state["cache"])):
        assert np.array_equal(x, np.asarray(y))
    while not np.asarray(state["finished"]).all():
        state = pool.chunk(state)
    toks = np.asarray(state["tokens"])
    _, want_c = _serve(pool, pool.alloc(2, 16), 1, c, 7)
    _, want_d = _serve(pool, pool.alloc(2, 16), 0, d, 7)
    assert np.array_equal(toks[1, 5:12], want_c)
    assert np.array_equal(toks[0, 3:10], want_d)


@pytest.mark.parametrize("tier", ["prefix", "speculative"])
def test_prefix_and_speculation_are_refused_over_recurrent_leaves(tier):
    cfg = tiny_cfg()
    w = weights(cfg)
    if tier == "prefix":
        kw = {"prefix": True}
    else:
        from paddle_tpu.serving.speculative import SpeculativeConfig

        step, make_cache = decoding.make_hybrid_ssm_lm_pooled_step_fn(
            w, cfg, kv_dtype="fp32")
        kw = {"speculative": SpeculativeConfig(
            lambda c, t, ts: (None, c), step, make_cache, k=2)}
    with pytest.raises(ValueError, match=r"recurrent leaves.*conv"):
        _pool(cfg, w, [16], **kw)
    if tier == "prefix":
        step, make_cache = decoding.make_hybrid_ssm_lm_pooled_step_fn(
            w, cfg, kv_dtype="fp32")
        with pytest.raises(ValueError, match="recurrent leaves"):
            DecodeServer(step, make_cache, eos_id=97, max_seq_len=16,
                         max_slots=2, prefix_cache=1 << 20)


# ---------------------------------------------------------------------------
# the server
# ---------------------------------------------------------------------------
def test_decode_server_end_to_end_with_slot_reuse():
    """Six requests through two slots: every one gets the tokens the
    reference's full forward ranks first (greedy, fp32), the reset
    counter equals the admissions and the gauge the spec's bytes."""
    import jax.numpy as jnp

    cfg = tiny_cfg()
    w = weights(cfg, seed=21)
    d = hs.dims(cfg)
    step, make_cache = decoding.make_hybrid_ssm_lm_pooled_step_fn(
        w, cfg, kv_dtype="fp32")
    srv = DecodeServer(step, make_cache, eos_id=97, max_seq_len=32,
                       max_slots=2, slot_ladder=[2], len_ladder=[32],
                       steps_per_tick=2, kv_dtype="fp32",
                       name="hybrid-e2e")
    lbl = {"server": "hybrid-e2e"}
    try:
        srv.warmup()
        rng = np.random.RandomState(8)
        prompts = [rng.randint(0, 97, n).astype(np.int32)
                   for n in (5, 9, 3, 7, 4, 6)]
        reqs = [srv.submit({"tokens": p}, max_new_tokens=6 + i)
                for i, p in enumerate(prompts)]
        outs = [r.result(timeout=120)[0] for r in reqs]
        m = srv.metrics()["decode"]
        resets = monitor.counter_value(
            "serving_decode_state_resets_total", **lbl)
        rec_bytes = monitor.counter_value(
            "serving_recurrent_state_bytes", **lbl)
        kv_bytes = monitor.counter_value("serving_kv_cache_bytes", **lbl)
    finally:
        srv.stop(drain=False, timeout=30)
    assert resets == len(prompts) == m["state_resets"]
    want_rec = d.n_layer * 2 * 4 * (d.ssm_heads * d.ssm_head_dim * d.d_state
                                    + (d.d_conv - 1) * d.d_xbc)
    assert rec_bytes in (want_rec, 0.0)  # 0 once the idle pool was dropped
    assert m["recurrent_state_bytes"] in (want_rec, 0)
    assert kv_bytes in (d.n_layer * 2 * 2 * 32 * d.d_kv * 4, 0.0)
    assert srv._state is None     # a stopped server holds no pool
    for p, out, i in zip(prompts, outs, range(len(prompts))):
        assert len(out) == 6 + i
        full = np.concatenate([p, out])[None, :]
        logits = np.asarray(ref.forward(w, jnp.asarray(full), cfg))[0]
        for j, tok in enumerate(out):
            row = logits[len(p) + j - 1]
            assert row.max() - row[tok] <= 1e-5 * (row.max() - row.min())


def test_kv_only_pool_counts_no_state_resets():
    """The transformer-LM pool has no recurrent leaves: the new counter
    and gauge stay at zero and the KV gauge means what it meant."""
    V = 31
    w = decoding.random_transformer_lm_state(
        np.random.RandomState(0), V, 16, 1, 2, 32, 16)
    step, make_cache = decoding.make_transformer_lm_pooled_step_fn(
        w, V, 16, 1, 2, 32)
    srv = DecodeServer(step, make_cache, eos_id=V, max_seq_len=16,
                       max_slots=2, slot_ladder=[2], len_ladder=[16],
                       steps_per_tick=2, name="kv-only")
    try:
        srv.warmup()
        srv.submit({"tokens": np.array([1, 2, 3], np.int32)},
                   max_new_tokens=4).result(timeout=60)
        assert srv._pool.recurrent_leaves == []
        assert srv._pool.recurrent_rung_bytes(2, 16) == 0
        assert srv._pool.kv_rung_bytes(2, 16) == 2 * 2 * 16 * 16 * 4
        assert monitor.counter_value("serving_decode_state_resets_total",
                                     server="kv-only") == 0
    finally:
        srv.stop(drain=False, timeout=30)


# ---------------------------------------------------------------------------
# the step the gpt1_117m cells run, pinned to the parent commit
# ---------------------------------------------------------------------------
def _gpt1_recording():
    """Admits beside live rows, a rung change, a reused slot, fp32 and
    int8 KV, on seed 1234.  ``tests/data/gpt1_pooled_step_pr26.npz`` is
    what this function returned in a checkout of the parent commit
    (b3a4608, PR 26) on the CPU."""
    import jax

    rng = np.random.RandomState(1234)
    V, D, L, H, DI = 61, 32, 2, 4, 64
    w = decoding.random_transformer_lm_state(rng, V, D, L, H, DI, 32)
    out = {}
    for kv in ("fp32", "int8"):
        step, make_cache = decoding.make_transformer_lm_pooled_step_fn(
            w, V, D, L, H, DI, kv_dtype=kv)
        pool = KVSlotPool(step, make_cache, eos_id=V, max_slots=4,
                          max_seq_len=32, slot_ladder=[4],
                          len_ladder=[16, 32], steps=3, kv_dtype=kv)
        st = pool.alloc(4, 16)
        prompts = [rng.randint(0, V, n).astype(np.int32) for n in (5, 3, 7)]
        st = pool.admit(st, 0, prompts[0], 5, 14)
        st = pool.chunk(st)
        st = pool.admit(st, 2, prompts[1], 3, 12)
        for _ in range(3):
            st = pool.chunk(st)
        st = pool.resize(st, 4, 32)
        st = pool.admit(st, 1, prompts[2], 7, 30)   # beside live rows
        for _ in range(6):
            st = pool.chunk(st)
        st = pool.admit(st, 0, prompts[1], 3, 20)   # a reused slot
        for _ in range(4):
            st = pool.chunk(st)
        out[kv + "_tokens"] = np.asarray(st["tokens"])
        out[kv + "_pos"] = np.asarray(st["pos"])
        cache = make_cache(4, 16)
        lg, _ = jax.jit(step)(cache, np.array([3, 7, 11, 0], np.int32),
                              np.array([0, 0, 0, -1], np.int32))
        out[kv + "_logits"] = np.asarray(lg)[:3]
    return out


@pytest.mark.parametrize("kv", ["fp32", "int8"])
def test_transformer_lm_pooled_step_equals_the_parent_recording(kv):
    """Token ids and positions exactly; logits to 1e-6 of their range
    (bit-equal where recorded — another CPU may order a dot product's
    sum differently)."""
    want = np.load(os.path.join(HERE, "data", "gpt1_pooled_step_pr26.npz"))
    got = _gpt1_recording()
    assert np.array_equal(got[kv + "_tokens"], want[kv + "_tokens"])
    assert np.array_equal(got[kv + "_pos"], want[kv + "_pos"])
    lg = want[kv + "_logits"]
    assert np.abs(got[kv + "_logits"] - lg).max() <= 1e-6 * (
        lg.max() - lg.min())
