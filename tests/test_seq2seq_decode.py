"""NMT training + greedy/beam decode on a copy task.

Reference: tests/book/test_machine_translation.py (train seq2seq then
beam-search decode).  The copy task (target = source) is learnable in a
few dozen steps and verifies the decoder end-to-end: a trained model
must reproduce the source under greedy and beam decoding.
"""
import numpy as np
import pytest

import paddle_tpu as fluid
from paddle_tpu import decoding, framework, models

V, T = 20, 8
BOS, EOS = 1, 2


def _make_batch(rng, n):
    # tokens in [3, V): 0/1/2 reserved for pad/bos/eos
    body = rng.randint(3, V, (n, T - 1))
    src = np.concatenate([body, np.full((n, 1), EOS)], axis=1).astype("int64")
    tgt_in = np.concatenate([np.full((n, 1), BOS), body], axis=1).astype("int64")
    labels = src[..., None].astype("int64")
    return src, tgt_in, labels


@pytest.mark.slow
def test_nmt_copy_task_train_and_decode():
    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 13
    with framework.program_guard(prog, startup):
        src = fluid.layers.data("src", [T], dtype="int64")
        tgt = fluid.layers.data("tgt", [T], dtype="int64")
        lbl = fluid.layers.data("lbl", [T, 1], dtype="int64")
        loss, logits = models.seq2seq.transformer_nmt(
            src, tgt, lbl,
            src_vocab=V, tgt_vocab=V, d_model=48, n_layer=2, n_head=4,
            d_inner=96, src_len=T, tgt_len=T,
        )
        fluid.optimizer.AdamOptimizer(0.005).minimize(loss)

    rng = np.random.RandomState(0)
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        losses = []
        for step in range(250):
            s, t_in, l = _make_batch(rng, 32)
            (lv,) = exe.run(prog, feed={"src": s, "tgt": t_in, "lbl": l}, fetch_list=[loss])
            losses.append(float(np.asarray(lv)))
        assert losses[-1] < 0.3 * losses[0], (losses[0], losses[-1])

        # --- decode with the trained params ---
        infer_prog, infer_startup = framework.Program(), framework.Program()
        with framework.program_guard(infer_prog, infer_startup):
            src_i = fluid.layers.data("src", [T], dtype="int64")
            tgt_i = fluid.layers.data("tgt", [T], dtype="int64")
            _, logits_i = models.seq2seq.transformer_nmt(
                src_i, tgt_i, None,
                src_vocab=V, tgt_vocab=V, d_model=48, n_layer=2, n_head=4,
                d_inner=96, src_len=T, tgt_len=T, is_test=True,
            )
        state = {
            v.name: scope.get(v.name)
            for v in infer_prog.list_vars()
            if v.persistable and scope.get(v.name) is not None
        }
        # the infer program must reuse the trained parameter names
        assert len(state) == len([v for v in infer_prog.list_vars() if v.persistable])

    logits_fn = decoding.make_program_logits_fn(
        infer_prog, state, ["src", "tgt"], logits_i.name
    )
    s, _, _ = _make_batch(np.random.RandomState(7), 4)

    toks, scores = decoding.greedy_search(
        logits_fn, s.astype("int32"), BOS, EOS, max_len=T
    )
    toks = np.asarray(toks)
    # greedy output (after BOS) should reproduce the source body
    match = (toks[:, 1:] == s[:, :-1]).mean()
    assert match > 0.9, (match, toks[:2], s[:2])

    btoks, bscores = decoding.beam_search(
        logits_fn, s.astype("int32"), BOS, EOS, beam_size=4, max_len=T
    )
    btoks = np.asarray(btoks)
    bmatch = (btoks[:, 0, 1:] == s[:, :-1]).mean()
    assert bmatch >= match - 1e-6, (bmatch, match)
    # beams are score-sorted
    assert np.all(np.asarray(bscores)[:, 0] >= np.asarray(bscores)[:, -1])


def test_cached_decode_matches_full_prefix():
    """KV-cached decoding (decoding.beam_search_cached +
    make_transformer_lm_step_fn) must produce exactly the same tokens —
    and the same scores within tolerance — as the full-prefix re-run
    path on the same transformer_lm weights.  O(T) per step vs O(T^2);
    the beam reorder gathers cache rows by parent."""
    V2, D, L, H, DI, ML = 24, 32, 2, 4, 64, 10
    B, K = 3, 3

    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 71
    with framework.program_guard(prog, startup):
        src = fluid.layers.data("src", [ML], dtype="int64")
        _, logits = models.transformer.transformer_lm(
            src, None, vocab_size=V2, d_model=D, n_layer=L, n_head=H,
            d_inner=DI, seq_len=ML, max_pos=ML, dropout_rate=0.0,
            is_test=True,
        )
    scope = fluid.Scope()
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(scope):
        exe.run(startup)
        state = {
            v.name: scope.get(v.name)
            for v in prog.list_vars()
            if v.persistable and scope.get(v.name) is not None
        }

    pfn = decoding.make_program_logits_fn(prog, state, ["src"], logits.name)

    def logits_fn(feeds):
        # decoder-only LM: the "target" prefix IS the model input
        return pfn({"src": feeds["tgt"]})

    dummy_src = np.zeros((B, 1), "int32")
    toks_full, scores_full = decoding.beam_search(
        logits_fn, dummy_src, BOS, EOS, beam_size=K, max_len=ML)

    step_fn, make_cache = decoding.make_transformer_lm_step_fn(
        state, V2, D, L, H, DI, ML)
    toks_c, scores_c = decoding.beam_search_cached(
        step_fn, make_cache(B * K), B, BOS, EOS, beam_size=K, max_len=ML)

    np.testing.assert_array_equal(np.asarray(toks_c), np.asarray(toks_full))
    np.testing.assert_allclose(
        np.asarray(scores_c), np.asarray(scores_full), rtol=1e-4, atol=1e-4)

    g_full, gs_full = decoding.greedy_search(
        logits_fn, dummy_src, BOS, EOS, max_len=ML)
    g_c, gs_c = decoding.greedy_search_cached(
        step_fn, make_cache(B), B, BOS, EOS, max_len=ML)
    np.testing.assert_array_equal(np.asarray(g_c), np.asarray(g_full))
    np.testing.assert_allclose(
        np.asarray(gs_c), np.asarray(gs_full), rtol=1e-4, atol=1e-4)


# ---------------------------------------------------------------------------
# cached-path coverage: pooled-step parity, EOS early-exit, cache reuse
# ---------------------------------------------------------------------------
from paddle_tpu.decoding import (  # noqa: E402 — test-local alias
    random_transformer_lm_state as _random_lm_state,
)


_LM = dict(vocab=18, d_model=16, n_layer=2, n_head=2, d_inner=32,
           max_pos=12)


def test_pooled_step_fn_matches_scalar_step_fn():
    """The slot-pool step fn (per-row positions ``ts``) must equal the
    scalar-``t`` step fn exactly when all rows sit at the same position
    — same weights, same caches, token by token."""
    import jax.numpy as jnp

    rng = np.random.RandomState(3)
    state = _random_lm_state(rng, **_LM)
    N, ML = 3, _LM["max_pos"]
    s_fn, s_cache = decoding.make_transformer_lm_step_fn(
        state, _LM["vocab"], _LM["d_model"], _LM["n_layer"],
        _LM["n_head"], _LM["d_inner"], ML)
    p_fn, p_cache = decoding.make_transformer_lm_pooled_step_fn(
        state, _LM["vocab"], _LM["d_model"], _LM["n_layer"],
        _LM["n_head"], _LM["d_inner"])
    sc, pc = s_cache(N), p_cache(N, ML)
    for t in range(ML):
        toks = jnp.asarray(rng.randint(0, _LM["vocab"], N), "int32")
        ls, sc = s_fn(sc, toks, t)
        lp, pc = p_fn(pc, toks, jnp.full((N,), t, "int32"))
        np.testing.assert_allclose(np.asarray(ls), np.asarray(lp),
                                   rtol=1e-5, atol=1e-5)
    # the scalar cache is [N, H, T, Dh]; the pooled fp32 cache folds the
    # heads into the lane axis: [N, T, H * Dh]
    for i in range(_LM["n_layer"]):
        np.testing.assert_allclose(
            np.asarray(sc[i]["k"]).transpose(0, 2, 1, 3).reshape(N, ML, -1),
            np.asarray(pc[i]["k"]), rtol=1e-5, atol=1e-5)


def test_pooled_step_fn_rows_at_different_positions():
    """Per-row positions are genuinely independent: running row A to
    position k with row B idle gives row A the same logits as running
    A alone — the pooled mask/scatter never leaks across rows."""
    import jax.numpy as jnp

    rng = np.random.RandomState(4)
    state = _random_lm_state(rng, **_LM)
    ML = _LM["max_pos"]
    p_fn, p_cache = decoding.make_transformer_lm_pooled_step_fn(
        state, _LM["vocab"], _LM["d_model"], _LM["n_layer"],
        _LM["n_head"], _LM["d_inner"])
    toks = rng.randint(0, _LM["vocab"], ML)
    # lane 0 alone
    c1 = p_cache(1, ML)
    solo = []
    for t in range(4):
        l1, c1 = p_fn(c1, jnp.asarray([toks[t]], "int32"),
                      jnp.asarray([t], "int32"))
        solo.append(np.asarray(l1[0]))
    # lane 0 advancing while lane 1 replays position 0 every step with
    # junk tokens (a stale/idle slot)
    c2 = p_cache(2, ML)
    for t in range(4):
        l2, c2 = p_fn(
            c2, jnp.asarray([toks[t], 7], "int32"),
            jnp.asarray([t, 0], "int32"))
        np.testing.assert_allclose(np.asarray(l2[0]), solo[t],
                                   rtol=1e-5, atol=1e-5)


def test_greedy_cached_eos_early_exit():
    """A sequence that emits EOS freezes: every later position stays
    EOS (finished beams extend only with EOS) and the score stops
    accumulating at the EOS transition."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(5)
    state = _random_lm_state(rng, **_LM)
    ML = _LM["max_pos"]
    step_fn, make_cache = decoding.make_transformer_lm_step_fn(
        state, _LM["vocab"], _LM["d_model"], _LM["n_layer"],
        _LM["n_head"], _LM["d_inner"], ML)
    bos = 1
    # whatever greedy picks first becomes the EOS of the rerun: the
    # decode must then finish at position 1 and pad EOS to max_len
    logits, _ = step_fn(make_cache(1), jnp.asarray([bos], "int32"), 0)
    eos = int(np.argmax(np.asarray(logits[0])))
    toks, scores = decoding.greedy_search_cached(
        step_fn, make_cache(1), 1, bos, eos, max_len=ML)
    toks = np.asarray(toks)
    assert toks[0, 0] == bos
    assert (toks[0, 1:] == eos).all()
    expected = float(jax.nn.log_softmax(
        jnp.asarray(logits[0]))[eos])
    np.testing.assert_allclose(float(np.asarray(scores)[0]), expected,
                               rtol=1e-4, atol=1e-4)


def test_cached_decode_cache_reuse_across_calls():
    """Cache buffers are reusable across calls without leakage: a
    second run on the same cache pytree — and a run on a junk-filled
    cache — produce identical tokens and scores, proving the
    write-before-read discipline the serving slot pool relies on."""
    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(6)
    state = _random_lm_state(rng, **_LM)
    B, ML = 2, _LM["max_pos"]
    step_fn, make_cache = decoding.make_transformer_lm_step_fn(
        state, _LM["vocab"], _LM["d_model"], _LM["n_layer"],
        _LM["n_head"], _LM["d_inner"], ML)
    cache = make_cache(B)
    t1, s1 = decoding.greedy_search_cached(
        step_fn, cache, B, BOS, EOS, max_len=ML)
    t2, s2 = decoding.greedy_search_cached(
        step_fn, cache, B, BOS, EOS, max_len=ML)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t2))
    np.testing.assert_array_equal(np.asarray(s1), np.asarray(s2))
    junk = [
        {"k": jnp.full_like(layer["k"], 7.5),
         "v": jnp.full_like(layer["v"], -3.25)}
        for layer in cache
    ]
    t3, s3 = decoding.greedy_search_cached(
        step_fn, junk, B, BOS, EOS, max_len=ML)
    np.testing.assert_array_equal(np.asarray(t1), np.asarray(t3))
    np.testing.assert_allclose(np.asarray(s1), np.asarray(s3),
                               rtol=1e-5, atol=1e-5)
    t4, s4 = decoding.beam_search_cached(
        step_fn, make_cache(B * 3), B, BOS, EOS, beam_size=3,
        max_len=ML)
    t5, s5 = decoding.beam_search_cached(
        step_fn, jax.tree.map(lambda c: jnp.full_like(c, 9.0),
                              make_cache(B * 3)),
        B, BOS, EOS, beam_size=3, max_len=ML)
    np.testing.assert_array_equal(np.asarray(t4), np.asarray(t5))
    np.testing.assert_allclose(np.asarray(s4), np.asarray(s5),
                               rtol=1e-5, atol=1e-5)
