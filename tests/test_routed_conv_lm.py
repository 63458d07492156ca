"""The routed-experts decoder (``model_type: lfm2_moe``) on the pooled
decode path: ``decoding.make_routed_conv_lm_pooled_step_fn`` ->
``KVSlotPool`` -> ``DecodeServer``, at the sizes of the benchmark
configuration's ``rehearse`` group on the CPU (seeded), against the
benchmark's plain reference (``benchmark/configs/
lfm2_24b_a2b_reference.py``: float32, full forward, no cache).

What is new under the pool: layers that hold DIFFERENT leaves (K/V rows,
or a conv window), a tied head, and counts made on the device (which
experts a step touched) that ride the scheduler's one fetch a tick.
"""
import importlib.util
import json
import os

import numpy as np
import pytest

from paddle_tpu import decoding, monitor
from paddle_tpu import routed_experts as rx
from paddle_tpu.serving.decode import DecodeServer
from paddle_tpu.serving.kv_pool import KVSlotPool

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(ROOT, "benchmark", "configs",
                         "lfm2_24b_a2b_reference.py"), "lfm2_reference")


def rehearse_cfg(**over):
    """The configuration file at its ``rehearse`` sizes (hidden 128, 16
    experts of width 64, four a token, conv / attention / conv / conv)."""
    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2_24b_a2b.json")) as f:
        cfg = json.load(f)
    tiny = cfg.pop("rehearse")
    cfg.update({k: v for k, v in tiny.items() if not isinstance(v, dict)})
    cfg.update(vocab_size=211, **over)
    return cfg


V = 211


def weights(cfg, seed=0, dtype="float32"):
    return rx.random_state(np.random.RandomState(seed), cfg, std=0.1,
                           dtype=dtype)


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


# fp32: the step and the reference differ in the order of float32 sums
# (and a token whose fourth and fifth expert tie to 1e-7 would differ by
# an expert: none does on this seed).  bf16: the step multiplies bf16
# weights by activations rounded to bf16 and keeps K/V in bf16; a
# marginal expert may go the other way, which moves a token's logits by
# a few per cent of their range: the MEAN gap is held as the cell's
# check holds it, the worst loosely.
@pytest.mark.parametrize("dtype,kv_dtype,worst,mean", [
    ("float32", "fp32", 2e-5, 2e-6), ("bfloat16", "bf16", 8e-2, 1e-2)])
def test_prefill_then_decode_equals_the_full_forward(dtype, kv_dtype, worst,
                                                     mean):
    import jax.numpy as jnp

    cfg = rehearse_cfg()
    w = weights(cfg, seed=3, dtype=dtype)
    step, make_cache = decoding.make_routed_conv_lm_pooled_step_fn(
        w, cfg, kv_dtype=kv_dtype)
    toks = np.random.RandomState(5).randint(0, V, (3, 12)).astype(np.int32)
    want = np.asarray(ref.forward(w, jnp.asarray(toks), cfg))
    got, cache = _staggered(step, make_cache, toks)
    gap = np.abs(got - want).max(-1) / (want.max() - want.min())
    assert gap.max() <= worst and gap.mean() <= mean
    # the row that was idle throughout was neither written nor started
    for layer in cache["layers"]:
        for leaf in layer.values():
            assert float(jnp.abs(leaf[3].astype("float32")).max()) == 0.0
    kinds = [sorted(layer) for layer in cache["layers"]]
    assert kinds == [["conv"], ["k", "v"], ["conv"], ["conv"]]
    assert cache["layers"][1]["k"].dtype == jnp.dtype(
        {"fp32": "float32", "bf16": "bfloat16"}[kv_dtype])
    assert cache["layers"][0]["conv"].dtype == jnp.float32


def test_the_counts_made_on_the_device_equal_a_recount_from_the_reference():
    """Every step adds, per expert layer, the (row, choice) pairs of its
    live rows, the experts touched, the largest group and 1: summed over
    the staggered run they are what the reference's routing of the same
    tokens gives, position by position."""
    import jax.numpy as jnp

    cfg = rehearse_cfg()
    w = weights(cfg, seed=3)
    step, make_cache = decoding.make_routed_conv_lm_pooled_step_fn(w, cfg)
    toks = np.random.RandomState(5).randint(0, V, (3, 12)).astype(np.int32)
    _, cache = _staggered(step, make_cache, toks)
    _, chosen = ref.forward(w, jnp.asarray(toks), cfg, with_routing=True)
    B, S = toks.shape
    want = np.zeros((len(chosen), 4), np.int64)
    for layer, sel in enumerate(np.asarray(c) for c in chosen):
        for it in range(S + B):       # the steps _staggered ran
            rows = [(b, it - b) for b in range(B) if 0 <= it - b < S]
            if not rows:
                continue
            counts = np.bincount(
                np.concatenate([sel[b, t] for b, t in rows]),
                minlength=cfg["num_experts"])
            want[layer] += [counts.sum(), (counts > 0).sum(),
                            counts.max(), 1]
    spec = decoding.spec_of(make_cache)
    assert spec.n_expert == 16
    got = np.asarray(spec.expert_stats(cache))
    assert got.tolist() == want.tolist()
    assert want[:, 0].tolist() == [B * S * 4] * 3


# ---------------------------------------------------------------------------
# the pool: different leaves a layer, a reused slot, refused tiers
# ---------------------------------------------------------------------------
def _pool(cfg, w, len_ladder, **kw):
    step, make_cache = decoding.make_routed_conv_lm_pooled_step_fn(
        w, cfg, kv_dtype="fp32")
    return KVSlotPool(step, make_cache, eos_id=V, max_slots=2,
                      max_seq_len=len_ladder[-1], slot_ladder=[2],
                      len_ladder=len_ladder, steps=2, kv_dtype="fp32",
                      **kw), make_cache


def _serve(pool, state, slot, prompt, n_new):
    state = pool.admit(state, slot, prompt, len(prompt), len(prompt) + n_new)
    while not bool(np.asarray(state["finished"])[slot]):
        state = pool.chunk(state)
    toks = np.asarray(state["tokens"])[slot]
    return state, toks[len(prompt):len(prompt) + n_new].copy()


@pytest.mark.parametrize("reset", [True, False])
def test_a_reused_slot_starts_its_conv_state_from_zero(reset, monkeypatch):
    """Request B in the slot request A left gets B's tokens exactly as a
    pool that never held A gives them; with the reset taken out of the
    step its first positions start from A's conv window and do not."""
    import jax.numpy as jnp

    if not reset:
        monkeypatch.setattr(rx, "starts_fresh",
                            lambda ts: jnp.zeros(ts.shape, bool))
    cfg = rehearse_cfg()
    w = weights(cfg, seed=11)
    rng = np.random.RandomState(2)
    a, b = (rng.randint(0, V, n).astype(np.int32) for n in (9, 4))
    pool, _ = _pool(cfg, w, [16])
    virgin, want_toks = _serve(pool, pool.alloc(2, 16), 0, b, 8)
    used, _ = _serve(pool, pool.alloc(2, 16), 0, a, 7)
    assert np.abs(np.asarray(used["cache"]["layers"][0]["conv"])[0]).max() > 0
    used, got_toks = _serve(pool, used, 0, b, 8)
    same = all(np.array_equal(np.asarray(u["conv"])[0],
                              np.asarray(v["conv"])[0])
               for u, v in zip(used["cache"]["layers"],
                               virgin["cache"]["layers"]) if "conv" in u)
    if reset:
        assert same and np.array_equal(got_toks, want_toks)
    else:
        assert not same


def test_layers_that_hold_different_leaves_are_declared_leaf_by_leaf():
    """An attention layer holds k and v and no state, a conv layer a
    window and no K/V, and the counts ride as one more undeclared-axis
    leaf: ``spec_of(make_cache)`` reads the
    declarations, ``resize`` cuts and pads to the spec (state and counts
    kept whatever the length rung), ``extract_kv`` skips what has no
    positions, and the bytes follow."""
    import jax

    cfg = rehearse_cfg()
    w = weights(cfg, seed=4)
    pool, make_cache = _pool(cfg, w, [16, 32])
    d = rx.dims(cfg)
    leaves = jax.tree.leaves(jax.eval_shape(lambda: make_cache(2, 16)))
    with pytest.raises(ValueError, match="make_cache declares nothing"):
        decoding.spec_of(lambda s, t: make_cache(s, t))
    spec = decoding.spec_of(make_cache)
    assert len(spec.flat) == len(leaves)
    # flattened: expert_stats, then layers: conv | k, v | conv | conv
    assert [leaf.seq_axis for leaf in spec.flat] == [
        None, None, 1, 1, None, None]
    assert pool.recurrent_leaves == spec.names(lambda leaf: leaf.seq_axis is None) == [
        "['expert_stats']", "['layers'][0]['conv']",
        "['layers'][2]['conv']", "['layers'][3]['conv']"]

    rng = np.random.RandomState(9)
    p0, p1 = (rng.randint(0, V, n).astype(np.int32) for n in (5, 3))
    # never resized: both requests in a pool at the long rung
    want = pool.admit(pool.alloc(2, 32), 0, p0, 5, 24)
    want = pool.chunk(pool.chunk(want))
    want = pool.admit(want, 1, p1, 3, 12)
    for _ in range(12):
        want = pool.chunk(want)
    # resized mid-flight
    state = pool.admit(pool.alloc(2, 16), 0, p0, 5, 24)
    state = pool.chunk(pool.chunk(state))
    conv = np.asarray(state["cache"]["layers"][0]["conv"])
    counts = np.asarray(state["cache"]["expert_stats"])
    assert counts[:, 0].tolist() == [4 * 4] * 3    # 4 steps x 1 row x top 4
    got = pool.extract_kv(state, 0, 3)
    assert [g is None for g in got] == [True, True, False, False, True, True]
    assert got[2].shape == (3, d.d_kv)
    state = pool.resize(state, 2, 32)
    assert np.array_equal(np.asarray(state["cache"]["layers"][0]["conv"]),
                          conv)
    assert np.array_equal(np.asarray(state["cache"]["expert_stats"]), counts)
    state = pool.admit(state, 1, p1, 3, 12)
    for _ in range(12):
        state = pool.chunk(state)
    assert np.array_equal(np.asarray(state["tokens"]),
                          np.asarray(want["tokens"]))
    assert np.array_equal(np.asarray(state["cache"]["expert_stats"]),
                          np.asarray(want["cache"]["expert_stats"]))
    # bytes: K/V of the ONE attention layer scale with the rung; the
    # three conv windows and the counts do not
    assert pool.kv_rung_bytes(2, 32) == 2 * pool.kv_rung_bytes(2, 16) == (
        2 * 2 * 32 * d.d_kv * 4)
    assert pool.recurrent_rung_bytes(2, 32) == pool.recurrent_rung_bytes(
        2, 16) == 3 * 2 * (d.conv_len - 1) * d.d_model * 4 + 3 * 4 * 4


def test_the_builder_declares_what_a_step_reads_of_its_leaves():
    """The spec's ``"kv"`` read is ``step_positions_read`` for
    this builder's leaves, so the server's read counter follows the
    chooser: at the published widths (32 query heads over 8 K/V heads of
    64 lanes, bf16) the grouped kernel's rounding on a TPU — blocks of
    512, the last in classes of 64 — and the whole rung off it; over
    heads no lane tiles hold (the rehearsal's 32 lanes) the whole rung
    everywhere."""
    import functools

    from paddle_tpu import decode_attention as da

    with open(os.path.join(ROOT, "benchmark", "configs",
                           "lfm2_24b_a2b.json")) as f:
        cfg = json.load(f)
    # the declaration needs the dimensions alone: no weight is read
    (kv,) = decoding.spec_of(decoding.make_routed_conv_lm_pooled_step_fn(
        {}, cfg, kv_dtype="bf16")[1]).reads
    assert kv.kind == "kv" and kv.rounds
    rule = kv.rule
    d = rx.dims(cfg)
    assert (d.n_head, d.n_kv_head, d.head_dim, d.d_kv) == (32, 8, 64, 512)
    want = functools.partial(da.step_positions_read, width=512,
                             dtype="bfloat16", n_head=32, n_kv_head=8)
    ts = np.asarray([0, 63, 64, 511, 512, 1000, 2047], np.int32)
    assert rule(ts, 2048).tolist() == want(ts, 2048).tolist() == [2048] * 7
    assert rule(ts, 2048, backend="tpu").tolist() == want(
        ts, 2048, backend="tpu").tolist() == da.kv_positions_read(
            ts, 512, 64).tolist() == [64, 64, 128, 512, 576, 1024, 2048]
    tiny = decoding.spec_of(decoding.make_routed_conv_lm_pooled_step_fn(
        {}, rehearse_cfg(), kv_dtype="bf16")[1]).reads[0].rule
    assert tiny(ts[:3], 64, backend="tpu").tolist() == [64] * 3


def test_a_chunk_of_steps_equals_the_steps_one_by_one_and_says_its_form():
    """Heads of 64 lanes (8 query heads over 2 K/V heads), bf16 leaves:
    four steps a chunk leave the tokens and every leaf that the same
    steps one a chunk leave; on the CPU the step takes the XLA form of
    the read and counts itself so (the kernel's path is a TPU's)."""
    from paddle_tpu import decode_attention as da

    cfg = rehearse_cfg(num_attention_heads=8, num_key_value_heads=2,
                       head_dim=64)
    w = weights(cfg, seed=6, dtype="bfloat16")

    def counted():
        return [da.GROUPED_LOWERED.labels(path=path).value
                for path in ("kernel", "xla")]

    def run(steps, chunks):
        step, make_cache = decoding.make_routed_conv_lm_pooled_step_fn(
            w, cfg, kv_dtype="bf16")
        pool = KVSlotPool(step, make_cache, eos_id=V, max_slots=2,
                          max_seq_len=32, slot_ladder=[2], len_ladder=[32],
                          steps=steps, kv_dtype="bf16")
        rng = np.random.RandomState(4)
        state = pool.alloc(2, 32)
        for slot, n in enumerate((5, 9)):
            state = pool.admit(state, slot, rng.randint(0, V, n).astype(
                np.int32), n, 30)
        for _ in range(chunks):
            state = pool.chunk(state)
        return state

    by_kernel, by_xla = counted()
    together, apart = run(4, 3), run(1, 12)
    assert counted()[0] == by_kernel and counted()[1] > by_xla
    assert np.array_equal(np.asarray(together["tokens"]),
                          np.asarray(apart["tokens"]))
    for a, b in zip(together["cache"]["layers"], apart["cache"]["layers"]):
        for name in a:
            assert np.array_equal(np.asarray(a[name].astype("float32")),
                                  np.asarray(b[name].astype("float32")))
    k = np.asarray(together["cache"]["layers"][1]["k"].astype("float32"))
    # twelve steps wrote positions 0 .. 11 of each live slot, no other
    assert k.shape == (2, 32, 128) and np.abs(k[:, :12]).max(-1).min() > 0
    assert not k[:, 12:].any()


@pytest.mark.parametrize("tier", ["prefix", "speculative"])
def test_prefix_and_speculation_are_refused_over_this_builder(tier):
    cfg = rehearse_cfg()
    w = weights(cfg)
    if tier == "prefix":
        kw = {"prefix": True}
    else:
        from paddle_tpu.serving.speculative import SpeculativeConfig

        step, make_cache = decoding.make_routed_conv_lm_pooled_step_fn(
            w, cfg, kv_dtype="fp32")
        kw = {"speculative": SpeculativeConfig(
            lambda c, t, ts: (None, c), step, make_cache, k=2)}
    with pytest.raises(ValueError, match=r"recurrent leaves"):
        _pool(cfg, w, [16], **kw)
    if tier == "prefix":
        step, make_cache = decoding.make_routed_conv_lm_pooled_step_fn(
            w, cfg, kv_dtype="fp32")
        with pytest.raises(ValueError, match="recurrent leaves"):
            DecodeServer(step, make_cache, eos_id=V, max_seq_len=16,
                         max_slots=2, prefix_cache=1 << 20)


def test_a_share_of_the_experts_serves_the_references_share():
    """``held``: a builder that holds experts 4..11 of 16 routes over
    all 16 and adds what its own give; the reference given the same
    share agrees, logits and all."""
    import jax.numpy as jnp

    cfg = rehearse_cfg()
    w = weights(cfg, seed=8)
    held = {k: (v[4:12] if "experts_w" in k else v) for k, v in w.items()}
    step, make_cache = decoding.make_routed_conv_lm_pooled_step_fn(
        held, cfg, kv_dtype="fp32", held=(4, 12))
    toks = np.random.RandomState(1).randint(0, V, (2, 9)).astype(np.int32)
    want = np.asarray(ref.forward(held, jnp.asarray(toks), cfg,
                                  held=(4, 12)))
    got, cache = _staggered(step, make_cache, toks)
    assert np.abs(got - want).max() <= 2e-5 * (want.max() - want.min())
    whole = np.asarray(ref.forward(w, jnp.asarray(toks), cfg))
    assert np.abs(whole - want).max() > 1e-2 * (want.max() - want.min())
    # fewer pairs than rows x 4: the rest went to experts held elsewhere
    pairs = np.asarray(cache["expert_stats"])[:, 0]
    assert (pairs < 2 * 9 * 4).all() and (pairs > 0).all()


# ---------------------------------------------------------------------------
# the server
# ---------------------------------------------------------------------------
def test_decode_server_end_to_end_with_slot_reuse_and_expert_counters():
    """Six requests through two slots: every one gets the tokens the
    reference's full forward ranks first (greedy, fp32), and the four
    expert counters equal a host recount from the reference's routing of
    the same tokens — every position a live row consumed, 4 experts a
    position and expert layer."""
    import jax.numpy as jnp

    cfg = rehearse_cfg()
    w = weights(cfg, seed=21)
    step, make_cache = decoding.make_routed_conv_lm_pooled_step_fn(
        w, cfg, kv_dtype="fp32")
    srv = DecodeServer(step, make_cache, eos_id=V, max_seq_len=32,
                       max_slots=2, slot_ladder=[2], len_ladder=[32],
                       steps_per_tick=2, kv_dtype="fp32", name="routed-e2e")
    try:
        srv.warmup()
        rng = np.random.RandomState(8)
        prompts = [rng.randint(0, V, n).astype(np.int32)
                   for n in (5, 9, 3, 7, 4, 6)]
        reqs = [srv.submit({"tokens": p}, max_new_tokens=6 + i)
                for i, p in enumerate(prompts)]
        outs = [r.result(timeout=120)[0] for r in reqs]
        m = srv.metrics()["decode"]
        status = srv.statusz()["metrics"]["decode"]
    finally:
        srv.stop(drain=False, timeout=30)
    consumed = 0
    for p, out, i in zip(prompts, outs, range(len(prompts))):
        assert len(out) == 6 + i
        full = np.concatenate([p, out])[None, :]
        logits = np.asarray(ref.forward(w, jnp.asarray(full), cfg))[0]
        for j, tok in enumerate(out):
            row = logits[len(p) + j - 1]
            assert row.max() - row[tok] <= 1e-5 * (row.max() - row.min())
        # a request consumes every position but its last token's
        consumed += len(p) + len(out) - 1
    assert m["state_resets"] == len(prompts)
    assert m["expert_assignments"] == consumed * 4 * 3
    assert m["expert_assignments"] == monitor.counter_value(
        "serving_decode_expert_assignments_total", server="routed-e2e")
    steps = m["expert_layer_steps"] // 3
    assert m["expert_layer_steps"] == 3 * steps and steps <= 2 * m["ticks"]
    # one or two live rows a step, four distinct experts each
    assert 4 * m["expert_layer_steps"] <= m["experts_touched"] <= (
        m["expert_assignments"])
    assert m["expert_assignments"] / 4 <= 4 * m["expert_peak_load"] <= (
        2 * m["expert_assignments"])
    assert status["expert_assignments"] == m["expert_assignments"]


def test_an_untraced_turn_fetches_once_and_dispatches_nothing_new(
        monkeypatch):
    """The counts ride the tick's ONE ``device_get`` (since PR 62 of ONE
    vector a chunk packs: the five arrays of the view as int32, then the
    counts) and no dispatch is added: a tick is still one ``chunk``
    call; a builder that declares no counts fetches the five alone."""
    import jax

    cfg = rehearse_cfg()
    w = weights(cfg, seed=2)
    step, make_cache = decoding.make_routed_conv_lm_pooled_step_fn(
        w, cfg, kv_dtype="fp32")
    gets, chunks = [], []
    real_get = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda x: (
        gets.append(getattr(x, "shape", type(x))), real_get(x))[1])
    real_chunk = KVSlotPool.chunk_view     # the scheduler's dispatch
    monkeypatch.setattr(KVSlotPool, "chunk_view", lambda self, st, **kw: (
        chunks.append(1), real_chunk(self, st, **kw))[1])
    srv = DecodeServer(step, make_cache, eos_id=V, max_seq_len=16,
                       max_slots=2, slot_ladder=[2], len_ladder=[16],
                       steps_per_tick=2, kv_dtype="fp32", name="routed-get")
    try:
        srv.warmup()
        gets.clear(), chunks.clear()
        srv.submit({"tokens": np.array([1, 2, 3], np.int32)},
                   max_new_tokens=5).result(timeout=60)
        ticks = srv.metrics()["decode"]["ticks"]
    finally:
        srv.stop(drain=False, timeout=30)
    fetched = [g for g in gets if isinstance(g, tuple)]
    assert len(fetched) == ticks == len(chunks)
    # tokens [2, 16], four [2]s, then four sums an expert layer
    assert len(set(fetched)) == 1 and len(fetched[0]) == 1
    counts = fetched[0][0] - (2 * 16 + 4 * 2)
    assert counts > 0 and counts % 4 == 0


def test_a_builder_without_counts_fetches_the_five_and_counts_nothing(
        monkeypatch):
    import jax

    w = decoding.random_transformer_lm_state(
        np.random.RandomState(0), 31, 16, 1, 2, 32, 16)
    step, make_cache = decoding.make_transformer_lm_pooled_step_fn(
        w, 31, 16, 1, 2, 32)
    gets = []
    real_get = jax.device_get
    monkeypatch.setattr(jax, "device_get", lambda x: (
        gets.append(getattr(x, "shape", type(x))), real_get(x))[1])
    srv = DecodeServer(step, make_cache, eos_id=31, max_seq_len=16,
                       max_slots=2, slot_ladder=[2], len_ladder=[16],
                       steps_per_tick=2, name="no-counts")
    try:
        srv.warmup()
        srv.submit({"tokens": np.array([1, 2, 3], np.int32)},
                   max_new_tokens=4).result(timeout=60)
        m = srv.metrics()["decode"]
    finally:
        srv.stop(drain=False, timeout=30)
    fetched = [g for g in gets if isinstance(g, tuple)]
    assert fetched and set(fetched) == {(2 * 16 + 4 * 2,)}
    assert m["expert_assignments"] == m["experts_touched"] == 0
    assert m["expert_peak_load"] == m["expert_layer_steps"] == 0
