"""Decode tier-2 tests: prefix KV caching, speculative decoding, and
cache-affinity fleet routing (serving/prefix_cache.py +
serving/speculative.py + the FleetBalancer affinity fold).

Same two model tiers as test_decode: :class:`PrefixKVCache` units need
no model at all, the parity/prefill tests run a small real
transformer-LM (random weights) against the SCALAR cached step fn as
the independent greedy reference, and the acceptance run hosts a saved
draft+prefix endpoint on a real 2-child wire fleet with ``/statusz``
as the recompile ground truth.
"""
import os
import threading
import time

import numpy as np
import pytest

from paddle_tpu.decoding import (
    make_transformer_lm_step_fn,
    make_transformer_lm_pooled_step_fn,
    random_transformer_lm_state,
)
from paddle_tpu.serving.decode import (
    DecodeServer,
    load_decode_endpoint,
    save_decode_endpoint,
)
from paddle_tpu.serving.prefix_cache import PrefixKVCache
from paddle_tpu.serving.speculative import make_lm_speculative

EOS = 9
V = 23
LM = dict(vocab=V, d_model=16, n_layer=2, n_head=2, d_inner=32,
          max_pos=32)
DRAFT = dict(d_model=8, n_layer=1, n_head=1, d_inner=16)


@pytest.fixture(scope="module")
def lm_state():
    return random_transformer_lm_state(np.random.RandomState(7), **LM)


@pytest.fixture(scope="module")
def draft_state():
    return random_transformer_lm_state(
        np.random.RandomState(8), vocab=V, max_pos=LM["max_pos"],
        name="draft", **DRAFT)


def _speculative(lm_state, draft_state, k=4):
    return make_lm_speculative(
        lm_state, vocab_size=V, d_model=LM["d_model"],
        n_layer=LM["n_layer"], n_head=LM["n_head"],
        d_inner=LM["d_inner"], draft_state=draft_state,
        draft_d_model=DRAFT["d_model"], draft_n_layer=DRAFT["n_layer"],
        draft_n_head=DRAFT["n_head"], draft_d_inner=DRAFT["d_inner"],
        k=k)


def _ref_continuation(state, prompt, total_len):
    """Greedy continuation via the SCALAR cached step fn — the
    independent reference the pooled/speculative paths must match."""
    import jax.numpy as jnp

    step_fn, make_cache = make_transformer_lm_step_fn(
        state, LM["vocab"], LM["d_model"], LM["n_layer"], LM["n_head"],
        LM["d_inner"], LM["max_pos"])
    cache = make_cache(1)
    logits = None
    for t, tok in enumerate(prompt):
        logits, cache = step_fn(cache, jnp.asarray([tok], "int32"), t)
    out, pos = [], len(prompt)
    while pos < total_len:
        nxt = int(np.argmax(np.asarray(logits[0])))
        out.append(nxt)
        if nxt == EOS:
            break
        logits, cache = step_fn(cache, jnp.asarray([nxt], "int32"), pos)
        pos += 1
    return out


def _wait(pred, timeout=10.0, interval=0.005):
    deadline = time.monotonic() + timeout
    while time.monotonic() < deadline:
        if pred():
            return True
        time.sleep(interval)
    return pred()


# ---------------------------------------------------------------------------
# PrefixKVCache units (no model, no server)
# ---------------------------------------------------------------------------
def _leaves(m):
    """A fake extract: one KV leaf whose content encodes ``m``."""
    return [np.full((m, 2), m, np.float32), None]


def test_probe_matches_longest_block_aligned_proper_prefix():
    c = PrefixKVCache(capacity_bytes=1 << 20, block_tokens=4,
                      name="u-probe")
    try:
        prompt = np.arange(12, dtype=np.int32)
        assert c.probe(prompt) == (0, None)  # empty cache: miss
        assert c.offer(prompt, consumed=12, extract=_leaves)
        # the stored key is the full 12-token block prefix; the SAME
        # prompt re-probing caps one token short (the step consuming
        # the last prompt token must run), so it cannot match its own
        # entry...
        assert c.probe(prompt) == (0, None)
        # ...but any LONGER prompt sharing the 12-token head matches
        m, kv = c.probe(np.concatenate([prompt, [99]]).astype(np.int32))
        assert m == 12 and kv[0].shape == (12, 2)
    finally:
        c.close()


def test_probe_cap_and_block_boundaries():
    c = PrefixKVCache(capacity_bytes=1 << 20, block_tokens=4,
                      name="u-bounds")
    try:
        prompt = np.arange(12, dtype=np.int32)
        # offer bounded by consumed: only 8 positions were consumed, so
        # the stored prefix is 8 tokens even though the prompt has 12
        assert c.offer(prompt, consumed=9, extract=_leaves)
        assert c.stats()["entries"] == 1
        # a longer prompt sharing the head matches the full 8
        m, kv = c.probe(np.concatenate([prompt[:8], [99, 98]]).astype(
            np.int32))
        assert m == 8
        assert kv[0].shape == (8, 2) and kv[1] is None
        # the probe never matches the WHOLE prompt: len 9 caps at 8,
        # len 8 caps at 4 (proper prefix only) and 4 is not stored
        assert c.probe(prompt[:9])[0] == 8
        assert c.probe(prompt[:8]) == (0, None)
        # sub-block prompts can never match
        assert c.probe(prompt[:3]) == (0, None)
        st = c.stats()
        assert st["hits"] == 2 and st["misses"] >= 2
    finally:
        c.close()


def test_hash_collision_never_serves_wrong_tokens(monkeypatch):
    c = PrefixKVCache(capacity_bytes=1 << 20, block_tokens=4,
                      name="u-collide")
    try:
        # force every hash to collide: the exact token compare is the
        # only thing standing between two different prompts
        monkeypatch.setattr(PrefixKVCache, "_hash",
                            staticmethod(lambda tokens: "same"))
        monkeypatch.setattr(
            PrefixKVCache, "_prefix_keys", staticmethod(
                lambda prompt, lengths: [(m, "same") for m in lengths]))
        a = np.arange(8, dtype=np.int32)
        b = a + 100
        assert c.offer(a, consumed=8, extract=_leaves)
        m, kv = c.probe(np.concatenate([b, [1, 2]]).astype(np.int32))
        assert m == 0 and kv is None
        # the true owner still matches its own entry
        assert c.probe(np.concatenate([a, [1, 2]]).astype(
            np.int32))[0] == 8
    finally:
        c.close()


def test_lru_byte_eviction_and_bytes_accounting():
    # each entry: 16 tokens (64B) + a (16, 2) f32 leaf (128B) = 192B
    def extract(m):
        return [np.zeros((m, 2), np.float32)]

    c = PrefixKVCache(capacity_bytes=500, block_tokens=16, name="u-lru")
    try:
        p1 = np.arange(0, 16, dtype=np.int32)
        p2 = np.arange(100, 116, dtype=np.int32)
        p3 = np.arange(200, 216, dtype=np.int32)
        assert c.offer(p1, 16, extract)
        assert c.offer(p2, 16, extract)
        # touch p1 so p2 is the LRU victim when p3 overflows the budget
        assert c.probe(np.concatenate([p1, [7]]).astype(np.int32))[0] == 16
        assert c.offer(p3, 16, extract)
        st = c.stats()
        assert st["evictions"] == 1 and st["entries"] == 2
        assert st["bytes"] <= 500
        assert c.probe(np.concatenate([p2, [7]]).astype(
            np.int32)) == (0, None)
        assert c.probe(np.concatenate([p3, [7]]).astype(np.int32))[0] == 16
        # a repeat offer of a retained prefix stores nothing new
        assert not c.offer(p3, 16, extract)
        assert c.stats()["entries"] == 2
    finally:
        c.close()


def test_invalidate_drops_everything():
    c = PrefixKVCache(capacity_bytes=1 << 20, block_tokens=4,
                      name="u-inval")
    try:
        c.offer(np.arange(8, dtype=np.int32), 8, _leaves)
        assert c.stats()["entries"] == 1 and c.stats()["bytes"] > 0
        c.invalidate()
        st = c.stats()
        assert st["entries"] == 0 and st["bytes"] == 0
        assert c.probe(np.arange(10, dtype=np.int32)) == (0, None)
    finally:
        c.close()


def test_cache_rejects_bad_budgets():
    with pytest.raises(ValueError):
        PrefixKVCache(capacity_bytes=0)
    with pytest.raises(ValueError):
        PrefixKVCache(block_tokens=0)


# ---------------------------------------------------------------------------
# shared-prefix admission on a real LM server
# ---------------------------------------------------------------------------
def test_shared_prefix_admit_cuts_prefill_and_keeps_parity(lm_state):
    step_fn, make_cache = make_transformer_lm_pooled_step_fn(
        lm_state, V, LM["d_model"], LM["n_layer"], LM["n_head"],
        LM["d_inner"])
    srv = DecodeServer(step_fn, make_cache, eos_id=EOS, max_seq_len=24,
                       max_slots=2, steps_per_tick=2, name="lm-prefix",
                       prefix_cache=PrefixKVCache(
                           capacity_bytes=1 << 20, block_tokens=4,
                           name="lm-prefix"))
    try:
        srv.warmup(configure_cache=False)
        rng = np.random.RandomState(3)
        prefix = rng.randint(2, V, 8).astype(np.int32)

        def decode(suffix, gen=6):
            prompt = np.concatenate([prefix, suffix]).astype(np.int32)
            p0 = int(srv.metrics()["decode"]["prefill_tokens"])
            out = srv.submit({"tokens": prompt},
                             max_new_tokens=gen).result(timeout=60.0)
            delta = int(srv.metrics()["decode"]["prefill_tokens"]) - p0
            ref = _ref_continuation(lm_state, prompt.tolist(),
                                    len(prompt) + gen)
            assert np.asarray(out[0]).tolist() == ref
            return delta

        # first request: full prefill, then its freed slot offers the
        # block-aligned prefix
        full = decode(np.array([3, 5], np.int32))
        assert full == 10
        assert _wait(lambda: srv.prefix_cache.stats()["entries"] >= 1)
        # matching prompts prefill only the unmatched suffix (>= 50%
        # cut — the ISSUE acceptance bar — here 80%)
        short = decode(np.array([7, 4], np.int32))
        assert short == 2
        assert short <= full * 0.5
        st = srv.prefix_cache.stats()
        assert st["hits"] >= 1 and st["fallbacks"] == 0
        assert srv.metrics()["decode"]["prefix_cache"]["hits"] >= 1
        # admission after invalidate() (the endpoint-reload path) is a
        # plain full prefill again
        srv.prefix_cache.invalidate()
        assert decode(np.array([6, 2], np.int32)) == 10
    finally:
        srv.stop(drain=False)


def test_prefix_hits_are_counted_beside_the_admissions_they_seat(lm_state):
    """A thread that reads ``serving_prefix_cache_hits_total`` and
    ``serving_decode_admitted_total`` while a turn is being seated (its
    ``admit_prefix`` dispatches run one after another) sees the two
    move together: no hit is counted before the admissions of its turn
    are (a benchmark's window check compares their deltas)."""
    from paddle_tpu import monitor

    step_fn, make_cache = make_transformer_lm_pooled_step_fn(
        lm_state, V, LM["d_model"], LM["n_layer"], LM["n_head"],
        LM["d_inner"])
    srv = DecodeServer(step_fn, make_cache, eos_id=EOS, max_seq_len=24,
                       max_slots=4, steps_per_tick=2, name="lm-prefix-c",
                       prefix_cache=PrefixKVCache(
                           capacity_bytes=1 << 20, block_tokens=4,
                           name="lm-prefix-c"))

    def counters():
        return (monitor.counter_value("serving_prefix_cache_hits_total",
                                      cache="lm-prefix-c"),
                monitor.counter_value("serving_decode_admitted_total",
                                      server="lm-prefix-c"))

    try:
        srv.warmup(configure_cache=False)
        prefix = np.random.RandomState(5).randint(2, V, 8).astype(np.int32)
        first = np.concatenate([prefix, [3, 5]]).astype(np.int32)
        srv.submit({"tokens": first}, max_new_tokens=4).result(timeout=60.0)
        assert _wait(lambda: srv.prefix_cache.stats()["entries"] >= 1)
        h0, a0 = counters()
        seen = []
        inner = srv._pool.admit_prefix

        def watched(*args, **kwargs):
            h, a = counters()
            seen.append((h - h0, a - a0))
            return inner(*args, **kwargs)

        srv._pool.admit_prefix = watched
        reqs = [srv.submit({"tokens": np.concatenate(
            [prefix, [7, t]]).astype(np.int32)}, max_new_tokens=4)
            for t in (2, 4, 6)]
        for r in reqs:
            r.result(timeout=60.0)
        assert len(seen) == 3
        assert all(h == a for h, a in seen), seen
        h1, a1 = counters()
        assert h1 - h0 == a1 - a0 == 3
    finally:
        srv.stop(drain=False)


# ---------------------------------------------------------------------------
# speculative decoding: greedy-exact parity on a real LM
# ---------------------------------------------------------------------------
def test_speculative_parity_and_telemetry(lm_state, draft_state):
    step_fn, make_cache = make_transformer_lm_pooled_step_fn(
        lm_state, V, LM["d_model"], LM["n_layer"], LM["n_head"],
        LM["d_inner"])
    srv = DecodeServer(step_fn, make_cache, eos_id=EOS, max_seq_len=24,
                       max_slots=2, steps_per_tick=2, name="lm-spec",
                       speculative=_speculative(lm_state, draft_state))
    try:
        srv.warmup(configure_cache=False)
        prompts = ([2, 3, 4], [5], [7, 8], [3, 5, 2])
        # mixed batches: speculative and plain requests share the pool
        reqs = [srv.submit({"tokens": np.asarray(p, np.int32)},
                           max_new_tokens=10, speculative=bool(i % 2))
                for i, p in enumerate(prompts)]
        for p, r in zip(prompts, reqs):
            got = np.asarray(r.result(timeout=60.0)[0]).tolist()
            assert got == _ref_continuation(lm_state, p, len(p) + 10)
        spec = srv.metrics()["decode"]["speculative"]
        assert spec["k"] == 4
        assert spec["proposed_tokens"] > 0
        assert 0 <= spec["accepted_tokens"] <= spec["proposed_tokens"]
        assert sum(spec["accepted_len_histogram"].values()) > 0
    finally:
        srv.stop(drain=False)


def test_batch_admit_carries_each_slots_spec_flag(lm_state, draft_state,
                                                  check_batch_admit):
    """A speculative pool's admit seats a mixed batch in one call: the
    per-slot ``spec`` flags, the draft cache and every other leaf come
    out as from one admit per request."""
    from paddle_tpu.serving.kv_pool import KVSlotPool

    step_fn, make_cache = make_transformer_lm_pooled_step_fn(
        lm_state, V, LM["d_model"], LM["n_layer"], LM["n_head"],
        LM["d_inner"])
    pool = KVSlotPool(step_fn, make_cache, eos_id=EOS, max_slots=4,
                      max_seq_len=16, slot_ladder=[4], len_ladder=[16],
                      steps=2, speculative=_speculative(lm_state,
                                                        draft_state))
    rng = np.random.RandomState(5)
    st = pool.admit(pool.alloc(4, 16), 2,
                    rng.randint(2, V, 3).astype(np.int32), 3, 12, spec=True)
    st = pool.chunk(pool.chunk(st))  # a live speculative row beside
    st = check_batch_admit(pool, st, [
        (0, rng.randint(2, V, 4).astype(np.int32), 11, True),
        (1, rng.randint(2, V, 2).astype(np.int32), 9, False),
        (3, rng.randint(2, V, 6).astype(np.int32), 16, True)])
    assert np.asarray(st["spec"]).tolist() == [True, False, True, True]
    # one bool for the whole batch still means every seat
    st = pool.admit(st, [0, 1], [np.array([2], np.int32)] * 2, [1, 1],
                    [5, 5], spec=True)
    assert np.asarray(st["spec"]).tolist() == [True] * 4


@pytest.mark.parametrize("spec", [False, True])
def test_admit_prefix_keeps_the_requests_own_spec_flag(lm_state,
                                                       draft_state, spec):
    """A request seated over a retained prefix is speculative only if it
    asked to be (the flag was once shadowed by the state's shapes and
    read as always set)."""
    from paddle_tpu.serving.kv_pool import KVSlotPool

    step_fn, make_cache = make_transformer_lm_pooled_step_fn(
        lm_state, V, LM["d_model"], LM["n_layer"], LM["n_head"],
        LM["d_inner"])
    pool = KVSlotPool(step_fn, make_cache, eos_id=EOS, max_slots=2,
                      max_seq_len=16, slot_ladder=[2], len_ladder=[16],
                      steps=2, prefix=True,
                      speculative=_speculative(lm_state, draft_state))
    prompt = np.arange(2, 8, dtype=np.int32)
    st = pool.admit(pool.alloc(2, 16), 0, prompt, 6, 12)
    st = pool.chunk(pool.chunk(st))
    kv = pool.extract_kv(st, 0, 4)
    st = pool.admit_prefix(st, 1, prompt, 6, 12, kv, 4, spec=spec)
    assert np.asarray(st["spec"]).tolist() == [False, spec]
    assert np.asarray(st["pos"]).tolist()[1] == 4


@pytest.mark.parametrize("kv", ["fp32", "int8"])
def test_verify_at_k_rows_equals_k_sequential_steps(lm_state, kv):
    """``verify_fn`` is the pooled step at K fresh rows per slot: its
    logits, and the cache it leaves, are those of K sequential steps —
    rows at staggered positions, an idle row untouched, a row whose
    last positions fall past the cache dropped.

    Tolerance: the K-wide and the one-row programs are different
    executables and may order a sum differently — a few fp32 ulps on
    O(1) values, 1e-5 absolute; an int8 code computed from such a value
    may land one step away."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.decoding import make_transformer_lm_pooled_verify_fn

    args = (lm_state, V, LM["d_model"], LM["n_layer"], LM["n_head"],
            LM["d_inner"])
    step_fn, make_cache = make_transformer_lm_pooled_step_fn(
        *args, kv_dtype=kv)
    verify_fn = make_transformer_lm_pooled_verify_fn(*args, kv_dtype=kv)
    S, T, K = 4, 16, 3
    rng = np.random.RandomState(5)
    toks = rng.randint(0, V, (S, K)).astype(np.int32)
    ts = np.array([0, 5, -1, T - 2], np.int32)   # row 3: its last row past T
    step, verify = jax.jit(step_fn), jax.jit(verify_fn)
    # a cache the slots have written themselves up to ts (write-before-read)
    cache = make_cache(S, T)
    for t in range(int(ts.max())):
        live = np.where(t < ts, t, -1).astype(np.int32)
        _, cache = step(cache, rng.randint(0, V, S).astype(np.int32), live)
    wide, wide_cache = verify(cache, toks, ts)
    seq, seq_cache = [], cache
    for j in range(K):
        at = np.where((ts >= 0) & (ts + j < T), ts + j, -1).astype(np.int32)
        lg, seq_cache = step(seq_cache, toks[:, j], at)
        seq.append(np.asarray(lg))
    seq = np.stack(seq, axis=1)
    valid = (ts[:, None] >= 0) & (ts[:, None] + np.arange(K)[None] < T)
    assert valid.sum() == 8
    np.testing.assert_allclose(np.asarray(wide)[valid], seq[valid],
                               rtol=0, atol=1e-5)
    assert np.array_equal(np.asarray(wide).argmax(-1)[valid],
                          seq.argmax(-1)[valid])
    for a, b, c in zip(jax.tree.leaves(wide_cache),
                       jax.tree.leaves(seq_cache), jax.tree.leaves(cache)):
        a, b, c = (np.asarray(x).astype(np.float32) for x in (a, b, c))
        atol = 1.0 if kv == "int8" and a.shape[-1] == LM["d_model"] else 1e-5
        np.testing.assert_allclose(a, b, rtol=0, atol=atol)
        assert np.array_equal(a[2], c[2])          # the idle row
        assert not np.array_equal(a[1], c[1])      # a live one


def test_speculative_submit_without_draft_raises_typed():
    state = random_transformer_lm_state(np.random.RandomState(1), **LM)
    step_fn, make_cache = make_transformer_lm_pooled_step_fn(
        state, V, LM["d_model"], LM["n_layer"], LM["n_head"],
        LM["d_inner"])
    srv = DecodeServer(step_fn, make_cache, eos_id=EOS, max_seq_len=16,
                       max_slots=2, name="lm-nospec")
    try:
        with pytest.raises(ValueError, match="no draft model"):
            srv.submit({"tokens": np.array([2, 3], np.int32)},
                       speculative=True)
    finally:
        srv.stop(drain=False)


# ---------------------------------------------------------------------------
# all three modes on: the compiled-shape set stays closed
# ---------------------------------------------------------------------------
def test_all_modes_on_zero_recompiles_after_warmup(lm_state, draft_state):
    step_fn, make_cache = make_transformer_lm_pooled_step_fn(
        lm_state, V, LM["d_model"], LM["n_layer"], LM["n_head"],
        LM["d_inner"])
    srv = DecodeServer(step_fn, make_cache, eos_id=EOS, max_seq_len=24,
                       max_slots=2, steps_per_tick=2, name="lm-all",
                       prefix_cache=PrefixKVCache(
                           capacity_bytes=1 << 20, block_tokens=4,
                           name="lm-all"),
                       speculative=_speculative(lm_state, draft_state))
    try:
        srv.warmup(configure_cache=False)
        rng = np.random.RandomState(5)
        prefix = rng.randint(2, V, 8).astype(np.int32)
        for i in range(6):
            sfx = rng.randint(2, V, 1 + i % 3).astype(np.int32)
            prompt = np.concatenate([prefix, sfx]).astype(np.int32)
            srv.submit({"tokens": prompt}, max_new_tokens=4 + i % 5,
                       speculative=bool(i % 2)).result(timeout=60.0)
            time.sleep(0.01)  # let freed slots offer their prefix KV
        m = srv.metrics()
        assert srv.prefix_cache.stats()["hits"] >= 1
        assert m["decode"]["speculative"]["proposed_tokens"] > 0
        assert int(m.get("recompiles", 0)) == 0
        assert srv._pool.jit_cache_stats()["misses"] == 0
    finally:
        srv.stop(drain=False)


# ---------------------------------------------------------------------------
# endpoint round trip: the draft + prefix budget ride the manifest
# ---------------------------------------------------------------------------
def test_endpoint_round_trip_with_draft_and_prefix_cache(
        tmp_path, lm_state, draft_state):
    d = str(tmp_path / "lm-tier2")
    save_decode_endpoint(
        d, lm_state, vocab_size=V, d_model=LM["d_model"],
        n_layer=LM["n_layer"], n_head=LM["n_head"],
        d_inner=LM["d_inner"], eos_id=EOS, max_seq_len=24, max_slots=2,
        steps_per_tick=2,
        draft={"state": draft_state, "d_model": DRAFT["d_model"],
               "n_layer": DRAFT["n_layer"], "n_head": DRAFT["n_head"],
               "d_inner": DRAFT["d_inner"], "name": "draft", "k": 4},
        prefix_cache_bytes=1 << 20)
    srv = load_decode_endpoint(d)
    try:
        assert srv.speculative_k == 4
        assert srv.prefix_cache is not None
        assert srv.prefix_cache.capacity_bytes == 1 << 20
        srv.warmup(configure_cache=False)
        p = [2, 3, 4]
        out = srv.submit({"tokens": np.asarray(p, np.int32)},
                         max_new_tokens=8,
                         speculative=True).result(timeout=60.0)
        assert np.asarray(out[0]).tolist() == _ref_continuation(
            lm_state, p, len(p) + 8)
    finally:
        srv.stop(drain=False)


# ---------------------------------------------------------------------------
# metrics snapshot: a COMPLETE offline kv-ladder input
# ---------------------------------------------------------------------------
def test_metrics_carry_kv_ladder_plan_and_feed_autotune(lm_state):
    step_fn, make_cache = make_transformer_lm_pooled_step_fn(
        lm_state, V, LM["d_model"], LM["n_layer"], LM["n_head"],
        LM["d_inner"])
    srv = DecodeServer(step_fn, make_cache, eos_id=EOS, max_seq_len=24,
                       max_slots=2, name="lm-plan")
    try:
        srv.warmup(configure_cache=False)
        srv.submit({"tokens": np.array([2, 3], np.int32)},
                   max_new_tokens=6).result(timeout=60.0)
        m = srv.metrics()
        blk = m["decode"]
        plan = blk["kv_ladder_plan"]
        assert plan and "len_ladder" in plan and "changed" in plan
        assert max(plan["len_ladder"]) <= blk["max_seq_len"]
        # the snapshot is directly consumable by the offline tool
        import importlib.util

        spec = importlib.util.spec_from_file_location(
            "autotune_ladder_tool",
            os.path.join(os.path.dirname(os.path.dirname(
                os.path.abspath(__file__))), "tools",
                "autotune_ladder.py"))
        tool = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tool)
        offline = tool.propose({"metrics": m}, max_rungs=6)
        assert offline["len_ladder"] == plan["len_ladder"]
    finally:
        srv.stop(drain=False)


# ---------------------------------------------------------------------------
# FleetBalancer prefix affinity (routing unit, no wire children)
# ---------------------------------------------------------------------------
def test_fleet_affinity_bounded_tie_break():
    from paddle_tpu.serving.wire.fleet import (
        FleetBalancer, _AFFINITY_SLACK)

    fb = FleetBalancer([("127.0.0.1", 1), ("127.0.0.1", 2)],
                       name="aff-unit", health_interval_s=None,
                       prefix_affinity=True, affinity_block=4,
                       affinity_hints=8)
    try:
        toks = np.arange(8, dtype=np.int32)
        key = fb._affinity_key(["tokens"], [toks])
        assert key is not None
        assert fb._affinity_key(["tokens"], [toks[:3]]) is None
        assert fb._affinity_key(["x"], [toks]) is None

        first = fb._acquire(None, None, key)
        fb._release(first, ok=True)
        # a returning prefix lands on the backend that served it
        be = fb._acquire(None, None, key)
        assert be is first and first.affinity_hits == 1
        fb._release(be, ok=True)
        # ... unless that backend is paused (shed retry-after): load
        # discipline wins and the key re-hints to the actual landing
        first.not_before = time.monotonic() + 5.0
        moved = fb._acquire(None, None, key)
        assert moved is not first
        fb._release(moved, ok=True)
        first.not_before = 0.0
        again = fb._acquire(None, None, key)
        assert again is moved
        fb._release(again, ok=True)
        # load imbalance beyond the slack defeats affinity
        with fb._route_cv:
            moved.in_flight = int(_AFFINITY_SLACK) + 2
        spill = fb._acquire(None, None, key)
        assert spill is not moved
        fb._release(spill, ok=True)
        with fb._route_cv:
            moved.in_flight = 0
        # per-backend hint books are LRU-bounded
        for i in range(20):
            k = fb._affinity_key(
                ["tokens"], [np.arange(i, i + 4, dtype=np.int32)])
            fb._release(fb._acquire(None, None, k), ok=True)
        stats = fb.backend_stats()
        for s in stats.values():
            assert s["prefix_hints"] <= 8
            assert "affinity_hits" in s
    finally:
        fb.stop()


# ---------------------------------------------------------------------------
# acceptance: a real 2-child fleet, all three modes on
# ---------------------------------------------------------------------------
def test_fleet_two_children_all_modes_zero_recompiles(
        tmp_path, lm_state, draft_state):
    """ISSUE acceptance: a 2-child wire fleet hosting a saved
    draft+prefix decode endpoint behind a prefix-affinity balancer —
    speculative streams bit-identical to the scalar reference,
    returning prompts hit the children's prefix caches, and BOTH
    children report zero jit-cache misses on ``/statusz``."""
    from paddle_tpu.serving.wire.fleet import FleetBalancer

    d = str(tmp_path / "lm-tier2-fleet")
    save_decode_endpoint(
        d, lm_state, vocab_size=V, d_model=LM["d_model"],
        n_layer=LM["n_layer"], n_head=LM["n_head"],
        d_inner=LM["d_inner"], eos_id=EOS, max_seq_len=24, max_slots=2,
        steps_per_tick=2,
        draft={"state": draft_state, "d_model": DRAFT["d_model"],
               "n_layer": DRAFT["n_layer"], "n_head": DRAFT["n_head"],
               "d_inner": DRAFT["d_inner"], "name": "draft", "k": 4},
        prefix_cache_bytes=1 << 20)
    fb = FleetBalancer.from_launch(d, 2, name="tier2-fleet",
                                   prefix_affinity=True,
                                   affinity_block=4)
    try:
        fb.warmup()
        rng = np.random.RandomState(9)
        # the endpoint's prefix cache keys at the default 16-token
        # block granularity, so the shared head must span a full block
        prefix = rng.randint(2, V, 16).astype(np.int32)
        ref_cache = {}
        # sequential returning rounds so each freed slot's prefix KV is
        # offered before the next round probes (the affinity routing
        # then keeps the session on the child that holds it)
        suffixes = [[3, 5], [7, 4], [6, 2], [3, 5]]
        for sfx in suffixes:
            prompt = np.concatenate([prefix, sfx]).astype(np.int32)
            chunks = list(fb.infer_stream({"tokens": prompt},
                                          max_new_tokens=6,
                                          speculative=True))
            got = [t for c in chunks for t in np.asarray(c).tolist()]
            key = tuple(prompt.tolist())
            if key not in ref_cache:
                ref_cache[key] = _ref_continuation(
                    lm_state, prompt.tolist(), len(prompt) + 6)
            assert got == ref_cache[key]
            time.sleep(0.05)
        # child-side prefix caches saw the shared head
        hits = 0
        for be in fb._backends:
            h = be.transport.get_json("/healthz")
            assert h.get("speculative_k") == 4
            pc = h.get("prefix_cache") or {}
            hits += int(pc.get("hits", 0))
        assert hits >= 1
        # ... because the balancer sent the returning head back to the
        # child that held it
        assert sum(s["affinity_hits"]
                   for s in fb.backend_stats().values()) >= 1
        # the whole storm compiled nothing after warmup, on BOTH
        # children — /statusz is the ground truth
        for be in fb._backends:
            st = be.transport.get_json("/statusz")
            assert st["jit_cache"]["misses"] == 0, st["jit_cache"]
    finally:
        fb.stop(shutdown_backends=True)


# ---------------------------------------------------------------------------
# snapshots and the one-pass probe (PR 31)
# ---------------------------------------------------------------------------
def test_probe_hashes_once_at_the_lengths_entries_have():
    """The running digest read at an entry's length is ``_hash`` of
    that prefix, whatever lengths came before it."""
    prompt = np.arange(1000, dtype=np.int32) * 7
    lengths = [16, 48, 512, 999]
    keys = dict(PrefixKVCache._prefix_keys(prompt, lengths))
    assert keys == {m: PrefixKVCache._hash(prompt[:m]) for m in lengths}


@pytest.mark.parametrize("n_prompt,want", [(40, 32), (33, 32), (32, 8),
                                           (20, 8), (8, 0)])
def test_probe_returns_the_longest_snapshot_that_is_a_proper_prefix(
        n_prompt, want):
    """Snapshots sit at whatever length a prefill stopped (here 8 and
    32, not multiples of ``block_tokens``); the match is capped one
    token short of the prompt."""
    c = PrefixKVCache(capacity_bytes=1 << 20, block_tokens=16,
                      name="u-snap-%d" % n_prompt)
    try:
        base = np.arange(64, dtype=np.int32)
        for m in (8, 32):
            assert c.put(base[:m], [np.full((2,), m, np.float32)])
        assert not c.put(base[:8], [np.zeros(2, np.float32)])  # held already
        assert c.holds(base[:32]) and not c.holds(base[:16])
        m, kv = c.probe(base[:n_prompt])
        assert m == want
        assert (kv is None) if want == 0 else (int(kv[0][0]) == want)
        other = base.copy()
        other[3] += 1                    # differs inside every snapshot
        assert c.probe(other[:n_prompt]) == (0, None)
    finally:
        c.close()


def test_snapshot_bytes_evictions_and_lengths():
    """The budget counts the leaves' bytes (device arrays' too), and an
    evicted entry's length stops being probed."""
    import jax.numpy as jnp

    c = PrefixKVCache(capacity_bytes=3000, block_tokens=4, name="u-snap-b")
    try:
        a, b = np.arange(10, dtype=np.int32), np.arange(100, 120,
                                                        dtype=np.int32)
        assert c.put(a, [jnp.zeros((500,), jnp.float32)])    # 2040 bytes
        assert c.stats()["bytes"] == 2040 and c._lengths == {10: 1}
        assert c.put(b, [jnp.zeros((600,), jnp.float32), None])
        s = c.stats()
        assert (s["entries"], s["evictions"], s["bytes"]) == (1, 1, 2480)
        assert c._lengths == {20: 1}
        assert c.probe(np.concatenate([a, [1]]).astype(np.int32))[0] == 0
        c.invalidate()
        assert c._lengths == {} and c.stats()["bytes"] == 0
    finally:
        c.close()
