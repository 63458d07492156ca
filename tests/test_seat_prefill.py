"""A prompt is prefilled in one pass at its admission (PR 45).

The transformer-LM pooled builder declares a BATCHED PREFILL
(``CacheSpec.prefill_rows_fn``); a pool that finds it seats a request
and feeds it all of its prompt but the last token in ONE
``seat_prefill`` dispatch, several seats a dispatch.  These tests hold
the mechanism to the step-only path it replaces — the same builder with
the declaration taken away — token for token, and count what it
compiles, dispatches and reports.
"""
import copy

import numpy as np
import pytest

from paddle_tpu.decoding import (
    declare,
    make_transformer_lm_pooled_step_fn,
    random_transformer_lm_state,
    spec_of,
)
from paddle_tpu.serving.decode import DecodeServer
from paddle_tpu.serving.kv_pool import KVSlotPool
from paddle_tpu.serving.prefix_cache import PrefixKVCache
from paddle_tpu.serving.speculative import make_lm_speculative

V, EOS = 61, 61      # eos an id no argmax produces: outputs run to length
DIMS = dict(vocab=V, d_model=16, n_layer=2, n_head=2, d_inner=32, max_pos=32)
T = 32               # the one length rung: classes of 8, 16 and 32 positions


@pytest.fixture(scope="module")
def lm_state():
    return random_transformer_lm_state(np.random.RandomState(45), **DIMS)


def _builder(state, kv="fp32", prefill=True):
    step_fn, make_cache = make_transformer_lm_pooled_step_fn(
        state, V, DIMS["d_model"], DIMS["n_layer"], DIMS["n_head"],
        DIMS["d_inner"], kv_dtype=kv)
    if not prefill:     # the step-only path: the same builder, undeclared
        spec = copy.copy(spec_of(make_cache))
        spec.prefill_rows_fn = None
        declare(make_cache, spec)
    return step_fn, make_cache


def _server(state, kv="fp32", prefill=True, **kw):
    kw.setdefault("max_slots", 4)
    kw.setdefault("len_ladder", [T])
    srv = DecodeServer(*_builder(state, kv, prefill), eos_id=EOS,
                       max_seq_len=T, steps_per_tick=2, kv_dtype=kv,
                       name="seat-%s-%d" % (kv, prefill), **kw)
    srv.warmup(configure_cache=False)
    return srv


@pytest.fixture(scope="module", params=["fp32", "int8"])
def pair(request, lm_state):
    """(kv, a server that seats and prefills, one whose prompts ride the
    step) over the same weights."""
    with_, without = (_server(lm_state, request.param, on)
                      for on in (True, False))
    assert with_._pool.seats_prefilled and not without._pool.seats_prefilled
    yield request.param, with_, without
    with_.stop(drain=False)
    without.stop(drain=False)


def _prompt(n, seed=0):
    return np.random.RandomState(1000 * seed + n).randint(
        0, V, n).astype(np.int32)


# 1: the benchmark's pilot (nothing to prefill); 8, 9, 10 and 16, 17, 18:
# all but the last token is C - 1, C, C + 1 positions of the classes 8
# and 16; 31: as long as the rung allows (one token is generated)
@pytest.mark.parametrize("n", [1, 2, 8, 9, 10, 16, 17, 18, 25, 31])
def test_greedy_output_is_token_identical_to_the_step_only_path(pair, n):
    _, with_, without = pair
    prompt = _prompt(n)
    got, want = (srv.submit({"tokens": prompt}, max_new_tokens=6).result(
        timeout=60.0)[0] for srv in (with_, without))
    assert len(want) == min(6, T - n)
    np.testing.assert_array_equal(got, want)


def test_a_storm_over_reused_slots_is_token_identical_and_compiles_nothing(
        pair):
    """More requests than slots, lengths mixed, so slots are reused over
    longer occupants' stale rows and turns seat several at once."""
    _, with_, without = pair
    prompts = [_prompt(n, seed=2) for n in
               (3, 30, 9, 1, 17, 24, 2, 12, 8, 29, 5, 16, 20, 4)]
    outs = []
    for srv in (with_, without):
        reqs = [srv.submit({"tokens": p}, max_new_tokens=5) for p in prompts]
        outs.append([r.result(timeout=60.0)[0] for r in reqs])
    for got, want in zip(*outs):
        np.testing.assert_array_equal(got, want)
    for srv in (with_, without):
        assert srv.metrics()["recompiles"] == 0
        assert srv._pool.jit_cache_stats()["misses"] == 0


@pytest.mark.parametrize("case", ["lm-fp32", "lm-int8", "lm-undeclared",
                                  "lm-draft-attached"])
def test_the_chunk_counter_and_prefill_tokens_add_up(lm_state, case):
    """``prefill_chunk_tokens_total`` + the prompt tokens that rode the
    step = ``prefill_tokens``: where the pool seats and prefills, every
    prompt's last token rides the step and nothing else does; where it
    does not (undeclared, or a draft model attached), nothing is fed by
    a pass."""
    kw = {}
    if case == "lm-draft-attached":
        draft = random_transformer_lm_state(
            np.random.RandomState(3), V, 8, 1, 2, 16, DIMS["max_pos"],
            name="draft")
        kw["speculative"] = make_lm_speculative(
            lm_state, vocab_size=V, d_model=DIMS["d_model"],
            n_layer=DIMS["n_layer"], n_head=DIMS["n_head"],
            d_inner=DIMS["d_inner"], draft_state=draft, draft_d_model=8,
            draft_n_layer=1, draft_n_head=2, draft_d_inner=16, k=3)
    srv = _server(lm_state, "int8" if case == "lm-int8" else "fp32",
                  case != "lm-undeclared", **kw)
    engaged = case in ("lm-fp32", "lm-int8")
    assert srv._pool.seats_prefilled == engaged
    assert ("seat_prefill" in srv._pool._kinds()) == engaged
    lens = (5, 1, 19, 8, 30, 2)
    try:
        for r in [srv.submit({"tokens": _prompt(n, seed=3)},
                             max_new_tokens=3) for n in lens]:
            r.result(timeout=60.0)
        m = srv.metrics()["decode"]
    finally:
        srv.stop(drain=False)
    assert m["prefill_tokens"] == sum(lens) and m["admitted"] == len(lens)
    rode_the_step = sum(lens) - m["prefill_chunk_tokens_total"]
    assert rode_the_step == (len(lens) if engaged else sum(lens))
    assert (m["prefill_chunks"] > 0) == engaged
    # a pass is an admission dispatch, and the only one of its turn
    assert m["admit_dispatches"] <= len(lens)
    if engaged:
        assert m["prefill_chunks"] == m["admit_dispatches"]
        # the steps only ever ran at positions >= prompt_len - 1
        assert m["kv_positions_live"] == sum(
            sum(range(n, n + min(3, T - n))) for n in lens)


def test_warmup_builds_one_more_kind_a_rung_pair_and_traffic_none(lm_state):
    """Two slot rungs x three length rungs: ``seat_prefill`` is warmed
    for each pair, the pool grows through them under traffic and never
    compiles."""
    srv = DecodeServer(*_builder(lm_state), eos_id=EOS, max_seq_len=T,
                       max_slots=4, slot_ladder=[2, 4],
                       len_ladder=[8, 16, T], steps_per_tick=2,
                       name="seat-rungs")
    try:
        assert srv.warmup(configure_cache=False) == 6 * 4
        assert KVSlotPool.prefill_classes(8) == [(2, 2), (4, 1), (8, 1)]
        assert KVSlotPool.prefill_classes(T) == [(8, 2), (16, 1), (T, 1)]
        for n, new in ((2, 3), (6, 6), (3, 4), (20, 8), (5, 2), (1, 9)):
            srv.submit({"tokens": _prompt(n, seed=4)},
                       max_new_tokens=new).result(timeout=60.0)
        reqs = [srv.submit({"tokens": _prompt(n, seed=5)}, max_new_tokens=4)
                for n in (4, 9, 2, 27, 11, 6)]
        for r in reqs:
            r.result(timeout=60.0)
        assert srv.metrics()["recompiles"] == 0
        assert srv._pool.jit_cache_stats()["misses"] == 0
    finally:
        srv.stop(drain=False)


def _run_out(pool, state, ticks=40):
    import jax

    for _ in range(ticks):
        state = pool.chunk(state)
    return jax.device_get({k: state[k] for k in (
        "tokens", "pos", "n_gen", "finished", "active", "prompt_len",
        "total_len")})


@pytest.mark.parametrize("kv", ["fp32", "int8"])
def test_more_seats_in_a_turn_than_a_pass_holds_take_more_passes(
        lm_state, kv, monkeypatch):
    """Seven seats where a pass holds two rows: four passes or more
    (a group of the narrowest class takes both rows), every slot seated
    at ``prompt_len - 1``, and run out they hold the tokens the
    step-only ``admit`` path generates."""
    monkeypatch.setattr(KVSlotPool, "_SEAT_ROWS", 2)
    lens = [3, 12, 30, 1, 7, 20, 9]
    prompts = [_prompt(n, seed=6) for n in lens]
    totals = [min(T, n + 5) for n in lens]
    finals = []
    for prefill in (True, False):
        pool = KVSlotPool(*_builder(lm_state, kv, prefill), eos_id=EOS,
                          max_slots=8, max_seq_len=T, slot_ladder=[8],
                          len_ladder=[T], steps=2, kv_dtype=kv)
        pool.warmup()
        state = pool.alloc(8, T)
        if prefill:
            state, passes = pool.seat_prefill(
                state, list(range(1, 8)), prompts, totals)
            assert passes >= 4
            assert np.asarray(state["pos"])[1:].tolist() == [
                n - 1 for n in lens]
            assert np.asarray(state["active"]).tolist() == [False] + [True] * 7
        else:
            state = pool.admit(state, list(range(1, 8)), prompts, lens,
                               totals)
        finals.append(_run_out(pool, state))
        assert pool.jit_cache_stats()["misses"] == 0
    got, want = finals
    assert want["finished"][1:].all() and not want["active"].any()
    for key in want:
        np.testing.assert_array_equal(got[key][1:], want[key][1:])
    for i, (n, tot) in enumerate(zip(lens, totals), 1):
        np.testing.assert_array_equal(got["tokens"][i, :tot],
                                      want["tokens"][i, :tot])


def test_the_packed_seats_lay_classes_out_in_groups_and_leave_no_row_idle(
        lm_state):
    """The host half: seats by class, each class's rows in groups of its
    ``G``, the plan's (first row, forwards) a class, and every row no
    seat took a REPEAT of the pass's first seat (the traced half then
    scatters in bounds: on a TPU an out-of-range index lost slot 0's
    tokens at a slot rung of 4); what does not fit goes to a second
    pass."""
    pool = KVSlotPool(*_builder(lm_state), eos_id=EOS, max_slots=8,
                      max_seq_len=T, slot_ladder=[8], len_ladder=[T], steps=2)
    lens = [9, 3, 17, 1, 8, 10, 31]      # classes 0: 9, 3, 1, 8; 1: 17, 10
    prompts = [np.full(n, 7 + i, np.int32) for i, n in enumerate(lens)]
    slots = [7, 6, 5, 4, 3, 2, 1]
    packed, = pool._pack_seat_passes(8, T, slots, prompts, [T] * 7)
    seats = packed[:8 * (T + 3)].reshape(8, T + 3)
    plan = packed[8 * (T + 3):].reshape(3, 2)
    assert plan.tolist() == [[0, 2], [4, 2], [6, 1]]
    assert seats[:, T].tolist() == [7, 6, 4, 3, 5, 2, 1, 7]
    assert seats[:, T + 1].tolist() == [9, 3, 1, 8, 17, 10, 31, 9]
    assert seats[0, :10].tolist() == [7] * 9 + [0]
    np.testing.assert_array_equal(seats[7], seats[0])
    # nine seats of the narrowest class in eight rows: a second pass
    first, second = pool._pack_seat_passes(
        8, T, list(range(8)) + [0], [np.ones(2, np.int32)] * 9, [T] * 9)
    assert first[8 * (T + 3):].tolist() == [0, 4, 8, 0, 8, 0]
    assert second[8 * (T + 3):].tolist() == [0, 1, 2, 0, 2, 0]
    assert second[:8 * (T + 3)].reshape(8, T + 3)[:, T].tolist() == [0] * 8


def test_a_prefix_hit_rides_the_step_from_its_suffix_and_stays_identical(
        lm_state):
    """A request seated over a retained prefix keeps its ``admit_prefix``
    dispatch and steps through its suffix; one that misses is seated and
    prefilled; both as the step-only server serves them."""
    shared = _prompt(12, seed=7)
    prompts = [np.concatenate([shared, _prompt(n, seed=8)])
               for n in (3, 6, 4)] + [_prompt(9, seed=9)]
    outs, stats = [], []
    for prefill in (True, False):
        srv = _server(lm_state, prefill=prefill, prefix_cache=PrefixKVCache(
            capacity_bytes=1 << 20, block_tokens=4, name="seat-%d" % prefill))
        try:
            outs.append([srv.submit({"tokens": p}, max_new_tokens=5).result(
                timeout=60.0)[0] for p in prompts])
            stats.append(srv.metrics()["decode"])
        finally:
            srv.stop(drain=False)
    for got, want in zip(*outs):
        np.testing.assert_array_equal(got, want)
    on, off = stats
    assert on["prefix_cache"]["hits"] == off["prefix_cache"]["hits"] >= 2
    assert on["prefill_tokens"] == off["prefill_tokens"]
    # fed by a pass: all but the last token of the two prompts that missed
    assert on["prefill_chunk_tokens_total"] == len(prompts[0]) - 1 + 9 - 1


def test_a_traced_admitting_turn_is_one_dispatch_that_says_what_it_fed(
        lm_state):
    from paddle_tpu.monitor import spans as mon_spans

    srv = _server(lm_state)
    mon_spans.start_recording()
    try:
        for n in (7, 20, 1):
            srv.submit({"tokens": _prompt(n, seed=10)},
                       max_new_tokens=4).result(timeout=60.0)
    finally:
        srv.stop()
        spans = mon_spans.stop_recording()
    admits = [s["args"] for s in spans
              if s["name"] == "serving/decode/admit_dispatch"]
    assert [(a["seated"], a["dispatches"], a["rows"], a["tokens"])
            for a in admits] == [(1, 1, 1, 6), (1, 1, 1, 19), (1, 1, 1, 0)]
    # no turn of this builder has a prefill phase: the pass IS the admit
    assert not [s for s in spans if s["name"] == "serving/decode/prefill"]


def test_the_prefill_program_holds_no_product_as_wide_as_the_vocabulary(
        lm_state):
    """No logits are made: the compiled program has no tensor with the
    vocabulary on an axis but the embedding table it gathers from."""
    import jax
    import jax.numpy as jnp

    _, make_cache = _builder(lm_state)
    text = jax.jit(spec_of(make_cache).prefill_rows_fn).lower(
        make_cache(4, T), jnp.zeros((2,), jnp.int32),
        jnp.zeros((2, 8), jnp.int32)).compile().as_text()
    wide = {line.split(" = ")[1].split(" ")[0] for line in text.splitlines()
            if " = " in line and "%d]" % V in line.split(" = ")[1].split(" ")[0]}
    assert not wide, wide


@pytest.mark.parametrize("dtype,n_head,n_kv_head", [
    ("float32", 2, 2), ("int8", 2, 2), ("bfloat16", 4, 2), ("float32", 4, 1)])
def test_fresh_prompt_attention_is_the_contract_at_c_rows_from_position_0(
        dtype, n_head, n_kv_head):
    """``fresh_prompt_attention`` + ``write_prompt_rows`` against
    ``grouped_masked_decode_attention`` at ``K = C`` fresh rows from
    ``ts = 0`` over the gathered slots' rows: the same contexts, and the
    same rows in the leaves — of the named slots only, a slot named
    twice written the same twice."""
    import jax.numpy as jnp

    from paddle_tpu.decode_attention import (
        fresh_prompt_attention, grouped_masked_decode_attention, kv_leaves,
        write_prompt_rows)

    S, C, Dh = 5, 6, 8
    rng = np.random.RandomState(5)
    kv = kv_leaves(S, 16, n_kv_head, Dh, dtype)
    kv = {k: jnp.asarray(rng.randint(-5, 5, v.shape), v.dtype)
          for k, v in kv.items()}       # stale rows of earlier occupants
    q = jnp.asarray(rng.randn(3, C, n_head * Dh), jnp.float32)
    k_new, v_new = (jnp.asarray(rng.randn(3, C, n_kv_head * Dh), jnp.float32)
                    for _ in range(2))
    # group row 1 repeats row 0, as a pass's unused rows repeat a seat
    q, k_new, v_new = (x.at[1].set(x[0]) for x in (q, k_new, v_new))
    rows = jnp.asarray([4, 4, 1], jnp.int32)
    kw = dict(n_head=n_head, n_kv_head=n_kv_head, scale=0.3)
    ctx, stored = fresh_prompt_attention(q, k_new, v_new, kv, **kw)
    assert set(stored) == set(kv)
    out = write_prompt_rows(kv, stored, rows)
    sub = {k: v[rows] for k, v in kv.items()}
    want_ctx, want = grouped_masked_decode_attention(
        q, k_new, v_new, sub, jnp.zeros((3,), jnp.int32), **kw)
    tol = 2e-2 if dtype == "bfloat16" else 1e-5
    np.testing.assert_allclose(ctx, want_ctx, rtol=tol, atol=tol)
    for name in kv:
        for g, slot in ((0, 4), (2, 1)):
            np.testing.assert_array_equal(
                np.asarray(out[name][slot, :C].astype(jnp.float32)),
                np.asarray(want[name][g, :C].astype(jnp.float32)))
            np.testing.assert_array_equal(       # past C: untouched
                np.asarray(out[name][slot, C:].astype(jnp.float32)),
                np.asarray(kv[name][slot, C:].astype(jnp.float32)))
        for slot in (0, 2, 3):                   # not named: untouched
            np.testing.assert_array_equal(
                np.asarray(out[name][slot].astype(jnp.float32)),
                np.asarray(kv[name][slot].astype(jnp.float32)))
    with pytest.raises(ValueError, match="fresh rows a slot"):
        write_prompt_rows(kv, {k: jnp.zeros((1, 17) + v.shape[2:], v.dtype)
                               for k, v in kv.items()}, rows[:1])
