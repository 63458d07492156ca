"""The SELF-DRAFTING speculative round (``serving.speculative.
SelfDraftConfig``: the draft is the target's own multi-token-prediction
module) through ``KVSlotPool`` and ``DecodeServer`` over ring leaves, at
a small size on the CPU (seeded).

Greedy-exact: the served tokens of a speculative request equal a plain
request's token for token — over rings of 4 rows that wrap many times,
with proposals that are sometimes right (a module built to be right: it
reads the plain run's own tokens) and sometimes wrong, with speculative
and plain slots in one pool.
"""
import copy

import numpy as np
import pytest

from paddle_tpu import decoding
from paddle_tpu import mtp_routed_lm as mr
from paddle_tpu.serving.decode import DecodeServer
from paddle_tpu.serving.kv_pool import KVSlotPool
from paddle_tpu.serving.speculative import (SelfDraftConfig,
                                            dispatch_spec_chunk,
                                            make_self_draft)
from test_k_exaone_lm import CHUNK, V, WINDOW, tiny_cfg, weights

RUNG = 64


def _builder(seed=0, chunk=CHUNK):
    cfg = tiny_cfg()
    w = weights(cfg, seed=seed)
    return cfg, decoding.make_mtp_routed_lm_pooled_step_fn(
        w, cfg, kv_dtype="fp32", prefill_tokens=chunk)


def _pool(step, make_cache, speculative=None, slots=3, **kw):
    return KVSlotPool(step, make_cache, eos_id=V, max_slots=slots,
                      max_seq_len=RUNG, slot_ladder=[slots],
                      len_ladder=[RUNG], steps=1, speculative=speculative,
                      kv_dtype="fp32", **kw)


def _prompts(n, seed=1):
    rng = np.random.RandomState(seed)
    return [rng.randint(0, V, int(k)).astype(np.int32)
            for k in rng.randint(3, 12, n)]


def _plain_tokens(step, make_cache, prompts, total):
    pool = _pool(step, make_cache, slots=len(prompts))
    state = pool.alloc(len(prompts), RUNG)
    state = pool.admit(state, list(range(len(prompts))), prompts,
                       [len(p) for p in prompts], [total] * len(prompts))
    for _ in range(total):
        state = pool.chunk(state)
    assert np.asarray(state["finished"]).all()
    return np.asarray(state["tokens"])[:, :total]


def _oracle(truth, wrong_every):
    """A module BUILT to be right: its 'logits' are one-hot at the plain
    run's own token for the position it predicts — except where that
    position is a multiple of ``wrong_every``, where it proposes another
    token.  The cache passes through."""
    import jax
    import jax.numpy as jnp

    table = jnp.asarray(truth)

    def module_fn(cache, hidden, next_tokens, ts):
        s, k = next_tokens.shape
        q = jnp.maximum(ts, 0)[:, None] + jnp.arange(k)[None, :] + 2
        tok = table[jnp.arange(s)[:, None],
                    jnp.minimum(q, table.shape[1] - 1)]
        tok = jnp.where(q % wrong_every == 0, (tok + 1) % V, tok)
        return jax.nn.one_hot(tok, V), cache

    return module_fn


@pytest.mark.parametrize("wrong_every", [3, 1, 10 ** 6])
def test_speculative_tokens_equal_plain_tokens_whatever_the_proposals(
        wrong_every):
    """Proposals sometimes right and sometimes wrong (every third
    position wrong), always wrong, always right; slot 1 is a PLAIN
    request beside two speculative ones; 48 positions over rings of 4."""
    _, (step, make_cache, _) = _builder()
    prompts, total = _prompts(3), 48
    want = _plain_tokens(step, make_cache, prompts, total)
    cfg = SelfDraftConfig(decoding.spec_of(make_cache).verify_fn,
                          _oracle(want, wrong_every))
    pool = _pool(step, make_cache, speculative=cfg)
    state = pool.alloc(3, RUNG)
    state = pool.admit(state, [0, 1, 2], prompts, [len(p) for p in prompts],
                       [total] * 3, spec=[True, False, True])
    rounds = 0
    while not np.asarray(state["finished"]).all():
        state = dispatch_spec_chunk(pool, state)
        rounds += 1
        assert rounds <= total
    np.testing.assert_array_equal(np.asarray(state["tokens"])[:, :total],
                                  want)
    if wrong_every == 1:        # nothing accepted past the prompts
        assert rounds >= total - max(len(p) for p in prompts)
    if wrong_every == 10 ** 6:  # a speculative slot went two at a time
        assert rounds <= total - 2 - min(len(p) for p in prompts[::2]) // 2
    # the proposals' record: by the position predicted
    props = np.asarray(state["proposals"])
    for slot in (0, 2):
        for q in range(len(prompts[slot]) + 1, total):
            right = want[slot, q] if q % wrong_every else (
                want[slot, q] + 1) % V
            assert props[slot, q] == right


def test_the_models_own_module_drafts_and_the_tokens_stay_exact():
    """``make_self_draft(make_cache)``: the real (random) module; its
    proposals are almost never right, the tokens are the plain run's."""
    _, (step, make_cache, _) = _builder(seed=2)
    prompts, total = _prompts(3, seed=5), 40
    want = _plain_tokens(step, make_cache, prompts, total)
    pool = _pool(step, make_cache, speculative=make_self_draft(make_cache))
    state = pool.alloc(3, RUNG)
    state = pool.admit(state, [0, 1, 2], prompts, [len(p) for p in prompts],
                       [total] * 3, spec=True)
    for _ in range(total):
        state = dispatch_spec_chunk(pool, state)
    np.testing.assert_array_equal(np.asarray(state["tokens"])[:, :total],
                                  want)
    # the module's expert layer counted its rows
    assert int(np.asarray(state["cache"]["expert_stats"])[-1, 3]) > 0


def test_a_rejected_round_leaves_every_ring_row_a_later_query_reads():
    """After a round whose proposal was wrong the slot stands at ``pos +
    1``; the ring rows that hold positions ``pos + 2 - W .. pos`` — what
    the next query reads besides the row it writes itself — are what the
    plain step left there."""
    _, (step, make_cache, _) = _builder()
    prompts, total = _prompts(1), 40
    want = _plain_tokens(step, make_cache, prompts, total)
    cfg = SelfDraftConfig(decoding.spec_of(make_cache).verify_fn,
                          _oracle(want, 1))
    spec_pool = _pool(step, make_cache, speculative=cfg, slots=1)
    plain_pool = _pool(step, make_cache, slots=1)
    args = ([0], prompts, [len(prompts[0])], [total])
    a = spec_pool.admit(spec_pool.alloc(1, RUNG), *args, spec=True)
    b = plain_pool.admit(plain_pool.alloc(1, RUNG), *args)
    checked = 0
    while int(np.asarray(a["pos"])[0]) < total - 4:
        pos = int(np.asarray(a["pos"])[0])
        a = dispatch_spec_chunk(spec_pool, a)
        new = int(np.asarray(a["pos"])[0])
        while int(np.asarray(b["pos"])[0]) < new:
            b = plain_pool.chunk(b)
        if pos + 1 < len(prompts[0]):   # teacher-forced: two at a time
            continue
        assert new == pos + 1           # the proposal was wrong
        for la, lb in zip(a["cache"]["layers"], b["cache"]["layers"]):
            if la["k"].shape[1] != WINDOW:
                continue
            for p in range(max(0, pos + 2 - WINDOW), pos + 1):
                for leaf in ("k", "v"):
                    # two rows a call against one: another order of sums
                    # (the verify scores the old ring and its fresh rows
                    # apart since PR 59: the worst row reads 1.24e-5,
                    # where one run of keys read 8.6e-6)
                    np.testing.assert_allclose(
                        np.asarray(la[leaf])[0, p % WINDOW],
                        np.asarray(lb[leaf])[0, p % WINDOW], atol=2e-5)
                    checked += 1
    assert checked > 100


@pytest.mark.parametrize("k", [2, 3])
def test_a_ring_carries_two_rows_a_round_and_refuses_three(k):
    from paddle_tpu.serving.speculative import SpeculativeConfig

    _, (step, make_cache, _) = _builder()

    def bare(s, t):
        return make_cache(s, t)["layers"]

    spec = decoding.spec_of(make_cache)
    decoding.declare(bare, decoding.CacheSpec(spec.leaves["layers"]))
    cfg = SpeculativeConfig(lambda c, t, ts: (None, c), step, bare, k=k)
    kw = dict(eos_id=V, max_slots=2, max_seq_len=RUNG, slot_ladder=[2],
              len_ladder=[RUNG])
    if k == 2:
        assert KVSlotPool(step, bare, speculative=cfg, **kw).ring_leaves
        return
    with pytest.raises(ValueError, match=r"k = 3\) over a cache with ring "
                       r"leaves.*k - 2 spare rows"):
        KVSlotPool(step, bare, speculative=cfg, **kw)
    with pytest.raises(ValueError, match="k = 2 rows"):
        SelfDraftConfig(spec.verify_fn, spec.mtp_fn, k=3)


def _server(step, make_cache, name, speculative=None, **kw):
    return DecodeServer(
        step, make_cache, eos_id=V, max_seq_len=RUNG, max_slots=4,
        slot_ladder=(4,), len_ladder=(RUNG,), steps_per_tick=2,
        queue_capacity=64, target_queue_wait_ms=600000.0, kv_dtype="fp32",
        name=name, speculative=speculative, **kw)


def _serve(srv, prompts, n_new, **kw):
    reqs = [srv.submit({"tokens": p}, max_new_tokens=n_new, **kw)
            for p in prompts]
    return [np.concatenate(r.result(timeout=300)) for r in reqs], reqs


def test_decode_server_serves_speculative_and_plain_requests_alike():
    """Six requests through four slots (two reused), speculative and
    plain in one pool, against a plain server: the same tokens; the
    round counters, the proposals kept on request and the expert and
    window counters say what happened."""
    _, (step, make_cache, _) = _builder(seed=4)
    prompts, n_new = _prompts(6, seed=9), 30
    plain = _server(step, make_cache, "plain-self-draft")
    try:
        plain.warmup()
        want, _ = _serve(plain, prompts, n_new)
    finally:
        plain.stop(drain=False, timeout=60.0)
    srv = _server(step, make_cache, "self-draft",
                  speculative=make_self_draft(make_cache))
    try:
        assert srv.warmup() > 0
        reqs = [srv.submit({"tokens": p}, max_new_tokens=n_new,
                           speculative=i != 1, keep_drafts=i == 0)
                for i, p in enumerate(prompts)]
        got = [np.concatenate(r.result(timeout=300)) for r in reqs]
        m = srv.metrics()
    finally:
        srv.stop(drain=False, timeout=60.0)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert m["recompiles"] == 0
    spec = m["decode"]["speculative"]
    assert spec["kind"] == "self" and spec["k"] == 2
    assert spec["rounds"] > 0 and spec["row_rounds"] >= spec["proposed_tokens"]
    # every generated token of a speculative request but those a
    # teacher-forced second row emitted came from a round that proposed
    gen_spec = 5 * n_new
    assert (spec["proposed_tokens"] + spec["accepted_tokens"]
            <= gen_spec <= spec["proposed_tokens"]
            + spec["accepted_tokens"] + 5)
    assert 0 <= spec["accepted_tokens"] <= spec["proposed_tokens"]
    # the proposals of request 0, one a generated position
    drafts = reqs[0].draft_tokens
    assert drafts is not None and drafts.shape == (n_new,)
    assert reqs[2].draft_tokens is None
    assert ((drafts >= 0) & (drafts < V)).all()
    d = m["decode"]
    assert d["expert_layer_steps"] > 0 and d["window_positions_read"] > 0
    # a round computes two rows a slot: the window layers read for both
    assert d["window_positions_read"] <= d["window_positions_live"]


def test_a_speculative_round_counts_its_read_by_the_builders_rule():
    """A self-drafting round reads the rung-long leaves ONCE for its K
    rows: where the builder declares a ``"kv"`` ``PositionRead``
    the server's read counter takes what that rule says at the slot's
    LAST fresh row (the rung's last where that passes the end), once a
    slot that advanced — under the pool's positions, at or over the live
    ones; a builder with no rule counts the whole pool a round, as
    before.  The live and pool counters count as they always did: the
    same with the rule and without."""
    _, (step, make_cache, _) = _builder(seed=4)
    prompts, n_new = _prompts(3, seed=11), RUNG - 12
    asked = []

    def rule(ts, t):
        asked.append((np.asarray(ts).copy(), t))
        return (ts // 8 + 1) * 8

    counted = {}
    spec = copy.copy(decoding.spec_of(make_cache))
    window = tuple(read for read in spec.reads if read.kind == "window")
    for ruled in (True, False):
        spec.reads = window + (
            (decoding.PositionRead("kv", rule),) if ruled else ())
        decoding.declare(make_cache, spec)
        srv = _server(step, make_cache, "spec-kv-%s" % ruled,
                      speculative=make_self_draft(make_cache))
        try:
            srv.warmup()
            _serve(srv, prompts, n_new, speculative=True)
            counted[ruled] = d = srv.metrics()["decode"]
        finally:
            srv.stop(drain=False, timeout=60.0)
        rounds = d["speculative"]["rounds"]
        assert rounds > 0       # slots x T a round
        assert d["kv_positions_pool"] == rounds * 4 * RUNG
    with_rule, without = counted[True], counted[False]
    assert with_rule["kv_positions_live"] == without["kv_positions_live"] > 0
    assert without["kv_positions_read"] == without["kv_positions_pool"]
    assert all(t == RUNG for _, t in asked)
    last = np.concatenate([ts.ravel() for ts, _ in asked])
    # one figure a slot and round: the position of its SECOND row
    assert 1 <= last.min() and last.max() < RUNG
    assert with_rule["kv_positions_live"] < with_rule["kv_positions_read"] \
        < with_rule["kv_positions_pool"]
    assert with_rule["kv_positions_read"] % 8 == 0


def test_a_prefix_snapshot_is_served_with_a_self_draft_attached():
    """``prefix=True`` over this builder keeps SNAPSHOTS (it has a
    prefill, which feeds the module's leaves too): a second request
    seated over the first's snapshot, speculative, gets the plain
    tokens."""
    _, (step, make_cache, _) = _builder(seed=6)
    rng = np.random.RandomState(3)
    doc = rng.randint(0, V, 2 * CHUNK + 3).astype(np.int32)
    prompts = [doc, np.concatenate([doc[:2 * CHUNK],
                                    rng.randint(0, V, 4).astype(np.int32)])]
    plain = _server(step, make_cache, "plain-snap")
    try:
        plain.warmup()
        want = [_serve(plain, [p], 12)[0][0] for p in prompts]
    finally:
        plain.stop(drain=False, timeout=60.0)
    srv = _server(step, make_cache, "self-draft-snap",
                  speculative=make_self_draft(make_cache),
                  prefix_cache=1 << 24)
    try:
        srv.warmup()
        got = [_serve(srv, [p], 12, speculative=True)[0][0]
               for p in prompts]
        stats = srv.metrics()["decode"]["prefix_cache"]
    finally:
        srv.stop(drain=False, timeout=60.0)
    for a, b in zip(got, want):
        np.testing.assert_array_equal(a, b)
    assert stats["hits"] >= 1
