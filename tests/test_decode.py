"""Continuous-batching decode tests (serving/decode.py +
serving/kv_pool.py + the infer_stream client surfaces).

Two model tiers keep this fast: a deterministic "chain" step fn (next
token = previous + 1 mod V; no cache math) exercises the SCHEDULER —
admission, slot reuse, EOS/cap termination, TTFT, ticks accounting,
streaming — with near-zero compile cost, while a small real
transformer-LM (random weights) proves NUMERIC parity of the slot-pool
path against the scalar cached step fn, and backs the 2-child wire
fleet acceptance run.
"""
import contextlib
import threading
import time

import numpy as np
import pytest

from conftest import WAIT

from paddle_tpu import monitor
from paddle_tpu.decode_attention import ragged_positions_read
from paddle_tpu.decoding import (
    CacheSpec,
    Leaf,
    PositionRead,
    declare,
    make_transformer_lm_pooled_step_fn,
    make_transformer_lm_step_fn,
    spec_of,
)
from paddle_tpu.serving.client import Client
from paddle_tpu.serving.decode import (
    DecodeRequest,
    DecodeServer,
    save_decode_endpoint,
)
from paddle_tpu.serving.errors import (
    DeadlineExceeded,
    ServerClosed,
    ServerOverloaded,
    ServingError,
)
from paddle_tpu.serving.kv_pool import KVSlotPool, default_len_ladder

EOS = 9
V = 23


# ---------------------------------------------------------------------------
# model builders
# ---------------------------------------------------------------------------
def chain_model():
    """next token = (consumed token + 1) % V; cache is a dummy leaf.
    From prompt [..., p] the generated chain is p+1, p+2, ... — EOS is
    reached exactly when the chain passes 9, so termination and token
    values are checkable by arithmetic."""
    import jax
    import jax.numpy as jnp

    def step_fn(cache, tokens, ts):
        logits = jax.nn.one_hot((tokens + 1) % V, V) * 10.0
        return logits, cache

    def make_cache(n_rows, seq_len):
        return {"z": jnp.zeros((n_rows, seq_len), "float32")}

    # counted as the transformer LM's fp32 leaves are
    declare(make_cache, CacheSpec({"z": Leaf(1)}, reads=[PositionRead(
        "kv", ragged_positions_read, rounds=False)]))
    return step_fn, make_cache


def slow_chain_model(work=320):
    """The chain model with ~5ms of dense matmul per step (the burn
    rides the cache so XLA cannot fold it): decode takes human-scale
    time, giving the mid-decode timing tests real room."""
    import jax
    import jax.numpy as jnp

    def step_fn(cache, tokens, ts):
        w = cache["w"]
        burn = (w @ w).sum() * 1e-30
        logits = jax.nn.one_hot((tokens + 1) % V, V) * 10.0 + burn
        return logits, cache

    def make_cache(n_rows, seq_len):
        return {"z": jnp.zeros((n_rows, seq_len), "float32"),
                "w": jnp.zeros((work, work), "float32")}

    declare(make_cache, CacheSpec({"z": Leaf(1), "w": Leaf()}))
    return step_fn, make_cache


@pytest.fixture(scope="module")
def slow_server():
    step_fn, make_cache = slow_chain_model()
    srv = DecodeServer(step_fn, make_cache, eos_id=EOS, max_seq_len=64,
                       max_slots=4, len_ladder=[64], steps_per_tick=1,
                       name="slowchain")
    srv.warmup(configure_cache=False)
    yield srv
    srv.stop(drain=False)


def expected_chain(prompt, total_len):
    """The chain model's generated tokens for ``prompt`` under length
    cap ``total_len`` (prompt + generated), EOS included."""
    out = []
    cur = prompt[-1]
    for _ in range(total_len - len(prompt)):
        cur = (cur + 1) % V
        out.append(cur)
        if cur == EOS:
            break
    return out


from paddle_tpu.decoding import random_transformer_lm_state as lm_weights


LM_DIMS = dict(vocab=V, d_model=16, n_layer=2, n_head=2, d_inner=32,
               max_pos=32)


@pytest.fixture(scope="module")
def lm_state():
    return lm_weights(np.random.RandomState(7), **LM_DIMS)


@pytest.fixture(scope="module")
def chain_server():
    """One warmed chain-model server shared by the scheduler tests
    (requests are independent; each test leaves it idle)."""
    step_fn, make_cache = chain_model()
    srv = DecodeServer(step_fn, make_cache, eos_id=EOS, max_seq_len=16,
                       max_slots=4, steps_per_tick=2, name="chain")
    srv.warmup(configure_cache=False)
    yield srv
    srv.stop(drain=False)


def _ref_continuation(state, prompt, total_len):
    """Greedy continuation via the SCALAR cached step fn — the
    independent reference the slot-pool path must match exactly."""
    import jax.numpy as jnp

    step_fn, make_cache = make_transformer_lm_step_fn(
        state, LM_DIMS["vocab"], LM_DIMS["d_model"], LM_DIMS["n_layer"],
        LM_DIMS["n_head"], LM_DIMS["d_inner"], LM_DIMS["max_pos"])
    cache = make_cache(1)
    logits = None
    for t, tok in enumerate(prompt):
        logits, cache = step_fn(cache, jnp.asarray([tok], "int32"), t)
    out = []
    pos = len(prompt)
    while pos < total_len:
        nxt = int(np.argmax(np.asarray(logits[0])))
        out.append(nxt)
        if nxt == EOS:
            break
        logits, cache = step_fn(cache, jnp.asarray([nxt], "int32"), pos)
        pos += 1
    return out


# ---------------------------------------------------------------------------
# KVSlotPool units
# ---------------------------------------------------------------------------
def test_default_len_ladder_shape():
    assert default_len_ladder(64) == [8, 16, 32, 64]
    assert default_len_ladder(48) == [8, 16, 32, 48]
    assert default_len_ladder(8) == [8]
    assert default_len_ladder(6) == [6]
    with pytest.raises(ValueError):
        default_len_ladder(0)


def test_a_make_cache_that_declares_no_leaf_axes_is_refused():
    """Nothing reads a leaf's sequence axis off its shape: a
    ``make_cache`` that was never ``declare``d is refused at
    construction, by the pool and by the server, in words that name the
    call; a declaration that does not fit the cache, by ``declare``."""
    step_fn, make_cache = chain_model()

    def undeclared(n_rows, seq_len):
        return make_cache(n_rows, seq_len)

    with pytest.raises(ValueError, match=r"decoding.declare\(make_cache"):
        KVSlotPool(step_fn, undeclared, eos_id=EOS, max_slots=4,
                   max_seq_len=32, steps=2)
    with pytest.raises(ValueError, match=r"decoding.declare\(make_cache"):
        DecodeServer(step_fn, undeclared, eos_id=EOS, max_seq_len=16,
                     max_slots=2)
    with pytest.raises(ValueError, match="declares 2 leaves, the cache "
                                         "has 1"):
        declare(undeclared, CacheSpec({"z": Leaf(1), "extra": Leaf(1)}))


def test_pool_alloc_resize_and_rungs():
    step_fn, make_cache = chain_model()
    pool = KVSlotPool(step_fn, make_cache, eos_id=EOS, max_slots=4,
                      max_seq_len=32, steps=2)
    st = pool.alloc(2, 8)
    assert pool.state_rungs(st) == (2, 8)
    assert st["tokens"].shape == (2, 8) and st["tokens"].dtype == np.int32
    st["tokens"][:] = np.arange(16).reshape(2, 8)
    st["pos"][:] = [3, 5]
    up = pool.resize(st, 4, 16)
    assert pool.state_rungs(up) == (4, 16)
    # old content zero-padded into the larger rungs
    np.testing.assert_array_equal(up["tokens"][:2, :8],
                                  np.arange(16).reshape(2, 8))
    assert up["tokens"][2:].sum() == 0 and up["tokens"][:2, 8:].sum() == 0
    np.testing.assert_array_equal(up["pos"][:2], [3, 5])
    down = pool.resize(up, 2, 8)
    np.testing.assert_array_equal(down["tokens"], st["tokens"])


def test_pool_warmup_covers_every_rung_pair_then_zero_misses():
    step_fn, make_cache = chain_model()
    pool = KVSlotPool(step_fn, make_cache, eos_id=EOS, max_slots=4,
                      max_seq_len=16, steps=2)
    n = pool.warmup()
    assert n == len(pool.rung_pairs()) * 3  # chunk + admit + release
    assert pool.warmup() == 0  # re-warm is free
    recompiles = []
    pool._on_recompile = lambda: recompiles.append(1)
    # dispatch at every rung pair: all warmed, no compile
    for s, t in pool.rung_pairs():
        st = pool.alloc(s, t)
        st = pool.admit(st, 0, np.array([2, 3], np.int32), 2, t)
        st = pool.chunk(st)
        st = pool.release(st, [0])
    stats = pool.jit_cache_stats()
    assert stats["misses"] == 0 and not recompiles
    assert stats["hits"] >= len(pool.rung_pairs()) * 3


@pytest.mark.parametrize("kv", ["fp32", "int8"])
def test_batch_admit_equals_single_admits_in_order(lm_state, kv,
                                                   check_batch_admit):
    """ONE admit call over k seats == k admit calls, leaf for leaf —
    beside a live row, into a reused slot, prompts of unequal length —
    and it is the same executable either way: nothing compiles."""
    step_fn, make_cache = make_transformer_lm_pooled_step_fn(
        lm_state, LM_DIMS["vocab"], LM_DIMS["d_model"], LM_DIMS["n_layer"],
        LM_DIMS["n_head"], LM_DIMS["d_inner"], kv_dtype=kv)
    pool = KVSlotPool(step_fn, make_cache, eos_id=EOS, max_slots=4,
                      max_seq_len=16, slot_ladder=[4], len_ladder=[16],
                      steps=2, kv_dtype=kv)
    # chunk + admit + release, and this builder's seat_prefill (PR 45)
    assert pool.warmup() == 4
    rng = np.random.RandomState(11)
    st = pool.alloc(4, 16)
    st = pool.admit(st, 1, rng.randint(2, V, 4).astype(np.int32), 4, 12)
    st = pool.admit(st, 2, rng.randint(2, V, 2).astype(np.int32), 2, 4)
    for _ in range(3):  # slot 1 is mid-flight, slot 2 finished: reusable
        st = pool.chunk(st)
    seats = [(0, rng.randint(2, V, 5).astype(np.int32), 14, False),
             (2, rng.randint(2, V, 1).astype(np.int32), 9, False),
             (3, rng.randint(2, V, 7).astype(np.int32), 16, False)]
    st = check_batch_admit(pool, st, seats)
    assert np.asarray(st["active"]).tolist() == [True] * 4
    assert np.asarray(st["pos"])[[0, 2, 3]].tolist() == [0, 0, 0]
    assert np.asarray(st["total_len"]).tolist() == [14, 12, 9, 16]
    assert pool.jit_cache_stats()["misses"] == 0


# ---------------------------------------------------------------------------
# scheduler semantics (chain model)
# ---------------------------------------------------------------------------
def test_generation_eos_and_cap_termination(chain_server):
    # EOS mid-stream: prompt ends at 5 -> 6, 7, 8, 9(EOS)
    req = chain_server.submit({"tokens": np.array([4, 5], np.int32)})
    assert req.result(timeout=WAIT)[0].tolist() == [6, 7, 8, 9]
    # cap termination: chain from 10 never hits EOS before the cap
    req = chain_server.submit({"tokens": np.array([10], np.int32)},
                              max_new_tokens=5)
    assert req.result(timeout=WAIT)[0].tolist() == [11, 12, 13, 14, 15]
    # 2-D [1, L] and positional feeds accepted
    req = chain_server.submit({"tokens": np.array([[4, 5]], np.int32)})
    assert req.result(timeout=WAIT)[0].tolist() == [6, 7, 8, 9]
    req = chain_server.submit([np.array([5], np.int32)])
    assert req.result(timeout=WAIT)[0].tolist() == [6, 7, 8, 9]


def test_seq_len_histogram_feeds_kv_ladder_proposal(chain_server):
    """Every admitted request records its TOTAL sequence length (prompt
    + generation budget) — the observed histogram the offline KV
    length-ladder proposal (autotune.plan_kv_ladder) consumes, surfaced
    through metrics() like the batching path's arrival histogram."""
    from paddle_tpu.serving import autotune

    before = chain_server.seq_len_histogram().get(8, 0)
    req = chain_server.submit({"tokens": np.array([10, 11, 12], np.int32)},
                              max_new_tokens=5)  # total = 3 + 5 = 8
    req.result(timeout=WAIT)
    hist = chain_server.seq_len_histogram()
    assert hist.get(8, 0) == before + 1
    assert chain_server.metrics()["decode"]["seq_len_histogram"]["8"] >= 1
    # the recorded histogram is a valid proposal input as-is
    doc = autotune.plan_kv_ladder(hist, chain_server.max_seq_len)
    assert doc["len_ladder"][-1] == chain_server.max_seq_len


def test_submit_validation(chain_server):
    with pytest.raises(ValueError):
        chain_server.submit({"tokens": np.zeros((2, 3), np.int32)})
    with pytest.raises(ValueError):
        chain_server.submit({"tokens": np.array([], np.int32)})
    with pytest.raises(ValueError):  # prompt leaves no room to generate
        chain_server.submit({"tokens": np.arange(16, dtype=np.int32)})
    with pytest.raises(ValueError):
        chain_server.submit({"wrong": np.array([1], np.int32)})
    with pytest.raises(ValueError):  # a 0 cap must not generate a token
        chain_server.submit({"tokens": np.array([2], np.int32)},
                            max_new_tokens=0)
    with pytest.raises(DeadlineExceeded):
        chain_server.submit({"tokens": np.array([2], np.int32)},
                            timeout_ms=0)


def test_stream_yields_chunks_before_completion(slow_server):
    """The streaming contract: the first chunk is in the consumer's
    hands while the sequence is still decoding (~5ms/tick leaves ~95ms
    of decode after tick 1)."""
    req = slow_server.submit({"tokens": np.array([10], np.int32)},
                             max_new_tokens=20)
    it = req.stream()
    first = next(it)
    assert not req.done()  # tokens in hand, sequence still in flight
    rest = [c for c in it]
    got = [t for c in [first] + rest for t in c.tolist()]
    assert got == expected_chain([10], 21)
    assert len(rest) >= 1  # chunked, not one blob
    assert req.result(timeout=WAIT)[0].tolist() == got


def test_mixed_storm_zero_recompiles_and_isolation(chain_server):
    """A concurrent mixed prompt-length storm: every sequence exact,
    zero executables built after warmup (the acceptance guarantee,
    in-process edition)."""
    misses0 = chain_server._pool.jit_cache_stats()["misses"]
    results = {}
    errs = []

    def one(i):
        plen = 1 + i % 4
        start = 10 + (i % 7)
        prompt = np.arange(start, start + plen, dtype=np.int32) % V
        cap = 2 + i % 9
        try:
            if i % 2:
                got = [t for c in Client(chain_server).infer_stream(
                    {"tokens": prompt}, max_new_tokens=cap)
                    for t in c.tolist()]
            else:
                got = chain_server.submit(
                    {"tokens": prompt},
                    max_new_tokens=cap).result(timeout=WAIT)[0].tolist()
            results[i] = (prompt.tolist(), cap, got)
        except Exception as e:  # noqa: BLE001
            errs.append(e)

    threads = [threading.Thread(target=one, args=(i,),
                                daemon=True) for i in range(24)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(WAIT)
    assert not any(t.is_alive() for t in threads)
    assert not errs
    assert len(results) == 24
    for prompt, cap, got in results.values():
        total = min(len(prompt) + cap, chain_server.max_seq_len)
        assert got == expected_chain(prompt, total)
    assert chain_server._pool.jit_cache_stats()["misses"] == misses0
    assert chain_server.metrics().get("recompiles", 0) == 0


def test_continuous_batching_beats_request_at_a_time(chain_server):
    """The scheduling win, measured in TICKS (each tick = one fixed-cost
    device dispatch, the honest proxy for wall time on a host-bound
    test): interleaved long/short traffic finishes in less than half
    the ticks request-at-a-time grouping burns, because a group held
    open by one long sequence wastes every freed slot."""
    def workload():
        reqs = []
        for i in range(16):
            if i % 4 == 0:
                reqs.append((np.array([10], np.int32), 14))  # long
            else:
                reqs.append((np.array([12], np.int32), 2))   # short
        return reqs

    def ticks():
        return chain_server.metrics()["decode"]["ticks"]

    # request-at-a-time: admit in arrival-order groups of max_slots,
    # wait the WHOLE group before admitting the next (what the
    # request-batching server does to an autoregressive endpoint)
    t0 = ticks()
    for g in range(0, 16, chain_server.max_batch_size):
        group = [chain_server.submit({"tokens": p}, max_new_tokens=c)
                 for p, c in workload()[g:g + chain_server.max_batch_size]]
        for r in group:
            r.result(timeout=WAIT)
    rat_ticks = ticks() - t0

    # continuous: submit everything; finished sequences free slots
    # mid-flight and the queue refills them at the next tick
    t0 = ticks()
    reqs = [chain_server.submit({"tokens": p}, max_new_tokens=c)
            for p, c in workload()]
    outs = [r.result(timeout=WAIT)[0].tolist() for r in reqs]
    cont_ticks = ticks() - t0

    for (p, c), got in zip(workload(), outs):
        assert got == expected_chain(p.tolist(), len(p) + c)
    assert rat_ticks >= 2 * cont_ticks, (rat_ticks, cont_ticks)


def test_late_arrival_first_token_before_batch_finishes(slow_server):
    """TTFT under continuous batching (the acceptance criterion): a
    request arriving mid-decode reaches its first token BEFORE the
    in-flight batch finishes — request-at-a-time would have parked it
    behind the whole decode.  Asserted on the scheduler's own
    ``first_token_t``/``done_t`` stamps, so the check is exact."""
    longs = [slow_server.submit({"tokens": np.array([10], np.int32)},
                                max_new_tokens=40) for _ in range(2)]
    # wait until the long batch is genuinely mid-decode (~200ms total)
    deadline = time.monotonic() + 10.0
    while slow_server.metrics()["decode"]["slot_occupancy"] == 0.0:
        assert time.monotonic() < deadline
        time.sleep(0.002)
    late = slow_server.submit({"tokens": np.array([4, 5], np.int32)})
    first_chunk = next(late.stream())
    assert first_chunk.tolist()[0] == 6
    for r in longs:
        assert r.result(timeout=30.0)[0].tolist() == expected_chain(
            [10], 41)
    # the late arrival's first token landed strictly before either
    # in-flight sequence completed: TTFT < remaining batch decode time
    assert late.first_token_t is not None
    assert late.first_token_t < min(r.done_t for r in longs)


def test_deadline_mid_decode_frees_slot(slow_server):
    """A deadline passing mid-decode fails the request typed and frees
    its slot for queued work.  The budget is a quarter of a MEASURED
    full decode (not a wall-clock guess), so tick speed can't flake
    the test either way."""
    t0 = time.perf_counter()
    slow_server.submit({"tokens": np.array([10], np.int32)},
                       max_new_tokens=40).result(timeout=30.0)
    full_ms = (time.perf_counter() - t0) * 1e3
    req = slow_server.submit({"tokens": np.array([10], np.int32)},
                             timeout_ms=full_ms / 4.0, max_new_tokens=40)
    with pytest.raises(DeadlineExceeded):
        req.result(timeout=30.0)
    deadline = time.monotonic() + 10.0
    while slow_server._active_count():
        assert time.monotonic() < deadline
        time.sleep(0.005)


def test_abandoned_stream_frees_slot(slow_server):
    it = Client(slow_server).infer_stream(
        {"tokens": np.array([10], np.int32)}, max_new_tokens=40)
    next(it)
    it.close()
    deadline = time.monotonic() + 10.0
    while slow_server._active_count():
        assert time.monotonic() < deadline
        time.sleep(0.005)


def test_abandoned_stream_never_started_frees_slot(slow_server):
    """A generator dropped BEFORE its first next() never runs its body,
    so only the GC finalizer can abort the decode — without it the slot
    generates its full chain (~22 tokens to EOS) for a caller that is
    gone.  The token delta is the discriminator: an aborted lane stops
    within a tick or two."""
    import gc

    def gen_tokens():
        return int(slow_server.metrics()["decode"]["generated_tokens"])

    g0 = gen_tokens()
    gen = Client(slow_server).infer_stream(
        {"tokens": np.array([10], np.int32)}, max_new_tokens=40)
    deadline = time.monotonic() + 10.0
    while not slow_server._active_count():
        assert time.monotonic() < deadline
        time.sleep(0.005)
    del gen
    gc.collect()
    deadline = time.monotonic() + 10.0
    while slow_server._active_count():
        assert time.monotonic() < deadline
        time.sleep(0.005)
    assert gen_tokens() - g0 < 12  # aborted mid-flight, not decoded out


def test_stream_on_non_decode_server_raises_typed():
    class NotDecode:
        _predictor = type("P", (), {
            "get_output_names": lambda self: ["y"]})()

    with pytest.raises(ServingError):
        Client(NotDecode()).infer_stream({"tokens": [1]})


def test_overload_shed_carries_retry_hint():
    step_fn, make_cache = chain_model()
    srv = DecodeServer(step_fn, make_cache, eos_id=EOS, max_seq_len=16,
                       max_slots=1, slot_ladder=[1], len_ladder=[16],
                       steps_per_tick=1, queue_capacity=2, name="tiny")
    srv.warmup(configure_cache=False)
    try:
        reqs = []
        with pytest.raises(ServerOverloaded) as ei:
            for _ in range(12):
                reqs.append(srv.submit(
                    {"tokens": np.array([10], np.int32)},
                    max_new_tokens=14))
        assert ei.value.retry_after_ms >= 1.0
        for r in reqs:  # admitted work still completes
            r.result(timeout=30.0)
    finally:
        srv.stop(drain=False)


def test_stop_drain_finishes_queued_and_abort_fails_typed():
    step_fn, make_cache = chain_model()
    srv = DecodeServer(step_fn, make_cache, eos_id=EOS, max_seq_len=16,
                       max_slots=2, steps_per_tick=2, name="draining")
    srv.warmup(configure_cache=False)
    reqs = [srv.submit({"tokens": np.array([10 + i], np.int32)},
                       max_new_tokens=4) for i in range(6)]
    srv.stop(drain=True, timeout=30.0)
    for i, r in enumerate(reqs):
        assert r.result(timeout=WAIT)[0].tolist() == expected_chain(
            [10 + i], 5)
    with pytest.raises(ServerClosed):
        srv.submit({"tokens": np.array([2], np.int32)})

    srv2 = DecodeServer(step_fn, make_cache, eos_id=EOS, max_seq_len=16,
                        max_slots=2, steps_per_tick=2, name="aborting")
    srv2.warmup(configure_cache=False)
    reqs = [srv2.submit({"tokens": np.array([10], np.int32)},
                        max_new_tokens=14) for _ in range(4)]
    srv2.stop(drain=False, timeout=30.0)
    for r in reqs:
        with pytest.raises(ServerClosed):
            r.result(timeout=WAIT)


def test_decode_metrics_series(chain_server):
    req = chain_server.submit({"tokens": np.array([2, 3, 4], np.int32)},
                              max_new_tokens=4)
    req.result(timeout=WAIT)
    d = chain_server.metrics()["decode"]
    assert d["generated_tokens"] > 0 and d["prefill_tokens"] > 0
    assert d["ticks"] > 0
    assert d["slot_ladder"] == [1, 2, 4] and d["len_ladder"] == [8, 16]
    snap = monitor.snapshot()
    for name in ("serving_decode_tokens_total",
                 "serving_decode_prefill_tokens_total",
                 "serving_decode_ticks_total",
                 "serving_decode_ttft_seconds",
                 "serving_decode_slot_occupancy"):
        assert name in snap, name


def test_kv_position_counters_follow_live_blocks():
    """``serving_decode_kv_positions_{read,live,pool}_total``: per tick
    the pool counter advances by slots x T x steps, the live counter by
    each active slot's live positions, the read counter by those rounded
    up as the ragged kernel rounds them (``kv_positions_read``: a slot's
    last block in classes of ``KV_TAIL`` rows) — so over requests of
    known lengths read / pool is the rounded live share of the pool and
    read / live what the rounding costs.  A request of total length L
    runs the steps ``ts = 0..L - 2``; a step at ``ts`` has ``ts + 1``
    live positions."""
    from paddle_tpu.decode_attention import (KV_BLOCK, KV_TAIL,
                                             kv_positions_read,
                                             kv_read_block)

    step_fn, make_cache = chain_model()
    S, T, steps = 4, 2 * KV_BLOCK, 4
    srv = DecodeServer(step_fn, make_cache, eos_id=V, max_seq_len=T,
                       max_slots=S, slot_ladder=[S], len_ladder=[T],
                       steps_per_tick=steps, name="kvcount")
    srv.warmup(configure_cache=False)
    try:
        assert kv_read_block(T) == KV_BLOCK
        lengths = [(3, KV_BLOCK - 1), (5, KV_BLOCK + 7), (2, 40)]
        ticks, pool, seen = 0, 0, []
        # one turn at a time (a poll from here can miss every tick of
        # so small a model when the machine is busy)
        with turn_held(srv) as run:
            reqs = [srv.submit({"tokens": np.arange(p, dtype=np.int32)},
                               max_new_tokens=total - p)
                    for p, total in lengths]
            while not all(r.done() for r in reqs):
                run(1)
                d = srv.metrics()["decode"]
                if d["ticks"] != ticks:
                    # both counters advance once a tick, by a whole pool
                    assert d["kv_positions_pool"] > pool
                    assert d["kv_positions_read"] > 0
                    ticks, pool = d["ticks"], d["kv_positions_pool"]
                    seen.append(d["kv_positions_read"])
        for r in reqs:
            r.result(timeout=30.0)
        assert seen == sorted(seen) and len(set(seen)) > 1
        d = srv.metrics()["decode"]
        every_ts = [ts for _, total in lengths for ts in range(total - 1)]
        want = sum(kv_positions_read(ts, KV_BLOCK) for ts in every_ts)
        assert d["kv_positions_read"] == want
        # the shared function rounds a last block to its tail class ...
        assert kv_positions_read(KV_BLOCK + 6, KV_BLOCK) == (
            KV_BLOCK + KV_TAIL)
        # ... so the counter lies between the live and the block-rounded
        live = sum(ts + 1 for ts in every_ts)
        assert d["kv_positions_live"] == live
        assert live < want < sum(-(-(ts + 1) // KV_BLOCK) * KV_BLOCK
                                 for ts in every_ts)
        assert d["kv_positions_pool"] == d["ticks"] * S * T * steps
        snap = monitor.snapshot()
        assert "serving_decode_kv_positions_read_total" in snap
        assert "serving_decode_kv_positions_live_total" in snap
        assert "serving_decode_kv_positions_pool_total" in snap
        share = d["kv_positions_read"] / d["kv_positions_pool"]
        assert share == pytest.approx(want / (d["ticks"] * S * T * steps))
        assert 0.0 < share < 0.5
    finally:
        srv.stop(drain=False)
    # a stopped server leaves none of the three series behind
    for kind in ("read", "live", "pool"):
        assert monitor.counter_value(
            "serving_decode_kv_positions_%s_total" % kind, None,
            server="kvcount") is None


# ---------------------------------------------------------------------------
# admission: one dispatch a scheduler turn
# ---------------------------------------------------------------------------
@contextlib.contextmanager
def turn_held(srv):
    """Park the scheduler thread at the top of a turn until the block
    exits: everything submitted inside is queued before ONE
    ``_admit_pending`` sees any of it.  Yields ``run(n)``, which lets
    ``n`` whole turns (admission, then a tick if anything is seated) go
    and returns with the thread parked again."""
    parked, permits = threading.Semaphore(0), threading.Semaphore(0)
    real = srv._admit_pending

    def gated(turn):
        parked.release()
        permits.acquire(timeout=30.0)
        real(turn)

    def run(n=1):
        for _ in range(n):
            permits.release()
            assert parked.acquire(timeout=30.0)

    srv._admit_pending = gated
    srv._batcher.wake()
    assert parked.acquire(timeout=30.0)
    try:
        yield run
    finally:
        del srv._admit_pending  # the next turn is the class's again
        permits.release()


def _admits(srv):
    d = srv.metrics()["decode"]
    return d["admit_dispatches"], d["admitted"]


def test_turn_seats_every_queued_request_in_one_dispatch(lm_state):
    """k requests queued before a turn: ONE admit dispatch seats them
    (lowest free slots, FIFO), and what they are served is token for
    token what request-at-a-time serving gives — k dispatches — and what
    the scalar step gives."""
    step_fn, make_cache = make_transformer_lm_pooled_step_fn(
        lm_state, LM_DIMS["vocab"], LM_DIMS["d_model"], LM_DIMS["n_layer"],
        LM_DIMS["n_head"], LM_DIMS["d_inner"])
    srv = DecodeServer(step_fn, make_cache, eos_id=EOS, max_seq_len=32,
                       max_slots=4, slot_ladder=[1, 2, 4],
                       len_ladder=[16, 32], steps_per_tick=3, name="lm-turn")
    srv.warmup(configure_cache=False)
    try:
        prompts = [[2, 3, 4], [5], [7, 8], [11, 12, 13, 14]]
        caps = [10, 6, 12, 8]

        def submit(p, c):
            return srv.submit({"tokens": np.array(p, np.int32)},
                              max_new_tokens=c)

        d0, n0 = _admits(srv)
        alone = [submit(p, c).result(timeout=60.0)[0].tolist()
                 for p, c in zip(prompts, caps)]
        d1, n1 = _admits(srv)
        assert (d1 - d0, n1 - n0) == (4, 4)  # one at a time: one each
        with turn_held(srv):
            reqs = [submit(p, c) for p, c in zip(prompts, caps)]
        together = [r.result(timeout=60.0)[0].tolist() for r in reqs]
        d2, n2 = _admits(srv)
        assert (d2 - d1, n2 - n1) == (1, 4)  # one turn: one dispatch
        assert together == alone
        for p, c, got in zip(prompts, caps, together):
            assert got == _ref_continuation(lm_state, p, len(p) + c), p
        for name in ("serving_decode_admit_dispatches_total",
                     "serving_decode_admitted_total"):
            assert monitor.counter_value(name, server="lm-turn") > 0
        assert srv._pool.jit_cache_stats()["misses"] == 0
    finally:
        srv.stop(drain=False)


def test_turn_seats_fifo_into_lowest_free_slots_and_stops_when_full():
    """The turn pops exactly what the request-at-a-time loop popped:
    FIFO, each to the lowest free slot, while a slot is free or the
    ladder can grow; the rest stay queued for the turn a slot frees."""
    step_fn, make_cache = chain_model()
    srv = DecodeServer(step_fn, make_cache, eos_id=V, max_seq_len=32,
                       max_slots=4, slot_ladder=[2, 4], len_ladder=[32],
                       steps_per_tick=1, name="fifo")
    srv.warmup(configure_cache=False)
    seated = []
    real = srv._pool.admit
    srv._pool.admit = lambda st, slots, prompts, *a, **kw: (
        seated.append([(i, int(p[0])) for i, p in zip(slots, prompts)]),
        real(st, slots, prompts, *a, **kw))[1]
    try:
        with turn_held(srv):
            reqs = [srv.submit({"tokens": np.array([i + 1], np.int32)},
                               max_new_tokens=3 + 2 * i) for i in range(6)]
        outs = [r.result(timeout=60.0)[0].tolist() for r in reqs]
        assert outs == [[(i + 2 + j) % V for j in range(3 + 2 * i)]
                        for i in range(6)]
        # four slots: four in the first dispatch, in order; the fifth and
        # sixth as the two shortest free their slots
        assert seated == [[(0, 1), (1, 2), (2, 3), (3, 4)],
                          [(0, 5)], [(1, 6)]]
        assert _admits(srv) == (3, 6)
    finally:
        srv.stop(drain=False)


def test_growing_ladders_resize_once_a_turn():
    """A turn whose batch outgrows both the slot rung and the length
    rung moves the pool to the rung pair the WHOLE batch needs in one
    resize, then seats it in one dispatch."""
    step_fn, make_cache = chain_model()
    srv = DecodeServer(step_fn, make_cache, eos_id=V, max_seq_len=32,
                       max_slots=4, slot_ladder=[1, 2, 4],
                       len_ladder=[8, 16, 32], steps_per_tick=1,
                       name="grow")
    srv.warmup(configure_cache=False)
    resizes = []
    real = srv._pool.resize
    srv._pool.resize = lambda st, s, t: (resizes.append((s, t)),
                                         real(st, s, t))[1]
    try:
        with turn_held(srv) as run:
            first = srv.submit({"tokens": np.array([1], np.int32)},
                               max_new_tokens=6)
            run()  # seated at rung pair (1, 8), one step taken
            assert srv._pool.state_rungs(srv._state) == (1, 8)
            d0, n0 = _admits(srv)
            more = [srv.submit({"tokens": np.array([2, 3], np.int32)},
                               max_new_tokens=c) for c in (4, 20, 9)]
        outs = [r.result(timeout=60.0)[0].tolist() for r in more]
        assert outs == [[(4 + j) % V for j in range(c)] for c in (4, 20, 9)]
        assert first.result(timeout=60.0)[0].tolist() == [2, 3, 4, 5, 6, 7]
        # one slot -> four, length 8 -> 32: ONE move, ONE dispatch
        assert resizes == [(4, 32)]
        d1, n1 = _admits(srv)
        assert (d1 - d0, n1 - n0) == (1, 3)
        assert srv._pool.jit_cache_stats()["misses"] == 0
    finally:
        srv.stop(drain=False)


def test_fault_at_admit_fails_the_turns_requests_typed_and_keeps_serving():
    """An exception out of the turn's one admit: every request the turn
    popped fails with it (none is stranded in neither queue nor slot),
    the in-flight one fails, the pool is dropped — and the next request
    is served from a fresh pool."""
    step_fn, make_cache = chain_model()
    srv = DecodeServer(step_fn, make_cache, eos_id=V, max_seq_len=32,
                       max_slots=4, len_ladder=[32], steps_per_tick=1,
                       name="admit-fault")
    srv.warmup(configure_cache=False)
    real = srv._pool.admit
    calls = []

    def admit(state, slots, *args, **kw):
        calls.append(list(np.atleast_1d(slots)))
        if len(calls) == 2:
            raise RuntimeError("injected admit fault")
        return real(state, slots, *args, **kw)

    srv._pool.admit = admit
    try:
        failed0 = srv.metrics().get("failed", 0)
        with turn_held(srv) as run:
            flying = srv.submit({"tokens": np.array([1], np.int32)},
                                max_new_tokens=25)
            run()  # seated, one step taken
            doomed = [srv.submit({"tokens": np.array([i + 2], np.int32)},
                                 max_new_tokens=4) for i in range(3)]
        for r in doomed + [flying]:
            with pytest.raises(RuntimeError, match="injected admit fault"):
                r.result(timeout=60.0)
        assert calls[1] == [1, 2, 3]  # the three rode one call
        assert srv.metrics()["failed"] - failed0 == 4
        after = srv.submit({"tokens": np.array([4, 5], np.int32)},
                           max_new_tokens=3)
        assert after.result(timeout=60.0)[0].tolist() == [6, 7, 8]
        assert srv.metrics()["decode"]["admitted"] == 2  # flying + after
    finally:
        srv.stop(drain=False)


# ---------------------------------------------------------------------------
# numeric parity: slot pool vs the scalar cached step fn
# ---------------------------------------------------------------------------
def test_pooled_matches_scalar_step_fn_mixed_prompts(lm_state):
    """The whole slot-pool machinery — per-row positions, interleaved
    prefill/decode, rung growth, slot reuse — must reproduce the
    scalar cached path's greedy continuations exactly, for concurrent
    prompts of different lengths."""
    step_fn, make_cache = make_transformer_lm_pooled_step_fn(
        lm_state, LM_DIMS["vocab"], LM_DIMS["d_model"], LM_DIMS["n_layer"],
        LM_DIMS["n_head"], LM_DIMS["d_inner"])
    srv = DecodeServer(step_fn, make_cache, eos_id=EOS, max_seq_len=32,
                       max_slots=2, slot_ladder=[1, 2],
                       len_ladder=[16, 32], steps_per_tick=3, name="lm")
    srv.warmup(configure_cache=False)
    try:
        prompts = [[2, 3, 4], [5], [7, 8], [11, 12, 13, 14]]
        caps = [10, 6, 12, 8]
        reqs = [srv.submit({"tokens": np.array(p, np.int32)},
                           max_new_tokens=c)
                for p, c in zip(prompts, caps)]
        outs = [r.result(timeout=60.0)[0].tolist() for r in reqs]
        for p, c, got in zip(prompts, caps, outs):
            assert got == _ref_continuation(lm_state, p, len(p) + c), p
        assert srv._pool.jit_cache_stats()["misses"] == 0
    finally:
        srv.stop(drain=False)


# ---------------------------------------------------------------------------
# streaming over the wire
# ---------------------------------------------------------------------------
def test_wire_stream_loopback_chunks_and_one_trace_id(chain_server):
    from paddle_tpu.serving.wire.client import RemoteClient
    from paddle_tpu.serving.wire.codec import parse_traceparent
    from paddle_tpu.serving.wire.server import ServingProcess

    sp = ServingProcess(chain_server)
    host, port = sp.start()
    try:
        rc = RemoteClient((host, port))
        assert rc.healthz()["streaming"] is True
        chunks = list(rc.infer_stream(
            {"tokens": np.array([10], np.int32)}, max_new_tokens=12))
        got = [t for c in chunks for t in c.tolist()]
        assert got == expected_chain([10], 13)
        assert len(chunks) >= 2  # incremental, not one blob
        final = rc.last_stream_final
        assert final["chunks"] == len(chunks)
        # ONE trace id spans the whole stream: client mint == every
        # chunk's meta == the final message
        assert final["trace_id"] == rc.last_trace_id
        # raw message-level check: every chunk meta carries the id
        from paddle_tpu.serving.wire.client import wire_stream_open
        tid = monitor.new_trace_id()
        it, first = wire_stream_open(
            rc._transport, ["tokens"], [np.array([10], np.int32)],
            None, tid, extra_meta={"max_new_tokens": 6})
        metas = [first[0]] + [m for m, _ in it]
        assert all(m["trace_id"] == tid for m in metas)
        assert metas[-1]["final"] and not any(
            m.get("final") for m in metas[:-1])
        # unary /infer works against the decode endpoint too
        out, = rc.infer({"tokens": np.array([4, 5], np.int32)})
        assert out.tolist() == [6, 7, 8, 9]
        rc.close()
    finally:
        sp.stop()


def test_wire_stream_deadline_is_typed_end_to_end(chain_server):
    from paddle_tpu.serving.wire.client import RemoteClient
    from paddle_tpu.serving.wire.server import ServingProcess

    sp = ServingProcess(chain_server)
    host, port = sp.start()
    try:
        rc = RemoteClient((host, port))
        with pytest.raises(DeadlineExceeded):
            for _ in rc.infer_stream(
                    {"tokens": np.array([10], np.int32)},
                    timeout_ms=0.0001, max_new_tokens=14):
                pass
        rc.close()
    finally:
        sp.stop()


def test_wire_stream_closed_from_other_thread_keeps_conn_usable():
    """An abandoned fleet stream is close()d by a GC finalizer on
    whatever thread runs GC — the connection the stream was reading
    must be torn down BY OBJECT (a thread-local drop on the closing
    thread is a no-op), or the opening thread's next request reuses a
    half-read socket and desyncs."""
    from paddle_tpu.serving.wire.client import RemoteClient
    from paddle_tpu.serving.wire.server import ServingProcess

    # own server: ServingProcess.stop() stops the wrapped server, so
    # the shared chain fixture would arrive here already closed
    step_fn, make_cache = chain_model()
    srv = DecodeServer(step_fn, make_cache, eos_id=EOS, max_seq_len=16,
                       max_slots=4, steps_per_tick=2, name="chain-x")
    srv.warmup(configure_cache=False)
    sp = ServingProcess(srv)
    host, port = sp.start()
    try:
        rc = RemoteClient((host, port))
        it = rc.infer_stream({"tokens": np.array([2], np.int32)},
                             max_new_tokens=12)
        next(it)  # stream live: this thread's pooled body is half-read
        t = threading.Thread(target=it.close, daemon=True)
        t.start()
        t.join(WAIT)
        assert not t.is_alive()
        # the SAME thread that opened the stream must get a clean
        # exchange (auto-reopened conn, not the desynced one)
        out, = rc.infer({"tokens": np.array([4, 5], np.int32)})
        assert out.tolist() == [6, 7, 8, 9]
        rc.close()
    finally:
        sp.stop()


# ---------------------------------------------------------------------------
# the acceptance run: a real 2-child wire fleet
# ---------------------------------------------------------------------------
def test_decode_fleet_two_children_stream_and_zero_recompiles(
        tmp_path, lm_state):
    """ISSUE acceptance: a real 2-child fleet hosting a saved decode
    endpoint — fleet-wide warmup, then a mixed stream/unary storm with
    ZERO recompiles on both children (``/statusz`` jit cache is the
    ground truth), streamed tokens correct and each stream under one
    trace id."""
    from paddle_tpu.serving.wire.fleet import FleetBalancer

    d = str(tmp_path / "lm-endpoint")
    save_decode_endpoint(
        d, lm_state, vocab_size=LM_DIMS["vocab"],
        d_model=LM_DIMS["d_model"], n_layer=LM_DIMS["n_layer"],
        n_head=LM_DIMS["n_head"], d_inner=LM_DIMS["d_inner"], eos_id=EOS,
        max_seq_len=32, max_slots=2, steps_per_tick=3)
    fb = FleetBalancer.from_launch(d, 2, name="decode-fleet")
    try:
        fb.warmup()
        ref = _ref_continuation(lm_state, [2, 3, 4], 11)
        errs = []
        streamed = []

        def one(i):
            try:
                if i % 2:
                    chunks = list(fb.infer_stream(
                        {"tokens": np.array([2, 3, 4], np.int32)},
                        max_new_tokens=8))
                    streamed.append((
                        [t for c in chunks for t in c.tolist()],
                        len(chunks)))
                else:
                    p = [5] if i % 4 else [7, 8]
                    fb.infer({"tokens": np.array(p, np.int32)})
            except Exception as e:  # noqa: BLE001
                errs.append(e)

        threads = [threading.Thread(target=one, args=(i,), daemon=True)
                   for i in range(12)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(WAIT)
        assert not any(t.is_alive() for t in threads)
        assert not errs
        for got, n_chunks in streamed:
            assert got == ref
            assert n_chunks >= 2
        for be in fb._backends:
            st = be.transport.get_json("/statusz")
            assert st["jit_cache"]["misses"] == 0, st["jit_cache"]
        # abandoning a stream BEFORE its first next() must not leak the
        # backend's in-flight slot (a never-started generator skips its
        # finally; the GC finalizer releases instead)
        import gc

        gen = fb.infer_stream({"tokens": np.array([2], np.int32)},
                              max_new_tokens=4)
        assert sum(be.in_flight for be in fb._backends) == 1
        del gen
        gc.collect()
        deadline = time.monotonic() + 5.0
        while (time.monotonic() < deadline
               and any(be.in_flight for be in fb._backends)):
            time.sleep(0.02)
        assert all(be.in_flight == 0 for be in fb._backends)
    finally:
        fb.stop(shutdown_backends=True)


# ---------------------------------------------------------------------------
# chunked prefill and prefix snapshots, on a model small enough to check
# by arithmetic: what a builder DECLARES is what the pool and the
# scheduler do (the real builder: tests/test_sparse_linear_lm.py)
# ---------------------------------------------------------------------------
SUM_V, SUM_C = 13, 4


def running_sum_model(with_prefill=True):
    """next token = (sum of every token consumed so far) % V.  The sum
    is a RECURRENT leaf ``r`` (zero at position 0, kept for an idle row);
    ``z`` is a sequence leaf holding the consumed tokens and ``zz`` one
    that advances a row every 2 positions.  ``prefill_fn`` feeds
    ``SUM_C`` tokens of one row at once."""
    import jax
    import jax.numpy as jnp

    def step_fn(cache, tokens, ts):
        live, rows = ts >= 0, jnp.arange(tokens.shape[0])
        r = jnp.where(ts == 0, 0, cache["r"]) + tokens
        r = jnp.where(live, r, cache["r"])
        at = jnp.where(live, ts, cache["z"].shape[1])
        z = cache["z"].at[rows, at].set(tokens.astype("float32"),
                                        mode="drop")
        zz = cache["zz"].at[rows, jnp.where(live & (ts % 2 == 1), ts // 2,
                                            cache["zz"].shape[1])].set(
            tokens.astype("float32"), mode="drop")
        return jax.nn.one_hot(r % SUM_V, SUM_V) * 10.0, {
            "r": r, "z": z, "zz": zz}

    def make_cache(n_rows, seq_len):
        return {"r": jnp.zeros((n_rows,), "int32"),
                "z": jnp.zeros((n_rows, seq_len), "float32"),
                "zz": jnp.zeros((n_rows, seq_len // 2), "float32")}

    def prefill_fn(cache, row, tokens, start, n_valid):
        r0 = jnp.where(start == 0, 0, cache["r"][row])
        z = jax.lax.dynamic_update_slice(
            cache["z"], tokens.astype("float32")[None], (row, start))
        zz = jax.lax.dynamic_update_slice(
            cache["zz"], tokens.astype("float32")[None, 1::2],
            (row, start // 2))
        return {"r": cache["r"].at[row].set(r0 + tokens.sum()), "z": z,
                "zz": zz}

    prefill_fn.chunk_tokens = SUM_C
    declare(make_cache, CacheSpec(
        {"r": Leaf(), "z": Leaf(1), "zz": Leaf(1, stride=2)},
        prefill_fn=prefill_fn if with_prefill else None))
    return step_fn, make_cache


def _sum_chain(prompt, n):
    total, out = int(np.sum(prompt)), []
    for _ in range(n):
        out.append(total % SUM_V)
        total += out[-1]
    return out


def _sum_server(name, **kw):
    step_fn, make_cache = running_sum_model(kw.pop("with_prefill", True))
    return DecodeServer(step_fn, make_cache, eos_id=SUM_V, max_seq_len=32,
                        max_slots=2, slot_ladder=[2], len_ladder=[32],
                        steps_per_tick=2, name=name, **kw)


@pytest.mark.parametrize("n_prompt,chunks", [
    (3, 0), (SUM_C, 0), (SUM_C + 1, 1), (2 * SUM_C, 1), (3 * SUM_C + 2, 3)])
def test_prompts_are_prefilled_in_whole_chunks_and_the_rest_by_steps(
        n_prompt, chunks):
    """A chunk runs only while a whole one fits with a token to spare
    (the step that eats the last prompt token makes the first generated
    one); what it serves is what stepping serves."""
    prompt = np.arange(1, n_prompt + 1, dtype=np.int32) % SUM_V
    with _sum_server("sum-%d" % n_prompt) as srv:
        assert srv.warmup() == 4     # chunk, admit, release + prefill
        got = srv.submit({"tokens": prompt}, max_new_tokens=6).result(30)
        assert got[0].tolist() == _sum_chain(prompt, 6)
        m = srv.metrics()
        assert m["decode"]["prefill_chunks"] == chunks
        assert m["decode"]["prefill_tokens"] == n_prompt
        assert m["decode"]["generated_tokens"] == 6
        assert m["recompiles"] == 0


def test_a_builder_without_a_prefill_keeps_its_three_executables():
    with _sum_server("sum-plain", with_prefill=False) as srv:
        assert srv.warmup() == 3
        prompt = np.arange(1, 12, dtype=np.int32)
        got = srv.submit({"tokens": prompt}, max_new_tokens=5).result(30)
        assert got[0].tolist() == _sum_chain(prompt, 5)
        assert srv.metrics()["decode"]["prefill_chunks"] == 0
    step_fn, make_cache = running_sum_model(with_prefill=False)
    with pytest.raises(ValueError, match="CacheSpec.prefill_fn"):
        KVSlotPool(step_fn, make_cache, eos_id=SUM_V, max_slots=2,
                   max_seq_len=32, prefix=True)


def test_a_snapshot_carries_the_recurrent_leaf_to_the_next_request():
    """Two prompts share their first 8 tokens: the first is prefilled in
    chunks and leaves ONE snapshot at position 8 (its last whole chunk's
    end), the second starts there — its running sum installed, not
    rebuilt — and both serve the arithmetic's tokens.  A third, in the
    slot the second left, shares nothing and starts from zero."""
    head = np.array([3, 1, 4, 1, 5, 9, 2, 6], np.int32)
    a = np.concatenate([head, [5, 3]]).astype(np.int32)
    b = np.concatenate([head, [7]]).astype(np.int32)
    c = np.array([2, 2, 2], np.int32)
    with _sum_server("sum-snap", prefix_cache=1 << 20) as srv:
        assert srv.warmup() == 6     # + admit_prefix and snapshot
        for prompt, hits, chunks in ((a, 0, 2), (b, 1, 2), (c, 1, 2)):
            got = srv.submit({"tokens": prompt},
                             max_new_tokens=5).result(30)
            assert got[0].tolist() == _sum_chain(prompt, 5)
            m = srv.metrics()["decode"]
            assert m["prefix_cache"]["hits"] == hits
            assert m["prefill_chunks"] == chunks
        assert m["prefix_cache"]["entries"] == 1
        assert m["state_resets"] == 2      # a and c; b resumed a state
        assert m["prefill_tokens"] == len(a) + 1 + len(c)


def test_a_strided_leaf_is_installed_by_its_own_row_count():
    """The legacy (host rows) installation over a leaf that advances a
    row every 2 positions: a prefix of 6 positions is 3 of its rows."""
    import jax

    step_fn, make_cache = running_sum_model(with_prefill=False)
    plain = lambda n, t: {k: v for k, v in make_cache(n, t).items()
                          if k != "r"}
    declare(plain, CacheSpec({k: leaf for k, leaf in
                              spec_of(make_cache).leaves.items()
                              if k != "r"}))
    step = lambda cache, tok, ts: (
        jax.nn.one_hot(tok % SUM_V, SUM_V), cache)
    pool = KVSlotPool(step, plain, eos_id=SUM_V, max_slots=2,
                      max_seq_len=16, slot_ladder=[2], len_ladder=[16],
                      prefix=True)
    st = pool.alloc(2, 16)
    st["cache"]["z"][1, :8] = np.arange(8) + 1
    st["cache"]["zz"][1, :4] = np.arange(4) + 10
    kv = pool.extract_kv(st, 1, 6)
    assert kv[0].tolist() == [1, 2, 3, 4, 5, 6]
    assert kv[1].tolist() == [10, 11, 12]
    out = pool.admit_prefix(pool.alloc(2, 16), 0, np.arange(9), 9, 12,
                            [np.full(16, 7.0, "float32"),
                             np.full(8, 9.0, "float32")], 6)
    assert np.asarray(out["cache"]["z"])[0].tolist() == [7.0] * 6 + [0.0] * 10
    assert np.asarray(out["cache"]["zz"])[0].tolist() == [9.0] * 3 + [0.0] * 5
    assert int(np.asarray(out["pos"])[0]) == 6


# ---------------------------------------------------------------------------
# a scheduler turn's spans: one serving/decode_tick tiled by its phases
# ---------------------------------------------------------------------------
PHASES = ["serving/decode/" + p for p in (
    "admit_plan", "admit_dispatch", "prefill", "dispatch", "wait", "copy",
    "deliver")]


def _served_under_recording(srv, prompts, max_new):
    """Serve ``prompts`` with a span session live, stop the server (the
    scheduler thread has then closed every span it opened) and return
    the session's spans."""
    from paddle_tpu.monitor import spans as mon_spans

    mon_spans.start_recording()
    try:
        reqs = [srv.submit({"tokens": np.asarray(p, np.int32)},
                           max_new_tokens=max_new) for p in prompts]
        for r in reqs:
            r.result(timeout=60.0)
    finally:
        srv.stop()
        spans = mon_spans.stop_recording()
    return spans


def _assert_ticks_are_tiled(spans):
    """Every ``serving/decode_tick`` has children whose ``parent`` is
    its id, in the order the turn runs its phases, that do not overlap
    and cover it; returns the leaf names seen."""
    ticks = [s for s in spans if s["name"] == "serving/decode_tick"]
    assert ticks
    seen = set()
    for tick in ticks:
        kids = sorted((s for s in spans if s.get("parent") == tick["id"]
                       and s["name"].startswith("serving/decode/")),
                      key=lambda s: s["ts"])
        order = [PHASES.index(s["name"]) for s in kids]
        assert order == sorted(set(order)), [s["name"] for s in kids]
        assert {"serving/decode/dispatch", "serving/decode/wait",
                "serving/decode/copy", "serving/decode/deliver"} <= {
                    s["name"] for s in kids}
        # a ``ts`` is a wall-clock double: a quarter of a microsecond
        for a, b in zip(kids, kids[1:]):
            assert a["ts"] + a["dur"] <= b["ts"] + 1e-6
        assert kids[0]["ts"] >= tick["ts"] - 1e-6
        assert (kids[-1]["ts"] + kids[-1]["dur"]
                <= tick["ts"] + tick["dur"] + 1e-6)
        assert sum(s["dur"] for s in kids) >= 0.95 * tick["dur"]
        assert set(tick["args"]) == {"server", "active", "steps"}
        seen.update(s["name"] for s in kids)
    # a leaf is never recorded outside a tick
    ids = {t["id"] for t in ticks}
    assert all(s.get("parent") in ids for s in spans if s["name"] in PHASES)
    return seen


def test_a_traced_turn_is_one_tick_tiled_by_its_phases():
    step_fn, make_cache = chain_model()
    srv = DecodeServer(step_fn, make_cache, eos_id=EOS, max_seq_len=16,
                       max_slots=4, steps_per_tick=2, name="chain-spans")
    srv.warmup(configure_cache=False)
    spans = _served_under_recording(
        srv, [[10, 11], [12], [10, 11, 12], [13], [14, 15], [11]], 6)
    seen = _assert_ticks_are_tiled(spans)
    assert seen == set(PHASES) - {"serving/decode/prefill"}
    by = {}
    for s in spans:
        by.setdefault(s["name"], []).append(s.get("args", {}))
    assert sum(a["popped"] for a in by["serving/decode/admit_plan"]) == 6
    assert sum(a["seated"] for a in by["serving/decode/admit_dispatch"]) == 6
    assert all(a["cpu_s"] >= 0 for a in by["serving/decode/admit_plan"]
               + by["serving/decode/deliver"])
    assert sum(a["fresh_tokens"] for a in by["serving/decode/deliver"]) == 36
    assert sum(a["finished"] for a in by["serving/decode/deliver"]) == 6
    assert {a["kind"] for a in by["serving/decode/dispatch"]} == {"chunk"}
    assert all(a["bytes"] > 0 for a in by["serving/decode/copy"])


def test_a_chunked_builders_turn_has_a_prefill_phase():
    srv = _sum_server("sum-spans")
    assert srv.warmup() == 4
    prompt = np.arange(1, 3 * SUM_C + 3, dtype=np.int32) % SUM_V
    spans = _served_under_recording(srv, [prompt], 4)
    assert "serving/decode/prefill" in _assert_ticks_are_tiled(spans)
    chunks = [s["args"] for s in spans
              if s["name"] == "serving/decode/prefill"]
    assert [c["last"] for c in chunks] == [False, False, True]
    # every slot held: the turn was its prefill chunk, nothing stepped
    assert "none" in {s["args"]["kind"] for s in spans
                      if s["name"] == "serving/decode/dispatch"}


def counting_chain_model(experts=8):
    """The chain model with counts made on the device, declared as a
    routed-experts builder declares them (``CacheSpec.expert_stats``):
    every step counts, in ONE "expert layer", two pairs a live row, as
    many experts touched as rows are live (at most ``experts``), a peak
    of 2 and itself."""
    import jax
    import jax.numpy as jnp

    def step_fn(cache, tokens, ts):
        live = jnp.sum(ts >= 0).astype(jnp.int32)
        add = jnp.stack([2 * live, jnp.minimum(live, experts),
                         2 * (live > 0), 1 * (live > 0)]).astype(jnp.int32)
        logits = jax.nn.one_hot((tokens + 1) % V, V) * 10.0
        return logits, {"z": cache["z"], "counts": cache["counts"] + add}

    def make_cache(n_rows, seq_len):
        return {"z": jnp.zeros((n_rows, seq_len), "float32"),
                "counts": jnp.zeros((1, 4), jnp.int32)}

    declare(make_cache, CacheSpec(
        {"z": Leaf(1), "counts": Leaf()},
        expert_stats=lambda cache: cache["counts"], n_expert=experts))
    return step_fn, make_cache


@pytest.mark.parametrize("traced", [False, True])
def test_counts_a_builder_makes_on_the_device_reach_the_four_counters(
        traced):
    """What the steps count rides the tick's one fetch and lands, as
    deltas, in ``serving_decode_expert_*_total`` and ``metrics()``; a
    traced turn also puts experts touched a layer-step and the peak over
    the mean group on its ``deliver`` span."""
    from paddle_tpu.monitor import spans as mon_spans

    name = "chain-counts-%d" % traced
    step_fn, make_cache = counting_chain_model()
    # ONE rung pair: with the default ladders the first turn may run at
    # rung pair 1 x 8, whose view is 58 bytes and not the 312 held below
    srv = DecodeServer(step_fn, make_cache, eos_id=EOS, max_seq_len=16,
                       max_slots=4, slot_ladder=[4], len_ladder=[16],
                       steps_per_tick=2, name=name)
    srv.warmup(configure_cache=False)
    prompts = [[10, 11], [12], [10, 11, 12], [13]]
    if traced:
        spans = _served_under_recording(srv, prompts, 6)
    else:
        reqs = [srv.submit({"tokens": np.asarray(p, np.int32)},
                           max_new_tokens=6) for p in prompts]
        for r in reqs:
            r.result(timeout=60.0)
        spans = []
    m = srv.metrics()["decode"]
    srv.stop()
    # a row-step is a consumed position: prompt + generated - 1 each
    row_steps = sum(len(p) + 6 - 1 for p in prompts)
    assert m["expert_assignments"] == 2 * row_steps
    assert m["experts_touched"] == row_steps
    assert m["expert_peak_load"] == 2 * m["expert_layer_steps"] > 0
    for key in ("expert_assignments", "experts_touched", "expert_peak_load",
                "expert_layer_steps"):
        assert monitor.counter_value("serving_decode_%s_total" % key,
                                     server=name) == m[key]
    delivered = [s["args"] for s in spans
                 if s["name"] == "serving/decode/deliver"]
    assert bool(delivered) == traced
    for a in delivered:
        # 8 experts x a peak of 2 over 2 pairs a live row
        assert 1.0 <= a["experts_touched"] <= 4.0
        assert a["peak_over_mean"] == pytest.approx(
            8.0 / a["experts_touched"])
    if traced:
        copied = {s["args"]["bytes"] for s in spans
                  if s["name"] == "serving/decode/copy"}
        # the five arrays of the view, packed as int32, and the 16 bytes
        # of counts: ONE vector a turn
        s_, t_ = 4, 16
        assert copied == {s_ * t_ * 4 + 4 * s_ * 4 + 16}


def test_counts_start_again_with_a_fresh_pool_state(monkeypatch):
    """A server that dropped its idle pool allocates a zeroed state: the
    counts it then fetches are deltas from zero, not from what the
    dropped state had reached."""
    from paddle_tpu.serving import decode as decode_mod

    monkeypatch.setattr(decode_mod, "_IDLE_WAIT_S", 0.05)
    step_fn, make_cache = counting_chain_model()
    srv = DecodeServer(step_fn, make_cache, eos_id=EOS, max_seq_len=16,
                       max_slots=2, steps_per_tick=2, name="chain-recount")
    srv.warmup(configure_cache=False)
    try:
        for want in (1, 2):
            srv.submit({"tokens": np.array([10, 11], np.int32)},
                       max_new_tokens=5).result(timeout=60.0)
            m = srv.metrics()["decode"]
            assert m["expert_assignments"] == want * 2 * 6
            deadline = time.monotonic() + 30.0
            while (srv.metrics()["decode"]["idle_drops"] < want
                   and time.monotonic() < deadline):
                time.sleep(0.01)
            assert srv.metrics()["decode"]["idle_drops"] == want
    finally:
        srv.stop()


def _idle_server_dropped_its_pool(monkeypatch, name):
    """A served request, then no arrival for a (shortened) idle wait:
    returns the stopped server once it has dropped its pool state."""
    from paddle_tpu.serving import decode as decode_mod

    monkeypatch.setattr(decode_mod, "_IDLE_WAIT_S", 0.05)
    step_fn, make_cache = chain_model()
    srv = DecodeServer(step_fn, make_cache, eos_id=EOS, max_seq_len=16,
                       max_slots=2, steps_per_tick=2, name=name)
    srv.warmup(configure_cache=False)
    try:
        srv.submit({"tokens": np.array([10], np.int32)},
                   max_new_tokens=3).result(timeout=60.0)
        assert srv.metrics()["decode"]["kv_cache_bytes"] > 0
        deadline = time.monotonic() + 30.0
        while (monitor.counter_value("serving_decode_idle_drops_total",
                                     server=name) < 1
               and time.monotonic() < deadline):
            time.sleep(0.01)
        # the dropped server waits on: its next waits drop nothing
        time.sleep(0.15)
        drops = srv.metrics()["decode"]["idle_drops"]
        held = srv.metrics()["decode"]["kv_cache_bytes"]
    finally:
        srv.stop()
    return drops, held


def test_an_idle_server_counts_its_drop_and_leaves_an_event(monkeypatch):
    drops, held = _idle_server_dropped_its_pool(monkeypatch, "chain-idle")
    assert (drops, held) == (1, 0)
    (ev,) = [e for e in monitor.eventz()["events"]
             if e["kind"] == "serving/pool_dropped"
             and e["server"] == "chain-idle"]
    assert ev["severity"] == "info"
    # the chain model's one leaf, [1, 8] float32 at the smallest rungs
    assert ev["bytes"] == 32 and ev["idle_s"] >= 0.05


def test_an_empty_servers_wait_is_a_span_of_its_own(monkeypatch):
    from paddle_tpu.monitor import spans as mon_spans

    mon_spans.start_recording()
    try:
        _idle_server_dropped_its_pool(monkeypatch, "chain-idle-spans")
    finally:
        spans = mon_spans.stop_recording()
    waits = [s for s in spans if s["name"] == "serving/decode/idle_wait"
             and s["args"]["server"] == "chain-idle-spans"]
    assert waits and all("parent" not in s for s in waits)
    assert [s["args"]["dropped"] for s in waits].count(True) == 1
    # the drop's event is mirrored into the span stream under its wait
    (inst,) = [s for s in spans if s["name"] == "serving/pool_dropped"
               and s["args"]["server"] == "chain-idle-spans"]
    assert inst["parent"] in {s["id"] for s in waits}
    _assert_ticks_are_tiled(spans)


def test_the_phases_are_on_the_profilers_clock_and_the_tick_is_not(
        tmp_path):
    """Under ``jax.profiler.start_trace`` the leaves land on a Python
    thread's line of the ``/host:CPU`` plane (the clock the device's ops
    share); the enclosing ``serving/decode_tick`` must not, or a reader
    that names a device gap after the host event covering most of it
    would call every gap a tick."""
    import glob

    import jax
    from jax.profiler import ProfileData

    step_fn, make_cache = chain_model()
    srv = DecodeServer(step_fn, make_cache, eos_id=EOS, max_seq_len=16,
                       max_slots=2, steps_per_tick=2, name="chain-xplane")
    srv.warmup(configure_cache=False)
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(str(tmp_path), profiler_options=opts)
    try:
        spans = _served_under_recording(srv, [[10], [11, 12]], 5)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    on_python_lines, elsewhere = set(), set()
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            names = {ev.name for ev in line.events
                     if ev.name.startswith("serving/")}
            if plane.name == "/host:CPU" and line.name.startswith("python"):
                on_python_lines |= names
            else:
                elsewhere |= names
    assert set(PHASES) - {"serving/decode/prefill"} <= on_python_lines
    assert "serving/decode_tick" not in on_python_lines | elsewhere
    assert any(s["name"] == "serving/decode_tick" for s in spans)


def test_an_untraced_turn_reads_the_sink_once_and_one_clock(monkeypatch):
    """What tracing costs while nothing records, by COUNTING what the
    scheduler thread calls over N whole turns: one ``recording()`` and
    one ``perf_counter`` (the stamp of the turn's tokens) a turn, and no
    ``thread_time``, ``block_until_ready`` or ``TraceAnnotation`` at
    all.  (Before the phases were spans a turn read the sink twice and
    the clock twice, the second for a span nobody recorded.)"""
    import types

    import jax

    from paddle_tpu.monitor import spans as mon_spans
    from paddle_tpu.serving import decode as decode_mod

    step_fn, make_cache = chain_model()
    srv = DecodeServer(step_fn, make_cache, eos_id=EOS, max_seq_len=16,
                       max_slots=4, steps_per_tick=2, name="chain-count")
    srv.warmup(configure_cache=False)
    calls = {}

    def counted(name, fn):
        def wrapper(*a, **kw):
            if threading.current_thread() is srv._worker:
                calls[name] = calls.get(name, 0) + 1
            return fn(*a, **kw)
        return wrapper

    # decode.py's own clock reads: its ``time`` alone is wrapped, so the
    # admission queue's and the metrics' reads are not in the count
    monkeypatch.setattr(decode_mod, "time", types.SimpleNamespace(
        perf_counter=counted("perf_counter", time.perf_counter),
        thread_time=counted("thread_time", time.thread_time),
        monotonic=time.monotonic))
    monkeypatch.setattr(mon_spans, "recording",
                        counted("recording", mon_spans.recording))
    monkeypatch.setattr(jax, "block_until_ready",
                        counted("block_until_ready", jax.block_until_ready))
    monkeypatch.setattr(jax.profiler, "TraceAnnotation", counted(
        "TraceAnnotation", jax.profiler.TraceAnnotation))
    ticks = counted("tick", srv._tick)
    srv._tick = ticks
    n = 5
    try:
        assert not mon_spans.recording()
        with turn_held(srv) as run:
            reqs = [srv.submit({"tokens": np.array(p, np.int32)},
                               max_new_tokens=14) for p in ([10], [11, 12])]
            run(1)          # seated; from here every turn is a whole one
            calls.clear()
            run(n)
            got = dict(calls)
        for r in reqs:
            r.result(timeout=60.0)
    finally:
        srv.stop(drain=False)
    assert got == {"tick": n, "recording": n, "perf_counter": n}


# ---------------------------------------------------------------------------
# run-ahead of depth one: a full pool with nothing to decide keeps ONE
# chunk queued behind the one that runs (DecodeServer._why_serial)
# ---------------------------------------------------------------------------
def _pin_serial(monkeypatch):
    """Every turn waits for its chunk with nothing queued: the parent's
    scheduler."""
    monkeypatch.setattr(DecodeServer, "_why_serial",
                        lambda self, flight: "free_seat")


def _turns(srv):
    """(ticks, chunks dispatched ahead, serial turns by reason), from the
    series' own children: they outlive the server's ``stop``."""
    return (int(srv._ticks_c.value), int(srv._ahead_c.value),
            {r: int(c.value) for r, c in srv._sync_cs.items()})


def _assert_turns_add_up(srv):
    ticks, ahead, sync = _turns(srv)
    assert ahead + sum(sync.values()) == ticks
    d = srv.metrics()["decode"]
    assert (d["ticks"], d["chunks_ahead"], d["sync_turns"]) == (
        ticks, ahead, sync)
    assert d["ahead_share"] == (ahead / ticks if ticks else 0.0)
    return ticks, ahead, sync


def _four_slot_chain(name, **kw):
    step_fn, make_cache = chain_model()
    srv = DecodeServer(step_fn, make_cache, eos_id=EOS, max_seq_len=16,
                       max_slots=4, slot_ladder=[4], len_ladder=[16],
                       steps_per_tick=2, name=name, **kw)
    srv.warmup(configure_cache=False)
    return srv


def _ask(srv, prompt, n, **kw):
    return srv.submit({"tokens": np.asarray(prompt, np.int32)},
                      max_new_tokens=n, **kw)


def _streamed(req):
    """Every token pushed to ``req`` so far (a failed request's too)."""
    out = []
    while not req._chunks.empty():
        kind, val = req._chunks.get_nowait()
        if kind == "tokens":
            out.extend(val.tolist())
    return out


def _lm_server(lm_state, name, speculative=None, eos=EOS, **kw):
    step_fn, make_cache = make_transformer_lm_pooled_step_fn(
        lm_state, LM_DIMS["vocab"], LM_DIMS["d_model"], LM_DIMS["n_layer"],
        LM_DIMS["n_head"], LM_DIMS["d_inner"])
    return DecodeServer(step_fn, make_cache, eos_id=eos, max_seq_len=32,
                        max_slots=4, slot_ladder=[4], len_ladder=[32],
                        steps_per_tick=2, name=name, speculative=speculative,
                        **kw)


def _draft_model_server(lm_state, name, eos=EOS):
    from paddle_tpu.serving.speculative import make_lm_speculative

    draft = dict(d_model=8, n_layer=1, n_head=1, d_inner=16)
    dims = {k: LM_DIMS[k] for k in ("d_model", "n_layer", "n_head",
                                    "d_inner")}
    return _lm_server(lm_state, name, eos=eos, speculative=make_lm_speculative(
        lm_state, vocab_size=V, draft_state=lm_weights(
            np.random.RandomState(8), vocab=V, max_pos=LM_DIMS["max_pos"],
            name="draft", **draft),
        k=3, **dims, **{"draft_" + k: v for k, v in draft.items()}))


def _self_draft_server(name, eos):
    from paddle_tpu import decoding
    from paddle_tpu.serving.speculative import make_self_draft
    from test_k_exaone_lm import CHUNK, tiny_cfg, weights

    cfg = tiny_cfg()
    step, make_cache, _ = decoding.make_mtp_routed_lm_pooled_step_fn(
        weights(cfg, seed=4), cfg, kv_dtype="fp32", prefill_tokens=CHUNK)
    return DecodeServer(
        step, make_cache, eos_id=eos, max_seq_len=64, max_slots=4,
        slot_ladder=(4,), len_ladder=(64,), steps_per_tick=2,
        queue_capacity=64, target_queue_wait_ms=600000.0, kv_dtype="fp32",
        name=name, speculative=make_self_draft(make_cache))


def _served(srv, prompts, caps, **kw):
    """Every request's tokens and kept proposals, and the turns' counts."""
    try:
        srv.warmup(configure_cache=False)
        reqs = [_ask(srv, p, c, **kw) for p, c in zip(prompts, caps)]
        got = [np.concatenate(r.result(timeout=WAIT)).tolist() for r in reqs]
        turns = _assert_turns_add_up(srv)
    finally:
        srv.stop(drain=False, timeout=WAIT)
    return got, [None if r.draft_tokens is None else r.draft_tokens.tolist()
                 for r in reqs], turns


@pytest.mark.parametrize("kind", ["plain", "draft_model", "self_draft"])
def test_run_ahead_serves_token_for_token_what_the_serial_turn_serves(
        kind, lm_state, monkeypatch):
    """Ten requests through four slots — lengths that finish at
    different turns, an EOS here and there found one chunk late — under
    run-ahead and under a server pinned serial: the same tokens and,
    where a self-drafting round keeps them, the same proposals."""
    rng = np.random.RandomState(3)
    vocab = 97 if kind == "self_draft" else V
    prompts = [rng.randint(0, vocab, int(n)).astype(np.int32)
               for n in rng.randint(2, 11, 10)]
    caps = [9, 14, 20, 11, 16, 7, 18, 12, 10, 15]
    kw = {}
    if kind == "plain":
        make = lambda name, eos: _lm_server(lm_state, name, eos=eos)
    elif kind == "draft_model":
        make = lambda name, eos: _draft_model_server(lm_state, name, eos)
        kw = dict(speculative=True)
    else:
        make = _self_draft_server
        kw = dict(speculative=True, keep_drafts=True)
    with monkeypatch.context() as pinned:
        _pin_serial(pinned)
        # no token ends a request of a random model by itself: take one
        # the longest answer holds half-way
        probe, _, _ = _served(make("ahead-probe-" + kind, vocab), prompts,
                              caps, **kw)
        eos = probe[2][len(probe[2]) // 2]
        want, want_drafts, (ticks, ahead, sync) = _served(
            make("ahead-%s-serial" % kind, eos), prompts, caps, **kw)
        assert ahead == 0 and sync["free_seat"] == ticks > 0
    got, got_drafts, (ticks, ahead, _) = _served(
        make("ahead-%s" % kind, eos), prompts, caps, **kw)
    assert got == want and got_drafts == want_drafts
    assert 0 < ahead < ticks
    # an EOS ended a request early: a finish no length predicts
    assert any(len(g) < c for g, c in zip(got, caps))
    if kind == "self_draft":
        assert all(len(d) == len(g) for d, g in zip(got_drafts, got))


@pytest.mark.parametrize("how", ["eos", "deadline", "abandoned"])
def test_a_slot_freed_behind_a_queued_chunk_is_freed_a_turn_late_and_leaks_nothing(
        how):
    """Slot 0's request ends inside a chunk that has another queued
    behind it — an EOS, an expired deadline, a stream given up: the host
    finds it when the older view is read, the slot sat through the
    queued chunk, and the request seated into it next is served ITS
    tokens from the first chunk dispatched after its seat — never the
    queued chunk's row, which is the old request's."""
    srv = _four_slot_chain("ahead-freed-" + how)
    try:
        with turn_held(srv) as run:
            first = _ask(srv, [5] if how == "eos" else [10], 10,
                         timeout_ms=None if how == "eos" else 600000.0)
            rest = [_ask(srv, [10], 10) for _ in range(3)]
            late = _ask(srv, [12], 4)            # queued: no seat for it
            run(1)          # chunk 1 read, chunk 2 queued behind it
            assert srv._flight is not None and _turns(srv)[:2] == (1, 1)
            if how == "deadline":
                first.deadline = time.monotonic() - 1.0
            elif how == "abandoned":
                first.fail(ServingError("the caller went away"))
            run(1)          # chunk 2 read (it ends the first), 3 queued
            assert first.done() and srv._slots[0] is None
            assert srv._flight is not None and _turns(srv)[:2] == (2, 2)
            assert _streamed(first) == (
                [6, 7, 8, 9] if how == "eos" else [11, 12, 13, 14])
            run(1)          # late seated behind chunk 3; its row is old
            assert srv._slots[0].req is late and late.first_token_t is None
            assert _turns(srv)[:2] == (3, 3)
            run(1)          # chunk 4: the first that stepped late
            assert _streamed(late) == [13, 14]
        assert late.result(timeout=WAIT)[0].tolist() == [13, 14, 15, 16]
        for r in rest:
            assert r.result(timeout=WAIT)[0].tolist() == expected_chain(
                [10], 11)
        if how == "eos":
            assert first.result(timeout=WAIT)[0].tolist() == [6, 7, 8, 9]
        else:
            with pytest.raises(DeadlineExceeded if how == "deadline"
                               else ServingError):
                first.result(timeout=WAIT)
        # what the first request's slot stepped in chunk 3 reached nobody
        assert _streamed(first) == []
        m = srv.metrics()["decode"]
        assert m["generated_tokens"] == 3 * 10 + 4 + 4
        _assert_turns_add_up(srv)
    finally:
        srv.stop(drain=False, timeout=WAIT)


@pytest.mark.parametrize("where", ["dispatch", "materialisation"])
def test_a_failure_with_a_chunk_queued_fails_every_seat_typed_and_heals(
        where, monkeypatch):
    """A fault at ``decode.step`` as the next chunk is dispatched behind
    a running one, and an error that surfaces when a view is read with a
    chunk queued behind it: every seated request fails typed, the queued
    view is dropped with the pool, and the server serves on."""
    import jax

    from paddle_tpu import faults

    srv = _four_slot_chain("ahead-fails-" + where)
    real_get, boom = jax.device_get, []

    def failing_get(x):
        if boom and threading.current_thread() is srv._worker:
            raise boom.pop()
        return real_get(x)

    monkeypatch.setattr(jax, "device_get", failing_get)
    try:
        with turn_held(srv) as run:
            reqs = [_ask(srv, [10], 10) for _ in range(4)]
            run(1)
            assert srv._flight is not None
            if where == "dispatch":
                faults.arm("decode.step=error:RuntimeError,times=1")
            else:
                boom.append(RuntimeError("the chunk failed on the device"))
            run(1)
            assert srv._flight is None and srv._state is None
        for r in reqs:
            with pytest.raises(RuntimeError):
                r.result(timeout=WAIT)
            assert _streamed(r) == [11, 12]
        assert srv.metrics()["failed"] == 4
        assert _ask(srv, [4, 5], 8).result(timeout=WAIT)[0].tolist() == [
            6, 7, 8, 9]
        assert srv.metrics().get("recompiles", 0) == 0
        _assert_turns_add_up(srv)
    finally:
        faults.disarm()
        srv.stop(drain=False, timeout=WAIT)


@pytest.mark.parametrize("drain", [True, False])
def test_stop_with_a_chunk_queued_delivers_it_or_drops_it(drain):
    """Four requests end by EOS in chunk 2 with chunk 3 queued behind
    it: a draining stop reads chunk 3's view (nobody's rows: the turns
    still add up) before its loop returns; an abort drops the queued
    view and does not hang."""
    srv = _four_slot_chain("ahead-stop-%d" % drain)
    with turn_held(srv) as run:
        reqs = [_ask(srv, [5], 10) for _ in range(4)]
        run(2 if drain else 1)
        assert srv._flight is not None
    srv.stop(drain=drain, timeout=WAIT)
    assert not srv._worker.is_alive() and srv._flight is None
    if drain:
        for r in reqs:
            assert r.result(timeout=WAIT)[0].tolist() == [6, 7, 8, 9]
        assert _turns(srv) == (3, 2, {"free_seat": 1, "held": 0,
                                      "length_finish": 0, "memory": 0})
    else:
        for r in reqs:
            with pytest.raises(ServerClosed):
                r.result(timeout=WAIT)


@pytest.mark.parametrize("reason", ["free_seat", "held", "length_finish",
                                    "memory"])
def test_each_reason_keeps_the_turn_serial_in_the_scenario_built_for_it(
        reason, monkeypatch):
    """One free seat; a held slot of a chunked builder; a request one
    token from its length; no free byte on the device: the first turn of
    each waits with nothing queued and says why, and the turns add up."""
    from paddle_tpu.serving import decode as decode_mod

    if reason == "held":
        srv = _sum_server("ahead-why-held")
        srv.warmup(configure_cache=False)
        asks = [(np.arange(1, 3 * SUM_C + 3) % SUM_V, 12), ([1, 2, 3], 20)]
    else:
        srv = _four_slot_chain("ahead-why-" + reason)
        asks = [([10], 10)] * 4
        if reason == "free_seat":
            asks = asks[:3]
        elif reason == "length_finish":
            asks = [([10], 3)] + asks[1:]     # its second chunk ends it
        else:
            monkeypatch.setattr(decode_mod, "_device_free_bytes",
                                lambda array: 0)
    others = [r for r in decode_mod.SYNC_REASONS if r != reason]
    try:
        with turn_held(srv) as run:
            reqs = [_ask(srv, p, n) for p, n in asks]
            run(2 if reason == "length_finish" else 1)
            ticks, ahead, sync = _turns(srv)
            if reason == "length_finish":
                # three tokens to go: the first chunk could not end it
                assert (ticks, ahead, sync[reason]) == (2, 1, 1)
            else:
                assert (ticks, ahead, sync[reason]) == (1, 0, 1)
            assert srv._flight is None
            assert not any(sync[r] for r in others)
        for r in reqs:
            r.result(timeout=WAIT)
        ticks, ahead, sync = _assert_turns_add_up(srv)
        if reason in ("free_seat", "memory"):
            assert ahead == 0
    finally:
        srv.stop(drain=False, timeout=WAIT)


def test_a_turn_ahead_is_one_tick_tiled_by_the_same_leaves():
    """Four long answers through four slots under a span sink: every
    turn is ONE ``serving/decode_tick`` tiled by ONE leaf a phase; its
    ``dispatch`` says whether a chunk was queued behind the one the turn
    waited for (``ahead``) and how many executables it launched — two
    where a run of turns ahead begins, none where it ends, and one a
    tick in all."""
    srv = _four_slot_chain("ahead-spans")
    spans = _served_under_recording(srv, [[10]] * 4, 11)
    _assert_ticks_are_tiled(spans)
    ticks, ahead, _ = _turns(srv)
    launched = [s["args"] for s in spans
                if s["name"] == "serving/decode/dispatch"]
    assert len(launched) == ticks
    assert sum(a["ahead"] for a in launched) == ahead > 0
    assert sum(a["chunks"] for a in launched) == ticks
    assert {a["chunks"] for a in launched} == {0, 1, 2}
    assert {a["kind"] for a in launched} == {"chunk"}
    # the view a turn ahead reads is the OLDER chunk's: its tokens reach
    # the callers one turn after the chunk that made them was launched
    fresh = [s["args"]["fresh_tokens"] for s in spans
             if s["name"] == "serving/decode/deliver"]
    assert sum(fresh) == 4 * 11 and max(fresh) == 4 * 2


def test_a_prefix_kept_from_a_slot_freed_a_chunk_late_serves_the_same_tokens(
        lm_state, monkeypatch):
    """A stream given up with a chunk queued behind the one running: the
    slot's prefix K/V is read out of a state one chunk on (rows below
    the positions the older view counted are what they were), and the
    request seated over it is served what a server with no prefix cache
    serves."""
    from paddle_tpu.serving.prefix_cache import PrefixKVCache

    rng = np.random.RandomState(11)
    head = rng.randint(10, V, 8).astype(np.int32)      # no EOS in it
    asks = [(np.concatenate([head, [10 + i]]), 16) for i in range(4)]
    again = (np.concatenate([head, [17, 18]]), 12)
    with monkeypatch.context() as pinned:
        _pin_serial(pinned)
        want, _, _ = _served(_lm_server(lm_state, "ahead-prefix-serial"),
                             [again[0]], [again[1]])
    cache = PrefixKVCache(capacity_bytes=1 << 20, block_tokens=4,
                          name="ahead-prefix")
    srv = _lm_server(lm_state, "ahead-prefix", prefix_cache=cache)
    srv.warmup(configure_cache=False)
    try:
        with turn_held(srv) as run:
            reqs = [_ask(srv, p, n) for p, n in asks]
            run(1)
            for _ in range(8):       # until a chunk is queued behind one
                if srv._flight is not None:
                    break
                run(1)
            assert srv._flight is not None
            reqs[0].fail(ServingError("the caller went away"))
            run(1)
            assert srv._slots[0] is None and cache.stats()["entries"] == 1
            late = _ask(srv, *again)
            run(1)
            assert srv._slots[0].req is late
        assert np.concatenate(late.result(timeout=WAIT)).tolist() == want[0]
        assert cache.stats()["hits"] == 1
        _assert_turns_add_up(srv)
    finally:
        srv.stop(drain=False, timeout=WAIT)


def test_the_run_ahead_probe_rehearses_and_both_orders_read_the_same_views():
    """``tools/time_run_ahead.py`` (what a chunk queued behind a running
    one costs the device and saves the turn) runs to its end at a cell's
    tiny sizes: the views read after the next dispatch are the serial
    order's, and a rehearsal prints no time."""
    import json
    import os
    import subprocess
    import sys

    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    proc = subprocess.run(
        [sys.executable, os.path.join(root, "tools", "time_run_ahead.py"),
         "gpt1_117m", "--rehearse-cpu", "--rounds", "4"],
        cwd=root, capture_output=True, text=True, timeout=240)
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    assert lines[-2].startswith("REHEARSAL")
    out = json.loads(lines[-1])
    assert out["views_equal_in_both_orders"] is True
    assert set(out["orders"]) == {"serial", "ahead", "serial_again",
                                  "ahead_again"}
    assert all(set(row) == {"views_sha1"} for row in out["orders"].values())
    assert out["memory"]["queued_bytes_by_memory_analysis"] > 0
