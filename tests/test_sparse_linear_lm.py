"""The ``minicpm_sala`` serving path at tiny sizes on the CPU: lightning
linear-attention layers beside block-sparse attention layers, through
``decoding.make_sparse_linear_lm_pooled_step_fn`` -> ``KVSlotPool`` ->
``DecodeServer``, held to the plain reference beside the benchmark's
configuration (``benchmark/configs/minicpm_sala_reference.py``: float32,
a scan over time, the selection rule as a dense mask, no cache)."""
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import decoding
from paddle_tpu import sparse_linear_lm as sl
from paddle_tpu.serving.decode import DecodeServer
from paddle_tpu.serving.kv_pool import KVSlotPool

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SPARSE_CFG = dict(kernel_size=4, kernel_stride=2, init_blocks=1,
                  block_size=8, window_size=16, topk=2, dense_len=24)
CFG = dict(
    vocab_size=97, hidden_size=32, num_hidden_layers=4,
    mixer_types=["minicpm4", "lightning-attn", "lightning-attn", "minicpm4"],
    num_attention_heads=4, num_key_value_heads=2, head_dim=8,
    intermediate_size=64, lightning_nh=4, lightning_nkv=4,
    lightning_head_dim=8, rms_norm_eps=1e-6, rope_theta=10000, scale_emb=12,
    scale_depth=1.4, mup_denominator=32, dim_model_base=16,
    sparse_config=SPARSE_CFG)
VOCAB, T, C = 97, 128, 16


@pytest.fixture(scope="module")
def ref():
    spec = importlib.util.spec_from_file_location(
        "minicpm_sala_reference", os.path.join(
            ROOT, "benchmark", "configs", "minicpm_sala_reference.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


@pytest.fixture(scope="module")
def weights():
    return sl.random_state(np.random.RandomState(0), CFG, std=0.3,
                           sparse_q_norm=3.0)


@pytest.fixture(scope="module")
def built(weights):
    with jax.default_matmul_precision("highest"):
        step, make_cache, prefill = \
            decoding.make_sparse_linear_lm_pooled_step_fn(
                weights, CFG, kv_dtype="fp32", prefill_tokens=C)
    return jax.jit(step), make_cache, jax.jit(prefill)


def _tokens(seed, n):
    return np.random.RandomState(seed).randint(0, VOCAB, n).astype(np.int32)


# -- the parts --------------------------------------------------------------
def test_pooled_steps_equal_the_reference_forward(ref, weights, built):
    """Rows at their own positions, one idle: logits of every step
    against the reference's full forward, past ``dense_len`` so that
    the selection decides what is read."""
    step, make_cache, _ = built
    a, b = _tokens(1, 96), _tokens(2, 64)
    with jax.default_matmul_precision("highest"):
        want_a = np.asarray(ref.forward(weights, jnp.asarray(a), CFG,
                                        query_block=32))
        want_b = np.asarray(ref.forward(weights, jnp.asarray(b), CFG,
                                        query_block=32))
        cache = make_cache(3, T)
        for t in range(96):
            tb = t - 20   # row 1 starts 20 steps later, row 2 stays idle
            live_b = 0 <= tb < 64
            lg, cache = step(
                cache, jnp.asarray([a[t], b[tb] if live_b else 0, 5],
                                   jnp.int32),
                jnp.asarray([t, tb if live_b else -1, -1], jnp.int32))
            np.testing.assert_allclose(lg[0], want_a[t], atol=2e-5)
            if live_b:
                np.testing.assert_allclose(lg[1], want_b[tb], atol=2e-5)


@pytest.mark.parametrize("n_valid", [C, 11, 1])
def test_lightning_chunk_equals_steps(n_valid):
    d = sl.dims(CFG)
    rng = np.random.RandomState(3)
    q, k, v = (jnp.asarray(rng.randn(C, 4, 8), jnp.float32)
               for _ in range(3))
    s0 = jnp.asarray(rng.randn(4, 8, 8), jnp.float32)
    o_chunk, s_chunk = sl.lightning_chunk(q, k, v, s0, n_valid, d)
    s, outs = s0[None], []
    for i in range(n_valid):
        # a position past 0: the state is carried, not restarted
        o, s = sl.lightning_step(q[i][None], k[i][None], v[i][None], s,
                                 jnp.asarray([5 + i]), d)
        outs.append(o[0])
    np.testing.assert_allclose(o_chunk[:n_valid], jnp.stack(outs),
                               rtol=2e-5, atol=2e-5)
    np.testing.assert_allclose(s_chunk, s[0], rtol=2e-5, atol=2e-5)


def test_lightning_step_resets_at_position_zero_and_keeps_idle_rows():
    d = sl.dims(CFG)
    rng = np.random.RandomState(4)
    q, k, v = (jnp.asarray(rng.randn(3, 4, 8), jnp.float32)
               for _ in range(3))
    s = jnp.asarray(rng.randn(3, 4, 8, 8), jnp.float32)
    _, out = sl.lightning_step(q, k, v, s, jnp.asarray([0, 7, -1]), d)
    np.testing.assert_allclose(out[0], k[0][:, :, None] * v[0][:, None, :],
                               rtol=1e-6)
    assert not np.allclose(out[1], k[1][:, :, None] * v[1][:, None, :])
    np.testing.assert_array_equal(out[2], s[2])


def _literal_selection(q, ck, t, d):
    """Steps 1-2 as loops: the set of blocks one query reads, per K/V
    head, or None for a dense read."""
    n = t + 1
    if n <= d.dense_len:
        return None
    out = []
    for g in range(q.shape[0]):
        n_done = (n - d.kernel_size) // d.kernel_stride + 1
        c = ck[:n_done, g * q.shape[2]:(g + 1) * q.shape[2]]
        rel = {}
        for h in range(q.shape[1]):
            sc = c @ q[g, h] / np.sqrt(q.shape[2])
            p = np.exp(sc - sc.max())
            p /= p.sum()
            for blk in range(t // d.block_size + 1):
                best = 0.0
                for j in range(n_done):
                    lo, hi = j * d.kernel_stride, j * d.kernel_stride \
                        + d.kernel_size
                    if lo < (blk + 1) * d.block_size and hi > \
                            blk * d.block_size:
                        best = max(best, p[j])
                rel[blk] = rel.get(blk, 0.0) + best
        forced = {blk for blk in rel if blk < d.init_blocks
                  or (blk + 1) * d.block_size > n - d.window_size}
        rest = sorted((blk for blk in rel if blk not in forced),
                      key=lambda blk: (-rel[blk], blk))
        out.append(forced | set(rest[:d.topk]))
    return out


@pytest.mark.parametrize("t", [9, 23, 24, 25, 40, 43, 46, 63, 90, 127])
def test_select_blocks_against_a_literal_loop(t):
    """Contexts below, at (n = 24) and above ``dense_len``; 43 and 46
    end mid-block and mid-kernel; every one past 24 has forced blocks
    (the first, the window's)."""
    d = sl.dims(CFG)
    rng = np.random.RandomState(100 + t)
    q = rng.randn(2, 2, 8).astype("float32") * 3.0
    k = rng.randn(T, 16).astype("float32")
    ck = np.asarray(sl.compress_keys(jnp.asarray(k), d))
    ck = np.concatenate([ck, np.zeros((T // 2 - len(ck), 16), "float32")])
    blocks, valid, dense = sl.select_blocks(
        jnp.asarray(q)[None], jnp.asarray(ck)[None], jnp.asarray([t]), d)
    want = _literal_selection(q, ck, t, d)
    if want is None:
        assert bool(dense[0])
        return
    assert not bool(dense[0])
    for g in range(2):
        got = [int(b) for b, ok in zip(blocks[0, g], valid[0, g]) if ok]
        assert len(got) == len(set(got)), "a block named twice"
        assert set(got) == want[g], (t, g)
    assert int(sl.selected_positions(t + 1, d)) == sum(
        min((b + 1) * d.block_size, t + 1) - b * d.block_size
        for b in want[0])


@pytest.mark.parametrize("t", [9, 25, 46, 90, 127])
def test_forced_runs_are_what_select_blocks_lists_them_as(t):
    """What the step declares to the block read (``forced_runs``) holds
    of the lists it hands over: in each run every head's entries are
    alike, and the valid ones lie next to each other and name
    consecutive blocks in rising order — at the rung's end too (127),
    where the ids are clamped, and where the window meets the first
    block (9, 25)."""
    d = sl.dims(CFG)
    rng = np.random.RandomState(200 + t)
    q = jnp.asarray(rng.randn(1, 2, 2, 8), jnp.float32)
    ck = jnp.asarray(rng.randn(1, T // 2, 16), jnp.float32)
    blocks, valid, _ = sl.select_blocks(q, ck, jnp.asarray([t]), d)
    blocks, valid = np.asarray(blocks)[0], np.asarray(valid)[0]
    runs = sl.forced_runs(d)
    assert runs == ((0, 1), (1, 3))
    assert sum(n for _, n in runs) + d.topk == blocks.shape[-1]
    for a, n in runs:
        np.testing.assert_array_equal(blocks[0, a:a + n], blocks[1, a:a + n])
        np.testing.assert_array_equal(valid[0, a:a + n], valid[1, a:a + n])
        at = np.flatnonzero(valid[0, a:a + n])
        if len(at):
            assert at.tolist() == list(range(at[0], at[-1] + 1))
            ids = blocks[0, a:a + n][at]
            assert ids.tolist() == list(range(ids[0], ids[0] + len(at)))


def test_compressed_keys_are_written_as_kernels_complete(built):
    """The step's ``ck`` leaf against compress_keys over the K rows it
    cached: row j exists once position stride * j + kernel - 1 is in."""
    step, make_cache, _ = built
    d = sl.dims(CFG)
    toks = _tokens(5, 37)
    cache = make_cache(1, T)
    for t in range(37):
        _, cache = step(cache, jnp.asarray([toks[t]]), jnp.asarray([t]))
    for layer in (0, 3):
        k = cache[layer]["k"][0, :37]
        want = sl.compress_keys(k, d)
        n = (37 - d.kernel_size) // d.kernel_stride + 1
        np.testing.assert_allclose(cache[layer]["ck"][0, :n], want[:n],
                                   atol=1e-6)
        assert not np.asarray(cache[layer]["ck"][0, n:]).any()


@pytest.mark.parametrize("poison", [0.0, 1.0])
def test_chunked_prefill_equals_stepping_leaf_for_leaf(built, poison):
    """80 prompt tokens in five chunks against 80 one-token steps, into
    a slot that held something else before."""
    step, make_cache, prefill = built
    toks = _tokens(6, 80)
    with jax.default_matmul_precision("highest"):
        chunked = jax.tree.map(lambda a: a + poison, make_cache(2, T))
        for st in range(0, 80, C):
            chunked = prefill(chunked, jnp.int32(1),
                              jnp.asarray(toks[st:st + C]), jnp.int32(st),
                              jnp.int32(C))
        stepped = make_cache(2, T)
        for t in range(80):
            _, stepped = step(stepped, jnp.asarray([0, toks[t]], jnp.int32),
                              jnp.asarray([-1, t], jnp.int32))
    n_ck = (80 - 4) // 2 + 1
    for a, b in zip(chunked, stepped):
        for name in a:
            rows = {"k": 80, "v": 80, "ck": n_ck}.get(name)
            x, y = np.asarray(a[name][1]), np.asarray(b[name][1])
            np.testing.assert_allclose(x[:rows], y[:rows], rtol=1e-4,
                                       atol=1e-4)
            # the other slot is not touched
            np.testing.assert_array_equal(a[name][0], poison + 0 * x)


def test_block_read_equals_the_masked_read_when_told_every_block():
    """``grouped_block_decode_attention`` over ALL live blocks is
    ``grouped_masked_decode_attention``: the two readings of the same
    leaves agree where they are asked the same thing."""
    from paddle_tpu import decode_attention as da

    rng = np.random.RandomState(7)
    kv = {"k": jnp.asarray(rng.randn(3, 64, 16), jnp.float32),
          "v": jnp.asarray(rng.randn(3, 64, 16), jnp.float32)}
    q = jnp.asarray(rng.randn(3, 32), jnp.float32)
    kn, vn = (jnp.asarray(rng.randn(3, 16), jnp.float32) for _ in range(2))
    ts = jnp.asarray([37, -1, 5])
    want, kv_w = da.grouped_masked_decode_attention(
        q, kn, vn, kv, ts, n_head=4, n_kv_head=2, scale=0.3)
    kv_b = da.append_rows(kv, kn, vn, ts)
    for name in kv:
        np.testing.assert_array_equal(kv_b[name], kv_w[name])
    blocks = jnp.broadcast_to(jnp.arange(8)[None, None], (3, 2, 8))
    for dense in (False, True):   # named blocks; the dense branch
        got = da.grouped_block_decode_attention(
            q, kv_b, ts, blocks, jnp.full((3, 2, 8), not dense),
            jnp.full((3,), dense), n_head=4, n_kv_head=2, scale=0.3,
            block=8, dense_len=64)
        np.testing.assert_allclose(got, want, rtol=1e-5, atol=1e-5)


# -- the pool ---------------------------------------------------------------
def _pool(built, **kw):
    step, make_cache, _ = built
    return KVSlotPool(step, make_cache, eos_id=VOCAB, max_slots=4,
                      max_seq_len=T, slot_ladder=[4], len_ladder=[T],
                      steps=4, kv_dtype="fp32", **kw)


def test_declarations(built):
    _, make_cache, _ = built
    spec = decoding.spec_of(make_cache)
    assert spec.prefill_fn.chunk_tokens == C
    assert len(spec.flat) == len(
        jax.tree.leaves(jax.eval_shape(lambda: make_cache(2, T))))
    assert [leaf.seq_axis for leaf in spec.flat] == [
        1, 1, 1, None, None, 1, 1, 1]
    assert [leaf.stride for leaf in spec.flat] == [
        2, 1, 1, 1, 1, 2, 1, 1]   # ck first: leaves sort by name
    assert spec.names(lambda leaf: leaf.seq_axis is None) == [
        "[1]['s']", "[2]['s']"]
    assert [(read.kind, read.layers) for read in spec.reads] == [
        ("kv", 1), ("sparse", 2)]
    with pytest.raises(ValueError, match="multiple of block_size"):
        make_cache(2, 100)


@pytest.mark.parametrize("prefix,want", [(False, 4), (True, 6)])
def test_executables_a_rung_pair(built, prefix, want):
    """Three, plus ``prefill`` where the builder has one; with a prefix
    cache ``admit_prefix`` and ``snapshot`` besides."""
    pool = _pool(built, prefix=prefix)
    assert pool.warmup() == want
    assert pool.jit_cache_stats()["entries"] == want
    assert pool.prefill_tokens == C and pool.snapshots is prefix


def test_a_pool_without_a_prefill_compiles_three():
    """A builder that declares no ``prefill_fn`` compiles what it always
    did, and its pool never asks for a chunk."""
    w = decoding.random_transformer_lm_state(
        np.random.RandomState(0), 50, 16, 1, 2, 32, 32)
    step, make_cache = decoding.make_transformer_lm_pooled_step_fn(
        w, 50, 16, 1, 2, 32)
    pool = KVSlotPool(step, make_cache, eos_id=50, max_slots=2,
                      max_seq_len=32, slot_ladder=[2], len_ladder=[32])
    # chunk, admit, release and (PR 45) this builder's own seat_prefill:
    # a BATCHED prefill is another declaration; no ``prefill`` kind
    assert pool.warmup() == 4 and pool.prefill_tokens == 0
    assert "prefill" not in pool._kinds()
    assert not pool.can_prefill(pool.alloc(2, 32), 0, 31)


def test_speculative_over_recurrent_leaves_stays_refused(built):
    with pytest.raises(ValueError, match="speculative="):
        _pool(built, speculative=object())


def _serve_cold(pool, prompt, n_new):
    """A prompt through admit + one-token steps only, no prefill chunk."""
    st = pool.alloc(4, T)
    st = pool.admit(st, 2, prompt, len(prompt), len(prompt) + n_new)
    while not np.asarray(st["finished"])[2]:
        st = pool.chunk(st)
    return np.asarray(st["tokens"])[2, len(prompt):len(prompt) + n_new]


def test_pool_prefill_then_decode_serves_the_stepped_tokens(built):
    pool = _pool(built)
    prompt = _tokens(8, 70)
    want = _serve_cold(pool, prompt, 12)
    st = pool.alloc(4, T)
    st = pool.admit(st, 1, prompt, 70, 82)
    st = pool.release(st, [1])                     # held
    pos = 0
    while pool.can_prefill(st, pos, 70):
        last = not pool.can_prefill(st, pos + C, 70)
        st = pool.prefill(st, 1, pos, last)
        pos += C
    assert pos == 64 and int(np.asarray(st["pos"])[1]) == 64
    assert bool(np.asarray(st["active"])[1])
    assert int(np.asarray(st["n_gen"])[1]) == 0
    while not np.asarray(st["finished"])[1]:
        st = pool.chunk(st)
    np.testing.assert_array_equal(np.asarray(st["tokens"])[1, 70:82], want)


def test_a_held_slot_is_left_alone_by_the_decode_chunk(built):
    pool = _pool(built)
    st = pool.alloc(4, T)
    st = pool.admit(st, [0, 1], [_tokens(9, 70), _tokens(10, 6)], [70, 6],
                    [80, 16])
    st = pool.release(st, [0])
    st = pool.chunk(st)
    pos = np.asarray(st["pos"])
    assert pos[0] == 0 and pos[1] == 4


# -- the server -------------------------------------------------------------
def _server(built, prefix_cache=None, name="sala"):
    step, make_cache, _ = built
    return DecodeServer(step, make_cache, eos_id=VOCAB, max_seq_len=T,
                        max_slots=4, slot_ladder=[4], len_ladder=[T],
                        steps_per_tick=4, prefix_cache=prefix_cache,
                        kv_dtype="fp32", name=name)


@pytest.fixture(scope="module")
def cold_answers(built):
    """What a server with no prefix cache and one-token prefill serves
    (a pool whose rung is no longer than a chunk never prefills in
    chunks: T == C here would be another model; instead the pool's own
    stepped path above is the cold reference)."""
    pool = _pool(built)
    doc = _tokens(20, 64)
    prompts = [np.concatenate([doc, _tokens(21 + i, 5 + i)])
               for i in range(3)]
    return doc, prompts, [_serve_cold(pool, p, 10) for p in prompts]


def test_server_serves_chunked_prefill_and_snapshot_hits(built, cold_answers):
    """The first request on a document misses, is prefilled in chunks
    and leaves ONE snapshot where its last whole chunk ends; the next
    ones start at pos = 64 over it — recurrent state and all — and every
    one serves what the cold path serves."""
    doc, prompts, want = cold_answers
    with _server(built, prefix_cache=64 << 20, name="sala-snap") as srv:
        srv.warmup()
        first = srv.submit({"tokens": prompts[0]}, max_new_tokens=10)
        np.testing.assert_array_equal(first.result(60)[0], want[0])
        m = srv.metrics()["decode"]
        assert m["prefill_chunks"] == 4 and m["prefill_tokens"] == 69
        stats = m["prefix_cache"]
        assert (stats["entries"], stats["hits"], stats["misses"]) == (1, 0, 1)
        rest = [srv.submit({"tokens": p}, max_new_tokens=10)
                for p in prompts[1:]]
        for r, w in zip(rest, want[1:]):
            np.testing.assert_array_equal(r.result(60)[0], w)
        m = srv.metrics()["decode"]
        assert m["prefill_chunks"] == 4          # no more chunks ran
        assert m["prefill_tokens"] == 69 + 6 + 7  # only the questions
        assert m["prefix_cache"]["hits"] == 2
        assert m["state_resets"] == 1             # the miss alone
        assert m["sparse_positions_live"] > m["sparse_positions_read"] > 0
        assert srv.metrics()["recompiles"] == 0


def test_a_slot_reused_after_a_snapshot_admission_carries_nothing_over(
        built, cold_answers):
    """One slot: a request over the snapshot, then a cold short request
    in the same slot, then another over the snapshot."""
    doc, prompts, want = cold_answers
    pool = _pool(built)
    short = _tokens(30, 9)
    want_short = _serve_cold(pool, short, 10)
    step, make_cache, _ = built
    srv = DecodeServer(step, make_cache, eos_id=VOCAB, max_seq_len=T,
                       max_slots=1, slot_ladder=[1], len_ladder=[T],
                       steps_per_tick=4, prefix_cache=64 << 20,
                       kv_dtype="fp32", name="sala-one-slot")
    with srv:
        srv.warmup()
        for prompt, w in ((prompts[0], want[0]), (prompts[1], want[1]),
                          (short, want_short), (prompts[2], want[2])):
            got = srv.submit({"tokens": prompt}, max_new_tokens=10)
            np.testing.assert_array_equal(got.result(60)[0], w)
        assert srv.metrics()["decode"]["prefix_cache"]["hits"] == 2


def test_two_long_prompts_take_turns_one_chunk_a_tick(built, cold_answers):
    """Two misses seated in one turn: both held, the older prefilled
    first, short traffic decoding meanwhile."""
    _, prompts, want = cold_answers
    other = np.concatenate([_tokens(40, 48), _tokens(41, 7)])
    want_other = _serve_cold(_pool(built), other, 10)
    with _server(built, name="sala-two") as srv:
        srv.warmup()
        reqs = [srv.submit({"tokens": p}, max_new_tokens=10)
                for p in (prompts[0], other, _tokens(42, 5))]
        np.testing.assert_array_equal(reqs[0].result(60)[0], want[0])
        np.testing.assert_array_equal(reqs[1].result(60)[0], want_other)
        assert len(reqs[2].result(60)[0]) == 10
        m = srv.metrics()["decode"]
        assert m["prefill_chunks"] == 4 + 3
