"""paddle_tpu.decode_attention: the pooled decode step's attention.

The Pallas TPU kernel runs here under interpret mode at toy widths
against the masked-einsum formulation (the CPU path of the pooled step);
it compiles for a described v5e chip at the benchmark's widths in
tests/test_v5e_compile.py.

Tolerance: both sides compute every product and sum in fp32.  They
differ only in the ORDER of the sums (online softmax over blocks of 8
positions against one softmax over T; per-head sums as an indicator
matmul whose fp32 operand is split into three bf16 terms carrying 24
mantissa bits) — a few fp32 ulps (6e-8) over sums of at most 32 terms of
O(1): 2e-6 absolute is ten times the largest difference seen.

Two geometries.  ``RAGGED``: rungs of 32 in blocks of 8, which
``KV_TAIL`` does not divide, so every item is a whole block (one body).
``TAILS``: rungs of 256 in blocks of ``KV_BLOCK``, where a slot's last
block is read in classes of ``KV_TAIL`` rows — every class, both edges
of each, with and without a whole block before the tail (sums of up to
256 terms there: the same tolerance holds).
"""
import functools

import numpy as np
import pytest

from paddle_tpu import decode_attention as da
from paddle_tpu import decoding

S, T, H, DH, BLOCK = 6, 32, 2, 8, 8
D = H * DH
SCALE = 1.0 / np.sqrt(DH)
ATOL = 2e-6


def _inputs(seed, ts, t=T):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    q, kn, vn = (jnp.asarray(rng.randn(len(ts), D), jnp.float32)
                 for _ in range(3))
    kc = jnp.asarray(rng.randn(len(ts), t, D), jnp.float32)
    vc = jnp.asarray(rng.randn(len(ts), t, D), jnp.float32)
    return q, kn, vn, kc, vc, jnp.asarray(ts, jnp.int32)


def _kernel(q, kn, vn, kc, vc, ts, block=None):
    t = kc.shape[1]
    block = block or (BLOCK if t == T else da.kv_read_block(t))
    return da.ragged_decode_attention(
        q, kn, vn, kc, vc, ts, da.decode_work_items(ts, t, block),
        n_head=H, scale=SCALE, block=block, interpret=True)


def _masked(q, kn, vn, kc, vc, ts):
    ctx, kv = da.grouped_masked_decode_attention(
        q, kn, vn, {"k": kc, "v": vc}, ts, n_head=H, n_kv_head=H,
        scale=SCALE)
    return ctx, kv["k"], kv["v"]


def _float64_reference(q, kn, vn, kc, vc, ts):
    q, kn, vn, kc, vc = (np.asarray(a, np.float64)
                         for a in (q, kn, vn, kc, vc))
    out = np.zeros((len(ts), D))
    for n, t in enumerate(np.asarray(ts)):
        if t < 0:
            continue
        k = np.concatenate([kc[n, :t], kn[n:n + 1]]).reshape(t + 1, H, DH)
        v = np.concatenate([vc[n, :t], vn[n:n + 1]]).reshape(t + 1, H, DH)
        s = np.einsum("hd,thd->ht", q[n].reshape(H, DH), k) * SCALE
        w = np.exp(s - s.max(-1, keepdims=True))
        w /= w.sum(-1, keepdims=True)
        out[n] = np.einsum("ht,thd->hd", w, v).reshape(D)
    return out


# ts = 0, block - 1, block, block + 1, T - 1 and an idle slot, in two
# orders (the work list is slot-major, so order moves the block seams)
RAGGED = [[0, BLOCK - 1, BLOCK, BLOCK + 1, T - 1, -1],
          [-1, T - 1, 0, -1, BLOCK, 2 * BLOCK - 1],
          [T - 1] * 6, [0] * 6, [-1] * 6]

# a slot's last block in classes of G rows: both edges of every class
# (``ts % B`` at 0, G - 1, G, 2G - 1, ... B - 1), in the rung's first
# block and behind a whole one; the orders move the seams between a
# short item and the next slot's
G, B = da.KV_TAIL, da.KV_BLOCK
T2 = 2 * B
_EDGES = [c * G + e for c in range(B // G) for e in (0, G - 1)]
TAILS = [_EDGES + [-1],
         [B + e for e in _EDGES] + [-1],
         [-1] + [e + B * (i % 2) for i, e in enumerate(reversed(_EDGES))],
         [T2 - 1, 5, B - 1, B + 1, G, -1]]


def _rung(ts):
    """The rung a case is run at: T, or for a TAILS case T2."""
    return T2 if any(ts is case for case in TAILS) else T


def _ids(ts):
    return "-".join(map(str, ts))


@pytest.mark.parametrize("ts", RAGGED + TAILS, ids=_ids)
def test_kernel_matches_masked_einsum(ts):
    args = _inputs(0, ts, _rung(ts))
    o, k2, v2 = _kernel(*args)
    ro, rk, rv = _masked(*args)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ro), rtol=0,
                               atol=ATOL)
    # the append is a copy: bit-identical whichever side made it
    assert np.array_equal(np.asarray(k2), np.asarray(rk))
    assert np.array_equal(np.asarray(v2), np.asarray(rv))


@pytest.mark.parametrize("ts", RAGGED[:1] + TAILS, ids=_ids)
@pytest.mark.parametrize("impl", [_kernel, _masked],
                         ids=["kernel", "masked"])
def test_fp32_accumulation_against_float64(impl, ts):
    args = _inputs(1, ts, _rung(ts))
    o, _, _ = impl(*args)
    np.testing.assert_allclose(np.asarray(o), _float64_reference(*args),
                               rtol=0, atol=ATOL)


@pytest.mark.parametrize("t,ts", [
    (T, [3, -1, BLOCK, -1, T - 1, -1]),
    (T2, [G - 1, -1, G, -1, T2 - 1, -1]),
    (T2, [-1, B + G - 1, -1, B - G, -1, B])], ids=["blocks", "tails",
                                                       "tails_idle_first"])
@pytest.mark.parametrize("impl", [_kernel, _masked],
                         ids=["kernel", "masked"])
def test_idle_slots_read_nothing_and_write_nothing(impl, t, ts):
    """An idle slot's rows are NaN: read, they would poison its output;
    written, they would no longer be NaN.  Its context row is zero."""
    import jax.numpy as jnp

    q, kn, vn, kc, vc, tsa = _inputs(2, ts, t)
    idle = np.asarray(ts) < 0
    kc = kc.at[idle].set(jnp.nan)
    vc = vc.at[idle].set(jnp.nan)
    o, k2, v2 = impl(q, kn, vn, kc, vc, tsa)
    o = np.asarray(o)
    assert np.all(o[idle] == 0.0)
    assert np.all(np.isfinite(o))
    assert np.all(np.isnan(np.asarray(k2)[idle]))
    assert np.all(np.isnan(np.asarray(v2)[idle]))


@pytest.mark.parametrize("ts", RAGGED[:1] + TAILS, ids=_ids)
@pytest.mark.parametrize("impl", [_kernel, _masked],
                         ids=["kernel", "masked"])
def test_garbage_beyond_ts_does_not_change_the_output(impl, ts):
    """Positions past ``ts`` hold a previous occupant's rows: large and
    finite, they must weigh exactly nothing (bit-equal outputs) — those
    inside the rows a tail item reads and those it never touches."""
    import jax.numpy as jnp

    t = _rung(ts)
    q, kn, vn, kc, vc, tsa = _inputs(3, ts, t)
    beyond = (np.arange(t)[None, :] > np.asarray(ts)[:, None])[:, :, None]
    clean, _, _ = impl(q, kn, vn, jnp.where(beyond, 0.0, kc),
                       jnp.where(beyond, 0.0, vc), tsa)
    dirty, _, _ = impl(q, kn, vn, jnp.where(beyond, 1e30, kc),
                       jnp.where(beyond, -1e30, vc), tsa)
    assert np.array_equal(np.asarray(clean), np.asarray(dirty))


@pytest.mark.parametrize("ts", RAGGED[:1] + TAILS, ids=_ids)
@pytest.mark.parametrize("impl", [_kernel, _masked],
                         ids=["kernel", "masked"])
def test_one_step_writes_one_row_per_active_slot(impl, ts):
    q, kn, vn, kc, vc, tsa = _inputs(4, ts, _rung(ts))
    _, k2, v2 = impl(q, kn, vn, kc, vc, tsa)
    for new, old, row in ((k2, kc, kn), (v2, vc, vn)):
        new, old, row = (np.asarray(a) for a in (new, old, row))
        want = old.copy()
        for n, t in enumerate(ts):
            if t >= 0:
                want[n, t] = row[n]
        assert np.array_equal(new, want)


@pytest.mark.parametrize("path,rep,dtype", [
    ("xla", rep, dtype) for rep in (1, 2)
    for dtype in ("float32", "bfloat16", "int8")] + [
    ("kernel", 2, "float32"), ("kernel", 2, "bfloat16")])
def test_k_fresh_rows_equal_k_appends_of_one_row(path, rep, dtype):
    """K rows per slot are K calls at one row, through the XLA form and
    through the grouped kernel (whole-lane-tile heads: its shapes): the
    same leaves bit for bit (an append is a copy, or the same
    quantization of the same row) and the same context to fp32 rounding
    of sums ordered differently (bf16 products: their rounding is the
    same on both sides), for grouped heads and every storage dtype."""
    import jax.numpy as jnp

    K, n_kv = 3, H
    n_head = n_kv * rep
    dh = DH if path == "xla" else 128
    attend = (da.grouped_masked_decode_attention if path == "xla"
              else functools.partial(_grouped_rows_kernel, block=T, tail=16))
    rng = np.random.RandomState(3)
    ts = jnp.asarray([0, 7, -1, T - K, T - 1, 12], jnp.int32)
    q = jnp.asarray(rng.randn(S, K, n_head * dh), jnp.float32)
    kn, vn = (jnp.asarray(rng.randn(S, K, n_kv * dh), jnp.float32)
              for _ in range(2))
    kv = da.kv_leaves(S, T, n_kv, dh, dtype)
    named = dict(n_head=n_head, n_kv_head=n_kv, scale=1.0 / np.sqrt(dh))
    _, kv = da.grouped_masked_decode_attention(   # something to read
        q[:, 0], kn[:, 0] * 0.5, vn[:, 0] * 0.5, kv,
        jnp.maximum(ts - 1, -1), **named)
    wide, wide_kv = attend(q, kn, vn, kv, ts, **named)
    assert wide.shape == q.shape and wide.dtype == jnp.float32
    seq_kv, in_range = kv, np.asarray(ts)[:, None] + np.arange(K) < T
    for j in range(K):
        at = jnp.where((ts >= 0) & (ts + j < T), ts + j, -1)
        one, seq_kv = attend(q[:, j], kn[:, j], vn[:, j], seq_kv, at,
                             **named)
        rows = in_range[:, j]          # slot 4's rows past T: dropped
        np.testing.assert_allclose(np.asarray(wide[:, j])[rows],
                                   np.asarray(one)[rows], rtol=0,
                                   atol=ATOL)
    assert sorted(wide_kv) == sorted(kv)
    for name in kv:
        assert wide_kv[name].dtype == kv[name].dtype
        assert np.array_equal(np.asarray(wide_kv[name], np.float32),
                              np.asarray(seq_kv[name], np.float32))
        assert np.array_equal(np.asarray(wide_kv[name][2], np.float32),
                              np.asarray(kv[name][2], np.float32))


@pytest.mark.parametrize("t,ts", [
    (T, [0, BLOCK - 2, BLOCK - 1, -1, T - 4, 5]),
    # steps that cross a class's edge, a block's, and neither
    (T2, [G - 2, B - 2, B + G - 1, -1, T2 - 4, 2 * G - 3])],
    ids=["blocks", "tails"])
def test_consecutive_steps_through_the_kernel(t, ts):
    """Three steps in a row, each appending where the last left off:
    the caches stay bit-equal to the masked path's, the contexts
    within tolerance (the second step reads what the first wrote)."""
    import jax.numpy as jnp

    ts = np.asarray(ts)
    _, _, _, kc, vc, _ = _inputs(5, ts, t)
    rk, rv = kc, vc
    for step in range(3):
        q, kn, vn, _, _, _ = _inputs(10 + step, ts, t)
        tsa = jnp.asarray(np.where(ts >= 0, ts + step, -1), jnp.int32)
        o, kc, vc = _kernel(q, kn, vn, kc, vc, tsa)
        ro, rk, rv = _masked(q, kn, vn, rk, rv, tsa)
        np.testing.assert_allclose(np.asarray(o), np.asarray(ro), rtol=0,
                                   atol=ATOL)
        assert np.array_equal(np.asarray(kc), np.asarray(rk))
        assert np.array_equal(np.asarray(vc), np.asarray(rv))


def test_one_block_spanning_the_rung():
    """A rung the block does not divide is read as one block."""
    assert da.kv_read_block(512) == da.KV_BLOCK
    assert da.kv_read_block(T) == T
    assert da.kv_positions_read(0, T) == da.kv_positions_read(T - 1, T) == T
    args = _inputs(6, RAGGED[0])
    o, _, _ = _kernel(*args, block=T)
    ro, _, _ = _masked(*args)
    np.testing.assert_allclose(np.asarray(o), np.asarray(ro), rtol=0,
                               atol=ATOL)


def test_work_items_are_the_live_blocks_slot_major():
    import jax.numpy as jnp

    ts = jnp.asarray([0, BLOCK - 1, BLOCK, -1, T - 1, -1], jnp.int32)
    n_items, slot, blk, rows = (np.asarray(a)
                                for a in da.decode_work_items(ts, T, BLOCK))
    n = int(n_items[0])
    assert slot.shape == blk.shape == rows.shape == (len(ts) * T // BLOCK,)
    assert list(zip(slot[:n], blk[:n])) == [
        (0, 0), (1, 0), (2, 0), (2, 1), (4, 0), (4, 1), (4, 2), (4, 3)]
    # KV_TAIL does not divide a block of 8: every item is a whole block
    assert rows[:n].tolist() == [BLOCK] * n


@pytest.mark.parametrize("ts", TAILS, ids=_ids)
def test_work_items_carry_the_rows_of_a_last_block(ts):
    """Blocks of ``KV_BLOCK``: a slot's blocks before its last are whole,
    its last one has the rows ``kv_positions_read`` leaves it — ``ts %
    B + 1`` rounded up to ``KV_TAIL`` — and a step's rows add up to what
    the counter's function says it reads."""
    import jax.numpy as jnp

    n_items, slot, blk, rows = (
        np.asarray(a) for a in da.decode_work_items(
            jnp.asarray(ts, jnp.int32), T2, B))
    items = list(zip(*(a[:int(n_items[0])].tolist()
                       for a in (slot, blk, rows))))
    want = []
    for n, t in enumerate(ts):
        if t < 0:
            continue
        want += [(n, b, B) for b in range(t // B)]
        want.append((n, t // B, -(-(t % B + 1) // G) * G))
    assert items == want
    assert {r for _, _, r in items} <= set(range(G, B + 1, G))
    for n, t in enumerate(ts):
        if t >= 0:
            assert sum(r for m, _, r in items if m == n) == (
                da.kv_positions_read(t, B))


_LM = dict(vocab=19, d_model=D, n_layer=2, n_head=H, d_inner=24, max_pos=T)


def test_pooled_step_leaves_an_idle_row_alone():
    """The pooled step under the idle contract (``ts < 0``): the idle
    row's cache is bit-identical after the step, an active row's logits
    do not depend on who sits idle beside it."""
    import jax.numpy as jnp

    state = decoding.random_transformer_lm_state(
        np.random.RandomState(8), **_LM)
    step, make_cache = decoding.make_transformer_lm_pooled_step_fn(
        state, _LM["vocab"], D, _LM["n_layer"], H, _LM["d_inner"])
    cache = [{k: v + 0.5 for k, v in layer.items()}
             for layer in make_cache(3, T)]
    assert cache[0]["k"].shape == (3, T, D)
    toks = jnp.asarray([3, 4, 5], jnp.int32)
    both, c2 = step(cache, toks, jnp.asarray([2, -1, 7], jnp.int32))
    for old, new in zip(cache, c2):
        for leaf in ("k", "v"):
            o, n = np.asarray(old[leaf]), np.asarray(new[leaf])
            assert np.array_equal(o[1], n[1])
            changed = np.argwhere((o != n).any(axis=-1))
            assert changed.tolist() == [[0, 2], [2, 7]]
    alone, _ = step(cache, toks, jnp.asarray([2, 9, 7], jnp.int32))
    np.testing.assert_allclose(np.asarray(both)[[0, 2]],
                               np.asarray(alone)[[0, 2]], rtol=0,
                               atol=1e-6)


def test_chunk_hands_idle_slots_to_the_step_as_minus_one():
    """``make_slot_decode_fns``: the step (and the draft step) see
    ``ts = pos`` for active slots and ``-1`` for the others, whatever
    stale ``pos`` those carry."""
    import jax
    import jax.numpy as jnp

    def spy(cache, tokens, ts):
        return jax.nn.one_hot(tokens, 5), {"ts": ts}

    chunk, _, _ = decoding.make_slot_decode_fns(spy, eos_id=99, steps=1,
                                                draft_step_fn=spy)
    n = 4
    state = {
        "cache": {"ts": jnp.zeros((n,), jnp.int32)},
        "draft_cache": {"ts": jnp.zeros((n,), jnp.int32)},
        "tokens": jnp.ones((n, 16), jnp.int32),
        "pos": jnp.asarray([3, 5, 0, 9], jnp.int32),
        "prompt_len": jnp.full((n,), 2, jnp.int32),
        "total_len": jnp.full((n,), 16, jnp.int32),
        "active": jnp.asarray([True, False, True, False]),
        "finished": jnp.asarray([False, True, False, False]),
        "n_gen": jnp.zeros((n,), jnp.int32),
    }
    out = chunk(state)
    assert np.asarray(out["cache"]["ts"]).tolist() == [3, -1, 0, -1]
    assert np.asarray(out["draft_cache"]["ts"]).tolist() == [3, -1, 0, -1]
    assert np.asarray(out["pos"]).tolist() == [4, 5, 1, 9]


# ---------------------------------------------------------------------------
# "read the blocks named for this row": the block kernel against the XLA
# form of the same read
# ---------------------------------------------------------------------------
def _named_blocks(rng, ts, n_blocks, b, g, block):
    """Per slot and head ``b`` distinct blocks, the one its position
    falls in among them (the rule always names it), some marked
    invalid."""
    blocks = np.stack([[rng.permutation(n_blocks)[:b] for _ in range(g)]
                       for _ in ts]).astype(np.int32)
    blocks[..., 0] = np.maximum(np.asarray(ts), 0)[:, None] // block
    for row in blocks.reshape(-1, b):   # keep the named blocks distinct
        dup = np.flatnonzero(row[1:] == row[0])
        row[1 + dup] = (row[0] + 1 + np.arange(len(dup))) % n_blocks
    valid = rng.rand(*blocks.shape) > 0.25
    valid[..., 0] = True
    return blocks, valid


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
def test_block_kernel_matches_the_gathered_read(dtype):
    """Interpret mode, at the kernel's own tile sizes: a head of 128
    lanes, blocks of 64 rows, 16 query heads a K/V head."""
    import jax.numpy as jnp

    rng = np.random.RandomState(11)
    s, t, g, d, rep, block, b = 4, 512, 2, 128, 16, 64, 6
    ts = np.array([300, -1, 77, 511], np.int32)
    kv = {n: jnp.asarray(rng.randn(s, t, g * d), dtype) for n in "kv"}
    q = jnp.asarray(rng.randn(s, g * rep * d), jnp.float32)
    blocks, valid = _named_blocks(rng, ts, t // block, b, g, block)
    assert da.block_kernel_supported(kv, g * rep, g, block)
    named = dict(n_head=g * rep, n_kv_head=g, scale=0.1, block=block)
    want = da._gathered_block_attention(
        q, kv, jnp.asarray(ts), jnp.asarray(blocks), jnp.asarray(valid),
        **named)
    got = da.block_sparse_decode_attention(
        q, kv["k"], kv["v"], jnp.asarray(ts), jnp.asarray(blocks),
        jnp.asarray(valid), interpret=True, **named)
    live = ts >= 0
    tol = 1e-5 if dtype == "float32" else 2e-2   # bf16 probabilities
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=tol)
    assert not np.asarray(got)[~live].any()      # an idle slot reads nothing


# the rule's own lists (``sparse_linear_lm.select_blocks``: the first
# block, the window's five, the top 4 of the rest) at positions that put
# the declared runs to the test, four slots a case; ``topk`` rewrites the
# last entries of both heads' lists where the case is about them
_RULE = dict(block_size=64, kernel_size=32, kernel_stride=16, init_blocks=1,
             window_size=256, topk=4, dense_len=0)
_RULE_CASES = {
    # the window's last block holds ts: rows past it are not read
    "run_ends_in_a_partly_live_block": ([700, 333, 950, 527], None),
    # under a window's length the window meets the first block, which the
    # list masks off in the window's entries: its rows count once
    "run_meets_the_first_block": ([200, 100, 63, 255], None),
    # 0, 1 or 3 blocks between the first and the window: entries of the
    # top-k that name nothing
    "fewer_blocks_than_topk": ([400, 340, 520, 450], None),
    "an_idle_slot_between_live_ones": ([900, -1, 600, -1], None),
    "both_heads_name_the_same_blocks": (
        [900, 1000, 800, 960], [[1, 3, 5, 7], [1, 3, 5, 7]]),
    "the_heads_name_disjoint_blocks": (
        [900, 1000, 800, 960], [[1, 3, 5, 7], [2, 4, 6, 0]]),
    "ts_on_a_blocks_first_and_last_row": ([640, 703, 704, 767], None),
    # the window's ids are clamped to the rung's last block: the run read
    # from its first id would leave the leaf
    "window_at_the_rungs_end": ([1023, 1000, 961, 960], None),
}
# keys scored at a time (``_SCORE_ROWS``) against a case's 384 rows of
# slabs and 256 of a head's tiles: one chunk each (the module's 512),
# the slabs in one and a half (256: the last chunk drawn back, its shared
# rows counted once), the tiles in one and a third (192), both in whole
# chunks (128)
_SCORE_ROWS = {"run_ends_in_a_partly_live_block": (256, 192, 128),
               "run_meets_the_first_block": (256,),
               "fewer_blocks_than_topk": (192,),
               "window_at_the_rungs_end": (256, 192)}
_RULE_RUNS = [(case, None) for case in sorted(_RULE_CASES)] + [
    (case, rows) for case in sorted(_SCORE_ROWS) for rows in _SCORE_ROWS[case]]


@pytest.mark.parametrize("dtype", ["bfloat16", "float32"])
@pytest.mark.parametrize("case,score_rows", _RULE_RUNS)
def test_block_kernel_reads_the_declared_runs_as_the_lists_say(
        case, score_rows, dtype, monkeypatch):
    """The kernel told which stretches of the lists are shared runs of
    consecutive blocks (``shared_runs``) against the XLA form, which
    takes the lists as they are; a unit scored in one chunk and in
    several, whole and not."""
    import types

    import jax.numpy as jnp

    from paddle_tpu import sparse_linear_lm as sl

    d = types.SimpleNamespace(**_RULE)
    ts, topk = _RULE_CASES[case]
    ts = np.asarray(ts, np.int32)
    rng = np.random.RandomState(sorted(_RULE_CASES).index(case))
    s, t, g, dh, rep = len(ts), 1024, 2, 128, 16
    kv = {n: jnp.asarray(rng.randn(s, t, g * dh), dtype) for n in "kv"}
    q = jnp.asarray(rng.randn(s, g * rep * dh), jnp.float32)
    ck = jnp.asarray(rng.randn(s, t // d.kernel_stride, g * dh), dtype)
    blocks, valid, _ = sl.select_blocks(
        q.reshape(s, g, rep, dh), ck, jnp.maximum(ts, 0), d)
    if topk is not None:
        blocks = blocks.at[..., -d.topk:].set(jnp.asarray(topk)[None])
    runs = sl.forced_runs(d)
    assert runs == ((0, 1), (1, 5)) and blocks.shape[-1] == 10
    named = dict(n_head=g * rep, n_kv_head=g, scale=0.1, block=d.block_size)
    want = da._gathered_block_attention(q, kv, jnp.asarray(ts), blocks,
                                        valid, **named)
    if score_rows is not None:
        monkeypatch.setattr(da, "_SCORE_ROWS", score_rows)
    got = da.block_sparse_decode_attention(
        q, kv["k"], kv["v"], jnp.asarray(ts), blocks, valid,
        shared_runs=runs, interpret=True, **named)
    live = ts >= 0
    tol = 1e-5 if dtype == "float32" else 2e-2   # bf16 probabilities
    np.testing.assert_allclose(np.asarray(got)[live], np.asarray(want)[live],
                               atol=tol)
    assert not np.asarray(got)[~live].any()
    if case == "fewer_blocks_than_topk":
        assert (~np.asarray(valid)[..., -d.topk:]).any()
    if case == "window_at_the_rungs_end":    # clamped: not consecutive
        assert np.asarray(blocks)[0, 0, 1:6].tolist() == [12, 13, 14, 15, 15]


@pytest.mark.parametrize("runs", [((0, 1), (1, 3)), ((1, 3),), ()])
def test_block_kernel_takes_lists_of_runs_only_and_of_tiles_only(runs):
    """A slot's units are the declared runs' slabs (if any) and a head's
    tiles (if any entry lies outside the runs): lists that are all runs,
    one run between tiles, and no run at all read alike."""
    import jax.numpy as jnp

    rng = np.random.RandomState(5)
    s, t, g, dh, rep, block = 3, 512, 2, 128, 16, 64
    kv = {n: jnp.asarray(rng.randn(s, t, g * dh), jnp.float32) for n in "kv"}
    q = jnp.asarray(rng.randn(s, g * rep * dh), jnp.float32)
    ts = jnp.asarray([400, 130, 255])
    # the first block and a window of three, both heads alike
    blocks = jnp.asarray([[[0, 4, 5, 6]] * 2, [[0, 0, 1, 2]] * 2,
                          [[0, 1, 2, 3]] * 2], jnp.int32)
    valid = jnp.asarray([[[1, 1, 1, 1]] * 2, [[1, 0, 1, 1]] * 2,
                         [[1, 1, 1, 1]] * 2], bool)
    named = dict(n_head=g * rep, n_kv_head=g, scale=0.1, block=block)
    want = da._gathered_block_attention(q, kv, ts, blocks, valid, **named)
    got = da.block_sparse_decode_attention(
        q, kv["k"], kv["v"], ts, blocks, valid, shared_runs=runs,
        interpret=True, **named)
    np.testing.assert_allclose(got, want, atol=1e-5)


@pytest.mark.parametrize("path", ["xla", "kernel"])
def test_grouped_block_read_counts_its_lowering(path, monkeypatch):
    """``block_sparse_lowered_total{path}`` counts one per call lowered;
    on a TPU (here: said to be one, the kernel in interpret mode) the
    declared runs reach the kernel and the contexts are the XLA form's."""
    import functools

    import jax
    import jax.numpy as jnp

    rng = np.random.RandomState(3)
    s, t, g, dh, rep, block = 2, 512, 2, 128, 16, 64
    kv = {n: jnp.asarray(rng.randn(s, t, g * dh), jnp.float32) for n in "kv"}
    q = jnp.asarray(rng.randn(s, g * rep * dh), jnp.float32)
    ts = jnp.asarray([400, 130])
    # the first block, a window of three, one more block a head
    blocks = jnp.asarray([[[0, 4, 5, 6, 2], [0, 4, 5, 6, 3]],
                          [[0, 0, 1, 2, 0], [0, 0, 1, 2, 0]]], jnp.int32)
    valid = jnp.asarray([[[1, 1, 1, 1, 1]] * 2, [[1, 0, 1, 1, 0]] * 2], bool)
    call = functools.partial(
        da.grouped_block_decode_attention, q, kv, ts, blocks, valid,
        jnp.zeros((s,), bool), n_head=g * rep, n_kv_head=g, scale=0.1,
        block=block, dense_len=0, shared_runs=((0, 1), (1, 3)))
    want = call()
    seen = []
    if path == "kernel":
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
        kernel = da.block_sparse_decode_attention
        monkeypatch.setattr(
            da, "block_sparse_decode_attention",
            lambda *a, **kw: seen.append(kw["shared_runs"]) or kernel(
                *a, interpret=True, **kw))
    before = da.BLOCK_SPARSE_LOWERED.labels(path=path).value
    got = call()
    assert da.BLOCK_SPARSE_LOWERED.labels(path=path).value == before + 1
    assert seen == ([((0, 1), (1, 3))] if path == "kernel" else [])
    np.testing.assert_allclose(got, want, atol=1e-5)


def test_block_kernel_is_refused_for_shapes_it_cannot_tile():
    import jax.numpy as jnp

    kv = {"k": jnp.zeros((2, 64, 32), jnp.float32)}
    assert not da.block_kernel_supported(kv, 4, 2, 8)      # heads of 16
    kv = {"k": jnp.zeros((2, 64, 256), jnp.bfloat16)}
    assert not da.block_kernel_supported(kv, 8, 2, 64)     # 4 heads a group
    assert da.block_kernel_supported(kv, 32, 2, 64)


# ---------------------------------------------------------------------------
# grouped heads narrower than a lane tile: the leaves read as they lie
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_lane_masked_form_equals_the_grouped_form(dtype):
    """Each query head laid into its K/V head's lanes of a whole-width
    row gives the sums the per-head view gives (the added terms are
    exact zeros): same context, same appended leaves, idle rows
    untouched.  float32 differs in the order of sums only; bf16 rounds
    the same operands on both sides."""
    import jax.numpy as jnp

    s, t, g, rep, dh = 5, 24, 2, 4, 8
    rng = np.random.RandomState(3)
    q = jnp.asarray(rng.randn(s, g * rep * dh), jnp.float32)
    kn, vn = (jnp.asarray(rng.randn(s, g * dh), jnp.float32)
              for _ in range(2))
    kv = {n: jnp.asarray(rng.randn(s, t, g * dh), dtype) for n in "kv"}
    ts = jnp.asarray([0, 7, -1, 23, 12], jnp.int32)
    kw = dict(n_head=g * rep, n_kv_head=g, scale=0.35)
    want, kv_want = da.grouped_masked_decode_attention(q, kn, vn, kv, ts,
                                                       **kw)
    got, kv_got = da.lane_masked_decode_attention(q, kn, vn, kv, ts, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               atol=2e-6 if dtype == "float32" else 2e-2)
    for n in "kv":
        assert np.array_equal(np.asarray(kv_got[n].astype("float32")),
                              np.asarray(kv_want[n].astype("float32")))
    assert not np.asarray(got)[2].any()


def _grouped_counts():
    return {path: da.GROUPED_LOWERED.labels(path=path).value
            for path in ("kernel", "xla")}


def test_make_decode_attention_takes_the_lane_form_only_where_it_pays(
        monkeypatch):
    """On a TPU a step of grouped heads over unquantized sequence leaves
    takes the grouped kernel where a whole number of lane tiles holds
    the K/V heads: heads that are whole lane tiles (falcon_h1,
    smallthinker's global layers: bf16 and fp32) and an EVEN number of
    64-lane heads, two a lane tile (lfm2: bf16 and fp32) — one fresh row
    a slot or K (a self-drafting round's verify: k_exaone); one query
    head a K/V head over bf16 leaves of whole-lane-tile heads
    (olmo_hybrid's full layers) takes that kernel too at one row — a
    head one row of a unit.  What is left reads the leaves as they lie
    at one row (the per-head view would be copied): grouped 64-lane heads
    in an ODD number or over a rung the block does not divide, and one
    query head a K/V head over bf16 leaves whose heads are 64 wide or
    whose rung the block does not divide; int8 leaves, a rung the
    kernel's block does not divide over whole-lane-tile heads, K rows
    where no kernel takes them, ring leaves at one row (K rows over a
    ring of whole-lane-tile heads take the ring's own kernel) and every
    CPU run keep the grouped XLA form.  Every grouped-head step over sequence leaves
    counts itself by the path it took, a K-row one also by its leaf, a
    step of one query head a K/V head in a counter of its own; a ring
    step never counts a path."""
    import jax
    import jax.numpy as jnp

    ts = jnp.zeros((2,), jnp.int32)
    narrow = da.kv_leaves(2, 128, 2, 64, jnp.bfloat16)
    wide = da.kv_leaves(2, 128, 2, 128, jnp.bfloat16)
    seen = []
    kernel = da.grouped_decode_attention
    monkeypatch.setattr(da, "lane_masked_decode_attention",
                        lambda q, *a, **k: seen.append("lane") or (q, a[2]))
    monkeypatch.setattr(da, "grouped_masked_decode_attention",
                        lambda q, *a, **k: seen.append("grouped") or (q, a[2]))
    monkeypatch.setattr(
        da, "grouped_decode_attention",
        lambda *a, **k: seen.append("kernel") or kernel(
            *a, interpret=True, **k))
    ring_kernel = da.ring_rows_decode_attention
    monkeypatch.setattr(
        da, "ring_rows_decode_attention",
        lambda *a, **k: seen.append("ring kernel") or ring_kernel(
            *a, interpret=True, **k))

    def rows_counted():
        return da.ROWS_LOWERED.labels(leaf="sequence").value

    def took(kv, heads, rows=None, **kw):
        """What a step over ``kv`` called, and what it counted."""
        before, n = _grouped_counts(), len(seen)
        f = da.make_decode_attention(ts, kv, n_head=heads[0],
                                     n_kv_head=heads[1], scale=1.0, **kw)
        width = kv["k"].shape[-1]
        q = jnp.zeros((2,) + (() if rows is None else (rows,))
                      + (width * heads[0] // heads[1],))
        new = jnp.zeros(q.shape[:-1] + (width,))
        f(q, new, new, kv)
        after = _grouped_counts()
        return seen[n:], {p: after[p] - before[p] for p in after}

    assert took(narrow, (8, 2)) == (["grouped"], {"kernel": 0, "xla": 1})
    assert took(wide, (8, 2)) == (["grouped"], {"kernel": 0, "xla": 1})
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    for kv in (wide, da.kv_leaves(2, 128, 2, 128, jnp.float32),
               da.kv_leaves(2, 2 * da._GROUPED_BLOCK, 2, 128, jnp.bfloat16)):
        assert took(kv, (8, 2)) == (["kernel"], {"kernel": 1, "xla": 0})
        assert took(kv, (10, 2)) == (["kernel"], {"kernel": 1, "xla": 0})
    # 64-lane heads, two a lane tile: the same kernel (lfm2's 32 / 8)
    for kv, heads in ((narrow, (8, 2)),
                      (da.kv_leaves(2, 128, 2, 64, jnp.float32), (8, 2)),
                      (da.kv_leaves(2, 2 * da._GROUPED_BLOCK, 8, 64,
                                    jnp.bfloat16), (32, 8))):
        assert took(kv, heads) == (["kernel"], {"kernel": 1, "xla": 0})
    # ... where they are an odd number, or the block does not divide the
    # rung, no unit is whole lane tiles: read as they lie
    odd = da.kv_leaves(2, 128, 3, 64, jnp.bfloat16)
    for kv, heads in ((odd, (12, 3)),
                      (da.kv_leaves(2, da._GROUPED_BLOCK + 128, 2, 64,
                                    jnp.bfloat16), (8, 2))):
        assert took(kv, heads) == (["lane"], {"kernel": 0, "xla": 1})
    # K fresh rows: the kernel's wherever one row is
    n_rows = rows_counted()
    for kv in (wide, da.kv_leaves(2, 128, 2, 128, jnp.float32), narrow):
        for rows in (2, 3):
            assert took(kv, (8, 2), rows=rows) == (
                ["kernel"], {"kernel": 1, "xla": 0})
    assert rows_counted() == n_rows + 6
    for kv, heads, kw in (
            (odd, (12, 3), {"rows": 3}),
            (da.kv_leaves(2, 128, 2, 128, jnp.int8), (8, 2), {}),
            (da.kv_leaves(2, 128, 2, 64, jnp.int8), (8, 2), {}),
            (da.kv_leaves(2, da._GROUPED_BLOCK + 128, 2, 128, jnp.bfloat16),
             (8, 2), {})):                          # no whole blocks
        assert took(kv, heads, **kw) == (["grouped"],
                                         {"kernel": 0, "xla": 1})
    # not grouped, or a ring: no grouped count, and never that kernel
    nothing = {"kernel": 0, "xla": 0}

    def ungrouped():
        return [da.UNGROUPED_LOWERED.labels(path=path).value
                for path in ("kernel", "xla")]

    by_kernel, by_xla = ungrouped()
    assert took(narrow, (2, 2)) == (["lane"], nothing)
    assert took(wide, (2, 2)) == (["kernel"], nothing)
    assert took(da.kv_leaves(2, 2 * da._GROUPED_BLOCK, 6, 128, jnp.bfloat16),
                (6, 6)) == (["kernel"], nothing)
    assert ungrouped() == [by_kernel + 2, by_xla + 1]
    assert took(da.kv_leaves(2, da._GROUPED_BLOCK + 128, 2, 128,
                             jnp.bfloat16), (2, 2)) == (["lane"], nothing)
    assert took(wide, (2, 2), rows=3) == (["grouped"], nothing)
    assert took(da.kv_leaves(2, 128, 2, 128, jnp.int8), (2, 2)) == (
        ["grouped"], nothing)
    assert ungrouped() == [by_kernel + 2, by_xla + 4]
    # fp32 leaves of one query head a K/V head: the ragged kernel's
    ragged = []
    monkeypatch.setattr(
        da, "ragged_decode_attention",
        lambda q, kn, vn, k, v, *a, **kw: ragged.append(1) or (q, k, v))
    assert took(da.kv_leaves(2, 128, 2, 128, jnp.float32), (2, 2)) == (
        [], nothing)
    assert ragged == [1] and ungrouped() == [by_kernel + 3, by_xla + 4]
    ring = da.kv_leaves(2, 128, 2, 128, jnp.bfloat16, window=64)
    assert took(ring, (8, 2), window=64) == (["grouped"], nothing)
    # K rows over a ring of whole-lane-tile heads: the ring's own kernel
    assert took(ring, (8, 2), rows=3, window=64) == (["ring kernel"],
                                                     nothing)
    for kv in (da.kv_leaves(2, 128, 2, 64, jnp.bfloat16, window=64),
               da.kv_leaves(2, 128, 2, 128, jnp.int8, window=64)):
        assert took(kv, (8, 2), rows=3, window=64) == (["grouped"], nothing)
    # off the TPU a K-row call is the XLA form's, counted as such
    monkeypatch.undo()
    before, n_rows = _grouped_counts(), rows_counted()
    q = jnp.zeros((2, 3, 8 * 128))
    new = jnp.zeros((2, 3, 2 * 128))
    da.make_decode_attention(ts, wide, n_head=8, n_kv_head=2, scale=1.0)(
        q, new, new, wide)
    after = _grouped_counts()
    assert {p: after[p] - before[p] for p in after} == {"kernel": 0,
                                                        "xla": 1}
    assert rows_counted() == n_rows + 1


# ---------------------------------------------------------------------------
# grouped heads that are whole lane tiles: the kernel that reads what is
# live, under interpret mode at toy rungs (two blocks of 128, a slot's
# last block in classes of 16 rows)
# ---------------------------------------------------------------------------
GB, GT = 128, 16        # the toy block and tail
GROUPED_TS = {
    # idle, 0, both sides of the block's edge and of a class's, T - 1
    "edges": [-1, 0, GB - 1, GB, GB + 1, 2 * GB - 1, GT - 1, GT, GT + 1],
    "all_idle": [-1] * 4,
    "one_live": [-1, GB + 40, -1, -1],
    # a rung of ONE block: idle, 0, a class's edge, the rung's end
    "one_block_rung": [-1, 0, GT - 1, GT, GB - GT, GB - 1],
}
#: (query heads a K/V head, K/V heads, a head's lanes): the grouped
#: cells' groupings at four heads of 128, ONE query head a K/V head — a
#: head one row of a unit — at 30 heads (olmo_hybrid's: no power of two),
#: 2 and 6, and heads of 64 lanes, two a lane tile: lfm2's 8 heads in one
#: unit, one pair, and a head of an odd number of half tiles (192)
GROUPINGS = [(5, 4, 128), (7, 4, 128), (8, 4, 128), (1, 30, 128),
             (1, 2, 128), (1, 6, 128), (4, 8, 64), (4, 2, 64), (2, 2, 192)]


def _grouped_case(rep, dtype, ts, g=4, dh=128, t=2 * GB):
    import jax.numpy as jnp

    rng = np.random.RandomState(5)
    s = len(ts)
    q = jnp.asarray(rng.randn(s, g * rep * dh), jnp.float32)
    kn, vn = (jnp.asarray(rng.randn(s, g * dh), jnp.float32)
              for _ in range(2))
    kv = {n: jnp.asarray(rng.randn(s, t, g * dh), dtype) for n in "kv"}
    return q, kn, vn, kv, jnp.asarray(ts, jnp.int32), dict(
        n_head=g * rep, n_kv_head=g, scale=dh ** -0.5)


@pytest.mark.parametrize("case", sorted(GROUPED_TS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rep,g,dh", GROUPINGS)
def test_grouped_kernel_matches_the_grouped_form(rep, g, dh, dtype, case):
    """The append and the kernel's read against the XLA form: the same
    context (fp32: the order of the sums differs; bf16: the weights are
    rounded before they are normalised, not after), bit-equal leaves,
    zero rows for idle slots — for grouped heads, for ONE query head a
    K/V head (the heads of a unit consecutive rows) and for 64-lane
    heads (two heads' contexts leave the kernel as one lane tile), over
    a rung of two blocks and of one."""
    t = GB if case == "one_block_rung" else 2 * GB
    q, kn, vn, kv, ts, kw = _grouped_case(rep, dtype, GROUPED_TS[case], g=g,
                                          dh=dh, t=t)
    want, kv_want = da.grouped_masked_decode_attention(q, kn, vn, kv, ts,
                                                       **kw)
    kv_got = da.append_rows(kv, kn, vn, ts)
    got = da.grouped_decode_attention(
        q, kv_got["k"], kv_got["v"], ts,
        da.decode_work_items(ts, t, GB, GT), block=GB, tail=GT,
        interpret=True, **kw)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=2e-6 if dtype == "float32" else 2e-2)
    for n in "kv":
        assert np.array_equal(np.asarray(kv_got[n].astype("float32")),
                              np.asarray(kv_want[n].astype("float32")))
    assert not np.asarray(got)[np.asarray(ts) < 0].any()


@pytest.mark.parametrize("rep,g,heads,dh", [
    (7, 4, 1, 128), (7, 4, 2, 128), (1, 6, 1, 128), (1, 6, 3, 128),
    (4, 8, 2, 64), (4, 8, 4, 64)])
def test_grouped_kernel_scores_any_number_of_heads_in_one_product(
        rep, g, heads, dh, monkeypatch):
    """The K/V heads a unit holds are a parameter of the q layout alone
    (a head's lane offset and width): one, two or all four heads a
    product give the same context, so do one, three or all six where a
    head is ONE row of its unit, and one pair, two or all four of 64-lane
    heads (units that are whole lane tiles)."""
    q, kn, vn, kv, ts, kw = _grouped_case(rep, "bfloat16",
                                          GROUPED_TS["edges"], g=g, dh=dh)
    kv = da.append_rows(kv, kn, vn, ts)
    args = (q, kv["k"], kv["v"], ts, da.decode_work_items(ts, 2 * GB, GB, GT))
    named = dict(block=GB, tail=GT, interpret=True, **kw)
    want = da.grouped_decode_attention(*args, **named)
    monkeypatch.setattr(da, "_GROUPED_HEADS", heads)
    got = da.grouped_decode_attention(*args, **named)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=1e-5)


#: ``ts`` of K-row cases (two blocks of GB, T = 2 * GB), by the edge named
GROUPED_ROWS_TS = {
    # the K rows straddle the block's edge: of the slot's LAST block the
    # earlier rows see nothing (ts = GB - 1) or only its first positions
    "straddle_a_block": lambda k: [GB - 1, GB - k + 1, GB, GT - 1],
    # ts + K - 1 at the rung's end and past it (the rows past it dropped)
    "rung_end": lambda k: [2 * GB - k, 2 * GB - k + 1, 2 * GB - 1],
    "from_zero": lambda k: [0, 0, 1],
    # idle slots between live ones: zero context, leaf untouched
    "idle_between": lambda k: [-1, 40, -1, GB + 3, -1],
}


def _grouped_rows_case(rep, dtype, ts, k, g=4, dh=128, seed=5):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    s, t = len(ts), 2 * GB
    q = jnp.asarray(rng.randn(s, k, g * rep * dh), jnp.float32)
    kn, vn = (jnp.asarray(rng.randn(s, k, g * dh), jnp.float32)
              for _ in range(2))
    kv = {n: jnp.asarray(rng.randn(s, t, g * dh), dtype) for n in "kv"}
    return q, kn, vn, kv, jnp.asarray(ts, jnp.int32), dict(
        n_head=g * rep, n_kv_head=g, scale=dh ** -0.5)


def _grouped_rows_kernel(q, kn, vn, kv, ts, block=GB, tail=GT, **kw):
    """One fresh row a slot or ``K`` through the append and the kernel,
    as ``make_decode_attention`` sends them on a TPU (interpret mode)."""
    fresh, t = 1 if q.ndim == 2 else q.shape[1], kv["k"].shape[1]
    kv = da.append_rows(kv, kn, vn, ts)
    return da.grouped_decode_attention(
        q, kv["k"], kv["v"], ts, da.decode_work_items(
            da.last_fresh_row(ts, fresh, t), t, block, tail),
        block=block, tail=tail, interpret=True, **kw), kv


@pytest.mark.parametrize("case", sorted(GROUPED_ROWS_TS))
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("rep,dh", [(7, 128), (8, 128), (4, 64)])
@pytest.mark.parametrize("k", [2, 3])
def test_grouped_kernel_takes_k_fresh_rows(k, rep, dh, dtype, case):
    """``K`` fresh rows a slot through the kernel against the XLA form
    of the same layout (``_grouped_rows_attention``): the same context
    for every row — those past the rung's end too, which read every
    position and are not written — bit-equal leaves, zero rows and
    untouched leaves for idle slots, and no NaN where a row's last block
    is fully masked for it; over 64-lane heads too (the chooser sends
    their K rows where it sends their one)."""
    ts = GROUPED_ROWS_TS[case](k)
    q, kn, vn, kv, ts, kw = _grouped_rows_case(rep, dtype, ts, k, dh=dh)
    want, kv_want = da.grouped_masked_decode_attention(q, kn, vn, kv, ts,
                                                       **kw)
    got, kv_got = _grouped_rows_kernel(q, kn, vn, kv, ts, **kw)
    assert got.shape == q.shape and np.isfinite(np.asarray(got)).all()
    np.testing.assert_allclose(np.asarray(got), np.asarray(want), rtol=0,
                               atol=2e-6 if dtype == "float32" else 2e-2)
    idle = np.asarray(ts) < 0
    for n in "kv":
        assert np.array_equal(np.asarray(kv_got[n].astype("float32")),
                              np.asarray(kv_want[n].astype("float32")))
        assert np.array_equal(np.asarray(kv_got[n].astype("float32"))[idle],
                              np.asarray(kv[n].astype("float32"))[idle])
    assert not np.asarray(got)[idle].any()


@pytest.mark.parametrize("k", [2, 3])
def test_grouped_kernel_row_reads_nothing_past_its_own_position(k):
    """Row ``j`` reads the positions ``<= ts + j``: garbage in the leaf
    beyond the slot's last fresh row changes no row's context, and other
    fresh rows after ``j`` change nothing of rows ``<= j``."""
    import jax.numpy as jnp

    ts = [3, GB - 1, GB + GT, 2 * GB - k - 1]
    q, kn, vn, kv, ts, kw = _grouped_rows_case(8, "float32", ts, k)
    want, _ = _grouped_rows_kernel(q, kn, vn, kv, ts, **kw)
    beyond = (jnp.arange(2 * GB)[None, :] >= (ts + k)[:, None])[..., None]
    spoiled = {n: jnp.where(beyond, 1e4, kv[n]) for n in "kv"}
    got, _ = _grouped_rows_kernel(q, kn, vn, spoiled, ts, **kw)
    np.testing.assert_array_equal(np.asarray(got), np.asarray(want))
    for j in range(k - 1):      # later fresh rows replaced
        later = jnp.arange(k)[None, :, None] > j
        got, _ = _grouped_rows_kernel(
            q, jnp.where(later, 7.0, kn), jnp.where(later, -7.0, vn), kv,
            ts, **kw)
        np.testing.assert_array_equal(np.asarray(got)[:, :j + 1],
                                      np.asarray(want)[:, :j + 1])


@pytest.mark.parametrize("k", [2, 3])
def test_grouped_work_list_of_k_rows_is_the_last_rows(k):
    """The work list of a ``K``-row read is the one-row list at the
    slot's LAST fresh row (the rung's last where that passes the end):
    its items add up to ``kv_positions_read`` there — what a builder's
    ``"kv"`` ``PositionRead`` hands the server's counter for a
    speculative round."""
    import jax.numpy as jnp

    seq_len = 4096
    leaves = dict(width=1024, dtype="bfloat16", n_head=64, n_kv_head=8)
    block, tail = da.step_read_sizes(seq_len, backend="tpu", **leaves)
    ts = np.asarray([-1, 0, tail - k, tail - 1, block - 1, block,
                     seq_len - k, seq_len - 1, 1500], np.int32)
    last = da.last_fresh_row(ts, k, seq_len)
    assert last.tolist() == [
        -1 if t < 0 else min(t + k - 1, seq_len - 1) for t in ts.tolist()]
    assert np.array_equal(
        np.asarray(da.last_fresh_row(jnp.asarray(ts), k, seq_len)), last)
    assert da.last_fresh_row(ts, 1, seq_len) is ts
    n, slot, _, rows = (np.asarray(x) for x in da.decode_work_items(
        jnp.asarray(last), seq_len, block, tail))
    read = np.bincount(slot[:n[0]], weights=rows[:n[0]],
                       minlength=len(ts)).astype(int)
    want = np.where(ts >= 0, da.kv_positions_read(last, block, tail), 0)
    assert read.tolist() == want.tolist()
    assert np.all(want[1:] >= last[1:] + 1)
    assert da.step_positions_read(last[1:], seq_len, backend="tpu",
                                  **leaves).tolist() == want[1:].tolist()


def test_unit_width_follows_from_the_shape():
    """K/V heads a product: all four at smallthinker's and Falcon's
    one-row shapes (8 rows a head), a unit of at most
    ``_GROUPED_UNIT_ROWS`` query rows at K-EXAONE's two-row one (16 rows
    a head, 8 heads), one head where a single head passes it; a number
    said (the tool's knob) is taken where it divides the heads."""
    assert da._unit_heads(4, 8) == 4
    rows = da._GROUPED_UNIT_ROWS
    assert da._unit_heads(8, 16) == max(
        h for h in (1, 2, 4, 8) if h * 16 <= rows or h == 1)
    assert da._unit_heads(8, 2 * rows) == 1
    assert da._unit_heads(8, 16, 8) == 8 and da._unit_heads(8, 16, 2) == 2
    assert da._unit_heads(8, 16, 3) == da._unit_heads(8, 16)
    # ONE query row a head: a head is a row, so olmo_hybrid's 30 heads
    # are one unit (grouped heads and K rows keep whole fp32 tiles a head)
    assert [da._head_rows(k, r) for k, r in ((1, 1), (1, 5), (1, 8), (2, 8),
                                             (2, 1))] == [1, 8, 8, 16, 8]
    assert da._unit_heads(30, da._head_rows(1, 1)) == 30
    assert da._unit_heads(2 * rows, 1) == rows
    assert da._unit_heads(30, 1, 6) == 6
    # heads of 64 lanes go two a lane tile: a unit is whole pairs (lfm2's
    # 8 heads of 8 rows one unit; a pair however many rows it holds)
    assert [da._heads_a_tile(d) for d in (64, 128, 192, 256, 96, 32)] == [
        2, 1, 2, 1, 0, 0]
    assert da._unit_heads(8, 8, pair=2) == 8
    assert da._unit_heads(6, 16, pair=2) == 2
    assert da._unit_heads(10, 8, pair=2) == 2
    assert da._unit_heads(8, 2 * rows, pair=2) == 2
    assert da._unit_heads(8, 8, 4, pair=2) == 4
    assert da._unit_heads(8, 8, 1, pair=2) == 8     # a knob no pair: the rule


def test_block_follows_from_the_slab_a_leaf_holds():
    """Blocks of ``_GROUPED_BLOCK`` positions where a leaf's slab of
    them is within ``_GROUPED_SLAB`` bytes (every grouped cell: 512
    lanes of bf16 at Falcon and smallthinker, 1,024 at K-EXAONE), halved
    until it is where the leaf is wider (olmo_hybrid's 3,840 lanes:
    256), never under a tail of 16 rows; the tail is the block over the
    classes."""
    def sizes(width, g, rep=1, dtype="bfloat16", seq_len=1024):
        return da.step_read_sizes(seq_len, width, dtype, n_head=g * rep,
                                  n_kv_head=g, backend="tpu")

    block, classes = da._GROUPED_BLOCK, da._GROUPED_CLASSES
    for width, g, rep in ((512, 4, 5), (512, 4, 7), (1024, 8, 8)):
        assert sizes(width, g, rep, seq_len=4096) == (block, block // classes)
    assert sizes(3840, 30) == (256, 256 // classes)
    assert 256 * 3840 * 2 <= da._GROUPED_SLAB < 512 * 3840 * 2
    assert sizes(1024, 8, 8, "float32") == (block, block // classes)
    assert sizes(2048, 16, 4, "float32") == (block // 2, block // 2 // classes)
    # however wide: whole sublane tiles of bf16 a class
    assert sizes(128 * 1024, 1024, 2) == (16 * classes, 16)
    assert sizes(3840, 30, seq_len=128) == (128, 128 // classes)


@pytest.mark.parametrize("n_head,n_kv_head", [(28, 4), (32, 8)],
                         ids=["heads_of_128", "heads_of_64"])
@pytest.mark.parametrize("seq_len", [1024, 16384, 512, 2048])
def test_grouped_work_list_reads_what_kv_positions_read_says(
        seq_len, n_head, n_kv_head):
    """The one rounding: with the grouped kernel's sizes a slot's items
    add up to ``kv_positions_read`` (whole blocks, then the last in
    classes of the tail), which is also what a builder's
    ``"kv"`` ``PositionRead`` hands the server's counter; off the
    TPU that rule says the whole rung.  Leaves 512 wide as four heads of
    128 and as eight of 64 (lfm2's, whose rung is 2,048) read alike."""
    import jax.numpy as jnp

    leaves = dict(width=512, dtype="bfloat16", n_head=n_head,
                  n_kv_head=n_kv_head)
    assert da.step_read_sizes(seq_len, **leaves) is None        # the CPU
    block, tail = da.step_read_sizes(seq_len, backend="tpu", **leaves)
    assert seq_len % block == 0 and block % tail == 0 and tail % 16 == 0
    ts = np.asarray([-1, 0, tail - 1, tail, block - 1, block,
                     seq_len - 1, seq_len // 2 + 3], np.int32)
    n, slot, _, rows = (np.asarray(x) for x in da.decode_work_items(
        jnp.asarray(ts), seq_len, block, tail))
    read = np.bincount(slot[:n[0]], weights=rows[:n[0]],
                       minlength=len(ts)).astype(int)
    want = np.where(ts >= 0, da.kv_positions_read(ts, block, tail), 0)
    assert read.tolist() == want.tolist()
    assert np.all(want[1:] >= ts[1:] + 1) and np.all(want[1:] - ts[1:] <= tail)
    assert np.all(rows[:n[0]] % tail == 0) and rows[:n[0]].max() <= block
    live = ts[1:]
    assert da.step_positions_read(live, seq_len, backend="tpu",
                                  **leaves).tolist() == want[1:].tolist()
    assert da.step_positions_read(live, seq_len, **leaves).tolist() == [
        seq_len] * len(live)
    # no kernel for heads that no whole lane tiles hold (96 lanes; an odd
    # number of 64-lane heads), int8, a ragged rung, nor for ONE query
    # head a K/V head over fp32 leaves (the ragged kernel's) or of 64
    # lanes — over bf16 ones of whole lane tiles it is the same read
    one_each = dict(n_head=n_kv_head)
    for change in (dict(width=96 * n_kv_head), dict(dtype="int8"),
                   dict(width=192, n_head=12, n_kv_head=3),
                   dict(dtype="float32", **one_each), dict(n_head=2),
                   dict(width=64 * n_kv_head, **one_each)):
        assert da.step_read_sizes(seq_len, backend="tpu",
                                  **{**leaves, **change}) is None
    whole_tiles = n_kv_head == 4
    assert da.step_read_sizes(
        seq_len, backend="tpu", **{**leaves, **one_each}) == (
            (block, tail) if whole_tiles else None)
    assert da.step_read_sizes(seq_len, backend="tpu", **{
        **leaves, "dtype": "float32"}) is not None
    assert da.step_positions_read(
        live, seq_len, backend="tpu", **{**leaves, **one_each}).tolist() == (
            want[1:].tolist() if whole_tiles else [seq_len] * len(live))
    assert da.step_read_sizes(da._GROUPED_BLOCK + 128, backend="tpu",
                              **leaves) is None


def _two_grouped_layers(sharding=None, shape="smallthinker"):
    """``(f, abstract arguments, equations)``: a step's two global layers
    at ``smallthinker_21b_a3b``'s shapes (40 slots x 16,384 x 512 bf16, 7
    query heads a K/V head) through the kernel, as
    ``tools/time_grouped_decode.py --build`` builds them; ``shape``
    ``k_exaone``: a self-drafting round's two rung-long leaves (128
    slots x 4,096 x 1,024 bf16, 8 query heads a K/V head, TWO fresh rows
    a slot); ``olmo``: two of ``olmo_hybrid_7b``'s full layers (80 slots
    x 1,024 x 3,840 bf16, 30 K/V heads, ONE query head each)."""
    import importlib.util
    import os
    import sys

    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    sys.path.insert(0, tools)
    try:
        spec = importlib.util.spec_from_file_location(
            "time_grouped_decode", os.path.join(tools,
                                                "time_grouped_decode.py"))
        tool = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tool)
    finally:
        sys.path.remove(tools)
    f, args = tool.two_layer_program(da, tool.SHAPES[shape], "bfloat16",
                                     False, sharding, tool.ROWS[shape])
    return f, args, tool.equations


#: equations of the grouped kernel's body at the cell's shapes: 256 as
#: written (PR 44: 0.88 s to trace and 0.30 s to lower two layers in a
#: fresh process on the chip), half as many again allowed (what a process
#: pays to trace and lower a kernel grows with its body:
#: _BLOCK_KERNEL_EQUATIONS_MAX)
_GROUPED_KERNEL_EQUATIONS_MAX = 384


@pytest.mark.parametrize("shape", ["smallthinker", "k_exaone", "olmo"])
def test_grouped_kernel_is_traced_once_and_its_body_stays_small(shape):
    """A step's two global layers — a self-drafting round's two
    rung-long leaves, read at two fresh rows a slot; two full layers of
    one query head a K/V head — share ONE traced function whose body
    holds one loop over the items, a class switch for the starts and one
    for the waits, and ONE scoring routine (30 one-row heads add a loop
    over a unit's rows, not a line a head)."""
    import jax

    f, args, equations = _two_grouped_layers(shape=shape)
    calls = [e for e in jax.make_jaxpr(f)(*args).jaxpr.eqns
             if "jaxpr" in e.params
             and e.params.get("name") == "_grouped"]
    assert len(calls) == 2      # one a layer ...
    # ... of one function traced once
    assert calls[0].params["jaxpr"] is calls[1].params["jaxpr"]
    kernels = [e for e in calls[0].params["jaxpr"].jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    assert len(kernels) == 1
    body = equations(kernels[0].params["jaxpr"])
    assert 60 < body <= _GROUPED_KERNEL_EQUATIONS_MAX, body


def _two_sparse_layers(sharding=None):
    """``(f, abstract arguments, equations)``: the block kernel called on
    two layers' leaves at ``minicpm_sala``'s shapes (64 slots x 32768 x
    256 bf16, 98 blocks of 64, the rule's runs declared), as a step with
    two sparse layers calls it, and the count of a jaxpr's equations:
    the program ``tools/time_block_sparse.py --build`` times."""
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "time_block_sparse", os.path.join(
            os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
            "tools", "time_block_sparse.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    f, args = tool.two_layer_program(
        da, (64, 32768, 2, 128, 16, 64, 98), "bfloat16", ((0, 1), (1, 33)),
        interpret=False, sharding=sharding)
    return f, args, tool.equations


#: equations of the block kernel's body at the cell's shapes: 277 as
#: written (PR 42), half as many again allowed.  PR 41's body read 2,317
#: on this count and cost every process of the cell 8-9 s of set-up,
#: compile cache hit or not: what a kernel's trace and lowering cost
#: grows with its body, and no compile time shows it.
_BLOCK_KERNEL_EQUATIONS_MAX = 415


def test_block_kernel_is_traced_once_and_its_body_stays_small():
    """What building the kernel costs a process, as a count (a clock
    would not be steady under six workers): a step's two sparse layers
    share ONE traced function, and its body holds a unit's start and its
    scoring once a unit kind — not once a unit, head and prologue, nor a
    unit's whole key axis written out."""
    import jax

    f, args, equations = _two_sparse_layers()
    calls = [e for e in jax.make_jaxpr(f)(*args).jaxpr.eqns
             if "jaxpr" in e.params]
    assert len(calls) == 2      # one a layer ...
    # ... of one function traced once
    assert calls[0].params["jaxpr"] is calls[1].params["jaxpr"]
    kernels = [e for e in calls[0].params["jaxpr"].jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    assert len(kernels) == 1
    body = equations(kernels[0].params["jaxpr"])
    assert 100 < body <= _BLOCK_KERNEL_EQUATIONS_MAX, body


#: equations of the dense latent kernel's body at the cell's shapes: 183
#: as written (PR 61), half as many again allowed (what a process pays to
#: trace and lower a kernel grows with its body:
#: _BLOCK_KERNEL_EQUATIONS_MAX)
_DENSE_LATENT_KERNEL_EQUATIONS_MAX = 274


def test_dense_latent_kernel_is_traced_once_and_its_body_stays_small():
    """A round's six leaves (the layers' and the module's) at
    ``openpangu_ultra_moe_718b``'s shapes share ONE traced function whose
    body holds one loop over the items and the block's chain twice (the
    masked blocks and the others), whatever the slots and the rung."""
    import importlib.util
    import os
    import sys

    import jax

    tools = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "tools")
    sys.path.insert(0, tools)
    try:
        spec = importlib.util.spec_from_file_location(
            "time_dense_latent", os.path.join(tools, "time_dense_latent.py"))
        tool = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(tool)
    finally:
        sys.path.remove(tools)
    f, args = tool.round_program(da, tool.SHAPE)
    calls = [e for e in jax.make_jaxpr(f)(*args).jaxpr.eqns
             if "jaxpr" in e.params
             and e.params.get("name") == "_dense_latent"]
    assert len(calls) == 6      # one a leaf ...
    # ... of one function traced once
    assert all(c.params["jaxpr"] is calls[0].params["jaxpr"] for c in calls)
    kernels = [e for e in calls[0].params["jaxpr"].jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    assert len(kernels) == 1
    body = tool.equations(kernels[0].params["jaxpr"])
    assert 60 < body <= _DENSE_LATENT_KERNEL_EQUATIONS_MAX, body
