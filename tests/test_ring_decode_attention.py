"""The RING leaf of ``decode_attention``: ``kv_leaves(..., window=W)``
is ``min(T, W)`` rows long, position ``p`` lives in row ``p mod W``, and
its append-and-read equals a full-length sequence leaf read under a
banded causal mask — below the window, at it, and twice past it; one
fresh row and ``K`` fresh rows that straddle the wrap; idle rows; fp32
and bf16 leaves."""
import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import decode_attention as da

S, G, REP, DH, W, T = 3, 2, 2, 8, 8, 32
H = G * REP
SCALE = 1.0 / np.sqrt(DH)


def banded_reference(q, k_all, v_all, pos, window):
    """``q`` [S, K, H*DH] at positions ``pos`` [S, K] against the whole
    history ``k_all``, ``v_all`` [S, T, G*DH]: plain masked softmax, the
    query at p reading keys p - window + 1 .. p."""
    s, kq, _ = q.shape
    t = k_all.shape[1]
    qh = q.reshape(s, kq, G, REP, DH) * SCALE
    kh, vh = (x.reshape(s, t, G, DH) for x in (k_all, v_all))
    sc = np.einsum("skgrd,stgd->skgrt", qh, kh)
    at = np.arange(t)[None, None, :]
    ok = (at <= pos[..., None]) & (pos[..., None] - at < window)
    sc = np.where(ok[:, :, None, None, :], sc, -1e9)
    w = np.exp(sc - sc.max(-1, keepdims=True))
    w = w / w.sum(-1, keepdims=True)
    return np.einsum("skgrt,stgd->skgrd", w, vh).reshape(s, kq, H * DH)


def history(rng, dtype):
    """K/V rows of every position, rounded as the leaf stores them."""
    k, v = (rng.randn(S, T, G * DH).astype("float32") for _ in range(2))
    return tuple(np.asarray(jnp.asarray(x, dtype).astype(jnp.float32))
                 for x in (k, v))


def ring_filled_to(k_all, v_all, ts, dtype):
    """Ring leaves as ``ts`` one-token steps would have left them."""
    kv = da.kv_leaves(S, T, G, DH, dtype, window=W)
    assert kv["k"].shape == (S, W, G * DH)
    k, v = np.zeros((S, W, G * DH), "float32"), np.zeros(
        (S, W, G * DH), "float32")
    for s in range(S):
        for p in range(max(ts[s], 0)):
            k[s, p % W], v[s, p % W] = k_all[s, p], v_all[s, p]
    return {"k": jnp.asarray(k, dtype), "v": jnp.asarray(v, dtype)}


def test_a_ring_leaf_is_as_long_as_the_lesser_of_rung_and_window():
    assert da.kv_leaves(2, 32, G, DH, jnp.bfloat16, window=8)["k"].shape \
        == (2, 8, G * DH)
    assert da.kv_leaves(2, 4, G, DH, jnp.bfloat16, window=8)["v"].shape \
        == (2, 4, G * DH)
    assert da.kv_leaves(2, 32, G, DH, jnp.float32)["k"].shape \
        == (2, 32, G * DH)


def test_ring_positions_names_the_position_each_row_holds():
    got = np.asarray(da.ring_positions(jnp.asarray([0, 1, 5, 8, 19]), 8))
    assert (got[0] < 0).all()                       # nothing written yet
    assert got[1].tolist()[0] == 0 and (got[1][1:] < 0).all()
    assert got[2].tolist()[:5] == [0, 1, 2, 3, 4] and (got[2][5:] < 0).all()
    assert got[3].tolist() == list(range(8))
    # at ts = 19 the ring holds 11 .. 18: row r holds the p with p % 8 == r
    assert got[4].tolist() == [16, 17, 18, 11, 12, 13, 14, 15]


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("ts", [[2, 5, 0], [7, 8, 9], [16, 17, -1],
                                [23, 15, 30]])
def test_one_fresh_row_equals_the_banded_mask_over_the_whole_history(
        ts, dtype, tol):
    rng = np.random.RandomState(3)
    k_all, v_all = history(rng, dtype)
    ts = np.asarray(ts, np.int32)
    q = rng.randn(S, H * DH).astype("float32")
    kv = ring_filled_to(k_all, v_all, ts, dtype)
    pos = np.maximum(ts, 0)
    k_new = k_all[np.arange(S), pos]
    v_new = v_all[np.arange(S), pos]
    attend = da.make_decode_attention(
        jnp.asarray(ts), kv, n_head=H, n_kv_head=G, scale=SCALE, window=W)
    ctx, out = attend(jnp.asarray(q), jnp.asarray(k_new), jnp.asarray(v_new),
                      kv)
    want = banded_reference(q[:, None], k_all, v_all, pos[:, None], W)[:, 0]
    for s in range(S):
        if ts[s] < 0:       # idle: zero context, row not written
            assert not np.asarray(ctx[s]).any()
            np.testing.assert_array_equal(np.asarray(out["k"][s], "float32"),
                                          np.asarray(kv["k"][s], "float32"))
            continue
        np.testing.assert_allclose(np.asarray(ctx[s]), want[s], atol=tol,
                                   rtol=tol)
        # the row landed at its position modulo the window
        np.testing.assert_allclose(
            np.asarray(out["k"][s, ts[s] % W], "float32"), k_new[s],
            atol=1e-6)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("ts,rows", [([0, 3, 6], 4),      # below, to the wrap
                                     ([5, 6, 13], 5),     # straddle the wrap
                                     ([14, -1, 21], 6),   # past it; an idle row
                                     ([2, 9, 17], 12)])   # more rows than ring
def test_fresh_rows_read_the_old_ring_and_themselves_before_overwriting(
        ts, rows, dtype, tol):
    rng = np.random.RandomState(5)
    k_all, v_all = history(rng, dtype)
    ts = np.asarray(ts, np.int32)
    q = rng.randn(S, rows, H * DH).astype("float32")
    kv = ring_filled_to(k_all, v_all, ts, dtype)
    pos = np.maximum(ts, 0)[:, None] + np.arange(rows)[None, :]
    take = lambda x: np.stack([x[s, pos[s]] for s in range(S)])
    before = da.RING_LOWERED.labels(form="rows").value
    ctx, out = da.grouped_masked_decode_attention(
        jnp.asarray(q), jnp.asarray(take(k_all)), jnp.asarray(take(v_all)),
        kv, jnp.asarray(ts), n_head=H, n_kv_head=G, scale=SCALE, window=W)
    assert da.RING_LOWERED.labels(form="rows").value == before + 1
    want = banded_reference(q, k_all, v_all, pos, W)
    for s in range(S):
        if ts[s] < 0:
            assert not np.asarray(ctx[s]).any()
            continue
        np.testing.assert_allclose(np.asarray(ctx[s]), want[s], atol=tol,
                                   rtol=tol)
        # afterwards the ring holds the last W positions, each in its row
        end = ts[s] + rows
        for p in range(max(end - W, 0), end):
            np.testing.assert_allclose(
                np.asarray(out["k"][s, p % W], "float32"), k_all[s, p],
                atol=1e-6)


def test_steps_through_the_ring_equal_steps_through_a_full_leaf():
    """Twice past the window, step by step under jit: the ring against a
    sequence leaf of the whole rung read under the banded mask."""
    rng = np.random.RandomState(7)
    k_all, v_all = history(rng, jnp.float32)
    qs = rng.randn(T, S, H * DH).astype("float32")
    kv = da.kv_leaves(S, T, G, DH, jnp.float32, window=W)

    @jax.jit
    def step(kv, q, k_new, v_new, ts):
        return da.make_decode_attention(
            ts, kv, n_head=H, n_kv_head=G, scale=SCALE, window=W)(
                q, k_new, v_new, kv)

    for t in range(3 * W):
        ts = np.full((S,), t, np.int32)
        ctx, kv = step(kv, jnp.asarray(qs[t]), jnp.asarray(k_all[:, t]),
                       jnp.asarray(v_all[:, t]), jnp.asarray(ts))
        want = banded_reference(qs[t][:, None], k_all, v_all,
                                ts[:, None], W)[:, 0]
        np.testing.assert_allclose(np.asarray(ctx), want, atol=2e-5,
                                   rtol=2e-5)


def test_the_choice_counts_the_ring_forms_and_leaves_the_others_alone():
    kv = da.kv_leaves(S, T, G, DH, jnp.float32, window=W)
    ts = jnp.asarray([1, 2, 3], jnp.int32)
    x = jnp.zeros((S, H * DH)), jnp.zeros((S, G * DH)), jnp.zeros(
        (S, G * DH))
    before = da.RING_LOWERED.labels(form="step").value
    da.make_decode_attention(ts, kv, n_head=H, n_kv_head=G, scale=SCALE,
                             window=W)(*x, kv)
    assert da.RING_LOWERED.labels(form="step").value == before + 1
    full = da.kv_leaves(S, T, G, DH, jnp.float32)
    da.make_decode_attention(ts, full, n_head=H, n_kv_head=G,
                             scale=SCALE)(*x, full)
    assert da.RING_LOWERED.labels(form="step").value == before + 1
