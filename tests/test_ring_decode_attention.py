"""The RING leaf of ``decode_attention``: ``kv_leaves(..., window=W)``
is ``min(T, W)`` rows long, position ``p`` lives in row ``p mod W``, and
its append-and-read equals a full-length sequence leaf read under a
banded causal mask — below the window, at it, and twice past it; one
fresh row and ``K`` fresh rows that straddle the wrap; idle rows; fp32
and bf16 leaves; a toy geometry and ``k_exaone_236b_a23b``'s class of
shape (eight query heads a K/V head of 128, ring = window = one lane
tile)."""
import collections

import numpy as np
import pytest

import jax
import jax.numpy as jnp

from paddle_tpu import decode_attention as da

Geometry = collections.namedtuple("Geometry", "S G REP DH W T")
TOY = Geometry(3, 2, 2, 8, 8, 32)
#: the cell's class of shape: the ring one lane tile, heads of one too
LANE_TILE = Geometry(3, 2, 8, 128, 128, 512)
S, G, REP, DH, W, T = TOY
H = G * REP
SCALE = 1.0 / np.sqrt(DH)


def banded_reference(q, k_all, v_all, pos, window, geo=TOY):
    """``q`` [S, K, H*DH] at positions ``pos`` [S, K] against the whole
    history ``k_all``, ``v_all`` [S, T, G*DH]: plain masked softmax, the
    query at p reading keys p - window + 1 .. p."""
    s, kq, _ = q.shape
    t = k_all.shape[1]
    qh = q.reshape(s, kq, geo.G, geo.REP, geo.DH) / np.sqrt(geo.DH)
    kh, vh = (x.reshape(s, t, geo.G, geo.DH) for x in (k_all, v_all))
    sc = np.einsum("skgrd,stgd->skgrt", qh, kh)
    at = np.arange(t)[None, None, :]
    ok = (at <= pos[..., None]) & (pos[..., None] - at < window)
    sc = np.where(ok[:, :, None, None, :], sc, -1e9)
    w = np.exp(sc - sc.max(-1, keepdims=True))
    w = w / w.sum(-1, keepdims=True)
    return np.einsum("skgrt,stgd->skgrd", w, vh).reshape(s, kq, -1)


def history(rng, dtype, geo=TOY):
    """K/V rows of every position, rounded as the leaf stores them."""
    k, v = (rng.randn(geo.S, geo.T, geo.G * geo.DH).astype("float32")
            for _ in range(2))
    return tuple(np.asarray(jnp.asarray(x, dtype).astype(jnp.float32))
                 for x in (k, v))


def ring_filled_to(k_all, v_all, ts, dtype, geo=TOY):
    """Ring leaves as ``ts`` one-token steps would have left them."""
    kv = da.kv_leaves(geo.S, geo.T, geo.G, geo.DH, dtype, window=geo.W)
    shape = (geo.S, geo.W, geo.G * geo.DH)
    assert kv["k"].shape == shape
    k, v = np.zeros(shape, "float32"), np.zeros(shape, "float32")
    for s in range(geo.S):
        for p in range(max(ts[s], 0)):
            k[s, p % geo.W], v[s, p % geo.W] = k_all[s, p], v_all[s, p]
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
@pytest.mark.parametrize("geo,ts,rows", [
    (TOY, [0, 3, 6], 4),                # below, to the wrap
    (TOY, [5, 6, 13], 5),               # straddle the wrap
    (TOY, [14, -1, 21], 6),             # past it; an idle row
    (TOY, [2, 9, 17], 12),              # more rows than ring
    (LANE_TILE, [126, 127, 300], 2),    # to the wrap, over it, past it
    (LANE_TILE, [255, -1, 383], 2),     # over the wrap twice; an idle row
    (LANE_TILE, [5, 200, 130], 130)],   # more rows than ring
    ids=["toy-below", "toy-straddle", "toy-idle", "toy-more_rows_than_ring",
         "lane_tile-straddle", "lane_tile-idle",
         "lane_tile-more_rows_than_ring"])
def test_fresh_rows_read_the_old_ring_and_themselves_before_overwriting(
        geo, ts, rows, dtype, tol):
    rng = np.random.RandomState(5)
    k_all, v_all = history(rng, dtype, geo)
    ts = np.asarray(ts, np.int32)
    q = rng.randn(geo.S, rows, geo.G * geo.REP * geo.DH).astype("float32")
    kv = ring_filled_to(k_all, v_all, ts, dtype, geo)
    pos = np.maximum(ts, 0)[:, None] + np.arange(rows)[None, :]
    take = lambda x: np.stack([x[s, pos[s]] for s in range(geo.S)])
    before = da.RING_LOWERED.labels(form="rows").value
    ctx, out = da.grouped_masked_decode_attention(
        jnp.asarray(q), jnp.asarray(take(k_all)), jnp.asarray(take(v_all)),
        kv, jnp.asarray(ts), n_head=geo.G * geo.REP, n_kv_head=geo.G,
        scale=1.0 / np.sqrt(geo.DH), window=geo.W)
    assert da.RING_LOWERED.labels(form="rows").value == before + 1
    want = banded_reference(q, k_all, v_all, pos, geo.W, geo)
    for s in range(geo.S):
        if ts[s] < 0:
            assert not np.asarray(ctx[s]).any()
            np.testing.assert_array_equal(np.asarray(out["k"][s], "float32"),
                                          np.asarray(kv["k"][s], "float32"))
            continue
        np.testing.assert_allclose(np.asarray(ctx[s]), want[s], atol=tol,
                                   rtol=tol)
        # afterwards the ring holds the last W positions, each in its row
        end = ts[s] + rows
        for p in range(max(end - geo.W, 0), end):
            np.testing.assert_allclose(
                np.asarray(out["k"][s, p % geo.W], "float32"), k_all[s, p],
                atol=1e-6)


@pytest.mark.parametrize("dtype,tol", [(jnp.float32, 2e-5),
                                       (jnp.bfloat16, 3e-2)])
@pytest.mark.parametrize("ts,rows", [
    ([126, 127, 300], 2),               # to the wrap, over it, past it
    ([255, -1, 383], 2),                # over the wrap twice; an idle row
    ([0, 1, 64], 2),                    # an empty ring, one row, half of it
    ([127, -1, 500], 3)],               # three rows a slot: padded rows
    ids=["straddle", "idle", "young", "three_rows"])
def test_the_ring_kernel_equals_the_banded_mask_and_the_xla_read(
        ts, rows, dtype, tol):
    """:func:`ring_rows_decode_attention` (interpret mode) at the cell's
    class of shape: the banded reference within the dtype's limit, the
    XLA read of the same rows far closer (one mathematics, another order
    of sums), the rings equal bit for bit."""
    geo = LANE_TILE
    rng = np.random.RandomState(11)
    k_all, v_all = history(rng, dtype, geo)
    ts = np.asarray(ts, np.int32)
    q = rng.randn(geo.S, rows, geo.G * geo.REP * geo.DH).astype("float32")
    kv = ring_filled_to(k_all, v_all, ts, dtype, geo)
    pos = np.maximum(ts, 0)[:, None] + np.arange(rows)[None, :]
    take = lambda x: np.stack([x[s, pos[s]] for s in range(geo.S)])
    args = (jnp.asarray(q), jnp.asarray(take(k_all)),
            jnp.asarray(take(v_all)), kv, jnp.asarray(ts))
    kw = dict(n_head=geo.G * geo.REP, n_kv_head=geo.G,
              scale=1.0 / np.sqrt(geo.DH), window=geo.W)
    assert da.ring_kernel_supported(kv, kw["n_head"], geo.G, rows)
    counted = [da.RING_LOWERED.labels(form="rows"),
               da.ROWS_LOWERED.labels(leaf="ring")]
    before = [c.value for c in counted]
    ctx, out = da.ring_rows_decode_attention(*args, interpret=True, **kw)
    assert [c.value for c in counted] == [b + 1 for b in before]
    xla, xla_out = da.grouped_masked_decode_attention(*args, **kw)
    np.testing.assert_allclose(np.asarray(ctx), np.asarray(xla), atol=2e-6)
    for leaf in ("k", "v"):
        np.testing.assert_array_equal(np.asarray(out[leaf], "float32"),
                                      np.asarray(xla_out[leaf], "float32"))
    want = banded_reference(q, k_all, v_all, pos, geo.W, geo)
    for s in range(geo.S):
        if ts[s] < 0:
            assert not np.asarray(ctx[s]).any()
            continue
        np.testing.assert_allclose(np.asarray(ctx[s]), want[s], atol=tol,
                                   rtol=tol)


@pytest.mark.parametrize("what,geo,dtype,rows,takes", [
    ("the cell's class of shape", LANE_TILE, jnp.bfloat16, 2, True),
    ("float32 leaves", LANE_TILE, jnp.float32, 2, True),
    ("one fresh row: the step form's", LANE_TILE, jnp.bfloat16, 1, False),
    ("more fresh rows than the vector unit takes", LANE_TILE, jnp.bfloat16,
     130, False),
    ("heads narrower than a lane tile", TOY, jnp.float32, 2, False)],
    ids=["lane_tile-bf16", "lane_tile-fp32", "one_row", "many_rows",
         "toy"])
def test_the_ring_kernel_takes_whole_lane_tile_heads_and_a_few_rows(
        what, geo, dtype, rows, takes):
    kv = da.kv_leaves(geo.S, geo.T, geo.G, geo.DH, dtype, window=geo.W)
    assert da.ring_kernel_supported(
        kv, geo.G * geo.REP, geo.G, rows) == takes, what


def test_int8_ring_leaves_are_not_the_ring_kernels():
    kv = da.kv_leaves(LANE_TILE.S, LANE_TILE.T, LANE_TILE.G, LANE_TILE.DH,
                      jnp.int8, window=LANE_TILE.W)
    assert not da.ring_kernel_supported(
        kv, LANE_TILE.G * LANE_TILE.REP, LANE_TILE.G, 2)


def test_a_rounds_window_layers_share_one_trace_of_the_ring_kernel(
        monkeypatch):
    """The layers of a round (``tools/time_ring_rows.py``'s program, at
    its rehearsal's shape) go through ONE jitted entry point: the
    kernel's body is traced once, whatever the layers."""
    import functools
    import importlib.util
    import os

    spec = importlib.util.spec_from_file_location(
        "time_ring_rows", os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "tools", "time_ring_rows.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    traced, real = [], da._ring_read
    monkeypatch.setattr(da, "_ring_read", lambda *a, **k: (
        traced.append(1), real(*a, **k))[1])
    da._ring_call.cache_clear()
    try:
        shape = tool.REHEARSAL
        assert shape[6] > 1                     # layers a pass
        f, _ = tool.program(functools.partial(
            da.ring_rows_decode_attention, interpret=True), shape, 2)
        jax.make_jaxpr(f)(*tool.inputs(shape))
        assert len(traced) == 1
    finally:
        da._ring_call.cache_clear()


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
