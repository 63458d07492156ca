"""The latent leaves of ``paddle_tpu.decode_attention``: the in-place
append and the read of the positions named for a row
(``selected_latent_attention``) against the plain masked form over the
whole rung (``masked_latent_attention``) and against numpy.  CPU only, no
described-v5e compile (``tests/test_decode_attention.py`` is the suite's
long pole already).
"""
import numpy as np
import pytest

from paddle_tpu import decode_attention as da

S, T, DC, DR, DI, H = 4, 40, 16, 8, 12, 3


def _filled(dtype, seed=0):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    kv = da.latent_leaves(S, T, DC + DR, DI, dtype)
    # whole 128-lane tiles, the lanes past the width zeros
    assert kv["latent"].shape == kv["index_k"].shape == (S, T, 128)
    assert da.latent_leaves(2, 8, 576, 128, dtype)["latent"].shape == (
        2, 8, 640)
    fill = lambda width: np.pad(rng.randn(S, T, width),
                                ((0, 0), (0, 0), (0, 128 - width)))
    return {"latent": jnp.asarray(fill(DC + DR), dtype),
            "index_k": jnp.asarray(fill(DI), dtype)}, rng


def test_the_append_writes_one_row_a_live_slot_in_place():
    import jax.numpy as jnp

    kv, rng = _filled("float32")
    lat = jnp.asarray(rng.randn(S, DC + DR).astype("float32"))
    idx = jnp.asarray(rng.randn(S, DI).astype("float32"))
    ts = jnp.asarray([5, -1, 39, 40], jnp.int32)   # live, idle, last, past
    out = da.append_latent_rows(kv, lat, idx, ts)
    for leaf, new in (("latent", lat), ("index_k", idx)):
        want = np.asarray(kv[leaf]).copy()
        width = new.shape[1]
        want[0, 5, :width], want[2, 39, :width] = (np.asarray(new[0]),
                                                   np.asarray(new[2]))
        np.testing.assert_array_equal(np.asarray(out[leaf]), want)


@pytest.mark.parametrize("dtype,atol", [("float32", 2e-6),
                                        ("bfloat16", 2e-2)])
def test_the_selected_read_equals_the_masked_form_and_numpy(dtype, atol):
    import jax.numpy as jnp

    kv, rng = _filled(dtype, seed=1)
    q = jnp.asarray(rng.randn(S, H, DC + DR).astype("float32"))
    ts = jnp.asarray([30, 3, -1, 39], jnp.int32)
    k = 8
    # a slot's list as ``select_positions`` gives it — ascending, unique,
    # in range (the read's precondition): some past ts, some not valid
    sel = np.stack([np.sort(rng.permutation(T)[:k])
                    for _ in range(S)]).astype(np.int32)
    valid = rng.rand(S, k) < 0.8
    before = da.LATENT_LOWERED.labels(path="xla").value
    got = np.asarray(da.selected_latent_attention(
        q, kv, ts, jnp.asarray(sel), jnp.asarray(valid), d_value=DC,
        scale=0.3))
    assert da.LATENT_LOWERED.labels(path="xla").value == before + 1
    allowed = np.zeros((S, T), bool)
    for s in range(S):
        for j in range(k):
            if valid[s, j] and sel[s, j] <= int(ts[s]):
                allowed[s, sel[s, j]] = True
    masked = np.asarray(da.masked_latent_attention(
        q, kv, jnp.asarray(allowed), d_value=DC, scale=0.3))
    np.testing.assert_allclose(got, masked, atol=atol)
    rows = np.asarray(kv["latent"].astype("float32"), np.float64)
    for s in range(S):
        at = np.flatnonzero(allowed[s])
        if not len(at):
            assert not got[s].any()         # idle, or nothing named
            continue
        a = 0.3 * np.asarray(q[s], np.float64) @ rows[s, at, :DC + DR].T
        p = np.exp(a - a.max(-1, keepdims=True))
        want = (p / p.sum(-1, keepdims=True)) @ rows[s, at, :DC]
        np.testing.assert_allclose(got[s], want, atol=max(atol, 1e-5) * 3)
    assert got.shape == (S, H, DC) and not got[2].any()


@pytest.mark.parametrize("dtype,atol", [("float32", 2e-6),
                                        ("bfloat16", 2e-2)])
def test_the_read_of_a_selected_list_equals_the_masked_form(dtype, atol):
    """The step's pair as it runs: ``select_positions`` lists a slot's
    best positions in ascending order (its lowering counted: ``threshold``)
    and the selected read, told the list is sorted and unique, gives what
    the masked form gives over the same SET — rows idle, under ``k`` live
    and far past it."""
    import jax.numpy as jnp

    from paddle_tpu import latent_sparse_lm as ls

    kv, rng = _filled(dtype, seed=2)
    q = jnp.asarray(rng.randn(S, H, DC + DR).astype("float32"))
    ts = jnp.asarray([30, 3, -1, 39], jnp.int32)
    k = 8
    scores = jnp.asarray(np.round(rng.randn(S, T) * 2) / 2, jnp.float32)
    before = da.INDEX_SELECT_LOWERED.labels(path="threshold").value
    sel, valid = ls.select_positions(scores, ts, k)
    assert da.INDEX_SELECT_LOWERED.labels(
        path="threshold").value == before + 1
    got = np.asarray(da.selected_latent_attention(
        q, kv, ts, sel, valid, d_value=DC, scale=0.3))
    sel, valid = np.asarray(sel), np.asarray(valid)
    assert (np.diff(sel, axis=1) > 0).all()
    allowed = np.zeros((S, T), bool)
    for s in range(S):
        allowed[s, sel[s][valid[s]]] = True
    assert allowed.sum(1).tolist() == [8, 4, 0, 8]
    masked = np.asarray(da.masked_latent_attention(
        q, kv, jnp.asarray(allowed), d_value=DC, scale=0.3))
    np.testing.assert_allclose(got, masked, atol=atol)
    assert not got[2].any()
