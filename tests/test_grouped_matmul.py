"""paddle_tpu.grouped_matmul: rows sorted by group against each group's
own matrix.

The Pallas TPU kernel runs here under interpret mode against the XLA
form (``jax.lax.ragged_dot``) and against a plain loop over the groups,
at ragged group sizes: empty groups (first, last, several in a row), a
group that straddles row tiles, one group holding every row, no group
holding any, rows that belong to no group.  It compiles for a described
v5e chip at the benchmark's widths in tests/test_v5e_compile.py
(one file loads the TPU's compiler).

Tolerance: every product and sum is float32 on both sides; they differ
in the order of sums of ``K`` = 128 terms of O(1): 1e-4 absolute.
"""
import numpy as np
import pytest

from paddle_tpu import grouped_matmul as gm

K, N = 128, 256
ATOL = 1e-4

SIZES = {
    "ragged_with_empty_groups": [5, 0, 130, 1, 0, 120],
    "empty_first_and_last": [0, 0, 256, 0],
    "one_group_holds_every_row": [0, 384, 0, 0],
    "first_group_holds_every_row": [256, 0, 0, 0],
    "no_group_holds_any": [0, 0, 0, 0],
    "rows_of_no_group_at_the_end": [3, 3, 3, 3],
    "groups_end_on_tile_edges": [128, 0, 128, 128],
    "a_group_across_three_tiles": [100, 300, 60, 4],
}


def _operands(sizes, seed=0, dtype="float32"):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    m = -(-max(sum(sizes), 1) // gm.ROW_TILE) * gm.ROW_TILE
    lhs = jnp.asarray(rng.randn(m, K).astype("float32"), dtype)
    rhs = jnp.asarray(rng.randn(len(sizes), K, N).astype("float32"), dtype)
    return lhs, rhs, gm.plan(jnp.asarray(sizes, jnp.int32), m)


def _loop(lhs, rhs, sizes):
    lhs, rhs = np.asarray(lhs, np.float64), np.asarray(rhs, np.float64)
    out = np.zeros((lhs.shape[0], rhs.shape[2]))
    at = 0
    for g, n in enumerate(sizes):
        out[at:at + n] = lhs[at:at + n] @ rhs[g]
        at += n
    return out


@pytest.mark.parametrize("name", sorted(SIZES))
def test_kernel_equals_xla_form_and_a_loop_over_groups(name):
    sizes = SIZES[name]
    lhs, rhs, p = _operands(sizes)
    want = _loop(lhs, rhs, sizes)
    got_kernel = np.asarray(gm.kernel_grouped_matmul(lhs, rhs, p,
                                                    interpret=True))
    got_xla = np.asarray(gm.xla_grouped_matmul(lhs, rhs, p))
    np.testing.assert_allclose(got_kernel, want, atol=ATOL)
    np.testing.assert_allclose(got_xla, want, atol=ATOL)
    # rows of no group are exact zeros, not leftovers
    assert not got_kernel[sum(sizes):].any()


def test_bf16_operands_accumulate_in_float32():
    sizes = SIZES["ragged_with_empty_groups"]
    lhs, rhs, p = _operands(sizes, dtype="bfloat16")
    got = np.asarray(gm.kernel_grouped_matmul(lhs, rhs, p, interpret=True))
    assert got.dtype == np.float32
    np.testing.assert_allclose(
        got, _loop(lhs.astype("float32"), rhs.astype("float32"), sizes),
        atol=1e-3)


@pytest.mark.parametrize("name", sorted(SIZES))
def test_plan_visits_each_nonempty_group_tile_once_and_no_empty_group(name):
    """The visits are the (group, row tile) pairs a group has rows in,
    in order; an empty group has none (its matrix is never fetched);
    visits past the last repeat it."""
    import jax.numpy as jnp

    sizes = SIZES[name]
    m = max(-(-sum(sizes) // gm.ROW_TILE), 1) * gm.ROW_TILE
    p = gm.plan(jnp.asarray(sizes, jnp.int32), m)
    want, at = [], 0
    for g, n in enumerate(sizes):
        if n:
            want += [(g, t) for t in range(at // gm.ROW_TILE,
                                           (at + n - 1) // gm.ROW_TILE + 1)]
        at += n
    n_visits = int(p.n_visits[0])
    got = list(zip(np.asarray(p.group_ids).tolist(),
                   np.asarray(p.tile_ids).tolist()))
    assert n_visits == len(want)
    assert got[:n_visits] == want
    assert len(got) == m // gm.ROW_TILE + len(sizes) - 1
    if want:
        assert set(got[n_visits:]) <= {want[-1]}
    # tile ids never go back: an output tile is revisited consecutively
    tiles = [t for _, t in got[:n_visits]]
    assert tiles == sorted(tiles)


def test_lowering_is_chosen_from_what_the_call_can_see():
    import jax
    import jax.numpy as jnp

    bf = lambda *s: jax.ShapeDtypeStruct(s, jnp.bfloat16)
    assert gm.lowering("tpu", bf(1024, 2048), bf(64, 2048, 3072)) == "kernel"
    assert gm.lowering("tpu", bf(1024, 1536), bf(64, 1536, 2048)) == "kernel"
    assert gm.lowering("cpu", bf(1024, 2048), bf(64, 2048, 3072)) == "xla"
    assert gm.lowering("tpu", bf(1000, 2048), bf(64, 2048, 3072)) == "xla"
    assert gm.lowering("tpu", bf(1024, 2048), bf(64, 2048, 200)) == "xla"
    assert gm.lowering("tpu", bf(1024, 100), bf(64, 100, 256)) == "xla"
    mixed = jax.ShapeDtypeStruct((64, 2048, 3072), jnp.float32)
    assert gm.lowering("tpu", bf(1024, 2048), mixed) == "xla"


def test_lowered_counter_counts_by_path():
    lhs, rhs, p = _operands(SIZES["rows_of_no_group_at_the_end"])
    before = gm.LOWERED.labels(path="xla").value
    gm.grouped_matmul(lhs, rhs, p)      # the CPU: the XLA form
    assert gm.LOWERED.labels(path="xla").value == before + 1
