"""A grouped matrix product: rows sorted by group, each group's rows
multiplied by that group's own matrix.

    out[r] = lhs[r] @ rhs[g]      for offsets[g] <= r < offsets[g + 1]
    out[r] = 0                    for r >= offsets[G]  (rows of no group)

``lhs`` ``[M, K]`` holds the rows of group 0 first, then group 1's, and
so on; ``group_sizes`` ``[G]`` int32 says how many each has (zero is
ordinary: an expert no token chose); ``rhs`` ``[G, K, N]``.  This is the
experts' product of a routed mixture (``paddle_tpu.routed_experts``):
at decode a group is a handful of rows against a matrix of megabytes, so
the product is a STREAM of the matrices of the groups that have rows —
each read once, none read for an empty group — with the arithmetic
hidden under it.

Two forms of the one contract, chosen by :func:`lowering` from what the
call can see (never a flag):

* the Pallas TPU kernel: the rows in tiles of :data:`ROW_TILE`, one grid
  step a *visit* — a (group, row tile) pair the group has rows in.  The
  visits' group and tile ids ride in SMEM (scalar prefetch) and the
  BlockSpecs' index maps turn them into the DMAs, so the pipeline
  fetches the next visit's ``[K, tn]`` slice of its group's matrix while
  this one multiplies; a group that straddles a tile boundary is two
  visits of ONE fetch (an unchanged block index is not fetched again).
  A visit multiplies the whole row tile and keeps the rows of its own
  group (rows of another group in the tile are that group's visit's).
  ``M / ROW_TILE + G - 1`` visits at most; those past the last real one
  repeat its indices and do nothing.
* the XLA form, ``jax.lax.ragged_dot``: the CPU, and shapes the kernel
  does not lower for.

:func:`plan` computes the visits once for every product over the same
grouping (a gated FFN has two).  ``grouped_matmul_lowered_total{path}``
counts the products traced, by the form taken.
"""
from __future__ import annotations

import functools
from typing import NamedTuple

from paddle_tpu.monitor import registry as _registry

__all__ = ["ROW_TILE", "Plan", "plan", "lowering", "grouped_matmul",
           "kernel_grouped_matmul", "xla_grouped_matmul", "LOWERED",
           "KERNEL_NAME"]

#: rows of one visit: the MXU's height, and a whole number of sublane
#: tiles of every dtype
ROW_TILE = 128
#: widest slice of a group's matrix one visit multiplies (columns)
_MAX_COLS = 1024
#: the kernel's name in the device trace (the benchmark's readers find
#: the product by it)
KERNEL_NAME = "grouped_matmul"

LOWERED = _registry.REGISTRY.counter(
    "grouped_matmul_lowered_total",
    "grouped matrix products lowered (traced into a program or run "
    "eagerly), by the lowering chosen: kernel (Pallas TPU: each group's "
    "matrix streamed once, empty groups never read) | xla "
    "(jax.lax.ragged_dot)", ("path",))


class Plan(NamedTuple):
    """What every product over one grouping shares."""
    group_sizes: object   # [G] int32
    offsets: object       # [G + 1] int32: group g is rows offsets[g:g + 2]
    group_ids: object     # [V] int32: the group of visit v
    tile_ids: object      # [V] int32: the row tile of visit v
    n_visits: object      # [1] int32: visits that do anything


def plan(group_sizes, n_rows: int) -> Plan:
    """The visits of a grouping of ``n_rows`` sorted rows: for each
    non-empty group, the row tiles its rows lie in, in order.  Visits
    past the last repeat it (same blocks: no fetch, no work)."""
    import jax.numpy as jnp

    sizes = group_sizes.astype(jnp.int32)
    g = sizes.shape[0]
    ends = jnp.cumsum(sizes)
    offsets = jnp.concatenate([jnp.zeros(1, jnp.int32), ends])
    first = offsets[:-1] // ROW_TILE
    tiles = jnp.where(sizes > 0, (ends - 1) // ROW_TILE - first + 1, 0)
    visit_ends = jnp.cumsum(tiles)
    total = visit_ends[-1]
    n_max = -(-int(n_rows) // ROW_TILE) + g - 1
    v = jnp.minimum(jnp.arange(n_max, dtype=jnp.int32),
                    jnp.maximum(total - 1, 0))
    gid = jnp.minimum(jnp.searchsorted(visit_ends, v, side="right"),
                      g - 1).astype(jnp.int32)
    tid = first[gid] + v - (visit_ends[gid] - tiles[gid])
    return Plan(sizes, offsets, gid, tid.astype(jnp.int32),
                total.reshape(1).astype(jnp.int32))


def _cols(n: int) -> int:
    """The widest column slice <= _MAX_COLS that tiles ``n`` in whole
    lane tiles; 0 if none does."""
    return max((c for c in range(128, _MAX_COLS + 1, 128) if n % c == 0),
               default=0)


def lowering(backend: str, lhs, rhs) -> str:
    """``"kernel"`` or ``"xla"`` for one product.  The kernel needs a
    TPU, bf16 or float32 operands of one dtype, whole row tiles, and
    ``K`` and ``N`` whole lane tiles."""
    import jax.numpy as jnp

    m, k = lhs.shape
    ok = (backend == "tpu" and lhs.dtype == rhs.dtype
          and lhs.dtype in (jnp.bfloat16, jnp.float32)
          and m % ROW_TILE == 0 and k % 128 == 0 and _cols(rhs.shape[2]))
    return "kernel" if ok else "xla"


def grouped_matmul(lhs, rhs, p: Plan):
    """``out [M, N]`` float32 (see the module docstring).  Rows of no
    group come out zero."""
    import jax

    path = lowering(jax.default_backend(), lhs, rhs)
    LOWERED.labels(path=path).inc()
    if path == "kernel":
        return kernel_grouped_matmul(lhs, rhs, p)
    return xla_grouped_matmul(lhs, rhs, p)


def xla_grouped_matmul(lhs, rhs, p: Plan):
    import jax
    import jax.numpy as jnp

    return jax.lax.ragged_dot(lhs, rhs, p.group_sizes,
                              preferred_element_type=jnp.float32)


def kernel_grouped_matmul(lhs, rhs, p: Plan, interpret: bool = False):
    """The Pallas TPU kernel.  Row tiles no group has rows in are never
    visited: their rows are zeroed here, after the call."""
    import jax.numpy as jnp

    out = _kernel_call()(p.group_ids, p.tile_ids, p.offsets, p.n_visits,
                         lhs, rhs, interpret=interpret)
    rows = jnp.arange(lhs.shape[0], dtype=jnp.int32)[:, None]
    return jnp.where(rows < p.offsets[-1], out, 0.0)


@functools.lru_cache(maxsize=None)
def _kernel_call():
    """:func:`_call` under ONE ``jax.jit`` (built once, jax imported
    late): the layers and steps of a chunk program share one trace and
    one lowered function of the kernel (decode_attention._kernel_call)."""
    import jax

    return jax.jit(_call, static_argnames=("interpret",))


def _call(group_ids, tile_ids, offsets, n_visits, lhs, rhs, *, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    m, k = lhs.shape
    _, _, n = rhs.shape
    tm, tn = ROW_TILE, _cols(n)
    f32 = jnp.float32

    def kernel(gid_ref, tid_ref, off_ref, nv_ref, lhs_ref, rhs_ref, out_ref):
        v = pl.program_id(1)

        @pl.when(v < nv_ref[0])
        def _():
            g, t = gid_ref[v], tid_ref[v]
            row = t * tm + jax.lax.broadcasted_iota(jnp.int32, (tm, tn), 0)
            mine = (row >= off_ref[g]) & (row < off_ref[g + 1])
            prod = jnp.dot(lhs_ref[...], rhs_ref[...],
                           preferred_element_type=f32)
            # the tile's first visit defines every row of it: rows of a
            # later group are overwritten by that group's visit, rows of
            # no group stay zero
            first = (v == 0) | (tid_ref[jnp.maximum(v - 1, 0)] != t)
            kept = jnp.where(first, 0.0, out_ref[...])
            out_ref[...] = jnp.where(mine, prod, kept)

    item = jnp.dtype(lhs.dtype).itemsize
    # both buffers of each block, the product and its selects
    resident = 2 * (tm * k * item + k * tn * item + tm * tn * 4) \
        + 4 * tm * tn * 4
    return pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=4,
            grid=(n // tn, group_ids.shape[0]),
            in_specs=[
                pl.BlockSpec((tm, k), lambda j, v, gid, tid, off, nv:
                             (tid[v], 0)),
                pl.BlockSpec((None, k, tn), lambda j, v, gid, tid, off, nv:
                             (gid[v], 0, j)),
            ],
            out_specs=pl.BlockSpec((tm, tn), lambda j, v, gid, tid, off, nv:
                                   (tid[v], j))),
        out_shape=jax.ShapeDtypeStruct((m, n), f32),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=min(100 << 20, max(32 << 20, 2 * resident))),
        name=KERNEL_NAME,
        interpret=interpret,
    )(group_ids, tile_ids, offsets, n_visits, lhs, rhs)
