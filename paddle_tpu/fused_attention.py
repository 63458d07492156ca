"""Training attention that keeps the scores on the chip.

The ``fused_attention`` op (``ops/nn_ops.py``) is scaled-dot-product
self-attention over ``Q/K/V [N, H, S, D]`` with an optional key-padding
``Mask [N, S]`` (1 = token) and ``causal``.  This module is its
mathematics, twice, and the rule that picks one:

* :func:`xla_attention` — plain XLA ops: the score product in the
  operands' dtype, then float32 for the additive ``(mask - 1) * 1e9``
  bias and the softmax, probabilities cast to the values' dtype for the
  context product.  Its backward is ``jax.vjp``
  over itself.  Every backend; the only form for cross-attention,
  float32 inputs, a partitioned (GSPMD) trace and odd shapes.
* :func:`kernel_attention` / :func:`kernel_attention_grad` — a Pallas
  TPU kernel pair.  One grid step owns one (block of batch rows, lane
  tile of heads): ``128 // D`` heads side by side, read straight out of the
  projection's ``[N, S, H * D]`` layout (the op's ``[N, H, S, D]`` views
  are transposes that XLA cancels against the model's own), so every
  HBM tile is lane-dense at ``D = 64``.  The whole K and V of the tile
  sit in VMEM, so a row's softmax is exact in two passes, not online.
  Forward emits the context and the per-row log-sum-exp ``[N, H, S]``;
  backward takes Q, K, V, dO, that statistic and ``delta = rowsum(dO *
  O)`` and recomputes the probabilities tile by tile in the transposed
  (key-major) orientation, where both row statistics broadcast along
  sublanes.  A head is picked out of its lane tile by zeroing the other
  heads' lanes of ONE operand of each product (the MXU contracts 128
  lanes either way) and selecting its lanes of the result.  Nothing
  score-shaped is written to HBM in either direction.

Same mathematics in both: key-only masking by the additive bias (a
padded QUERY row attends every real key), fp32 scores and softmax, bf16
probabilities into the context product.

:func:`attention_lowering` is the rule, a pure function of what the op
can see.  The numbers that set it (one v5e chip, jax 0.9.0, PR 28,
``tools/chip_bringup.py flash``) are in its docstring.

``jax.experimental.pallas`` is imported inside the kernel builders only.
"""
from __future__ import annotations

import functools

from paddle_tpu.monitor import registry as _registry

__all__ = ["attention_lowering", "xla_attention", "kernel_attention",
           "kernel_attention_grad", "LOWERED", "KERNEL_MAX_SEQ"]

_LANES = 128
_MASK_BIAS = -1e9    # what models/transformer.py's materialized bias uses
#: longest sequence whose K/V tile and one score chunk fit VMEM together
KERNEL_MAX_SEQ = 2048
_ROWS = 512          # score rows (fwd) / columns (bwd) worked on at once
_STEP_ROWS = 1024    # score rows of all the batch rows of one grid step

LOWERED = _registry.REGISTRY.counter(
    "fused_attention_lowered_total",
    "fused_attention ops lowered (traced into a program or run eagerly), "
    "by the lowering chosen: kernel (Pallas TPU pair, scores stay in "
    "VMEM) | xla (plain XLA ops) | ring (sequence-parallel ring "
    "attention)", ("path",))


def attention_lowering(backend: str, seq_q: int, seq_k: int, n_head: int,
                       d_head: int, dtype, partitioned: bool = False) -> str:
    """``"kernel"`` or ``"xla"`` for one fused_attention op.

    The kernel needs a TPU, one program on one device (GSPMD cannot
    partition a Mosaic call: a ``CompiledProgram`` keeps the XLA form),
    self-attention (``seq_q == seq_k``), bf16 operands (what AMP hands
    over; float32 operands keep their float32 products), heads that tile
    the 128 lanes (``d_head`` 64 or 128, ``n_head`` a multiple of
    ``128 // d_head``), and a sequence of whole 128-row tiles no longer
    than :data:`KERNEL_MAX_SEQ`.  Every mask kind (none, key padding,
    causal, both) takes the same path.

    Wherever it can run it wins, so there is no shape at which the rule
    prefers the XLA form on a TPU.  Forward + backward of one layer in
    the model's layout, ms (one v5e chip, PR 28, ``tools/chip_bringup.py
    flash``; kernel / XLA form / the four-op lowering it replaced):

    ====================  ======  =====  =======
    [N, H, S, D] bf16     kernel  xla    four-op
    ====================  ======  =====  =======
    [32, 12, 512, 64]      1.23    3.93    3.42
    [128, 12, 128, 64]     0.71    1.07    0.93
    [16, 12, 1024, 64]     2.17    7.57    6.62
    [8, 12, 2048, 64]      4.60   14.33   12.20
    [16, 6, 1024, 128]     1.26    4.07    3.52
    ====================  ======  =====  =======

    jax's library ``flash_attention`` took 5.02 ms at the first shape
    with blocks of 512 (12.7 at its default 128) and 5.3 ms at the
    second; an XLA-only form saving bf16 probabilities 3.89 / 1.26.
    """
    import jax.numpy as jnp

    if backend != "tpu" or partitioned or seq_q != seq_k:
        return "xla"
    if jnp.dtype(dtype) != jnp.bfloat16:
        return "xla"
    if d_head not in (64, 128) or n_head % (_LANES // d_head):
        return "xla"
    if seq_q % _LANES or seq_q > KERNEL_MAX_SEQ:
        return "xla"
    return "kernel"


def _key_bias(mask):
    """``Mask [N, S]`` (1 = token) as the additive float32 key bias."""
    import jax.numpy as jnp

    return (mask.astype(jnp.float32) - 1.0) * -_MASK_BIAS


def _bias(mask, causal, seq_q, seq_k):
    """The additive fp32 bias ``[N or 1, 1, seq_q or 1, seq_k]``, or None."""
    import jax.numpy as jnp

    bias = None
    if causal:
        allowed = (jnp.arange(seq_k)[None, :] <= jnp.arange(seq_q)[:, None])
        bias = jnp.where(allowed, 0.0, _MASK_BIAS)[None, None]
    if mask is not None:
        pad = _key_bias(mask)[:, None, None]
        bias = pad if bias is None else bias + pad
    return bias


# hot-path: begin attention_trace (everything below is traced into a step
# program: device ops only, never a host sync)
def xla_attention(q, k, v, mask=None, causal=False, scale=1.0):
    """``(context [N, H, Sq, D], lse [N, H, Sq])`` as plain XLA ops."""
    import jax.numpy as jnp

    # the score product leaves in the operands' dtype (bf16 under AMP,
    # as the four-op build's matmul does): float32 scores through HBM
    # cost this form 6.2 ms a layer at [32,12,512,64] against 3.4
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k).astype(jnp.float32) * scale
    bias = _bias(mask, causal, q.shape[2], k.shape[2])
    if bias is not None:
        s = s + bias
    m = jnp.max(s, axis=-1, keepdims=True)
    e = jnp.exp(s - m)
    l = jnp.sum(e, axis=-1, keepdims=True)
    out = jnp.einsum("bhqk,bhkd->bhqd", (e / l).astype(v.dtype), v)
    return out.astype(q.dtype), (m + jnp.log(l))[..., 0]


# ---------------------------------------------------------------------------
# the Pallas TPU kernel pair
# ---------------------------------------------------------------------------
_NT = (((1,), (1,)), ((), ()))   # a @ b.T


def _lanes_of_head(h, d_head, shape, axis):
    """Boolean ``shape``: True where index along ``axis`` belongs to the
    ``h``-th head of the lane tile."""
    import jax

    i = jax.lax.broadcasted_iota(jax.numpy.int32, shape, axis)
    return (i >= h * d_head) & (i < (h + 1) * d_head)


def _only_head(x, h, d_head):
    """``x [S, 128]`` with the other heads' lanes zeroed (in fp32: a
    v5e has no bf16 vector unit), back in ``x``'s dtype."""
    import jax.numpy as jnp

    if d_head == _LANES:
        return x
    keep = _lanes_of_head(h, d_head, x.shape, 1)
    return jnp.where(keep, x.astype(jnp.float32), 0.0).astype(x.dtype)


def _fwd_kernel(bias_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, *,
                scale, causal, d_head, rows):
    """Refs of one grid step: ``bias [B, 1, S]``, ``q/k/v/o [B, S, 128]``
    (``128 // d_head`` heads side by side), ``lse [B, group, S]``."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    batch, S = q_ref.shape[0], q_ref.shape[1]
    group = _LANES // d_head
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, _LANES), 1)
    for b in range(batch):
        v = v_ref[b]
        heads_k = [_only_head(k_ref[b], h, d_head) for h in range(group)]
        bias = bias_ref[b]                                  # [1, S] keys
        for c in range(S // rows):
            rows_c = pl.ds(c * rows, rows)
            q = q_ref[b, rows_c, :]
            chunk_bias = bias
            if causal:
                qi = c * rows + jax.lax.broadcasted_iota(
                    jnp.int32, (rows, S), 0)
                ki = jax.lax.broadcasted_iota(jnp.int32, (rows, S), 1)
                chunk_bias = jnp.where(ki <= qi, 0.0, _MASK_BIAS) + bias
            out = None
            stats = jnp.zeros((rows, _LANES), jnp.float32)
            for h in range(group):
                s = jax.lax.dot_general(
                    q, heads_k[h], _NT,
                    preferred_element_type=jnp.float32) * scale + chunk_bias
                m = jnp.max(s, axis=1, keepdims=True)
                e = jnp.exp(s - m)
                l = jnp.sum(e, axis=1, keepdims=True)
                ctx = jnp.dot(e.astype(v.dtype), v,
                              preferred_element_type=jnp.float32) / l
                out = ctx if out is None else jnp.where(
                    _lanes_of_head(h, d_head, ctx.shape, 1), ctx, out)
                stats = jnp.where(lane == h, m + jnp.log(l), stats)
            o_ref[b, rows_c, :] = out.astype(o_ref.dtype)
            # the row statistic leaves lane-dense: columns -> rows
            lse_ref[b, :, rows_c] = stats.T[:group, :]


def _bwd_kernel(bias_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, delta_ref,
                dq_ref, dk_ref, dv_ref, *, scale, causal, d_head, rows):
    """The forward's refs plus ``do``, ``delta [B, group, S]`` and the
    three gradients, worked in the key-major orientation: a score tile
    is ``[S keys, rows queries]``, so ``lse`` and ``delta`` broadcast
    along sublanes as they are stored."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl

    batch, S = q_ref.shape[0], q_ref.shape[1]
    group = _LANES // d_head

    def merged(parts):
        out = parts[0]
        for h in range(1, group):
            out = jnp.where(_lanes_of_head(h, d_head, out.shape, 1),
                            parts[h], out)
        return out

    for b in range(batch):
        k = k_ref[b]
        heads_k = [_only_head(k, h, d_head) for h in range(group)]
        heads_v = [_only_head(v_ref[b], h, d_head) for h in range(group)]
        k_t = k.astype(jnp.float32).T.astype(k.dtype)        # [128, S]
        # the key bias as a column: rows of the key-major score tile
        bias = jnp.broadcast_to(bias_ref[b], (_LANES, S)).T[:, :1]  # [S, 1]
        dk = [jnp.zeros((S, _LANES), jnp.float32) for _ in range(group)]
        dv = [jnp.zeros((S, _LANES), jnp.float32) for _ in range(group)]
        for c in range(S // rows):
            cols_c = pl.ds(c * rows, rows)
            q = q_ref[b, cols_c, :]
            do = do_ref[b, cols_c, :]
            chunk_bias = bias
            if causal:
                ki = jax.lax.broadcasted_iota(jnp.int32, (S, rows), 0)
                qi = c * rows + jax.lax.broadcasted_iota(
                    jnp.int32, (S, rows), 1)
                chunk_bias = jnp.where(ki <= qi, 0.0, _MASK_BIAS) + bias
            dq_t = None
            for h in range(group):
                s_t = jax.lax.dot_general(
                    heads_k[h], q, _NT,
                    preferred_element_type=jnp.float32) * scale + chunk_bias
                p_t = jnp.exp(s_t - lse_ref[b, h:h + 1, cols_c])
                dv[h] = dv[h] + jnp.dot(p_t.astype(do.dtype), do,
                                        preferred_element_type=jnp.float32)
                dp_t = jax.lax.dot_general(
                    heads_v[h], do, _NT, preferred_element_type=jnp.float32)
                ds_t = (p_t * (dp_t - delta_ref[b, h:h + 1, cols_c])
                        ).astype(q.dtype)
                dk[h] = dk[h] + jnp.dot(ds_t, q,
                                        preferred_element_type=jnp.float32)
                part = jnp.dot(k_t, ds_t, preferred_element_type=jnp.float32)
                dq_t = part if dq_t is None else jnp.where(
                    _lanes_of_head(h, d_head, part.shape, 0), part, dq_t)
            dq_ref[b, cols_c, :] = (dq_t.T * scale).astype(dq_ref.dtype)
        dk_ref[b] = (merged(dk) * scale).astype(dk_ref.dtype)
        dv_ref[b] = merged(dv).astype(dv_ref.dtype)


def _folded(x):
    """``[N, H, S, D] -> [N, S, H * D]``: the projection's own layout."""
    n, h, s, d = x.shape
    return x.transpose(0, 2, 1, 3).reshape(n, s, h * d)


def _unfolded(x, n_head):
    n, s, hd = x.shape
    return x.reshape(n, s, n_head, hd // n_head).transpose(0, 2, 1, 3)


def _key_bias_rows(mask, n, s):
    import jax.numpy as jnp

    if mask is None:
        return jnp.zeros((n, 1, s), jnp.float32)
    return _key_bias(mask)[:, None]


def _batch_rows(n, s):
    """Batch rows one grid step owns: as many short sequences as make
    :data:`_STEP_ROWS` score rows (a grid step costs about 0.35 us
    whatever it does: at S = 128, 8 rows a step take 0.70 ms a layer
    where 1 takes 1.25), and a divisor of ``n``."""
    want = max(1, _STEP_ROWS // s)
    return max(b for b in range(1, want + 1) if n % b == 0)


def _plan(pl, shape):
    """``(grid, (bias, tile, stat), stat_shape)`` for ``Q [N, H, S, D]``:
    the grid ``(batch blocks, lane tiles)`` and the BlockSpecs of one
    step — the key bias rows, a ``[batch, S, 128]`` tile of a folded
    tensor, and a ``[batch, group, S]`` tile of a row statistic kept as
    ``stat_shape = [N, H // group, group, S]``."""
    n, n_head, s, d_head = shape
    group = _LANES // d_head
    batch = _batch_rows(n, s)
    bias = pl.BlockSpec((batch, 1, s), lambda n, t: (n, 0, 0))
    tile = pl.BlockSpec((batch, s, _LANES), lambda n, t: (n, 0, t))
    stat = pl.BlockSpec((batch, None, group, s), lambda n, t: (n, t, 0, 0))
    return ((n // batch, n_head // group), (bias, tile, stat),
            (n, n_head // group, group, s))


def _compiler_params(pltpu):
    return pltpu.CompilerParams(
        dimension_semantics=("parallel", "parallel"),
        vmem_limit_bytes=64 * 1024 * 1024)


def _jitted(fn):
    """``fn`` under ``jax.jit`` (built once, jax imported late): the
    twelve layers of a step program then share ONE trace and ONE lowered
    function of each kernel, instead of tracing and lowering the Mosaic
    body twelve times in every process (a cache hit on the executable
    does not spare the lowering)."""
    @functools.lru_cache(maxsize=None)
    def build():
        import jax

        return jax.jit(fn, static_argnames=("causal", "scale", "interpret"))

    @functools.wraps(fn)
    def call(*args, **kwargs):
        return build()(*args, **kwargs)

    return call


@_jitted
def kernel_attention(q, k, v, mask=None, causal=False, scale=1.0,
                     interpret=False):
    """``(context [N, H, S, D], lse [N, H, S])`` by the forward kernel."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, n_head, s, d_head = q.shape
    grid, (bias, tile, stat), stat_shape = _plan(pl, q.shape)
    out, lse = pl.pallas_call(
        functools.partial(_fwd_kernel, scale=float(scale),
                          causal=bool(causal), d_head=d_head,
                          rows=min(_ROWS, s)),
        grid=grid,
        in_specs=[bias, tile, tile, tile],
        out_specs=[tile, stat],
        out_shape=[
            jax.ShapeDtypeStruct((n, s, n_head * d_head), q.dtype),
            jax.ShapeDtypeStruct(stat_shape, jnp.float32)],
        compiler_params=_compiler_params(pltpu),
        name="fused_attention_fwd",
        interpret=interpret,
    )(_key_bias_rows(mask, n, s), _folded(q), _folded(k), _folded(v))
    return _unfolded(out, n_head), lse.reshape(n, n_head, s)


@_jitted
def kernel_attention_grad(q, k, v, mask, out, lse, dout, causal=False,
                          scale=1.0, interpret=False):
    """``(dQ, dK, dV)`` by the backward kernel, from the forward's
    context and row statistic: the forward is not run again."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    n, n_head, s, d_head = q.shape
    grid, (bias, tile, stat), stat_shape = _plan(pl, q.shape)
    delta = jnp.sum(dout.astype(jnp.float32) * out.astype(jnp.float32),
                    axis=-1)                                  # [N, H, S]
    folded = jax.ShapeDtypeStruct((n, s, n_head * d_head), q.dtype)
    dq, dk, dv = pl.pallas_call(
        functools.partial(_bwd_kernel, scale=float(scale),
                          causal=bool(causal), d_head=d_head,
                          rows=min(_ROWS, s)),
        grid=grid,
        in_specs=[bias, tile, tile, tile, tile, stat, stat],
        out_specs=[tile, tile, tile],
        out_shape=[folded, folded, folded],
        compiler_params=_compiler_params(pltpu),
        name="fused_attention_bwd",
        interpret=interpret,
    )(_key_bias_rows(mask, n, s), _folded(q), _folded(k), _folded(v),
      _folded(dout.astype(q.dtype)),
      lse.astype(jnp.float32).reshape(stat_shape),
      delta.reshape(stat_shape))
    return tuple(_unfolded(g, n_head) for g in (dq, dk, dv))
# hot-path: end attention_trace
