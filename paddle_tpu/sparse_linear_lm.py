"""The parts of a decoder that mixes two kinds of layer
(``model_type: minicpm_sala``): *lightning* linear-attention layers,
whose whole past is one ``[heads, d, d]`` state per row, beside
*InfLLM-v2* block-sparse attention layers (``minicpm4``), which cache
every K/V row but read only the blocks a learned-free rule selects.
The counterpart of ``paddle_tpu.hybrid_ssm`` (whose ``linear``,
``rms_norm``, ``rotary``, ``swiglu`` and ``starts_fresh`` it reuses).

    h0     = scale_emb * E[token]
    u      = RMS_1(h)
    h      = h + c * W_o (sigmoid(W_g u) * Mixer(u)),   c = scale_depth / sqrt(mup_denominator)
    h      = h + c * SwiGLU(RMS_2(h))
    logits = W_head (RMS_f(h) / (hidden_size / dim_model_base))

*Lightning* (``lightning-attn``; ``q, k`` RMS-normed per head, rotary):
``S_t = lambda_h S_{t-1} + k_t^T v_t``, ``o_t = q_t S_t / sqrt(d)``,
``Mixer = RMS_o(o)``; ``lambda_h = exp(-2^{-8 (h + 1) / H})``.
:func:`lightning_step` is one token of every row, :func:`lightning_chunk`
``C`` tokens of one row (equal to ``C`` steps).

*Sparse* (``minicpm4``; ``q, k`` RMS-normed per head, no positions): a
query at position ``t`` (context ``n = t + 1``) attends densely while
``n <= dense_len`` and else to the positions ``<= t`` of the blocks
:func:`select_blocks` names: the first ``init_blocks``, every block that
meets the last ``window_size`` positions, and the ``topk`` other blocks
whose compressed keys (means of ``kernel_size`` K rows every
``kernel_stride``: :func:`compress_keys`) score highest, summed over the
query heads that share the K/V head.  The step's read of the named
blocks is ``decode_attention``'s (``grouped_block_decode_attention``);
:func:`chunk_attend` is the prefill chunk's masked form of the same rule.

``decoding.make_sparse_linear_lm_pooled_step_fn`` strings these into the
slot-pooled step and the chunked prefill; nothing here knows a pool or a
server.  Weights are multiplied in the dtype they are given (bf16 as
stored), norms, rotary angles, softmaxes, the selection and the
recurrence run in fp32.
"""
from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from paddle_tpu.hybrid_ssm import (linear, rms_norm, rotary, starts_fresh,
                                   swiglu)

__all__ = ["SPARSE", "LIGHTNING", "dims", "param_shapes", "random_state",
           "mixer_inputs", "lightning_step", "lightning_chunk",
           "compress_keys", "update_compressed", "select_blocks",
           "forced_runs", "selected_positions", "chunk_attend", "linear",
           "rms_norm", "rotary", "swiglu", "starts_fresh",
           "LINEAR_STATE_SCOPE",
           "SPARSE_SELECT_SCOPE", "SPARSE_ATTEND_SCOPE",
           "PREFILL_CHUNK_SCOPE"]

#: the two ``mixer_types`` entries
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"

#: ``jax.named_scope`` names, for the device trace
LINEAR_STATE_SCOPE = "linear_state_update"
SPARSE_SELECT_SCOPE = "sparse_block_select"
SPARSE_ATTEND_SCOPE = "sparse_block_attend"
PREFILL_CHUNK_SCOPE = "prefill_chunk"

_SPARSE_KEYS = ("kernel_size", "kernel_stride", "init_blocks", "block_size",
                "window_size", "topk", "dense_len")


def dims(cfg) -> SimpleNamespace:
    """Sizes and scalars from a ``minicpm_sala`` config dict (the
    published key names; the sparse sizes the published config does not
    carry come from ``sparse_config``, at the top level or under
    ``assumed``)."""
    sp = cfg.get("sparse_config") or cfg.get("assumed", {}).get(
        "sparse_config")
    if not sp or any(k not in sp for k in _SPARSE_KEYS):
        raise ValueError("config needs sparse_config with %s"
                         % (_SPARSE_KEYS,))
    o = SimpleNamespace(
        vocab=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        n_layer=int(cfg["num_hidden_layers"]),
        kinds=tuple(cfg["mixer_types"]),
        n_head=int(cfg["num_attention_heads"]),
        n_kv_head=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]), d_mlp=int(cfg["intermediate_size"]),
        l_heads=int(cfg["lightning_nh"]), l_kv_heads=int(cfg["lightning_nkv"]),
        l_head_dim=int(cfg["lightning_head_dim"]),
        eps=float(cfg.get("rms_norm_eps", 1e-6)),
        rope_theta=float(cfg["rope_theta"]),
        scale_emb=float(cfg.get("scale_emb", 1.0)),
        res_scale=float(cfg.get("scale_depth", 1.0))
        / float(np.sqrt(cfg.get("mup_denominator",
                                cfg["num_hidden_layers"]))),
        logit_div=float(cfg["hidden_size"])
        / float(cfg.get("dim_model_base", cfg["hidden_size"])),
        **{k: int(sp[k]) for k in _SPARSE_KEYS})
    if len(o.kinds) != o.n_layer or set(o.kinds) - {SPARSE, LIGHTNING}:
        raise ValueError("mixer_types must name %d layers of %r / %r"
                         % (o.n_layer, SPARSE, LIGHTNING))
    if o.n_head % o.n_kv_head or o.l_heads != o.l_kv_heads:
        raise ValueError("heads must divide into their K/V heads "
                         "(lightning: one each)")
    if (o.kernel_size % o.kernel_stride or o.block_size % o.kernel_stride
            or o.window_size % o.block_size or o.dense_len < o.window_size):
        raise ValueError("sparse_config: kernel_size and block_size must be "
                         "multiples of kernel_stride, window_size of "
                         "block_size, and dense_len >= window_size")
    o.d_kv = o.n_kv_head * o.head_dim
    o.l_width = o.l_heads * o.l_head_dim
    #: blocks a sparse query can be told to read
    o.n_sel = o.init_blocks + o.window_size // o.block_size + 1 + o.topk
    # ALiBi slopes as the per-head decay rates: lambda_h = exp(-slope_h)
    o.slopes = (2.0 ** (-8.0 * (np.arange(o.l_heads) + 1.0)
                        / o.l_heads)).astype("float32")
    return o


def param_shapes(cfg, name: str = "lm") -> dict:
    """Names and shapes of every weight: the one place the schema lives.
    Matrices are ``[in, out]``."""
    d = dims(cfg)
    out = {name + "_emb": (d.vocab, d.d_model),
           name + "_final_norm": (d.d_model,),
           name + "_head": (d.d_model, d.vocab)}
    for i, kind in enumerate(d.kinds):
        p = "%s_l%d_" % (name, i)
        light = kind == LIGHTNING
        width = d.l_width if light else d.n_head * d.head_dim
        d_kv = d.l_width if light else d.d_kv
        dh = d.l_head_dim if light else d.head_dim
        out.update({
            p + "norm1": (d.d_model,), p + "norm2": (d.d_model,),
            p + "attn_q": (d.d_model, width), p + "attn_k": (d.d_model, d_kv),
            p + "attn_v": (d.d_model, d_kv), p + "attn_g": (d.d_model, width),
            p + "attn_o": (width, d.d_model),
            p + "q_norm": (dh,), p + "k_norm": (dh,),
            p + "mlp_gate": (d.d_model, d.d_mlp),
            p + "mlp_up": (d.d_model, d.d_mlp),
            p + "mlp_down": (d.d_mlp, d.d_model)})
        if light:
            out[p + "o_norm"] = (width,)
    return out


def random_state(rng, cfg, name: str = "lm", std: float = 0.02,
                 dtype="float32", sparse_q_norm: float = 1.0) -> dict:
    """Seeded random weights under :func:`param_shapes` (tests): normal
    matrices in ``dtype``, unit norm vectors in fp32 — the sparse
    layers' ``q_norm`` at ``sparse_q_norm``, which sets how peaked their
    attention is."""
    import jax.numpy as jnp

    kinds = dims(cfg).kinds
    w = {}
    for k, shp in param_shapes(cfg, name).items():
        if len(shp) == 1:
            w[k] = np.ones(shp, "float32")
            if k.endswith("q_norm") and kinds[
                    int(k[len(name) + 2:].split("_")[0])] == SPARSE:
                w[k] *= np.float32(sparse_q_norm)
        else:
            w[k] = jnp.asarray((rng.randn(*shp) * std).astype("float32"),
                               dtype)
    return w


def mixer_inputs(u, w, p: str, kind: str, pos, d):
    """``q, k, v`` of one layer for the rows ``u`` ``[M, d_model]`` at
    positions ``pos`` ``[M]``: projected, ``q`` and ``k`` RMS-normed per
    head (``qk_norm``) and, in a lightning layer, rotated
    (``lightning_use_rope``; the sparse layers carry no positions).
    Each ``[M, heads, head_dim]`` fp32."""
    light = kind == LIGHTNING
    dh = d.l_head_dim if light else d.head_dim
    m = u.shape[0]
    q = rms_norm(linear(u, w[p + "attn_q"]).reshape(m, -1, dh),
                 w[p + "q_norm"], d.eps)
    k = rms_norm(linear(u, w[p + "attn_k"]).reshape(m, -1, dh),
                 w[p + "k_norm"], d.eps)
    v = linear(u, w[p + "attn_v"]).reshape(m, -1, dh)
    if light:
        q, k = rotary(q, pos, d.rope_theta), rotary(k, pos, d.rope_theta)
    return q, k, v


# ---------------------------------------------------------------------------
# lightning (linear) attention
# ---------------------------------------------------------------------------
def lightning_step(q, k, v, s, ts, d):
    """One token of every row.  ``q, k, v`` ``[N, H, D]`` fp32 (from
    :func:`mixer_inputs`), ``s`` ``[N, H, D, D]`` the rows' recurrent
    state, ``ts`` ``[N]`` (``< 0`` idle: state kept, ``0`` a fresh
    sequence: state read as zero).  Returns ``(o [N, H, D], s)``."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    live, fresh = ts >= 0, starts_fresh(ts)
    decay = jnp.exp(-jnp.asarray(d.slopes))
    with jax.named_scope(LINEAR_STATE_SCOPE):
        s_prev = jnp.where(fresh[:, None, None, None], 0.0, s.astype(f32))
        s_new = (decay[None, :, None, None] * s_prev
                 + k[..., :, None] * v[..., None, :])
        o = jnp.sum(q[..., :, None] * s_new, axis=-2)
        s_out = jnp.where(live[:, None, None, None], s_new,
                          s.astype(f32)).astype(s.dtype)
    return o / np.sqrt(q.shape[-1]).astype("float32"), s_out


def lightning_chunk(q, k, v, s_in, n_valid, d):
    """``C`` tokens of ONE row: ``q, k, v`` ``[C, H, D]`` fp32, ``s_in``
    ``[H, D, D]`` fp32 the state before the chunk's first token.
    Returns ``(o [C, H, D], s_out)`` with ``s_out`` the state after the
    first ``n_valid`` tokens; equal to that many :func:`lightning_step`s
    (rows past ``n_valid`` of ``o`` mean nothing).  Intra-chunk causal
    products under the decay mask plus the incoming state's share; the
    products are small beside the layer's matmuls and run at "highest"
    so that a prefilled state is the stepped one to fp32 rounding."""
    import jax
    import jax.numpy as jnp

    hi = jax.lax.Precision.HIGHEST
    c = q.shape[0]
    rate = -jnp.asarray(d.slopes)                       # log decay [H]
    i = jnp.arange(c)
    valid = i < n_valid
    k = jnp.where(valid[:, None, None], k, 0.0)
    gap = i[:, None] - i[None, :]                       # query - key
    mask = jnp.where(gap >= 0, jnp.exp(
        rate[:, None, None] * jnp.maximum(gap, 0)[None]), 0.0)  # [H, C, C]
    qk = jnp.einsum("chd,jhd->hcj", q, k, precision=hi)
    o = jnp.einsum("hcj,jhd->chd", qk * mask, v, precision=hi)
    o = o + (jnp.einsum("chd,hde->che", q, s_in, precision=hi)
             * jnp.exp(rate[None, :] * (i + 1)[:, None])[..., None])
    left = jnp.maximum(n_valid - 1 - i, 0)              # decays still to come
    wgt = jnp.where(valid[None, :], jnp.exp(rate[:, None] * left[None, :]),
                    0.0)                                # [H, C]
    s_out = (jnp.exp(rate * n_valid)[:, None, None] * s_in
             + jnp.einsum("jhd,hj,jhe->hde", k, wgt, v, precision=hi))
    return o / np.sqrt(q.shape[-1]).astype("float32"), s_out


# ---------------------------------------------------------------------------
# block-sparse attention: compressed keys, selection, the chunk's attend
# ---------------------------------------------------------------------------
def compress_keys(rows, d):
    """Compressed keys of a window of K rows ``[..., W, Dkv]`` that
    starts on a stride boundary: the mean of every ``kernel_size`` rows,
    every ``kernel_stride``; ``[..., (W - kernel_size) // kernel_stride
    + 1, Dkv]`` fp32."""
    import jax.numpy as jnp

    st, m = d.kernel_stride, d.kernel_size // d.kernel_stride
    w = rows.shape[-2] // st
    g = rows[..., :w * st, :].astype(jnp.float32).reshape(
        rows.shape[:-2] + (w, st, rows.shape[-1])).mean(axis=-2)
    n = w - m + 1
    return sum(g[..., j:j + n, :] for j in range(m)) / float(m)


def update_compressed(ck, k_cache, ts, d):
    """The step's ``ck`` leaf: a row at position ``ts`` whose new K row
    completes a kernel (``(ts + 1 - kernel_size) % kernel_stride == 0``)
    gets that kernel's compressed key written at its own index, from the
    last ``kernel_size`` rows of ``k_cache`` ``[N, T, Dkv]`` (the new row
    already appended).  Other rows, and idle ones, are untouched."""
    import jax.numpy as jnp

    n_rows, n_k = ck.shape[0], ck.shape[1]
    first = ts + 1 - d.kernel_size
    done = (ts >= 0) & (first >= 0) & (first % d.kernel_stride == 0)
    rows = jnp.arange(n_rows)
    at = jnp.maximum(first, 0)[:, None] + jnp.arange(d.kernel_size)[None, :]
    new = k_cache[rows[:, None], at].astype(jnp.float32).mean(axis=1)
    j = jnp.where(done, first // d.kernel_stride, n_k)   # else: dropped
    return ck.at[rows, j].set(new.astype(ck.dtype), mode="drop")


def selected_positions(n, d):
    """Positions a sparse query of context ``n`` (numpy, any shape)
    reads: all of them up to ``dense_len``; past it the first
    ``init_blocks`` blocks, the live part of the blocks that meet the
    last ``window_size`` positions, and ``topk`` more blocks."""
    n = np.asarray(n, np.int64)
    b = d.block_size
    sparse = ((d.init_blocks + d.topk) * b
              + n - ((n - d.window_size) // b) * b)
    return np.where(n <= d.dense_len, n, np.minimum(n, sparse))


def select_blocks(q, ck, ts, d):
    """Which blocks each query reads (steps 1-2 of the rule).

    ``q`` ``[M, G, R, D]`` fp32 (``G`` K/V heads, ``R`` query heads a
    group); ``ck`` ``[M, NK, G * D]`` the queries' compressed-key rows,
    or ``[1, NK, G * D]`` shared by all of them; ``ts`` ``[M]``
    positions.  Returns ``(blocks [M, G, n_sel] int32, valid [M, G,
    n_sel] bool, dense [M] bool)``: a query with ``dense`` set reads
    every position ``<= ts`` instead; ``valid`` marks the entries of
    ``blocks`` that name a block (distinct where valid).  Scores and the
    softmax over the complete kernels in fp32 at "highest"; a block's
    relevance is the sum over its group's heads of the largest
    probability among the kernels that overlap it; ``lax.top_k`` over
    the live blocks that are not forced (ties to the lower index)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    with jax.named_scope(SPARSE_SELECT_SCOPE):
        m_q, g, _, dh = q.shape
        n_k = ck.shape[1]
        per_blk = d.block_size // d.kernel_stride
        over = d.kernel_size // d.kernel_stride - 1
        n_b = n_k // per_blk
        n = ts + 1
        n_done = jnp.where(n >= d.kernel_size,
                           (n - d.kernel_size) // d.kernel_stride + 1, 0)
        # a head's compressed keys are a lane-tile-aligned slice of the
        # leaf as it lies (a reshape to heads would re-tile the rung).
        # fp32 products at "highest": block ranks are near ties, and
        # with bf16 products the served selection left the reference's
        # often enough to triple the check's reading (0.0016 -> 0.007-
        # 0.010 on the chip, PR 31) for no time the trace could show
        sub = "kd" if ck.shape[0] == 1 else "mkd"
        scores = jnp.stack([jnp.einsum(
            "mrd,%s->mrk" % sub, q[:, i],
            (ck[0] if ck.shape[0] == 1 else ck)[
                ..., i * dh:(i + 1) * dh].astype(f32),
            precision=jax.lax.Precision.HIGHEST) for i in range(g)],
            axis=1) / np.sqrt(dh).astype("float32")
        done = (jnp.arange(n_k)[None, :] < n_done[:, None])[:, None, None, :]
        p = jax.nn.softmax(jnp.where(done, scores, -1e30), axis=-1) * done
        # block b overlaps kernels b * per_blk - over .. b * per_blk +
        # per_blk - 1: shifted strided views of the padded probabilities
        pp = jnp.pad(p, ((0, 0),) * 3 + ((over, over),))
        rel = pp[..., 0::per_blk][..., :n_b]
        for o in range(1, per_blk + over):
            rel = jnp.maximum(rel, pp[..., o::per_blk][..., :n_b])
        rel = rel.sum(axis=2)                              # [M, G, NB]
        last = (ts // d.block_size)[:, None]
        b = jnp.arange(n_b)[None, :]
        win_lo = jnp.maximum((n - d.window_size) // d.block_size, 0)[:, None]
        live = b <= last
        forced = live & ((b < d.init_blocks) | (b >= win_lo))
        cand = (live & ~forced)[:, None, :]
        vals, idx = jax.lax.top_k(jnp.where(cand, rel, -1.0),
                                  min(d.topk, n_b))
        init = jnp.broadcast_to(jnp.arange(d.init_blocks)[None, :],
                                (m_q, d.init_blocks))
        win = win_lo + jnp.arange(d.window_size // d.block_size + 1)[None, :]
        fixed = jnp.concatenate([init, win], axis=1)
        fixed_ok = jnp.concatenate(
            [init <= last, (win <= last) & (win >= d.init_blocks)], axis=1)
        blocks = jnp.concatenate(
            [jnp.broadcast_to(fixed[:, None, :], (m_q, g, fixed.shape[1])),
             idx], axis=-1).astype(jnp.int32)
        valid = jnp.concatenate(
            [jnp.broadcast_to(fixed_ok[:, None, :],
                              (m_q, g, fixed.shape[1])), vals >= 0.0],
            axis=-1)
        return jnp.minimum(blocks, n_b - 1), valid, n <= d.dense_len


def forced_runs(d):
    """The stretches of :func:`select_blocks`' list that every K/V head
    holds alike and that name consecutive blocks where valid — the first
    ``init_blocks`` entries, then the window's — as ``((first entry,
    entries), ...)``: what the step declares to
    ``decode_attention.grouped_block_decode_attention``
    (``shared_runs``), which then reads each as one stretch of rows."""
    return ((0, d.init_blocks),
            (d.init_blocks, d.window_size // d.block_size + 1))


def chunk_attend(q, k_leaf, v_leaf, row, ts, blocks, valid, dense, n_live,
                 d, key_block: int = 2048):
    """The prefill chunk's attend: ``C`` queries of ONE row against that
    row's cached K/V, by the same rule as the step (dense up to
    ``dense_len``, the selected blocks past it, per query), in the
    masked form: scores against every live key, a ``key_block`` at a
    time with an online softmax, masked to what each query may read.

    ``q`` ``[C, G, R, D]`` fp32; ``k_leaf``, ``v_leaf`` ``[N, T, G * D]``
    (the chunk's rows already written); ``row`` the slot; ``ts`` ``[C]``
    the queries' positions (``< 0``: no query); ``blocks``, ``valid``,
    ``dense`` from :func:`select_blocks`; ``n_live`` positions to read
    (the chunk's end).  Returns ``[C, G, R, D]`` fp32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    with jax.named_scope(SPARSE_ATTEND_SCOPE):
        c, g, r, dh = q.shape
        t_len = k_leaf.shape[1]
        kb = min(key_block, t_len)
        while t_len % kb or kb % d.block_size:
            kb -= 1                    # tiny test rungs: a divisor
        n_b = t_len // d.block_size
        # [C, G, NB]: may query c read block b of group g
        sel = (jax.nn.one_hot(blocks, n_b, dtype=jnp.bool_)
               & valid[..., None]).any(axis=-2) | dense[:, None, None]
        dt = k_leaf.dtype
        qs = (q / np.sqrt(dh).astype("float32")).astype(dt)

        def body(i, carry):
            m, l, acc = carry
            at = i * kb
            kk = jax.lax.dynamic_slice(
                k_leaf, (row, at, 0), (1, kb, g * dh))[0].reshape(kb, g, dh)
            vv = jax.lax.dynamic_slice(
                v_leaf, (row, at, 0), (1, kb, g * dh))[0].reshape(kb, g, dh)
            pos = at + jnp.arange(kb)
            may = jnp.repeat(jax.lax.dynamic_slice(
                sel, (0, 0, at // d.block_size),
                (c, g, kb // d.block_size)), d.block_size, axis=-1)
            ok = (may & (pos[None, None, :] <= ts[:, None, None]))[
                :, :, None, :]                                # [C, G, 1, kb]
            s = jnp.einsum("cgrd,kgd->cgrk", qs, kk,
                           preferred_element_type=f32)
            s = jnp.where(ok, s, -1e30)
            m_new = jnp.maximum(m, s.max(axis=-1))
            alpha = jnp.exp(m - m_new)
            pr = jnp.exp(s - m_new[..., None]) * ok
            acc = alpha[..., None] * acc + jnp.einsum(
                "cgrk,kgd->cgrd", pr.astype(dt), vv,
                preferred_element_type=f32)
            return m_new, alpha * l + pr.sum(axis=-1), acc

        m0 = jnp.full((c, g, r), -1e30, f32)
        _, l, acc = jax.lax.fori_loop(
            0, (n_live + kb - 1) // kb, body,
            (m0, jnp.zeros((c, g, r), f32), jnp.zeros((c, g, r, dh), f32)))
        return acc / jnp.maximum(l, 1e-30)[..., None]
