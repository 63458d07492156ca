"""Plain reference for ``minicpm_sala``: the full causal forward of a
``minicpm_sala`` decoder in float32 ``jax.numpy`` at matmul precision
"highest".  No cache, no chunks, no state carried between calls,
nothing from ``paddle_tpu``: a lightning layer is a ``lax.scan`` over
time, one token a step; a sparse layer is a masked softmax over the
whole sequence whose mask is built, query by query, from the selection
rule itself.

The equations (``h`` the residual, ``RMS`` RMSNorm with weight, eps from
the config; ``c = scale_depth / sqrt(mup_denominator)``):

    h0     = scale_emb * E[ids]
    u      = RMS_1(h)
    h      = h + c * W_o (sigmoid(W_g u) * Mixer(u))
    h      = h + c * W_down(silu(W_gate v) * W_up v),      v = RMS_2(h)
    logits = W_head (RMS_f(h) / (hidden_size / dim_model_base))

    lightning-attn:  q, k, v = W_q u, W_k u, W_v u as [heads, d]; q, k <-
        RMS per head (qk_norm), then rotary (rotate-half over the whole
        head, theta from the config); per head S_t = lambda_h S_{t-1} +
        k_t^T v_t, o_t = q_t S_t / sqrt(d); Mixer = RMS_o(o) over the
        concatenated heads; lambda_h = exp(-2^{-8 (h + 1) / heads}).
    minicpm4:  q, k <- RMS per head, no positions; K/V heads shared by
        n_head / n_kv_head query heads (a group).  A query at position t
        (context n = t + 1):
        1. n <= dense_len: every position <= t;
        2. else compressed keys c_j = mean(k[stride j .. stride j +
           kernel - 1]) for every kernel that ends at or before t;
           p_{h,j} = softmax_j(q_h . c_j / sqrt(d)); relevance of block b
           (positions block b .. block (b + 1) - 1) = sum over the
           group's heads of max over the kernels that overlap b of p;
           forced blocks: the first init_blocks and every block that
           meets the last window_size positions; selected = forced plus
           the topk unforced live blocks of largest relevance (ties to
           the lower block);
        3. softmax over the selected positions <= t of q_h . k / sqrt(d).

Weights come in under the names the served program uses (``lm_emb``,
``lm_l<i>_attn_q`` ...; matrices ``[in, out]``) and in the dtype it
serves them in (bf16): they are upcast here, one layer at a time
(``block`` takes one layer's weights and its kind), the MLP in slices of
its width, the sparse attention in blocks of queries and the head in
blocks of the vocabulary (``head_stats``), so that a forward of 15k
positions fits beside 5.6 GB of served weights.  That naming is the only
thing shared with the system under test.

What the catalog's config does not give is listed in the configuration
file under ``assumed`` (the sparse sizes, the decay rates, where the
norms and the gate sit).

Tolerances (``check`` in the config).  The served step rounds each
matmul's activations to bf16 and keeps K/V and the compressed keys in
bf16; this forward keeps them in float32.  With random weights the top
logits of 73,448 sit closer than that rounding, so tokens cannot be
compared; logits can: a served token's GAP is how far its reference
logit lies below that position's maximum, as a share of the position's
logit range (max - min).  Two limits, because this model has a source of
error Falcon's has not: block ranks are near ties (compressed keys of
random keys score within a fraction of a percent of each other), so the
served bf16 path and this float32 forward select different MARGINAL
blocks in most sparse queries, and once in a few hundred tokens such a
flip moves a logit visibly (worst gap 0.005-0.022 over seven unharmed
runs on the chip).  ``mean_logit_gap_share`` (0.0003) bounds the mean
gap over every sampled token — what rounding and wrong mathematics move,
and a rare flip does not: unharmed 0.00003-0.00005, int8-rounded weights
0.00068, a selection that ignores the scores 0.011.  ``logit_gap_share``
(0.05) bounds the worst single token, loose on purpose.  PERF.md section
4 has every reading and the harmed variants that must fail
(``benchmark/tests/test_sparse_linear_check.py``).
"""
import jax
import jax.numpy as jnp

F32 = jnp.float32
SPARSE, LIGHTNING = "minicpm4", "lightning-attn"


def _f(a):
    return jnp.asarray(a).astype(F32)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f(w)


def _size(t):
    return jnp.sqrt(jnp.mean(t * t))


def _sparse(cfg):
    return cfg.get("sparse_config") or cfg["assumed"]["sparse_config"]


def _rope(x, theta):
    """x [S, H, D] at positions 0..S-1, rotate-half over all of D."""
    s, half = x.shape[0], x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[:, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def _qkv(w, p, u, dh, eps):
    s = u.shape[0]
    q = _rms((u @ _f(w[p + "attn_q"])).reshape(s, -1, dh), w[p + "q_norm"], eps)
    k = _rms((u @ _f(w[p + "attn_k"])).reshape(s, -1, dh), w[p + "k_norm"], eps)
    return q, k, (u @ _f(w[p + "attn_v"])).reshape(s, -1, dh)


def lightning(w, p, u, cfg):
    """u [S, D] -> the mixer's output [S, heads * d] (after RMS_o)."""
    heads, dh = int(cfg["lightning_nh"]), int(cfg["lightning_head_dim"])
    eps = float(cfg["rms_norm_eps"])
    q, k, v = _qkv(w, p, u, dh, eps)
    q, k = _rope(q, float(cfg["rope_theta"])), _rope(k, float(cfg["rope_theta"]))
    lam = jnp.exp(-(2.0 ** (-8.0 * (jnp.arange(heads, dtype=F32) + 1.0)
                            / heads)))

    def step(state, inp):
        q_t, k_t, v_t = inp                                    # [H, d]
        state = (lam[:, None, None] * state
                 + k_t[:, :, None] * v_t[:, None, :])
        return state, jnp.einsum("hd,hde->he", q_t, state)

    _, o = jax.lax.scan(step, jnp.zeros((heads, dh, dh), F32), (q, k, v))
    o = o / jnp.sqrt(F32(dh))
    return _rms(o.reshape(u.shape[0], heads * dh), w[p + "o_norm"], eps)


def selection(q, k, t, sp):
    """Steps 1-2 for one K/V head: q [R, Q, d] (the group's heads, Q
    queries at positions t [Q]), k [S, d] the head's keys.  Returns
    allowed [Q, S]: may query i read position j."""
    kernel, stride, block = (int(sp[x]) for x in (
        "kernel_size", "kernel_stride", "block_size"))
    s, dh = k.shape
    assert s % block == 0, "pad the sequence to whole blocks"
    n_k, n_b = (s - kernel) // stride + 1, s // block
    n = t + 1
    starts = jnp.arange(n_k) * stride
    c = jnp.stack([k[i:i + n_k * stride:stride] for i in range(kernel)]
                  )[:, :n_k].mean(axis=0)                       # [NK, d]
    ends = starts + kernel - 1
    done = ends[None, :] <= t[:, None]                          # [Q, NK]
    sc = jnp.einsum("rqd,kd->rqk", q, c) / jnp.sqrt(F32(dh))
    p = jax.nn.softmax(jnp.where(done[None], sc, -jnp.inf), axis=-1)
    p = jnp.where(done[None], p, 0.0)       # no complete kernel: all zero
    # the kernels that overlap block b: from (block b - kernel) / stride,
    # exclusive, to block (b + 1) / stride, exclusive
    per, over = block // stride, kernel // stride - 1
    pp = jnp.pad(p, ((0, 0), (0, 0), (over, per * n_b - n_k)))
    rel = jax.lax.reduce_window(
        pp, -jnp.inf, jax.lax.max, (1, 1, per + over), (1, 1, per),
        "VALID").sum(axis=0)                                    # [Q, NB]
    b = jnp.arange(n_b)[None, :]
    live = b <= (t // block)[:, None]
    forced = live & ((b < int(sp["init_blocks"]))
                     | (b >= ((n - int(sp["window_size"])) // block)[:, None]))
    cand = live & ~forced
    order = jnp.argsort(jnp.where(cand, -rel, jnp.inf), axis=-1, stable=True)
    rank = jnp.argsort(order, axis=-1, stable=True)
    chosen = forced | (cand & (rank < int(sp["topk"])))
    by_pos = jnp.repeat(chosen, block, axis=-1)                 # [Q, S]
    dense = (n <= int(sp["dense_len"]))[:, None]
    return (dense | by_pos) & (jnp.arange(s)[None, :] <= t[:, None])


def sparse_attention(w, p, u, cfg, query_block=128):
    """u [S, D] -> the attention's context [S, n_head * d]."""
    nh, nkv, dh = (int(cfg[k]) for k in (
        "num_attention_heads", "num_key_value_heads", "head_dim"))
    sp, s, rep = _sparse(cfg), u.shape[0], nh // nkv
    q, k, v = _qkv(w, p, u, dh, float(cfg["rms_norm_eps"]))
    qb = min(query_block, s)
    assert s % qb == 0, "query_block must divide the sequence"
    qg = q.reshape(s // qb, qb, nkv, rep, dh)
    ts = jnp.arange(s).reshape(s // qb, qb)

    def one(args):
        qq, t = args                                # [qb, G, R, d], [qb]
        out = []
        for g in range(nkv):
            qh = jnp.moveaxis(qq[:, g], 0, 1)                   # [R, qb, d]
            ok = selection(qh, k[:, g], t, sp)
            sc = jnp.einsum("rqd,kd->rqk", qh, k[:, g]) / jnp.sqrt(F32(dh))
            pr = jax.nn.softmax(jnp.where(ok[None], sc, -jnp.inf), axis=-1)
            out.append(jnp.einsum("rqk,kd->qrd", pr, v[:, g]))
        return jnp.stack(out, axis=1)                           # [qb,G,R,d]

    ctx = jax.lax.map(one, (qg, ts))
    return ctx.reshape(s, nh * dh)


def mlp(w, p, v, blocks=1):
    """SwiGLU, the intermediate width in ``blocks`` equal slices (each
    slice's down-projection adds into the same sum), so that only one
    slice of the three matrices is upcast at a time."""
    wg, wu, wd = w[p + "mlp_gate"], w[p + "mlp_up"], w[p + "mlp_down"]
    n = wg.shape[1] // blocks
    assert n * blocks == wg.shape[1], "blocks must divide the MLP width"
    out = jnp.zeros_like(v)
    for j in range(blocks):
        cols = slice(j * n, (j + 1) * n)
        out = out + (jax.nn.silu(v @ _f(wg[:, cols]))
                     * (v @ _f(wu[:, cols]))) @ _f(wd[cols, :])
    return out


def embed(w, tokens, cfg, name="lm"):
    """tokens [S] -> h [S, D]."""
    return _f(w[name + "_emb"][tokens]) * float(cfg["scale_emb"])


def block(w, i, kind, h, cfg, name="lm", mlp_blocks=1, query_block=128):
    """One layer of ``kind`` over ``h`` [S, D]; ``w`` needs only layer
    ``i``'s weights.  Returns ``(h, shares)``: the rms of the mixer's and
    the MLP's contribution over the rms of the residual each is added
    to."""
    with jax.default_matmul_precision("highest"):
        p = "%s_l%d_" % (name, i)
        eps = float(cfg["rms_norm_eps"])
        c = float(cfg["scale_depth"]) / float(cfg["mup_denominator"]) ** 0.5
        u = _rms(h, w[p + "norm1"], eps)
        o = (lightning(w, p, u, cfg) if kind == LIGHTNING
             else sparse_attention(w, p, u, cfg, query_block))
        mix = c * ((jax.nn.sigmoid(u @ _f(w[p + "attn_g"])) * o)
                   @ _f(w[p + "attn_o"]))
        mid = h + mix
        out = c * mlp(w, p, _rms(mid, w[p + "norm2"], eps), mlp_blocks)
        return mid + out, jnp.stack([_size(mix) / _size(h),
                                     _size(out) / _size(mid)])


def _head_input(w, h, cfg, name):
    x = _rms(h, w[name + "_final_norm"], float(cfg["rms_norm_eps"]))
    return x / (float(cfg["hidden_size"]) / float(cfg["dim_model_base"]))


def head(w, h, cfg, name="lm"):
    """All logits [S, V] (small vocabularies: the CPU tests)."""
    with jax.default_matmul_precision("highest"):
        return _head_input(w, h, cfg, name) @ _f(w[name + "_head"])


def head_stats(w, h, targets, cfg, blocks, name="lm"):
    """What the check needs of the logits at the rows ``h`` [M, D]
    without holding them: ``(max, min, argmax, logit of targets)``, each
    [M], the head taken in ``blocks`` slices of the vocabulary."""
    with jax.default_matmul_precision("highest"):
        x = _head_input(w, h, cfg, name)
        wh = w[name + "_head"]
        vocab = wh.shape[1]
        vb = -(-vocab // blocks)
        shp = targets.shape
        hi, lo = jnp.full(shp, -jnp.inf, F32), jnp.full(shp, jnp.inf, F32)
        arg, got = jnp.zeros(shp, jnp.int32), jnp.zeros(shp, F32)
        for j in range(blocks):   # static slices: no copy of the head
            lo_v, hi_v = j * vb, min((j + 1) * vb, vocab)
            lg = x @ _f(wh[:, lo_v:hi_v])                       # [M, <=vb]
            bmax = lg.max(-1)
            arg = jnp.where(bmax > hi, lo_v + lg.argmax(-1), arg)
            local = targets - lo_v
            picked = jnp.take_along_axis(
                lg, jnp.clip(local, 0, hi_v - lo_v - 1)[..., None],
                -1)[..., 0]
            got = jnp.where((local >= 0) & (local < hi_v - lo_v), picked, got)
            hi, lo = jnp.maximum(hi, bmax), jnp.minimum(lo, lg.min(-1))
        return hi, lo, arg, got


def forward(w, tokens, cfg, name="lm", query_block=128):
    """tokens [S] int32 -> logits [S, V]; position s sees positions
    <= s.  ``S`` a multiple of ``block_size``."""
    h = embed(w, tokens, cfg, name)
    for i, kind in enumerate(cfg["mixer_types"]):
        h, _ = block(w, i, kind, h, cfg, name, query_block=query_block)
    return head(w, h, cfg, name)
