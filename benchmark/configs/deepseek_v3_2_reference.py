"""Plain reference for ``deepseek_v3_2``: the full causal forward of a
``deepseek_v32`` decoder in float32 ``jax.numpy`` at matmul precision
"highest".  No cache, no batching, no absorbed form, no grouped product,
nothing from ``paddle_tpu``: attention is EXPANDED (every position's
latent row expanded to its 128 heads' keys and values), the lightning
indexer scores every earlier position in float32 and ``lax.top_k`` picks
each query's own set, attention is a masked softmax over exactly that
set, the experts are a loop over the held ones, each applied to EVERY
token and kept where the token chose it.

The equations (``h`` the residual ``[S, D]``; ``RMS`` RMSNorm with
weight, eps ``rms_norm_eps``; no bias but the indexer's LayerNorm):

    x = RMS(h; input_norm)
    cq = RMS(x W_dq; q_a_norm);  q_i = cq W_uq,i = [qC_i ; qR_i]
    [c ; kR] = x W_dkv;  c = RMS(c; kv_a_norm)
    qR_i, kR rotated at their positions: YaRN over the rope lanes
       (theta, factor, original_max_position_embeddings, beta_fast,
       beta_slow: each inverse frequency a blend of theta^(-2j/lanes)
       and that / factor, by the linear ramp between the two correction
       dims), lanes paired half-split (j with j + lanes / 2)
    kC_s,i = c_s W_uk,i^T;  v_s,i = c_s W_uv,i
    indexer: qI_j = (cq W_iq)_j, kI = LayerNorm(x W_ik; weight, bias,
       eps 1e-6), the first rope lanes of each rotated the same way;
       w = (x W_iw) * heads^-0.5 * dim^-0.5
       I_t,s = sum_j w_t,j relu(qI_t,j . kI_s)   for s <= t
       S_t = the min(index_topk, t + 1) positions of largest I_t,s
             (lax.top_k: ties the lowest position first)
    a_t,s,i = scale (qC_t,i . kC_s,i + qR_t,i . kR_s),
       scale = (nope + rope)^-0.5 * m^2, m = 0.1 mscale_all_dim ln(factor) + 1
    o_t = concat_i(sum_{s in S_t} softmax_{s in S_t}(a_t,s,i) v_s,i) W_o
    h = h + o;  f = RMS(h; ffn_norm)
    dense layer:   h = h + W2 (silu(W1 f) * W3 f)
    sparse layer:  s = sigmoid(f W_r)      float32, all the experts
                   z = s + b;  the experts are n_group consecutive groups,
                   a group scored by the sum of its two largest z, the
                   topk_group best groups kept;  sel = top_k of z inside them
                   g_e = s_e / (sum_{e in sel} s_e + 1e-6) * routed_scaling_factor
                   h = h + sum_{e in sel, lo <= e < hi} g_e E_e(f) + E_shared(f)
    logits = RMS(h; norm) W_head                    (the head is untied)

``held = (lo, hi)`` is the contiguous range of experts this share
computes (routing is over all of them; what the absent ones would add is
left out); ``shared=False`` leaves the shared expert out (a share summed
with others counts it once).  The vocabulary is the slice the weights
hold.  The weights come in under the names the served program uses
(``lm_emb``, ``lm_l<i>_attn_q_a`` ...; matrices ``[in, out]``, the
latent's up projections a head a batch — ``attn_uk`` ``[heads, nope,
kv_lora_rank]``, ``attn_uv`` ``[heads, kv_lora_rank, v]`` — an expert
layer's gate and up matrices as ONE ``[held, d, 2 * width]`` with the
gate's columns first: the only things shared with the system under
test) and in the dtype it serves them in: they are upcast here, a layer
at a time, attention ``head_block`` heads and ``query_block`` query rows
at a time and the head in vocabulary blocks (``head_stats``), so that
19k positions fit in under 2 GB of temporaries.

Departures from the released code, and what the catalog's config does
not say (``departures`` and ``assumed`` in the config file): no Hadamard
rotation of ``qI``, ``kI`` (orthonormal: every dot product is as it is);
index keys bf16 as stored and scores float32 where the release stores
FP8; half-split lane pairing in attention too (the release interleaves
there: a permutation under random weights); 1e-6 (released 1e-20) added
to the sum of the chosen scores; no multi-token-prediction module; the
share (8 held experts of 256, 16,160 vocabulary rows of 129,280).

The operands the configuration states (``matmul_inputs``), as
``k_exaone_236b_a23b``'s reference and for its reason: with
``cfg["matmul_inputs"] = "bfloat16"`` each operand the configuration
says is rounded is rounded HERE too (``_mm_in``, by
``lax.reduce_precision``), in float32: the arithmetic stays float32 at
"highest".  The CPU tests keep the default.
"""
import math

import jax
import jax.numpy as jnp

F32 = jnp.float32
_EPS_SUM = 1e-6
_LN_EPS = 1e-6


def _f(a):
    return jnp.asarray(a).astype(F32)


def _mm_in(x, cfg, like=None):
    """``x`` as a matrix product takes it: unchanged (float32) unless the
    configuration's ``matmul_inputs`` names a dtype — then rounded to
    that dtype's precision, in float32 (``like``: the stored weight it
    meets; a float32 weight, the router's, leaves its input alone)."""
    dt = cfg.get("matmul_inputs")
    if dt is None or (like is not None and jnp.asarray(like).dtype == F32):
        return x
    fi = jnp.finfo(dt)
    return jax.lax.reduce_precision(x, fi.nexp, fi.nmant)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f(w)


def inv_freq(cfg):
    """YaRN's inverse frequencies over the rope lanes, ``[lanes / 2]``."""
    lanes, base = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    extra = [base ** (-2.0 * j / lanes) for j in range(lanes // 2)]
    sc = cfg.get("rope_scaling")
    if not sc:
        return jnp.asarray(extra, F32)
    factor = float(sc["factor"])
    orig = float(sc["original_max_position_embeddings"])

    def dim_of(rotations):
        return lanes * math.log(orig / (rotations * 2 * math.pi)) / (
            2 * math.log(base))

    low = max(math.floor(dim_of(float(sc["beta_fast"]))), 0)
    high = min(math.ceil(dim_of(float(sc["beta_slow"]))), lanes - 1)
    span = (high - low) or 0.001
    out = []
    for j, f in enumerate(extra):
        ramp = min(max((j - low) / span, 0.0), 1.0)   # 1: interpolated
        out.append(f / factor * ramp + f * (1.0 - ramp))
    return jnp.asarray(out, F32)


def softmax_scale(cfg):
    sc = cfg.get("rope_scaling") or {}
    m = 1.0
    if sc.get("mscale_all_dim") and float(sc.get("factor", 1)) > 1:
        m = 0.1 * float(sc["mscale_all_dim"]) * math.log(
            float(sc["factor"])) + 1.0
    width = int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
    return width ** -0.5 * m * m


def _rope(x, cfg, at=None):
    """x [S, ..., lanes] at positions ``at`` (default 0..S-1), lane j
    paired with j + lanes / 2 over ALL of the last axis."""
    s, half = x.shape[0], x.shape[-1] // 2
    at = jnp.arange(s) if at is None else at
    ang = at.astype(F32)[:, None] * inv_freq(cfg)[None, :]
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (half,))
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def _rope_head(x, cfg, at=None):
    """The first rope lanes of each index head rotated, the rest bare."""
    r = int(cfg["qk_rope_head_dim"])
    return jnp.concatenate([_rope(x[..., :r], cfg, at), x[..., r:]], axis=-1)


def projections(w, p, x, cfg):
    """Everything attention and the indexer take of the normed rows
    ``x`` [S, D] but the heads' and the indexer's queries
    (:func:`queries`, :func:`index_queries`): ``(cq [S, q_lora_rank], c
    [S, rank], kR [S, rope], kI [S, Di], wI [S, Hi])``; what the
    configuration states as stored (``c``, ``kR``, ``kI``) is rounded as
    stored."""
    s = x.shape[0]
    eps = float(cfg["rms_norm_eps"])
    rank = int(cfg["kv_lora_rank"])
    hi, di = int(cfg["index_n_heads"]), int(cfg["index_head_dim"])
    x = _mm_in(x, cfg)
    cq = _mm_in(_rms(x @ _f(w[p + "attn_q_a"]), w[p + "q_a_norm"], eps), cfg)
    ckr = x @ _f(w[p + "attn_kv_a"])
    c = _mm_in(_rms(ckr[:, :rank], w[p + "kv_a_norm"], eps), cfg)
    kr = _mm_in(_rope(ckr[:, rank:], cfg), cfg)
    k = x @ _f(w[p + "index_k"])
    mu = k.mean(-1, keepdims=True)
    k = ((k - mu) / jnp.sqrt(((k - mu) ** 2).mean(-1, keepdims=True)
                             + _LN_EPS)
         * _f(w[p + "index_k_norm"]) + _f(w[p + "index_k_norm_bias"]))
    ki = _mm_in(_rope_head(k, cfg), cfg)
    wi = (x @ _f(w[p + "index_w"])) * (hi ** -0.5 * di ** -0.5)
    return cq, c, kr, ki, wi


def index_queries(w, p, cq, at, cfg):
    """``qI [Q, Hi, Di]`` of the query latents ``cq`` [Q, q_lora_rank] at
    positions ``at``, rounded as the product takes them."""
    hi, di = int(cfg["index_n_heads"]), int(cfg["index_head_dim"])
    q = (cq @ _f(w[p + "index_q"])).reshape(cq.shape[0], hi, di)
    return _mm_in(_rope_head(q, cfg, at), cfg)


def queries(w, p, cq, mine, cfg):
    """``(qC [S, hb, nope], qR [S, hb, rope])`` of a group of heads
    (``mine(t, axis)`` slices them out of a head axis) from the query
    latents ``cq`` [S, q_lora_rank]: ``qR`` rotated, both times the
    softmax scale and rounded as a product takes them."""
    nh, nope = int(cfg["num_attention_heads"]), int(cfg["qk_nope_head_dim"])
    rope = int(cfg["qk_rope_head_dim"])
    wq = mine(w[p + "attn_q_b"].reshape(-1, nh, nope + rope), 1)
    q = jnp.einsum("sr,rhd->shd", cq, _f(wq))
    scale = softmax_scale(cfg)
    return (_mm_in(q[..., :nope] * scale, cfg),
            _mm_in(_rope(q[..., nope:], cfg) * scale, cfg))


def index_scores(qi, wi, ki):
    """``I[t, s] = sum_j wi[t, j] relu(qi[t, j] . ki[s])``: [Q, S]."""
    return jnp.einsum("qhs,qh->qs",
                      jax.nn.relu(jnp.einsum("qhd,sd->qhs", qi, ki)), wi)


def selected(scores, at, top_k):
    """Which positions each query reads, [Q, S] bool: the ``min(top_k,
    at + 1)`` positions ``<= at`` of largest score."""
    q, s = scores.shape
    causal = jnp.arange(s)[None, :] <= at[:, None]
    top, idx = jax.lax.top_k(jnp.where(causal, scores, -jnp.inf),
                             min(int(top_k), s))
    return jnp.zeros((q, s), bool).at[jnp.arange(q)[:, None], idx].set(
        top > -jnp.inf)


def attention(w, p, x, cfg, query_block=None, index_rows=None,
              head_block=16):
    """Selected causal attention of the normed rows ``x`` [S, D] over the
    whole sequence, EXPANDED: first WHICH positions every query reads
    (``[S, S]`` bool, ``query_block`` query rows at a time), then
    ``head_block`` heads at a time their keys and values expanded from
    the latent rows and a masked softmax over exactly that set,
    ``query_block`` query rows at a time.  Returns ``(o [S, D], I)``:
    ``I`` the index scores ``[len(index_rows), S]`` of the query rows
    named (None: not asked)."""
    s = x.shape[0]
    cq, c, kr, ki, wi = projections(w, p, x, cfg)
    top_k = int(cfg["index_topk"])
    qb = s if query_block is None else int(query_block)
    assert s % qb == 0, "query_block must divide the sequence"
    nh, dv = int(cfg["num_attention_heads"]), int(cfg["v_head_dim"])
    hb = min(int(head_block), nh)
    assert nh % hb == 0, "head_block must divide the heads"
    blocks = lambda t: t.reshape((s // qb, qb) + t.shape[1:])
    member = jax.lax.map(
        lambda a: selected(index_scores(
            index_queries(w, p, a[0], a[2], cfg), a[1], ki), a[2], top_k),
        (blocks(cq), blocks(wi), jnp.arange(s).reshape(s // qb, qb)))
    wo = w[p + "attn_o"].reshape(nh, dv, -1)

    def heads(o, g):
        mine = lambda t, axis=0: jax.lax.dynamic_slice_in_dim(
            t, g * hb, hb, axis)
        qcg, qrg = queries(w, p, cq, mine, cfg)             # [S, hb, .]
        kc = _mm_in(jnp.einsum("sc,hdc->shd", c,
                               _f(mine(w[p + "attn_uk"]))), cfg)
        v = _mm_in(jnp.einsum("sc,hcd->shd", c,
                              _f(mine(w[p + "attn_uv"]))), cfg)

        def rows(args):
            qci, qri, mem = args
            a = (jnp.einsum("qhd,shd->hqs", qci, kc)
                 + jnp.einsum("qhr,sr->hqs", qri, kr))
            probs = jax.nn.softmax(jnp.where(mem[None], a, -1e9), -1)
            return jnp.einsum("hqs,shd->qhd", _mm_in(probs, cfg), v)

        ctx = jax.lax.map(rows, (blocks(qcg), blocks(qrg), member))
        return o + jnp.einsum("shd,hdm->sm",
                              _mm_in(ctx.reshape(s, hb, dv), cfg),
                              _f(mine(wo))), None

    o = jax.lax.scan(heads, jnp.zeros((s, wo.shape[-1]), F32),
                     jnp.arange(nh // hb))[0]
    asked = None
    if index_rows is not None:
        asked = index_scores(index_queries(w, p, cq[index_rows], index_rows,
                                           cfg), wi[index_rows], ki)
    return o, asked


def routing(w, p, x, cfg):
    """``(sel [S, k], gate [S, k])`` over ALL the experts: sigmoid
    scores, the bias in the choice only, the choice inside the best
    groups."""
    n_group, keep = int(cfg.get("n_group", 1)), int(cfg.get("topk_group", 1))
    s = jax.nn.sigmoid(_mm_in(x, cfg, w[p + "router"]) @ _f(w[p + "router"]))
    z = s + _f(w[p + "expert_bias"])
    if n_group > 1:
        rows = z.shape[0]
        by_group = z.reshape(rows, n_group, -1)
        score = jax.lax.top_k(by_group, 2)[0].sum(-1)
        _, best = jax.lax.top_k(score, keep)
        kept = jnp.zeros((rows, n_group), bool).at[
            jnp.arange(rows)[:, None], best].set(True)
        z = jnp.where(kept[:, :, None], by_group, -jnp.inf).reshape(rows, -1)
    _, sel = jax.lax.top_k(z, int(cfg["num_experts_per_tok"]))
    gate = jnp.take_along_axis(s, sel, axis=-1)
    if cfg.get("norm_topk_prob", True):
        gate = gate / (gate.sum(-1, keepdims=True) + _EPS_SUM)
    return sel, gate * float(cfg.get("routed_scaling_factor", 1.0))


def _gated(x, a13, a2, cfg):
    gu = x @ _f(a13)
    width = a13.shape[-1] // 2
    return _mm_in(jax.nn.silu(gu[..., :width]) * gu[..., width:], cfg) @ _f(a2)


def experts(w, p, x, sel, gate, cfg, held=None, shared=True):
    """The held experts' part of the mixture — every held expert applied
    to every token, weighed by the token's gate for it (zero where the
    token did not choose it), one expert after another — plus, with
    ``shared``, the shared expert's unweighed term."""
    n_all = int(cfg.get("n_routed_experts_all", cfg["n_routed_experts"]))
    lo, hi = (0, n_all) if held is None else held
    w13, w2 = w[p + "experts_w13"], w[p + "experts_w2"]
    assert w13.shape[0] == hi - lo, "state must hold the held experts"
    x = _mm_in(x, cfg, w13)

    def one(out, expert):
        e, a13, a2 = expert
        weight = jnp.sum(jnp.where(sel == e, gate, 0.0), axis=-1,
                         keepdims=True)
        return out + weight * _gated(x, a13, a2, cfg), None

    out = jax.lax.scan(one, jnp.zeros_like(x),
                       (jnp.arange(lo, hi), w13, w2))[0]
    if shared and int(cfg.get("n_shared_experts", 0)):
        out = out + _gated(x, w[p + "shared_w13"], w[p + "shared_w2"], cfg)
    return out


def _size(t):
    return jnp.sqrt(jnp.mean(t * t))


def embed(w, tokens, cfg, name="lm"):
    return _f(w[name + "_emb"][tokens])


def block(w, p, h, cfg, dense, held=None, query_block=None, shared=True,
          index_rows=None):
    """One block over ``h`` [S, D]; ``w`` needs only the weights under
    prefix ``p``.  Returns ``(h, shares, I)``: the rms of the attention
    branch's and of the FFN branch's contribution over the rms of the
    residual each is added to, and the index scores of ``index_rows``."""
    with jax.default_matmul_precision("highest"):
        eps = float(cfg["rms_norm_eps"])
        o, asked = attention(w, p, _rms(h, w[p + "input_norm"], eps), cfg,
                             query_block, index_rows)
        mid = h + o

        def ffn(rows):          # a block of rows: nothing [S, 18432] held
            f = _rms(rows, w[p + "ffn_norm"], eps)
            if dense:
                x = _mm_in(f, cfg)
                return _mm_in(jax.nn.silu(x @ _f(w[p + "ffn_gate"]))
                              * (x @ _f(w[p + "ffn_up"])), cfg) @ _f(
                                  w[p + "ffn_down"])
            sel, gate = routing(w, p, f, cfg)
            return experts(w, p, f, sel, gate, cfg, held, shared)

        s = h.shape[0]
        rb = 1024 if s % 1024 == 0 else s
        y = jax.lax.map(ffn, mid.reshape(s // rb, rb, -1)).reshape(s, -1)
        return mid + y, jnp.stack([_size(o) / _size(h),
                                   _size(y) / _size(mid)]), asked


def head(w, h, cfg, name="lm"):
    """All logits [S, V] (small vocabularies: the CPU tests)."""
    with jax.default_matmul_precision("highest"):
        x = _mm_in(_rms(h, w[name + "_final_norm"],
                        float(cfg["rms_norm_eps"])), cfg)
        return x @ _f(w[name + "_head"])


def head_stats(w, h, targets, cfg, blocks, name="lm"):
    """What the check needs of the logits at the rows ``h`` [..., D]
    without holding them: ``(max, min, argmax, logit of targets)``, each
    shaped like ``targets``, the head taken in ``blocks`` equal slices of
    the vocabulary."""
    with jax.default_matmul_precision("highest"):
        x = _mm_in(_rms(h, w[name + "_final_norm"],
                        float(cfg["rms_norm_eps"])), cfg)
        wh = w[name + "_head"]
        vb = wh.shape[1] // blocks
        assert vb * blocks == wh.shape[1], "blocks must divide the vocabulary"
        shp = targets.shape
        hi, lo = jnp.full(shp, -jnp.inf, F32), jnp.full(shp, jnp.inf, F32)
        arg, got = jnp.zeros(shp, jnp.int32), jnp.zeros(shp, F32)
        for j in range(blocks):   # static slices: no copy of the matrix
            lg = x @ _f(wh[:, j * vb:(j + 1) * vb])             # [..., vb]
            bmax = lg.max(-1)
            arg = jnp.where(bmax > hi, j * vb + lg.argmax(-1), arg)
            local = targets - j * vb
            picked = jnp.take_along_axis(
                lg, jnp.clip(local, 0, vb - 1)[..., None], -1)[..., 0]
            got = jnp.where((local >= 0) & (local < vb), picked, got)
            hi, lo = jnp.maximum(hi, bmax), jnp.minimum(lo, lg.min(-1))
        return hi, lo, arg, got


def hidden(w, tokens, cfg, name="lm", held=None, shared=True,
           query_block=None):
    """tokens [S] int32 -> the last block's output [S, D]."""
    h = embed(w, tokens, cfg, name)
    for i in range(int(cfg["num_hidden_layers"])):
        h = block(w, "%s_l%d_" % (name, i), h, cfg,
                  i < int(cfg["first_k_dense_replace"]), held, query_block,
                  shared)[0]
    return h


def forward(w, tokens, cfg, name="lm", held=None, query_block=None):
    """tokens [S] int32 -> logits [S, V]; position s reads the positions
    <= s its indexer selects."""
    return head(w, hidden(w, tokens, cfg, name, held,
                          query_block=query_block), cfg, name)
