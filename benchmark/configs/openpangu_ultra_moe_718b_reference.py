"""Plain reference for ``openpangu_ultra_moe_718b``: the full causal
forward of a ``pangu_ultra_moe`` decoder and of its multi-token-
prediction module in float32 ``jax.numpy`` at matmul precision
"highest".  No cache, no batching, no absorbed form, no grouped product,
no round, nothing from ``paddle_tpu``: attention is EXPANDED (every
position's latent row expanded to its 128 heads' keys and values) and
DENSE (a causal softmax over every earlier position), the experts are a
loop over the held ones, each applied to EVERY token and kept where the
token chose it.

The equations (``h`` the residual ``[S, D]``; ``RMS`` RMSNorm with
weight, eps ``rms_norm_eps``; no bias anywhere):

    x = RMS(h; input_norm)
    cq = RMS(x W_dq; q_a_norm);  q_i = cq W_uq,i = [qC_i ; qR_i]
    [c ; kR] = x W_dkv;  c = RMS(c; kv_a_norm)
    qR_i, kR rotated at their positions: plain rotary over the rope
       lanes, inverse frequencies rope_theta^(-2j/lanes), NO YaRN, lanes
       paired half-split (j with j + lanes / 2)
    kC_s,i = c_s W_uk,i^T;  v_s,i = c_s W_uv,i
    a_t,s,i = (nope + rope)^-0.5 (qC_t,i . kC_s,i + qR_t,i . kR_s),  s <= t
    o_t = concat_i(sum_{s <= t} softmax_s(a_t,s,i) v_s,i) W_o
    h = h + RMS(o; post_attn_norm)            (sandwich_norm: the branch
    f = RMS(h; pre_mlp_norm)                   between TWO norms)
    dense layer:   y = W2 (silu(W1 f) * W3 f)
    sparse layer:  s = sigmoid(f W_r)      float32, all the experts
                   sel = top_k(s): no selection bias, ONE group
                   g_e = s_e / (sum_{e in sel} s_e + 1e-6) * routed_scaling_factor
                   y = sum_{e in sel, lo <= e < hi} g_e E_e(f) + E_shared(f)
    h = h + RMS(y; post_mlp_norm)
    logits = RMS(h; final_norm) W_head              (the head is untied)

The module (one; DeepSeek-V3's form), ``h_i`` the last block's output at
position ``i`` and ``t_{i+1}`` the token after it:

    u_i = W_eh [RMS(Emb(t_{i+1}); mtp_e_norm) ; RMS(h_i; mtp_h_norm)]
    u -> one sandwich-normed SPARSE block (its own weights, causal over
         the u's) -> RMS(.; final_norm) W_head: logits for t_{i+2}

``held = (lo, hi)`` is the contiguous range of experts this share
computes (routing is over all of them; what the absent ones would add is
left out); ``shared=False`` leaves the shared expert out (a share summed
with others counts it once).  The vocabulary is the slice the weights
hold.  The weights come in under the names the served program uses
(``lm_emb``, ``lm_l<i>_attn_q_a`` ..., the module's under ``lm_mtp_``;
matrices ``[in, out]``, the latent's up projections a head a batch —
``attn_uk`` ``[heads, nope, kv_lora_rank]``, ``attn_uv`` ``[heads,
kv_lora_rank, v]`` — an expert layer's gate and up matrices as ONE
``[held, d, 2 * width]`` with the gate's columns first: the only things
shared with the system under test) and in the dtype it serves them in:
they are upcast here, a layer at a time, attention ``head_block`` heads
and ``query_block`` query rows at a time and the head in vocabulary
blocks (``head_stats``), so that 9k positions fit.

Departures from the published description, and what the catalog's config
does not say (``departures`` and ``assumed`` in the config file): the
router's scoring (sigmoid), its grouping (one group) and its absent bias
are the DeepSeek-V3 lineage's convention with the group limit and the
bias left out; the module's form and the order of its two halves are
DeepSeek-V3's; half-split lane pairing (a permutation of lanes under
random weights where a release interleaves); 1e-6 added to the sum of
the chosen scores; the share (8 held experts of 256, 19,200 vocabulary
rows of 153,600, layer 0 and layers 3-6 of 61 and the one module).

The operands the configuration states (``matmul_inputs``), as
``deepseek_v3_2``'s reference and for its reason: with
``cfg["matmul_inputs"] = "bfloat16"`` each operand the configuration
says is rounded is rounded HERE too (``_mm_in``, by
``lax.reduce_precision``), in float32: the arithmetic stays float32 at
"highest".  The CPU tests keep the default.
"""
import jax
import jax.numpy as jnp

F32 = jnp.float32
_EPS_SUM = 1e-6


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


def _rope(x, cfg):
    """x [S, ..., lanes] at positions 0..S-1, lane j paired with j +
    lanes / 2; plain rotary."""
    s, half = x.shape[0], x.shape[-1] // 2
    lanes, base = int(cfg["qk_rope_head_dim"]), float(cfg["rope_theta"])
    freq = jnp.asarray([base ** (-2.0 * j / lanes) for j in range(half)],
                       F32)
    ang = jnp.arange(s).astype(F32)[:, None] * freq[None, :]
    ang = ang.reshape((s,) + (1,) * (x.ndim - 2) + (half,))
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def attention(w, p, x, cfg, query_block=None, head_block=16):
    """Dense causal attention of the normed rows ``x`` [S, D] over the
    whole sequence, EXPANDED: ``head_block`` heads at a time their keys
    and values expanded from the latent rows and a causal softmax,
    ``query_block`` query rows at a time.  Returns ``o`` [S, D]."""
    s = x.shape[0]
    eps = float(cfg["rms_norm_eps"])
    rank = int(cfg["kv_lora_rank"])
    nh, dv = int(cfg["num_attention_heads"]), int(cfg["v_head_dim"])
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    scale = (nope + rope) ** -0.5
    x = _mm_in(x, cfg)
    cq = _mm_in(_rms(x @ _f(w[p + "attn_q_a"]), w[p + "q_a_norm"], eps), cfg)
    ckr = x @ _f(w[p + "attn_kv_a"])
    # what the configuration states as stored is rounded as stored
    c = _mm_in(_rms(ckr[:, :rank], w[p + "kv_a_norm"], eps), cfg)
    kr = _mm_in(_rope(ckr[:, rank:], cfg), cfg)
    qb = s if query_block is None else int(query_block)
    assert s % qb == 0, "query_block must divide the sequence"
    hb = min(int(head_block), nh)
    assert nh % hb == 0, "head_block must divide the heads"
    blocks = lambda t: t.reshape((s // qb, qb) + t.shape[1:])
    wq = w[p + "attn_q_b"].reshape(-1, nh, nope + rope)
    wo = w[p + "attn_o"].reshape(nh, dv, -1)
    at = jnp.arange(s)

    def heads(o, g):
        mine = lambda t, axis=0: jax.lax.dynamic_slice_in_dim(
            t, g * hb, hb, axis)
        q = jnp.einsum("sr,rhd->shd", cq, _f(mine(wq, 1)))
        qc = _mm_in(q[..., :nope] * scale, cfg)
        qr = _mm_in(_rope(q[..., nope:], cfg) * scale, cfg)
        kc = _mm_in(jnp.einsum("sc,hdc->shd", c,
                               _f(mine(w[p + "attn_uk"]))), cfg)
        v = _mm_in(jnp.einsum("sc,hcd->shd", c,
                              _f(mine(w[p + "attn_uv"]))), cfg)

        def rows(args):
            qci, qri, ati = args
            a = (jnp.einsum("qhd,shd->hqs", qci, kc)
                 + jnp.einsum("qhr,sr->hqs", qri, kr))
            causal = at[None, :] <= ati[:, None]
            probs = jax.nn.softmax(jnp.where(causal[None], a, -1e9), -1)
            return jnp.einsum("hqs,shd->qhd", _mm_in(probs, cfg), v)

        ctx = jax.lax.map(rows, (blocks(qc), blocks(qr), blocks(at)))
        return o + jnp.einsum("shd,hdm->sm",
                              _mm_in(ctx.reshape(s, hb, dv), cfg),
                              _f(mine(wo))), None

    return jax.lax.scan(heads, jnp.zeros((s, wo.shape[-1]), F32),
                        jnp.arange(nh // hb))[0]


def routing(w, p, x, cfg):
    """``(sel [S, k], gate [S, k])`` over ALL the experts: sigmoid
    scores, the top-k of the scores themselves (no bias, one group)."""
    s = jax.nn.sigmoid(_mm_in(x, cfg, w[p + "router"]) @ _f(w[p + "router"]))
    gate, sel = jax.lax.top_k(s, int(cfg["num_experts_per_tok"]))
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


def block(w, p, h, cfg, dense, held=None, query_block=None, shared=True):
    """One sandwich-normed block over ``h`` [S, D]; ``w`` needs only the
    weights under prefix ``p``.  Returns ``(h, shares)``: the rms of the
    attention branch's and of the FFN branch's contribution (after its
    closing norm) over the rms of the residual each is added to."""
    with jax.default_matmul_precision("highest"):
        eps = float(cfg["rms_norm_eps"])
        o = _rms(attention(w, p, _rms(h, w[p + "input_norm"], eps), cfg,
                           query_block), w[p + "post_attn_norm"], eps)
        mid = h + o

        def ffn(rows):          # a block of rows: nothing [S, 18432] held
            f = _rms(rows, w[p + "pre_mlp_norm"], eps)
            if dense:
                x = _mm_in(f, cfg)
                y = _mm_in(jax.nn.silu(x @ _f(w[p + "ffn_gate"]))
                           * (x @ _f(w[p + "ffn_up"])), cfg) @ _f(
                               w[p + "ffn_down"])
            else:
                sel, gate = routing(w, p, f, cfg)
                y = experts(w, p, f, sel, gate, cfg, held, shared)
            return _rms(y, w[p + "post_mlp_norm"], eps)

        s = h.shape[0]
        rb = 1024 if s % 1024 == 0 else s
        y = jax.lax.map(ffn, mid.reshape(s // rb, rb, -1)).reshape(s, -1)
        return mid + y, jnp.stack([_size(o) / _size(h),
                                   _size(y) / _size(mid)])


def module_input(w, h, next_emb, cfg, name="lm"):
    """``W_eh [RMS(next_emb; e_norm) ; RMS(h; h_norm)]``: the embedding's
    half first."""
    with jax.default_matmul_precision("highest"):
        p, eps = name + "_mtp_", float(cfg["rms_norm_eps"])
        both = jnp.concatenate([_rms(next_emb, w[p + "e_norm"], eps),
                                _rms(h, w[p + "h_norm"], eps)], axis=-1)
        return _mm_in(both, cfg) @ _f(w[p + "eh"])


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
    """tokens [S] int32 -> logits [S, V]; position s reads every
    position <= s."""
    return head(w, hidden(w, tokens, cfg, name, held,
                          query_block=query_block), cfg, name)


def mtp_hidden(w, h, tokens, cfg, name="lm", held=None, query_block=None):
    """The module's block output for every position: ``h`` [S, D] the
    last block's output, ``tokens`` [S]; position ``i`` is fed the
    embedding of ``tokens[i + 1]`` (the last position wraps to token 0:
    it predicts nothing that is read)."""
    nxt = jnp.concatenate([tokens[1:], tokens[:1]])
    u = module_input(w, h, embed(w, nxt, cfg, name), cfg, name)
    return block(w, name + "_mtp_", u, cfg, False, held, query_block)[0]


def mtp_logits(w, tokens, cfg, name="lm", held=None, query_block=None):
    """tokens [S] int32 -> the module's logits [S, V]: row ``i`` is its
    distribution for the token at ``i + 2``."""
    h = hidden(w, tokens, cfg, name, held, query_block=query_block)
    return head(w, mtp_hidden(w, h, tokens, cfg, name, held, query_block),
                cfg, name)
