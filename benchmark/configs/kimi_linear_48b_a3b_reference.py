"""Plain reference for ``kimi_linear_48b_a3b``: the full causal forward of
a ``kimi_linear`` decoder in float32 ``jax.numpy`` at matmul precision
"highest".  No cache, no snapshot, no chunk, no batching, no absorbed
form, no kernel, no grouped product, nothing from ``paddle_tpu``: the
delta rule is a ``lax.scan`` over POSITIONS with ``Diag(alpha)`` written
out (never its chunkwise form), the short convolution a padded causal
one, latent attention EXPANDED (every position's latent row expanded to
its 32 heads' keys and values) and DENSE (a causal softmax over every
earlier position), the experts a loop over the held ones, each applied to
EVERY token and kept where the token chose it.

The equations (``h`` the residual ``[S, D]``; ``RMS`` RMSNorm with
weight, eps ``rms_norm_eps``; no bias anywhere; PRE-norm; published
layer ``i + 1`` is a K layer where ``linear_attn_config.kda_layers``
names it and an M layer where ``full_attn_layers`` does, both 1-indexed;
the first ``first_k_dense_replace`` layers' FFN is dense):

    h0 = E[ids]
    x = RMS(h; mixer_norm);   h = h + mixer(x)
    f = RMS(h; ffn_norm);     h = h + ffn(f)
    logits = RMS(h; final_norm) W_head                (the head is untied)

    K layer (Kimi Delta Attention, arXiv:2510.26692), per head of 32,
    dk = dv = 128, state S [dk, dv] from zero:
      [q; k; v] = silu(causal depthwise conv, kernel 4, of [W_q; W_k; W_v] x)
      q = q / ||q|| * dk^-1/2,   k = k / ||k||            (eps 1e-6 in the norm)
      a     = W_fb (W_fa x)                  2304 -> 128 -> 32 x 128
      alpha = exp(-exp(A_log[h]) * softplus(a + dt_bias))   in (0, 1)^dk:
                                             ONE FACTOR A KEY CHANNEL
      beta  = sigmoid(W_b x)                 in (0, 1): no factor 2
      S <- Diag(alpha) S                     row i of S times alpha_i
      u  = S^T k;   S <- S + k (beta (v - u))^T;   o = S^T q
      out = W_o [RMS_dv(o; one weight [128]) * sigmoid(W_gb (W_ga x))]
    M layer (multi-head latent attention, 32 heads, NO positions):
      q_i = x W_q,i = [qC_i (128) ; qR_i (64)]     ONE matrix: q_lora_rank null
      [c' ; kR] = x W_kva (512 + 64);  c = RMS(c'; kv_a_norm)
      NO rotary on qR or kR (mla_use_nope: the 64 shared lanes are carried
        as projected; with mla_use_nope false they would be rotated,
        plain rotary, lanes paired half-split)
      kC_s,i = c_s W_uk,i^T;  v_s,i = c_s W_uv,i
      a_t,s,i = 192^-0.5 (qC_t,i . kC_s,i + qR_t,i . kR_s),  s <= t
      out_t = concat_i(sum_{s <= t} softmax_s(a_t,s,i) v_s,i) W_o
    FFN: dense (layer 0):  W2 (silu(W1 f) * W3 f), width 9216
         sparse:  s = sigmoid(f W_r)      float32, all 256 experts
                  sel = top_8(s + b)      b chooses, it does not weigh; one group
                  g_e = s_e / (sum_{e in sel} s_e + 1e-6) * routed_scaling_factor
                  y = sum_{e in sel, lo <= e < hi} g_e E_e(f) + E_shared(f)
                  E(f) = W2 (silu(W1 f) * W3 f), width 1024

``held = (lo, hi)`` is the contiguous range of experts this share
computes (routing is over all of them; what the absent ones would add is
left out, here and in the program alike); ``shared=False`` leaves the
shared expert out (a share summed with others counts it once).  The
vocabulary is the slice the weights hold.  The weights come in under the
names the served program uses (``lm_emb``, ``lm_l<i>_lin_q`` ...,
``lm_l<i>_attn_q`` ...; matrices ``[in, out]``, the conv kernel ``[4,
channels]`` oldest tap first, the latent's up projections a head a batch
— ``attn_uk`` ``[heads, nope, kv_lora_rank]``, ``attn_uv`` ``[heads,
kv_lora_rank, v]`` —, an expert layer's gate and up matrices as ONE
``[held, d, 2 * width]`` with the gate's columns first: the only things
shared with the system under test) and in the dtype it serves them in
(bf16): they are upcast here, a layer at a time, the experts one at a
time, attention ``head_block`` heads and ``query_block`` query rows at a
time, the FFN in blocks of rows and the head in vocabulary blocks
(``head_stats``), so that 17k positions fit.

What the catalog's config does not say (``assumed`` in the config file):
the pre-norm block; SiLU after the conv, the L2 norms, ``dk^-1/2`` on q,
the low rank 128, ``A_log`` a head and ``dt_bias`` a channel, the sigmoid
output gate over an RMSNorm of one weight (the published KDA layer,
``fla.layers.KimiDeltaAttention``); ``beta`` without a factor 2;
``mla_use_nope`` read as it stands; the sigmoid router's selection bias.

The operands the configuration states (``matmul_inputs``), as
``solar_open2_250b``'s reference and for its reason: with
``cfg["matmul_inputs"] = "bfloat16"`` each operand of a product with a
bf16 weight, and the stored latent row, is rounded HERE too (``_mm_in``,
by ``lax.reduce_precision``), in float32: the arithmetic stays float32
at "highest".  The delta rule's state, its inputs and its gates are
float32 in the program and here.  The CPU tests keep the default.

Tolerances (``check`` in the config file; readings in PERF.md section
4): each served token's reference logit is placed in its position's logit
range, ``gap = (max - logit[served token]) / (max - min)``, held under
TWO limits, the mean over the sampled tokens and the worst token's, as
``solar_open2_250b``'s reference says and for its reasons.
"""
import jax
import jax.numpy as jnp

F32 = jnp.float32
K_LAYER, M_LAYER = "kda", "mla"
_EPS_SUM = 1e-6
_EPS_L2 = 1e-6


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


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + _EPS_L2)


def _rope(x, cfg):
    """x [S, ..., lanes] at positions 0..S-1, lane j paired with j +
    lanes / 2; plain rotary (only where ``mla_use_nope`` is false: the
    published file says true)."""
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


def kinds_of(cfg):
    """The layers' kinds, ``(mixer, whether the FFN is the dense one)``:
    the mixers from the two 1-INDEXED lists."""
    lin = cfg["linear_attn_config"]
    kda = set(int(i) for i in lin["kda_layers"])
    full = set(int(i) for i in lin["full_attn_layers"])
    n = int(cfg["num_hidden_layers"])
    assert not kda & full and kda | full == set(range(1, n + 1)), (
        "kda_layers and full_attn_layers must name each layer once")
    return [(K_LAYER if i + 1 in kda else M_LAYER,
             i < int(cfg.get("first_k_dense_replace", 0)))
            for i in range(n)]


def kimi_delta_attention(w, p, x, cfg):
    """A K layer over the normed rows ``x`` [S, D]: the rule a scan over
    positions from a zero state, the decay ``Diag(alpha)`` a factor a key
    channel."""
    s = x.shape[0]
    lin = cfg["linear_attn_config"]
    heads, dk, kc = (int(lin[k]) for k in (
        "num_heads", "head_dim", "short_conv_kernel_size"))
    dv = dk
    x = _mm_in(x, cfg)
    qkv = jnp.concatenate([x @ _f(w[p + "lin_q"]), x @ _f(w[p + "lin_k"]),
                           x @ _f(w[p + "lin_v"])], axis=-1)
    # causal depthwise conv: y_t = sum_j w[j] * x_{t - (kc - 1) + j}
    padded = jnp.pad(qkv, ((kc - 1, 0), (0, 0)))
    cw = _f(w[p + "lin_conv_w"])
    qkv = jax.nn.silu(sum(padded[j:j + s] * cw[j] for j in range(kc)))
    q = _l2(qkv[:, :heads * dk].reshape(s, heads, dk)) / jnp.sqrt(F32(dk))
    k = _l2(qkv[:, heads * dk:2 * heads * dk].reshape(s, heads, dk))
    v = qkv[:, 2 * heads * dk:].reshape(s, heads, dv)
    beta = jax.nn.sigmoid(x @ _f(w[p + "lin_b"]))                  # [S, H]
    if cfg.get("kda_allow_neg_eigval"):
        beta = 2.0 * beta
    a = _mm_in(x @ _f(w[p + "lin_fa"]), cfg) @ _f(w[p + "lin_fb"])
    dt = jax.nn.softplus(a + _f(w[p + "lin_dt_bias"])).reshape(s, heads, dk)
    alpha = jnp.exp(-jnp.exp(_f(w[p + "lin_A_log"]))[None, :, None] * dt)

    def step(state, inp):                       # state [H, dk, dv]
        q_t, k_t, v_t, a_t, b_t = inp
        state = a_t[..., None] * state          # Diag(alpha) S
        u = jnp.einsum("hkv,hk->hv", state, k_t)
        state = state + k_t[..., None] * (b_t[..., None] * (v_t - u))[
            :, None, :]
        return state, jnp.einsum("hkv,hk->hv", state, q_t)

    _, o = jax.lax.scan(step, jnp.zeros((heads, dk, dv), F32),
                        (q, k, v, alpha, beta))                # [S, H, dv]
    gate = jax.nn.sigmoid(
        _mm_in(x @ _f(w[p + "lin_ga"]), cfg) @ _f(w[p + "lin_gb"]))
    y = _rms(o, w[p + "lin_norm"], float(cfg["rms_norm_eps"])) \
        * gate.reshape(s, heads, dv)
    return _mm_in(y.reshape(s, heads * dv), cfg) @ _f(w[p + "lin_o"])


def latent_attention(w, p, x, cfg, query_block=None, head_block=16):
    """An M layer over the normed rows ``x`` [S, D]: dense causal
    attention over the whole sequence, EXPANDED: ``head_block`` heads at
    a time their keys and values expanded from the latent rows and a
    causal softmax, ``query_block`` query rows at a time; no positions
    (``mla_use_nope``)."""
    s = x.shape[0]
    eps = float(cfg["rms_norm_eps"])
    rank = int(cfg["kv_lora_rank"])
    nh, dv = int(cfg["num_attention_heads"]), int(cfg["v_head_dim"])
    nope, rope = int(cfg["qk_nope_head_dim"]), int(cfg["qk_rope_head_dim"])
    scale = (nope + rope) ** -0.5
    turn = ((lambda t: t) if cfg.get("mla_use_nope", False)
            else (lambda t: _rope(t, cfg)))
    x = _mm_in(x, cfg)
    ckr = x @ _f(w[p + "attn_kv_a"])
    # what the configuration states as stored is rounded as stored
    c = _mm_in(_rms(ckr[:, :rank], w[p + "kv_a_norm"], eps), cfg)
    kr = _mm_in(turn(ckr[:, rank:]), cfg)
    qb = s if query_block is None else int(query_block)
    assert s % qb == 0, "query_block must divide the sequence"
    hb = min(int(head_block), nh)
    assert nh % hb == 0, "head_block must divide the heads"
    blocks = lambda t: t.reshape((s // qb, qb) + t.shape[1:])
    wq = w[p + "attn_q"].reshape(-1, nh, nope + rope)
    wo = w[p + "attn_o"].reshape(nh, dv, -1)
    at = jnp.arange(s)

    def heads(o, g):
        mine = lambda t, axis=0: jax.lax.dynamic_slice_in_dim(
            t, g * hb, hb, axis)
        q = jnp.einsum("sr,rhd->shd", x, _f(mine(wq, 1)))
        qc = _mm_in(q[..., :nope] * scale, cfg)
        qr = _mm_in(turn(q[..., nope:]) * scale, cfg)
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


def routing(w, p, f, cfg):
    """``(sel [S, k], gate [S, k])`` over ALL the experts: sigmoid
    scores, the bias in the choice only, one group."""
    s = jax.nn.sigmoid(_mm_in(f, cfg, w[p + "router"]) @ _f(w[p + "router"]))
    _, sel = jax.lax.top_k(s + _f(w[p + "expert_bias"]),
                           int(cfg["num_experts_per_token"]))
    gate = jnp.take_along_axis(s, sel, axis=-1)
    if cfg.get("moe_renormalize", True):
        gate = gate / (gate.sum(-1, keepdims=True) + _EPS_SUM)
    return sel, gate * float(cfg.get("routed_scaling_factor", 1.0))


def _gated(x, a13, a2, cfg):
    gu = x @ _f(a13)
    width = a13.shape[-1] // 2
    return _mm_in(jax.nn.silu(gu[..., :width]) * gu[..., width:], cfg) @ _f(a2)


def experts(w, p, f, sel, gate, cfg, held=None, shared=True):
    """The held experts' part of the mixture — every held expert applied
    to every token, weighed by the token's gate for it (zero where the
    token did not choose it), one expert after another — plus, with
    ``shared``, the shared expert's unweighed term."""
    n_all = int(cfg.get("num_experts_all", cfg["num_experts"]))
    lo, hi = (0, n_all) if held is None else held
    w13, w2 = w[p + "experts_w13"], w[p + "experts_w2"]
    assert w13.shape[0] == hi - lo, "state must hold the held experts"
    x = _mm_in(f, cfg, w13)

    def one(out, expert):
        e, a13, a2 = expert
        weight = jnp.sum(jnp.where(sel == e, gate, 0.0), axis=-1,
                         keepdims=True)
        return out + weight * _gated(x, a13, a2, cfg), None

    out = jax.lax.scan(one, jnp.zeros_like(x),
                       (jnp.arange(lo, hi), w13, w2))[0]
    if shared and int(cfg.get("num_shared_experts", 0)):
        out = out + _gated(x, w[p + "shared_w13"], w[p + "shared_w2"], cfg)
    return out


def dense_ffn(w, p, f, cfg):
    x = _mm_in(f, cfg)
    return _mm_in(jax.nn.silu(x @ _f(w[p + "ffn_gate"]))
                  * (x @ _f(w[p + "ffn_up"])), cfg) @ _f(w[p + "ffn_down"])


def _size(t):
    return jnp.sqrt(jnp.mean(t * t))


def embed(w, tokens, cfg, name="lm"):
    return _f(w[name + "_emb"][tokens])


def block(w, p, h, cfg, kind, held=None, query_block=None, shared=True):
    """One block of ``kind`` (an entry of :func:`kinds_of`) over ``h``
    [S, D]; ``w`` needs only the weights under prefix ``p``.  Returns
    ``(h, shares)``: the rms of the mixer's and of the FFN's contribution
    over the rms of the residual each is added to."""
    with jax.default_matmul_precision("highest"):
        eps = float(cfg["rms_norm_eps"])
        mixer, dense = kind
        x = _rms(h, w[p + "mixer_norm"], eps)
        o = (latent_attention(w, p, x, cfg, query_block) if mixer == M_LAYER
             else kimi_delta_attention(w, p, x, cfg))
        mid = h + o

        def ffn(rows):          # a block of rows: nothing [S, 9216] held
            f = _rms(rows, w[p + "ffn_norm"], eps)
            if dense:
                return dense_ffn(w, p, f, cfg)
            sel, gate = routing(w, p, f, cfg)
            return experts(w, p, f, sel, gate, cfg, held, shared)

        s = h.shape[0]
        rb = 1024 if s % 1024 == 0 else s
        y = jax.lax.map(ffn, mid.reshape(s // rb, rb, -1)).reshape(s, -1)
        return mid + y, jnp.stack([_size(o) / _size(h),
                                   _size(y) / _size(mid)])


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
    for i, kind in enumerate(kinds_of(cfg)):
        h = block(w, "%s_l%d_" % (name, i), h, cfg, kind, held,
                  query_block, shared)[0]
    return h


def forward(w, tokens, cfg, name="lm", held=None, query_block=None):
    """tokens [S] int32 -> logits [S, V]; position s sees positions
    <= s."""
    return head(w, hidden(w, tokens, cfg, name, held,
                          query_block=query_block), cfg, name)
