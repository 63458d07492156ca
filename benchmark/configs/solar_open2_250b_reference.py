"""Plain reference for ``solar_open2_250b``: the full causal forward of a
``solar_open2`` decoder in float32 ``jax.numpy`` at matmul precision
"highest".  No cache, no batching of requests, no kernel, no grouped
product, nothing from ``paddle_tpu``: the delta rule is a ``lax.scan``
over time with ``Diag(alpha)`` written out, the short convolution a
padded causal one, a G layer a plain causal softmax with no positions of
any kind, the experts a loop over the held ones, each applied to EVERY
token and kept where the token chose it, the shared expert a plain gated
FFN.

The equations (``h`` the residual, ``RMS`` RMSNorm with weight, eps
``rms_norm_eps``; no bias anywhere; PRE-norm; layer ``i`` is a G layer
where ``i`` is in ``gqa_layers`` and a K layer otherwise; EVERY layer is
followed by the experts: ``first_k_dense_replace`` 0):

    h0 = E[ids]
    x = RMS(h; mixer_norm);   h = h + mixer(x)
    f = RMS(h; ffn_norm);     h = h + moe(f)
    logits = RMS(h; final_norm) W_head                (the head is untied)

    K layer (Kimi Delta Attention, arXiv:2510.26692), per head of 64,
    dk = dv = 128, state S [dk, dv] from zero:
      [q; k; v] = silu(causal depthwise conv, kernel 4, of [W_q; W_k; W_v] x)
      q = q / ||q|| * dk^-1/2,   k = k / ||k||            (eps 1e-6 in the norm)
      a     = W_fb (W_fa x)                  4096 -> 128 -> 64 x 128
      alpha = exp(-exp(A_log[h]) * softplus(a + dt_bias))   in (0, 1)^dk:
                                             ONE FACTOR A KEY CHANNEL
      beta  = 2 sigmoid(W_b x)               (the 2: kda_allow_neg_eigval)
      S <- Diag(alpha) S                     row i of S times alpha_i
      u  = S^T k;   S <- S + k (beta (v - u))^T;   o = S^T q
      out = W_o [RMS_dv(o; one weight [128]) * sigmoid(W_gb (W_ga x))]
    G layer: q = W_q x (64 x 128), k, v = W_k x, W_v x (8 x 128), NO
      rotary (use_rope false), causal softmax at 128^-1/2, query head j
      reads K/V head j // 8;  out = W_o [ctx * sigmoid(W_g x)]
    moe: s = sigmoid(f W_r)     float32, all 320 experts
         sel = top_8(s + b)     b chooses, it does not weigh; one group
         g_e = s_e / (sum_{e in sel} s_e + 1e-6) * routed_scaling_factor
         y = sum_{e in sel, lo <= e < hi} g_e E_e(f) + E_shared(f)
         E(f) = W2 (silu(W1 f) * W3 f), width 1280

``held = (lo, hi)`` is the contiguous range of experts this share
computes (routing is over all of them; what the absent ones would add is
left out, here and in the program alike); ``shared=False`` leaves the
shared expert out (a share summed with others counts it once).  The
vocabulary is the slice the weights hold.  The weights come in under the
names the served program uses (``lm_emb``, ``lm_l<i>_lin_q`` ...;
matrices ``[in, out]``, the conv kernel ``[4, channels]`` oldest tap
first, an expert layer's gate and up matrices as ONE ``[held, d, 2 *
width]`` with the gate's columns first — the only things shared with the
system under test) and in the dtype it serves them in (bf16): they are
upcast here, one layer at a time, the experts one at a time, attention
``query_block`` query rows at a time and the head in vocabulary blocks
(``head_stats``).

What the catalog's config does not say (``assumed`` in the config file):
the pre-norm block; SiLU after the conv, the L2 norms, ``dk^-1/2`` on q,
the low rank 128, ``A_log`` a head and ``dt_bias`` a channel, the sigmoid
output gate over an RMSNorm of one weight (the published KDA layer,
``fla.layers.KimiDeltaAttention``); the G layer's gate elementwise over
its 8192 output lanes from the same normed input, no q/k norm; the
sigmoid router with a selection bias; the shared expert's width 1280.

The operands the configuration states (``matmul_inputs``), as
``k_exaone_236b_a23b``'s reference and for its reason: with
``cfg["matmul_inputs"] = "bfloat16"`` each operand of a product with a
bf16 weight, and the stored K/V, is rounded HERE too (``_mm_in``, by
``lax.reduce_precision``), in float32: the arithmetic stays float32 at
"highest".  The delta rule's state, its inputs and its gates are float32
in the program and here.  The CPU tests keep the default.

Tolerances (``check`` in the config file; readings in PERF.md section
4).  Each served token's reference logit is placed in its position's
logit range, ``gap = (max - logit[served token]) / (max - min)``: 0
where the served token is the reference's argmax.  TWO limits, because
one cannot do both jobs: the MEAN over the sampled tokens
(``mean_gap_share``) is what tells a lower precision and a harmed
mechanism whose error is spread thin — a per-head decay, a missing
factor 2 — and the WORST token's (``worst_gap_share``) gross failure
only: a maximum over thousands of tokens of random weights grows with
the sample (one near tie decides it).  The config's ``check.why`` holds
the chip readings both were set from and the harmed variants
(``benchmark/tests/test_kda_routed_check.py``); int8-rounded weights,
the precision below, must fail by the mean and pass the worst.
"""
import jax
import jax.numpy as jnp

F32 = jnp.float32
K_LAYER, G_LAYER = "kda", "gqa"
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


def _rope(x, theta):
    """x [B, S, H, D] at positions 0..S-1, rotate-half over all of D
    (only where ``use_rope`` is true: the published file says false)."""
    s, half = x.shape[1], x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def kinds_of(cfg):
    gqa = set(int(i) for i in cfg["gqa_layers"])
    return [G_LAYER if i in gqa else K_LAYER
            for i in range(int(cfg["num_hidden_layers"]))]


def gated_attention(w, p, x, cfg, query_block=None):
    """A G layer over the normed rows ``x`` [B, S, D]: causal softmax
    with no positions, ``query_block`` query rows at a time, a sigmoid
    gate from the same input over the output's lanes."""
    b, s, _ = x.shape
    nh, nkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    dh = int(cfg["head_dim"])
    x = _mm_in(x, cfg)
    q = (x @ _f(w[p + "attn_q"])).reshape(b, s, nh, dh)
    k = (x @ _f(w[p + "attn_k"])).reshape(b, s, nkv, dh)
    v = (x @ _f(w[p + "attn_v"])).reshape(b, s, nkv, dh)
    if cfg.get("use_rope", True):
        q, k = _rope(q, float(cfg["rope_theta"])), _rope(
            k, float(cfg["rope_theta"]))
    q = _mm_in(q / jnp.sqrt(F32(dh)), cfg)
    k, v = _mm_in(k, cfg), _mm_in(v, cfg)                   # as stored
    k, v = (jnp.repeat(t, nh // nkv, axis=2) for t in (k, v))
    qb = s if query_block is None else int(query_block)
    assert s % qb == 0, "query_block must divide the sequence"
    key_at = jnp.arange(s)

    def rows(args):
        qi, at = args                       # [B, qb, H, D], [qb]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qi, k)
        ok = key_at[None, :] <= at[:, None]
        probs = jax.nn.softmax(jnp.where(ok[None, None], scores, -1e9), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", _mm_in(probs, cfg), v)

    ctx = jax.lax.map(rows, (
        jnp.moveaxis(q.reshape(b, s // qb, qb, nh, dh), 1, 0),
        key_at.reshape(s // qb, qb)))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, s, nh * dh)
    if cfg.get("use_gqa_gate", False):
        ctx = ctx * jax.nn.sigmoid(x @ _f(w[p + "attn_gate"]))
    return _mm_in(ctx, cfg) @ _f(w[p + "attn_o"])


def kimi_delta_attention(w, p, x, cfg):
    """A K layer over the normed rows ``x`` [B, S, D]: the rule a scan
    over time from a zero state, the decay ``Diag(alpha)`` a factor a key
    channel."""
    b, s, _ = x.shape
    lin = cfg["linear_attn_config"]
    heads, dk, kc = (int(lin[k]) for k in (
        "num_heads", "head_dim", "short_conv_kernel_size"))
    dv = dk
    x = _mm_in(x, cfg)
    qkv = jnp.concatenate([x @ _f(w[p + "lin_q"]), x @ _f(w[p + "lin_k"]),
                           x @ _f(w[p + "lin_v"])], axis=-1)
    # causal depthwise conv: y_t = sum_j w[j] * x_{t - (kc - 1) + j}
    padded = jnp.pad(qkv, ((0, 0), (kc - 1, 0), (0, 0)))
    cw = _f(w[p + "lin_conv_w"])
    qkv = jax.nn.silu(sum(padded[:, j:j + s] * cw[j] for j in range(kc)))
    q = _l2(qkv[..., :heads * dk].reshape(b, s, heads, dk)) / jnp.sqrt(F32(dk))
    k = _l2(qkv[..., heads * dk:2 * heads * dk].reshape(b, s, heads, dk))
    v = qkv[..., 2 * heads * dk:].reshape(b, s, heads, dv)
    beta = jax.nn.sigmoid(x @ _f(w[p + "lin_b"]))              # [B, S, H]
    if cfg.get("kda_allow_neg_eigval"):
        beta = 2.0 * beta
    a = _mm_in(x @ _f(w[p + "lin_fa"]), cfg) @ _f(w[p + "lin_fb"])
    dt = jax.nn.softplus(a + _f(w[p + "lin_dt_bias"])).reshape(
        b, s, heads, dk)
    alpha = jnp.exp(-jnp.exp(_f(w[p + "lin_A_log"]))[None, None, :, None]
                    * dt)                                  # [B, S, H, dk]

    def step(state, inp):                       # state [B, H, dk, dv]
        q_t, k_t, v_t, a_t, b_t = inp
        state = a_t[..., None] * state          # Diag(alpha) S
        u = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + k_t[..., None] * (b_t[..., None] * (v_t - u))[
            :, :, None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    t_first = lambda t: jnp.moveaxis(t, 1, 0)                  # noqa: E731
    _, o = jax.lax.scan(step, jnp.zeros((b, heads, dk, dv), F32),
                        tuple(t_first(t) for t in (q, k, v, alpha, beta)))
    o = jnp.moveaxis(o, 0, 1)                                  # [B, S, H, dv]
    gate = jax.nn.sigmoid(
        _mm_in(x @ _f(w[p + "lin_ga"]), cfg) @ _f(w[p + "lin_gb"]))
    y = _rms(o, w[p + "lin_norm"], float(cfg["rms_norm_eps"])) \
        * gate.reshape(b, s, heads, dv)
    return _mm_in(y.reshape(b, s, heads * dv), cfg) @ _f(w[p + "lin_o"])


def routing(w, p, f, cfg):
    """``(sel [B, S, k], gate [B, S, k])`` over ALL the experts: sigmoid
    scores, the bias in the choice only."""
    s = jax.nn.sigmoid(_mm_in(f, cfg, w[p + "router"]) @ _f(w[p + "router"]))
    _, sel = jax.lax.top_k(s + _f(w[p + "expert_bias"]),
                           int(cfg["num_experts_per_tok"]))
    gate = jnp.take_along_axis(s, sel, axis=-1)
    if cfg.get("norm_topk_prob", True):
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
    n_all = int(cfg.get("n_routed_experts_all", cfg["n_routed_experts"]))
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
    if shared and int(cfg.get("n_shared_experts", 0)):
        out = out + _gated(x, w[p + "shared_w13"], w[p + "shared_w2"], cfg)
    return out


def _size(t):
    return jnp.sqrt(jnp.mean(t * t))


def embed(w, tokens, cfg, name="lm"):
    return _f(w[name + "_emb"][tokens])


def block(w, p, h, cfg, kind, held=None, query_block=None, shared=True):
    """One block over ``h`` [B, S, D]; ``w`` needs only the weights under
    prefix ``p``.  Returns ``(h, shares)``: the rms of the mixer's and of
    the experts' contribution over the rms of the residual each is added
    to."""
    with jax.default_matmul_precision("highest"):
        eps = float(cfg["rms_norm_eps"])
        x = _rms(h, w[p + "mixer_norm"], eps)
        o = (gated_attention(w, p, x, cfg, query_block) if kind == G_LAYER
             else kimi_delta_attention(w, p, x, cfg))
        mid = h + o
        f = _rms(mid, w[p + "ffn_norm"], eps)
        sel, gate = routing(w, p, f, cfg)
        y = experts(w, p, f, sel, gate, cfg, held, shared)
        return mid + y, jnp.stack([_size(o) / _size(h),
                                   _size(y) / _size(mid)])


def head(w, h, cfg, name="lm"):
    """All logits [B, S, V] (small vocabularies: the CPU tests)."""
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


def hidden(w, tokens, cfg, name="lm", held=None, shared=True):
    """tokens [B, S] int32 -> the last block's output [B, S, D]."""
    h = embed(w, tokens, cfg, name)
    for i, kind in enumerate(kinds_of(cfg)):
        h, _ = block(w, "%s_l%d_" % (name, i), h, cfg, kind, held,
                     shared=shared)
    return h


def forward(w, tokens, cfg, name="lm", held=None):
    """tokens [B, S] int32 -> logits [B, S, V]; position s sees positions
    <= s."""
    return head(w, hidden(w, tokens, cfg, name, held), cfg, name)
