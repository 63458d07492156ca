"""Plain reference for ``k_exaone_236b_a23b``: the full causal forward of
an ``exaone_moe`` decoder AND of its multi-token-prediction module in
float32 ``jax.numpy`` at matmul precision "highest".  No cache and NO
RING, no batching, no grouped product, nothing from ``paddle_tpu``: a
window layer is a banded causal mask over the whole sequence, a global
layer a plain causal mask with no positions of any kind, the experts a
loop over the held ones, each applied to EVERY token and kept where the
token chose it, the shared expert a plain gated FFN.

The equations (``h`` the residual, ``RMS`` RMSNorm with weight, eps
``rms_norm_eps``; no bias anywhere; NO norm before a branch, one after
it; layer ``i`` is a WINDOW layer where ``layer_types[i]`` is
``sliding_attention`` and DENSE where ``mlp_layer_types[i]`` is
``dense``):

    h0 = E[ids]
    q = RMS_head(h W_q; q_norm) -> [heads, 128], k = RMS_head(h W_k;
        k_norm), v = h W_v -> [kv, 128]       (per-head norms, then:)
    window layer:  rotary (rotate-half over the whole head, theta 1e6)
                   on q and k; the query at p reads keys p - W + 1 .. p
                   (W = sliding_window: the window counts the query's
                   own position)
    global layer:  no rotary, nothing positional; keys 0 .. p
    softmax at 1 / sqrt(128); query head j reads K/V head j // (heads / kv)
    h = h + RMS(ctx W_o; post_attn_norm)
    dense layer:   y = W2 (silu(W1 h) * W3 h)
    sparse layer:  s = sigmoid(h W_r)      float32, all 128 experts, on the
                                           FFN's own input
                   sel = top_8(s + b)      b chooses, it does not weigh
                   g_e = s_e / (sum_{e in sel} s_e + 1e-6) * 2.5
                   y = sum_{e in sel, lo <= e < hi} g_e E_e(h) + E_shared(h)
    h = h + RMS(y; post_ffn_norm)
    logits = RMS(h; final_norm) W_head                (the head is untied)

The module, for every position ``i`` of a sequence (``mtp_logits``):

    u_i = W_eh [RMS(E[t_{i+1}]; mtp_e_norm) ; RMS(h_i; mtp_h_norm)]
    u -> ONE global sparse block (weights ``lm_mtp_*``), causal over the
         module's own inputs 0 .. i
    logits for t_{i+2} = RMS(u'; final_norm) W_head   (the model's own)

``held = (lo, hi)`` is the contiguous range of experts this share
computes (routing is over all of them; what the absent ones would add is
left out, and that partial result is what goes on); ``shared=False``
leaves the shared expert out (a share summed with others counts it
once).  The vocabulary is the slice the weights hold.  The weights come
in under the names the served program uses (``lm_emb``,
``lm_l<i>_attn_q`` ...; matrices ``[in, out]``, an expert layer's gate
and up matrices as ONE ``[held, d, 2 * width]`` with the gate's columns
first, the shared expert's as ONE ``[d, 2 * width]`` — the only things
shared with the system under test) and in the dtype it serves them in
(bf16): they are upcast here, one layer at a time, the experts one at a
time, attention ``query_block`` query rows at a time and the head in
vocabulary blocks (``head_stats``).

Departures from the published description, and what the catalog's
config does not say (``assumed`` in the config file): per-head RMSNorms
on q and k; rotary in the window layers ONLY; each branch closed by its
norm and none before it; the window counts the query's own position;
the half-split rotary convention; 1e-6 added to the sum of the chosen
scores; in the module the embedding's half comes FIRST in the
concatenation and its FFN is sparse; the share (16 held experts of 128,
19,200 vocabulary rows of 153,600).

The operands the configuration states (``matmul_inputs``), as
``smallthinker_21b_a3b``'s reference and for its reason: with
``cfg["matmul_inputs"] = "bfloat16"`` each operand the configuration
says is rounded is rounded HERE too (``_mm_in``, by
``lax.reduce_precision``: a cast pair is dropped under ``jit``), in
float32: the arithmetic stays float32 at "highest".  The CPU tests keep
the default.

Tolerances (``check`` in the config file; readings in PERF.md section
4): each served token's reference logit is placed in its position's
logit range, ``gap = (max - logit[served token]) / (max - min)``; the
MEAN over the sampled tokens is held tight and the WORST loose; the
module's proposals are held the same way against ``mtp_logits``.
"""
import jax
import jax.numpy as jnp

F32 = jnp.float32
GLOBAL, WINDOW = 0, 1
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


def _rope(x, theta):
    """x [B, S, H, D] at positions 0..S-1, rotate-half over all of D."""
    s, half = x.shape[1], x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def kinds_of(cfg):
    return [WINDOW if k == "sliding_attention" else GLOBAL
            for k in cfg["layer_types"]]


def attention(w, p, x, cfg, kind, query_block=None):
    """Causal attention of the rows ``x`` [B, S, D] (the residual itself)
    over the whole sequence, ``query_block`` query rows at a time: q and
    k normed per head; banded to the window and rotated where ``kind``
    is :data:`WINDOW`."""
    b, s, _ = x.shape
    nh, nkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    dh, eps = int(cfg["head_dim"]), float(cfg["rms_norm_eps"])
    x = _mm_in(x, cfg)
    q = _rms((x @ _f(w[p + "attn_q"])).reshape(b, s, nh, dh),
             w[p + "q_norm"], eps)
    k = _rms((x @ _f(w[p + "attn_k"])).reshape(b, s, nkv, dh),
             w[p + "k_norm"], eps)
    v = (x @ _f(w[p + "attn_v"])).reshape(b, s, nkv, dh)
    if kind == WINDOW:
        theta = float(cfg["rope_parameters"]["rope_theta"])
        q, k = _rope(q, theta), _rope(k, theta)
    q = _mm_in(q / jnp.sqrt(F32(dh)), cfg)
    k, v = _mm_in(k, cfg), _mm_in(v, cfg)                   # as stored
    k, v = (jnp.repeat(t, nh // nkv, axis=2) for t in (k, v))
    qb = s if query_block is None else int(query_block)
    assert s % qb == 0, "query_block must divide the sequence"
    window = int(cfg["sliding_window"])
    key_at = jnp.arange(s)

    def rows(args):
        qi, at = args                       # [B, qb, H, D], [qb]
        scores = jnp.einsum("bqhd,bkhd->bhqk", qi, k)
        ok = key_at[None, :] <= at[:, None]
        if kind == WINDOW:
            ok = ok & (at[:, None] - key_at[None, :] < window)
        probs = jax.nn.softmax(jnp.where(ok[None, None], scores, -1e9), -1)
        return jnp.einsum("bhqk,bkhd->bqhd", _mm_in(probs, cfg), v)

    ctx = jax.lax.map(rows, (
        jnp.moveaxis(q.reshape(b, s // qb, qb, nh, dh), 1, 0),
        key_at.reshape(s // qb, qb)))
    ctx = jnp.moveaxis(ctx, 0, 1).reshape(b, s, nh * dh)
    return _mm_in(ctx, cfg) @ _f(w[p + "attn_o"])


def routing(w, p, x, cfg):
    """``(sel [B, S, k], gate [B, S, k])`` over ALL the experts, from the
    FFN's own input ``x``: sigmoid scores, the bias in the choice only."""
    s = jax.nn.sigmoid(_mm_in(x, cfg, w[p + "router"]) @ _f(w[p + "router"]))
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


def experts(w, p, x, sel, gate, cfg, held=None, shared=True):
    """The held experts' part of the mixture — every held expert applied
    to every token, weighed by the token's gate for it (zero where the
    token did not choose it), one expert after another — plus, with
    ``shared``, the shared expert's unweighed term."""
    n_all = int(cfg.get("num_experts_all", cfg["num_experts"]))
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
    if shared and int(cfg.get("num_shared_experts", 0)):
        out = out + _gated(x, w[p + "shared_w13"], w[p + "shared_w2"], cfg)
    return out


def _size(t):
    return jnp.sqrt(jnp.mean(t * t))


def embed(w, tokens, cfg, name="lm"):
    return _f(w[name + "_emb"][tokens])


def block(w, p, h, cfg, kind, dense, held=None, query_block=None,
          shared=True):
    """One block over ``h`` [B, S, D]; ``w`` needs only the weights under
    prefix ``p``.  Returns ``(h, shares)``: the rms of the attention
    branch's and of the FFN branch's contribution over the rms of the
    residual each is added to."""
    with jax.default_matmul_precision("highest"):
        eps = float(cfg["rms_norm_eps"])
        o = _rms(attention(w, p, h, cfg, kind, query_block),
                 w[p + "post_attn_norm"], eps)
        mid = h + o
        if dense:
            x = _mm_in(mid, cfg)
            y = _mm_in(jax.nn.silu(x @ _f(w[p + "ffn_gate"]))
                       * (x @ _f(w[p + "ffn_up"])), cfg) @ _f(
                           w[p + "ffn_down"])
        else:
            sel, gate = routing(w, p, mid, cfg)
            y = experts(w, p, mid, sel, gate, cfg, held, shared)
        y = _rms(y, w[p + "post_ffn_norm"], eps)
        return mid + y, jnp.stack([_size(o) / _size(h),
                                   _size(y) / _size(mid)])


def module_input(w, h, next_emb, cfg, name="lm"):
    """``u_i`` of every position: ``h`` [B, S, D] the last block's output,
    ``next_emb`` [B, S, D] the embedding of the token AFTER each
    position."""
    with jax.default_matmul_precision("highest"):
        p, eps = name + "_mtp_", float(cfg["rms_norm_eps"])
        both = jnp.concatenate([_rms(next_emb, w[p + "e_norm"], eps),
                                _rms(h, w[p + "h_norm"], eps)], axis=-1)
        return _mm_in(both, cfg) @ _f(w[p + "eh"])


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
    kinds = kinds_of(cfg)
    for i in range(int(cfg["num_hidden_layers"])):
        h, _ = block(w, "%s_l%d_" % (name, i), h, cfg, kinds[i],
                     cfg["mlp_layer_types"][i] == "dense", held,
                     shared=shared)
    return h


def forward(w, tokens, cfg, name="lm", held=None):
    """tokens [B, S] int32 -> logits [B, S, V]; position s sees positions
    <= s (a window layer: the last ``sliding_window`` of them)."""
    return head(w, hidden(w, tokens, cfg, name, held), cfg, name)


def mtp_hidden(w, h, tokens, cfg, name="lm", held=None, query_block=None):
    """The module's block output for every position: ``h`` [B, S, D] the
    last block's output, ``tokens`` [B, S]; position ``i`` is fed the
    embedding of ``tokens[:, i + 1]`` (the last position wraps to token 0:
    it predicts nothing that is read)."""
    nxt = jnp.concatenate([tokens[:, 1:], tokens[:, :1]], axis=1)
    u = module_input(w, h, embed(w, nxt, cfg, name), cfg, name)
    return block(w, name + "_mtp_", u, cfg, GLOBAL, False, held,
                 query_block)[0]


def mtp_logits(w, tokens, cfg, name="lm", held=None):
    """tokens [B, S] -> the module's logits [B, S, V]: row ``i`` is its
    distribution over the token at ``i + 2``."""
    h = hidden(w, tokens, cfg, name, held)
    return head(w, mtp_hidden(w, h, tokens, cfg, name, held), cfg, name)
