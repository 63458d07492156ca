"""Plain reference for ``lfm2_24b_a2b``: the full causal forward of an
``lfm2_moe`` decoder in float32 ``jax.numpy`` at matmul precision
"highest".  No cache, no grouped product, no batching tricks, nothing
from ``paddle_tpu``: the short convolution is a causal depthwise
convolution over the whole sequence, attention a masked softmax over the
whole sequence, the experts a loop over all of them, each applied to
EVERY token and kept where the token chose it.

The equations (``h`` the residual, ``RMS`` RMSNorm with weight, eps
``norm_eps``; pre-norm, no bias anywhere):

    h0 = E[ids]
    r  = RMS(h; operator_norm)
    conv layer:  [B | C | x] = r W_in                (thirds in this order)
                 u = B * x
                 y_t = sum_{j<L} w[j] * u_{t-(L-1)+j}    (L = conv_L_cache;
                                                      u before the start = 0)
                 o = (C * y) W_out
    attn layer:  q = r W_q -> [heads, 64], k = r W_k, v = r W_v -> [kv, 64]
                 q = RMS_64(q; q_layernorm), k = RMS_64(k; k_layernorm)
                 rotary (rotate-half over the whole head, theta 1e6) on
                 q and k; causal softmax at 1 / sqrt(64); query head j
                 reads K/V head j // (heads / kv);  o = ctx W_o
    h  = h + o;  f = RMS(h; ffn_norm)
    layer < num_dense_layers:  h = h + W2 (silu(W1 f) * W3 f)
    else:  s = sigmoid(f W_r)                         all num_experts
           sel = top_k(s + expert_bias)   the bias chooses, it does not weigh
           g_e = s_e / (sum_{e in sel} s_e + 1e-6) * routed_scaling_factor
           h = h + sum_{e in sel, lo <= e < hi} g_e W2_e (silu(W1_e f) * W3_e f)
    logits = RMS(h; embedding_norm) E^T               (the head is tied)

``held = (lo, hi)`` is the contiguous range of experts this share
computes (the chip's share of an expert-parallel deployment: routing is
over all of them, what the absent ones would add is left out); default
all.  The weights come in under the names the served program uses
(``lm_emb``, ``lm_l<i>_conv_in`` ...; matrices ``[in, out]``, the conv
kernel ``[L, channels]`` oldest tap first, an expert layer's gate and up
matrices as ONE ``[experts, d, 2 * width]`` with the gate's columns
first — the only things shared with the system under test) and in the
dtype it serves them in (bf16): they are upcast here, one layer at a
time (``block`` takes one layer's weights), the experts ``expert_blocks``
at a time and the head in vocabulary blocks (``head_stats``), so the
check fits beside 10.4 GB of served weights.

Departures from the published description, and what the catalog's
config does not say (``assumed`` in the config file): the head is tied
to the embedding; the ``1e-6`` in the weights' sum; the order ``B, C, x``
of the in-projection's thirds; q/k norms before rotary; the half-split
rotary convention.

The operands the configuration states (``matmul_inputs``).  By default
every product here is float32 by float32.  The configuration STATES its
precision (``departures`` in its file): matmul inputs rounded to bf16 and
accumulated in float32, K/V stored in bf16; the router, norms, rotary,
softmax and the conv state float32.  A mixture makes that statement part
of the FUNCTION, not of its error: where a token's fourth and fifth
expert score within the rounding of each other, a float32 forward and
any faithful bf16 forward choose DIFFERENT experts at that layer, both
defensibly; that token's hidden state then differs by a tenth of an
expert layer's output, every later layer's choice with it, and through
the conv windows and the attention the tokens after it.  Measured on the
chip at the published widths against the float32 forward (PR 40, PERF.md
section 4): the unharmed served path agrees on 84-85% of tokens, mean gap
0.0024-0.0028, worst 0.12-0.14 — and "the selection bias added to the
weights" reads 0.0032, inside that noise: a float32 reference cannot hold
this model's gates.  So the cell's check asks for ``cfg["matmul_inputs"]
= "bfloat16"``: each operand the configuration says is rounded is rounded
HERE too (``_mm_in``: the input of every product with a stored bf16
matrix, the scaled query, the stored K and V, the softmax weights, the
experts' activation) and taken straight back to float32, so the
arithmetic is still float32 at "highest", nothing is shared with the
system under test, and a step that rounds what the configuration does
NOT say is rounded (the router's input, the conv state, a norm) now
shows as the routing flips it causes.  The CPU tests keep the default.

Tolerances (``check`` in the config file; readings in PERF.md section 4).
Each served token's reference logit is placed in its position's logit
range: ``gap = (max - logit[served token]) / (max - min)``.  Two bounds:

* ``mean_gap_share`` holds the MEAN of ``gap`` over the sampled tokens:
  tight, so that a lower precision (every matrix rounded to int8) or a
  wrong equation that touches every token (gates that include the bias,
  three experts for four, no normalisation, q/k norms left out) fails
  it.  Even at matched operands the two programs sum in different
  orders, so once in thousands of (token, layer) pairs a near-tied
  expert still goes the other way and drags the rest of that answer
  with it: the unharmed path reads 0.0006-0.0007 on the chip, and the
  bound (0.0012) sits between that and the nearest harmed variant (the
  bias added to the weights, 0.0022; int8-rounded weights read 0.010).
* ``worst_gap_share`` holds the WORST token: loose (such a flipped token
  sits up to 0.09 of the range down; the bound is 0.3), for gross
  failure that a mean over thousands of tokens could dilute — a conv
  window one position late reads 0.89, gates left unnormalised 0.54.

Besides: at least ``min_reused_share`` of the sample sat in a reused
slot, and every branch of every block (operator, FFN or experts) is at
least ``min_branch_share`` of the residual it is added to, by ``block``'s
own measure.
"""
import jax
import jax.numpy as jnp

F32 = jnp.float32
CONV, ATTENTION = "conv", "full_attention"
WEIGHT_SUM_EPS = 1e-6


def _f(a):
    return jnp.asarray(a).astype(F32)


def _mm_in(x, cfg, like=None):
    """``x`` as a matrix product takes it: unchanged (float32) unless the
    configuration's ``matmul_inputs`` names a dtype — then rounded to it
    and taken back to float32, so that the arithmetic stays float32 at
    "highest" while the operand is the one the configuration STATES
    (``like``: the stored weight it meets; a float32 weight, the
    router's, leaves its input alone)."""
    dt = cfg.get("matmul_inputs")
    if dt is None or (like is not None and jnp.asarray(like).dtype == F32):
        return x
    return x.astype(dt).astype(F32)


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


def _head_dim(cfg):
    return int(cfg.get("head_dim")
               or int(cfg["hidden_size"]) // int(cfg["num_attention_heads"]))


def attention(w, p, r, cfg):
    b, s, _ = r.shape
    nh, nkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    dh, eps = _head_dim(cfg), float(cfg["norm_eps"])
    theta = float(cfg["rope_parameters"]["rope_theta"])
    r = _mm_in(r, cfg)
    q = _rms((r @ _f(w[p + "attn_q"])).reshape(b, s, nh, dh),
             w[p + "q_layernorm"], eps)
    k = _rms((r @ _f(w[p + "attn_k"])).reshape(b, s, nkv, dh),
             w[p + "k_layernorm"], eps)
    v = (r @ _f(w[p + "attn_v"])).reshape(b, s, nkv, dh)
    q = _mm_in(_rope(q, theta) / jnp.sqrt(F32(dh)), cfg)
    k, v = _mm_in(_rope(k, theta), cfg), _mm_in(v, cfg)    # as stored
    k, v = (jnp.repeat(t, nh // nkv, axis=2) for t in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k)
    causal = jnp.tril(jnp.ones((s, s), bool))[None, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e9), axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", _mm_in(probs, cfg), v).reshape(
        b, s, nh * dh)
    return _mm_in(ctx, cfg) @ _f(w[p + "attn_o"])


def short_conv(w, p, r, cfg):
    s, kc = r.shape[1], int(cfg["conv_L_cache"])
    bm, cm, x = jnp.split(_mm_in(r, cfg) @ _f(w[p + "conv_in"]), 3, axis=-1)
    u = bm * x
    padded = jnp.pad(u, ((0, 0), (kc - 1, 0), (0, 0)))
    cw = _f(w[p + "conv_w"])
    y = sum(padded[:, j:j + s] * cw[j] for j in range(kc))
    return _mm_in(cm * y, cfg) @ _f(w[p + "conv_out"])


def dense_ffn(w, p, f, cfg, blocks=1):
    """SwiGLU, the width taken in ``blocks`` equal slices one after
    another: the same products, one slice of the matrices upcast at a
    time."""
    wg, wu, wd = w[p + "ffn_gate"], w[p + "ffn_up"], w[p + "ffn_down"]
    n = wg.shape[1] // blocks
    assert n * blocks == wg.shape[1], "blocks must divide the FFN width"
    out, f = jnp.zeros_like(f), _mm_in(f, cfg)
    for j in range(blocks):
        cols = slice(j * n, (j + 1) * n)
        out = out + _mm_in(jax.nn.silu(f @ _f(wg[:, cols]))
                           * (f @ _f(wu[:, cols])), cfg) @ _f(wd[cols, :])
    return out


def routing(w, p, f, cfg):
    """``(sel [B, S, k], gate [B, S, k])`` over all the experts."""
    s = jax.nn.sigmoid(_mm_in(f, cfg, w[p + "router"]) @ _f(w[p + "router"]))
    chosen = s + _f(w[p + "expert_bias"]) if cfg.get(
        "use_expert_bias", True) else s
    _, sel = jax.lax.top_k(chosen, int(cfg["num_experts_per_tok"]))
    gate = jnp.take_along_axis(s, sel, axis=-1)
    if cfg.get("norm_topk_prob", True):
        gate = gate / (gate.sum(-1, keepdims=True) + WEIGHT_SUM_EPS)
    return sel, gate * float(cfg.get("routed_scaling_factor", 1.0))


def experts(w, p, f, cfg, held=None, expert_blocks=1):
    """The held experts' part of the mixture: every held expert applied
    to every token, weighed by the token's gate for it (zero where the
    token did not choose it).  ``experts_w13`` / ``experts_w2`` hold the
    held experts only, in order; ``expert_blocks`` of them are upcast at
    a time."""
    n_all, width = int(cfg["num_experts"]), int(cfg["moe_intermediate_size"])
    lo, hi = (0, n_all) if held is None else held
    sel, gate = routing(w, p, f, cfg)
    w13, w2 = w[p + "experts_w13"], w[p + "experts_w2"]
    assert w13.shape[0] == hi - lo, "state must hold the held experts"
    per = (hi - lo) // expert_blocks
    assert per * expert_blocks == hi - lo, "blocks must divide the experts"
    out, f = jnp.zeros_like(f), _mm_in(f, cfg, w13)
    for j in range(expert_blocks):      # static slices: no whole copy
        a13 = _f(w13[j * per:(j + 1) * per])
        a2 = _f(w2[j * per:(j + 1) * per])
        for e in range(per):
            weight = jnp.sum(jnp.where(sel == lo + j * per + e, gate, 0.0),
                             axis=-1, keepdims=True)
            gu = f @ a13[e]
            out = out + weight * (_mm_in(
                jax.nn.silu(gu[..., :width]) * gu[..., width:], cfg) @ a2[e])
    return out


def _size(t):
    return jnp.sqrt(jnp.mean(t * t))


def embed(w, tokens, cfg, name="lm"):
    return _f(w[name + "_emb"][tokens])


def _ffn_input(w, p, h, cfg, kind):
    """``(o, mid, f)``: the operator's output, the residual after it and
    the normed input of the layer's FFN or experts."""
    eps = float(cfg["norm_eps"])
    r = _rms(h, w[p + "operator_norm"], eps)
    o = short_conv(w, p, r, cfg) if kind == CONV else attention(w, p, r, cfg)
    return o, h + o, _rms(h + o, w[p + "ffn_norm"], eps)


def block(w, i, h, cfg, kind, dense, name="lm", held=None, ffn_blocks=1,
          expert_blocks=1):
    """One block over ``h`` [B, S, D]; ``w`` needs only layer ``i``'s
    weights; ``kind`` its operator, ``dense`` whether its FFN is the
    dense one.  Returns ``(h, shares)``: the rms of the operator's and of
    the FFN's contribution over the rms of the residual each is added
    to."""
    with jax.default_matmul_precision("highest"):
        p = "%s_l%d_" % (name, i)
        o, mid, f = _ffn_input(w, p, h, cfg, kind)
        y = (dense_ffn(w, p, f, cfg, ffn_blocks) if dense
             else experts(w, p, f, cfg, held, expert_blocks))
        return mid + y, jnp.stack([_size(o) / _size(h),
                                   _size(y) / _size(mid)])


def head(w, h, cfg, name="lm"):
    """All logits [B, S, V] (small vocabularies: the CPU tests)."""
    with jax.default_matmul_precision("highest"):
        x = _mm_in(_rms(h, w[name + "_embedding_norm"],
                        float(cfg["norm_eps"])), cfg)
        return x @ _f(w[name + "_emb"]).T


def head_stats(w, h, targets, cfg, blocks, name="lm"):
    """What the check needs of the logits at every position without
    holding them: ``(max, min, argmax, logit of targets)``, each [B, S],
    the tied head taken in ``blocks`` equal slices of the vocabulary."""
    with jax.default_matmul_precision("highest"):
        x = _mm_in(_rms(h, w[name + "_embedding_norm"],
                        float(cfg["norm_eps"])), cfg)
        emb = w[name + "_emb"]
        vb = emb.shape[0] // blocks
        assert vb * blocks == emb.shape[0], "blocks must divide the vocabulary"
        shp = targets.shape
        hi, lo = jnp.full(shp, -jnp.inf, F32), jnp.full(shp, jnp.inf, F32)
        arg, got = jnp.zeros(shp, jnp.int32), jnp.zeros(shp, F32)
        for j in range(blocks):   # static slices: no copy of the matrix
            lg = x @ _f(emb[j * vb:(j + 1) * vb]).T              # [B, S, vb]
            bmax = lg.max(-1)
            arg = jnp.where(bmax > hi, j * vb + lg.argmax(-1), arg)
            local = targets - j * vb
            picked = jnp.take_along_axis(
                lg, jnp.clip(local, 0, vb - 1)[..., None], -1)[..., 0]
            got = jnp.where((local >= 0) & (local < vb), picked, got)
            hi, lo = jnp.maximum(hi, bmax), jnp.minimum(lo, lg.min(-1))
        return hi, lo, arg, got


def forward(w, tokens, cfg, name="lm", held=None, with_routing=False):
    """tokens [B, S] int32 -> logits [B, S, V]; position s sees positions
    <= s.  ``with_routing``: also each expert layer's ``sel`` [B, S, k]
    (for a recount of what the served path counted)."""
    h = embed(w, tokens, cfg, name)
    kinds, n_dense = cfg["layer_types"], int(cfg["num_dense_layers"])
    chosen = []
    for i in range(int(cfg["num_hidden_layers"])):
        if with_routing and i >= n_dense:
            with jax.default_matmul_precision("highest"):
                p = "%s_l%d_" % (name, i)
                chosen.append(routing(
                    w, p, _ffn_input(w, p, h, cfg, kinds[i])[2], cfg)[0])
        h, _ = block(w, i, h, cfg, kinds[i], i < n_dense, name, held)
    logits = head(w, h, cfg, name)
    return (logits, chosen) if with_routing else logits
