"""Plain reference for ``smallthinker_21b_a3b``: the full causal forward
of a ``smallthinker`` decoder in float32 ``jax.numpy`` at matmul
precision "highest".  No cache and NO RING, no grouped product, nothing
from ``paddle_tpu``: a window layer is a banded causal mask over the
whole sequence, a global layer a plain causal mask with no positions of
any kind, the experts a loop over all of them, each applied to EVERY
token and kept where the token chose it.

The equations (``h`` the residual, ``RMS`` RMSNorm with weight, eps
``rms_norm_eps``; pre-norm, no bias anywhere; layer ``i`` is a WINDOW
layer where ``sliding_window_layout[i] == 1`` and is rotated where
``rope_layout[i] == 1`` — the published lists are equal: a window layer
is rotated, a GLOBAL layer has no positions):

    h0 = E[ids]
    r  = RMS(h; input_norm)
    router:  z = r W_r                  float32, all the experts; it reads
                                        the block's normed INPUT, before
                                        attention
             sel = top_k(z)             on the logits
             g = softmax(z[sel])        over the chosen alone;
             g = g / sum(g)             norm_topk_prob (a no-op after the
                                        softmax, kept)
    q = r W_q -> [heads, 128], k = r W_k, v = r W_v -> [kv, 128]
    window layer:  rotary (rotate-half over the whole head, theta 1.5e6)
                   on q and k; the query at p reads keys p - W + 1 .. p
                   (W = sliding_window_size: the window counts the
                   query's own position)
    global layer:  no rotary, nothing positional; keys 0 .. p
    softmax at 1 / sqrt(128); query head j reads K/V head j // (heads / kv)
    h = h + ctx W_o;   f = RMS(h; ffn_norm)
    h = h + sum_{e in sel, lo <= e < hi} g_e W2_e (relu(W1_e f) * W3_e f)
    logits = RMS(h; final_norm) W_head                (the head is untied)

``held = (lo, hi)`` is the contiguous range of experts this share
computes (routing is over all of them; what the absent ones would add is
left out); default all.  The weights come in under the names the served
program uses (``lm_emb``, ``lm_l<i>_attn_q`` ...; matrices ``[in, out]``,
an expert layer's gate and up matrices as ONE ``[experts, d, 2 *
width]`` with the gate's columns first — the only things shared with the
system under test) and in the dtype it serves them in (bf16): they are
upcast here, one layer at a time (``block`` takes one layer's weights),
the experts one at a time, attention ``query_block`` query rows at a
time and the head in vocabulary blocks (``head_stats``), so
that the check fits beside 7.9 GB of served weights.

Departures from the published description, and what the catalog's
config does not say (``assumed`` in the config file): the router reads
the NORMED block input (not the raw residual); the top-k is taken on the
logits and the softmax runs over the chosen; the window counts the
query's own position; the half-split rotary convention; no q/k norm.

The operands the configuration states (``matmul_inputs``), as
``lfm2_24b_a2b``'s reference and for its reason: a mixture makes the
stated precision part of the FUNCTION — where a token's sixth and
seventh expert score within the rounding of each other, a float32
forward and any faithful bf16 forward choose different experts, both
defensibly, and everything after that token's layer differs.  With
``cfg["matmul_inputs"] = "bfloat16"`` each operand the configuration
says is rounded is rounded HERE too (``_mm_in``: the input of every
product with a stored bf16 matrix, the scaled query, the stored K and V,
the softmax weights, the experts' activation) to that precision, in
float32: the arithmetic stays float32 at "highest", and a step that
rounds what the configuration does NOT say is rounded (the router's
input, a norm) shows as the routing flips it causes.  The CPU tests keep
the default.

Tolerances (``check`` in the config file; readings in PERF.md section
4): each served token's reference logit is placed in its position's
logit range, ``gap = (max - logit[served token]) / (max - min)``; the
MEAN over the sampled tokens is held tight (a lower precision, a window
one off, a wrong equation move every token a little) and the WORST token
loose (a marginal expert's flip moves one token far; gross failure
only).
"""
import jax
import jax.numpy as jnp

F32 = jnp.float32
GLOBAL, WINDOW = 0, 1


def _f(a):
    return jnp.asarray(a).astype(F32)


def _mm_in(x, cfg, like=None):
    """``x`` as a matrix product takes it: unchanged (float32) unless the
    configuration's ``matmul_inputs`` names a dtype — then rounded to
    that dtype's precision, in float32 (``like``: the stored weight it
    meets; a float32 weight, the router's, leaves its input alone).

    The rounding is ``lax.reduce_precision`` and NOT a cast there and
    back: under ``jit`` XLA may drop a ``float32 -> bfloat16 -> float32``
    pair as excess precision it is allowed to keep, and on the TPU it
    does — the reference then multiplies UNROUNDED operands (with the
    cast pair the served K rows of layer 1 part from the reference's by
    0.47%, all of them; with this 0.26%: chip runs, PR 43).  What is
    left is not a flaw of either side: two programs that differ by
    1e-5 before a bf16 rounding differ by the geometric mean of that and
    an ulp after it, so a few products on they differ by an ulp whatever
    the operands (PERF.md section 6)."""
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


def attention(w, p, r, cfg, kind, query_block=None, rotated=None):
    """Causal attention over the whole sequence, ``query_block`` query
    rows at a time (default: all at once): banded to the window where
    ``kind`` is :data:`WINDOW`, rotary where ``rotated`` (default: in a
    window layer and only there)."""
    b, s, _ = r.shape
    nh, nkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    dh = int(cfg["head_dim"])
    r = _mm_in(r, cfg)
    q = (r @ _f(w[p + "attn_q"])).reshape(b, s, nh, dh)
    k = (r @ _f(w[p + "attn_k"])).reshape(b, s, nkv, dh)
    v = (r @ _f(w[p + "attn_v"])).reshape(b, s, nkv, dh)
    if kind == WINDOW if rotated is None else rotated:
        theta = float(cfg["rope_theta"])
        q, k = _rope(q, theta), _rope(k, theta)
    q = _mm_in(q / jnp.sqrt(F32(dh)), cfg)
    k, v = _mm_in(k, cfg), _mm_in(v, cfg)                   # as stored
    k, v = (jnp.repeat(t, nh // nkv, axis=2) for t in (k, v))
    qb = s if query_block is None else int(query_block)
    assert s % qb == 0, "query_block must divide the sequence"
    window = int(cfg["sliding_window_size"])
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


def routing(w, p, r, cfg):
    """``(sel [B, S, k], gate [B, S, k])`` over all the experts, from
    the block's normed input ``r``."""
    z = _mm_in(r, cfg, w[p + "router"]) @ _f(w[p + "router"])
    z, sel = jax.lax.top_k(z, int(cfg["moe_num_active_primary_experts"]))
    gate = z
    if cfg.get("moe_primary_router_apply_softmax", True):
        gate = jax.nn.softmax(z, axis=-1)
    if cfg.get("norm_topk_prob", True):
        gate = gate / gate.sum(-1, keepdims=True)
    return sel, gate


def experts(w, p, f, sel, gate, cfg, held=None):
    """The held experts' part of the mixture: every held expert applied
    to every token, weighed by the token's gate for it (zero where the
    token did not choose it), one expert after another (a scan: one
    expert's matrices upcast and one expert's products alive at a time).
    ``experts_w13`` / ``experts_w2`` hold the held experts only, in
    order."""
    n_all = int(cfg["moe_num_primary_experts"])
    width = int(cfg["moe_ffn_hidden_size"])
    lo, hi = (0, n_all) if held is None else held
    w13, w2 = w[p + "experts_w13"], w[p + "experts_w2"]
    assert w13.shape[0] == hi - lo, "state must hold the held experts"
    f = _mm_in(f, cfg, w13)

    def one(out, expert):
        e, a13, a2 = expert
        weight = jnp.sum(jnp.where(sel == e, gate, 0.0), axis=-1,
                         keepdims=True)
        gu = f @ _f(a13)
        return out + weight * (_mm_in(
            jax.nn.relu(gu[..., :width]) * gu[..., width:], cfg)
            @ _f(a2)), None

    return jax.lax.scan(one, jnp.zeros_like(f),
                        (jnp.arange(lo, hi), w13, w2))[0]


def _size(t):
    return jnp.sqrt(jnp.mean(t * t))


def embed(w, tokens, cfg, name="lm"):
    return _f(w[name + "_emb"][tokens])


def block(w, i, h, cfg, kind, name="lm", held=None, query_block=None,
          rotated=None):
    """One block over ``h`` [B, S, D]; ``w`` needs only layer ``i``'s
    weights; ``kind`` :data:`WINDOW` or :data:`GLOBAL` (the layer's
    ``sliding_window_layout`` entry), ``rotated`` its ``rope_layout``
    entry (default: as ``kind``).  Returns ``(h, shares)``: the rms of the attention's and of the experts'
    contribution over the rms of the residual each is added to."""
    with jax.default_matmul_precision("highest"):
        p = "%s_l%d_" % (name, i)
        eps = float(cfg["rms_norm_eps"])
        r = _rms(h, w[p + "input_norm"], eps)
        sel, gate = routing(w, p, r, cfg)
        o = attention(w, p, r, cfg, kind, query_block, rotated)
        mid = h + o
        y = experts(w, p, _rms(mid, w[p + "ffn_norm"], eps), sel, gate, cfg,
                    held)
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


def forward(w, tokens, cfg, name="lm", held=None, with_routing=False):
    """tokens [B, S] int32 -> logits [B, S, V]; position s sees positions
    <= s (a window layer: the last ``sliding_window_size`` of them).
    ``with_routing``: also each layer's ``sel`` [B, S, k]."""
    h = embed(w, tokens, cfg, name)
    kinds = [int(x) for x in cfg["sliding_window_layout"]]
    rotated = [bool(x) for x in cfg["rope_layout"]]
    chosen = []
    for i in range(int(cfg["num_hidden_layers"])):
        if with_routing:
            with jax.default_matmul_precision("highest"):
                p = "%s_l%d_" % (name, i)
                chosen.append(routing(w, p, _rms(
                    h, w[p + "input_norm"], float(cfg["rms_norm_eps"])),
                    cfg)[0])
        h, _ = block(w, i, h, cfg, kinds[i], name, held,
                     rotated=rotated[i])
    logits = head(w, h, cfg, name)
    return (logits, chosen) if with_routing else logits
