"""The parts of a decoder whose every block is multi-head LATENT
attention read through a learned selection (a "lightning indexer"), then
a dense SwiGLU (the leading layers) or group-limited routed experts
beside a shared expert (``model_type: deepseek_v32``).  Pre-norm, no
bias anywhere but the indexer's LayerNorm:

    x = RMS(h; input_norm)
    queries:  cq = RMS(x W_dq; q_a_norm)                       [q_lora_rank]
              q_i = cq W_uq,i = [qC_i (nope) ; qR_i (rope)]    per head i
              qR_i rotated at its position
    cached:   [c ; kR] = x W_dkv;  c = RMS(c; kv_a_norm);  kR rotated,
              ONE head shared by all: the layer's cache row is (c, kR)
    indexer:  qI_j = (cq W_iq)_j  for index_n_heads heads, the first rope
              lanes of each rotated;  kI = LayerNorm(x W_ik), the first
              rope lanes rotated: the INDEX KEY, cached
              w = (x W_iw) * index_n_heads^-0.5 * index_head_dim^-0.5
              I_t,s = sum_j w_t,j relu(qI_t,j . kI_s)   float32, s <= t
              S_t = the min(index_topk, t + 1) positions of largest I_t,s
              (ties: the lowest position first), alike for every head
    expanded: [kC_s,i ; v_s,i] = c_s W_ukv,i
              a_t,s,i = scale (qC_t,i . kC_s,i + qR_t,i . kR_s)
              o_t = concat_i(sum_{s in S_t} softmax_s(a_t,s,i) v_s,i) W_o
    absorbed: qA_t,i = qC_t,i W_uk,i^T  [kv_lora_rank]
              a_t,s,i = scale (qA_t,i . c_s + qR_t,i . kR_s)
              u_t,i = sum_{s in S_t} p_t,s,i c_s;  o_t = concat_i(u_t,i W_uv,i) W_o
    h = h + o;   f = RMS(h; ffn_norm)
    dense layer:  h = h + W2 (silu(W1 f) * W3 f)
    sparse layer: routed_experts.expert_layer (sigmoid scores, a bias
                  that chooses, GROUP-LIMITED choice, a routed scale)
                  + the shared expert
    logits = RMS(h; final_norm) W_head            (the head is untied)

The two forms are the same function (tests/test_latent_sparse_lm.py).
The rotary is YaRN's over the rope lanes (:func:`yarn_inv_freq`), the
softmax scale carries its ``mscale`` squared (:func:`softmax_scale`);
lanes are paired half-split (rotate-half) in attention and indexer alike.

``decoding.make_latent_sparse_lm_pooled_step_fn`` strings them into the
slot-pooled step (absorbed: the cache is never expanded) and the chunked
prefill (expanded, a key block at a time); nothing here knows a pool or
a server.  The cache is ``decode_attention``'s latent leaves; the up
projections are stored as they are multiplied (``attn_uk`` ``[heads,
nope, kv_lora_rank]``, ``attn_uv`` ``[heads, kv_lora_rank, v]``: a head a
batch of one product, no relayout a step).  Weights are multiplied in
the dtype they are given (bf16 as stored), accumulated in float32; the
router, the index scores' sum, norms and rotary angles are float32.
"""
from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from paddle_tpu.hybrid_ssm import linear, rms_norm, swiglu
from paddle_tpu.routed_experts import SIGMOID_BIAS, SILU

__all__ = ["dims", "latent_dims", "latent_attention_dims", "param_shapes",
           "random_state",
           "yarn_inv_freq", "softmax_scale", "rotate", "latent_inputs",
           "index_inputs",
           "index_scores", "top_members", "select_positions",
           "absorb_queries", "attend_out", "chunk_select",
           "chunk_attend_expanded",
           "LATENT_PROJECT_SCOPE", "INDEX_SCORE_SCOPE", "INDEX_SELECT_SCOPE",
           "LATENT_ATTEND_SCOPE", "PREFILL_CHUNK_SCOPE", "FLOAT32_PARAMS",
           "linear", "rms_norm", "swiglu"]

#: ``jax.named_scope`` names, for the device trace
LATENT_PROJECT_SCOPE = "latent_project"
INDEX_SCORE_SCOPE = "index_score"
INDEX_SELECT_SCOPE = "index_select"
LATENT_ATTEND_SCOPE = "latent_attend"
PREFILL_CHUNK_SCOPE = "prefill_chunk"

#: parameters kept float32 whatever the matrices' dtype, by name ending
FLOAT32_PARAMS = ("_norm", "_norm_bias", "router", "expert_bias")

_LN_EPS = 1e-6
_MASK = -1e30


def yarn_inv_freq(cfg) -> np.ndarray:
    """The rotary's inverse frequencies over ``qk_rope_head_dim`` lanes
    (``[lanes / 2]`` float32): YaRN — each frequency a blend of the
    extrapolated ``theta^(-2i/lanes)`` and that over ``factor``, by a
    linear ramp between the two correction dims of ``beta_fast`` and
    ``beta_slow``; plain rotary without ``rope_scaling``."""
    lanes = int(cfg["qk_rope_head_dim"])
    base = float(cfg["rope_theta"])
    extra = base ** (-np.arange(0, lanes, 2, dtype=np.float64) / lanes)
    sc = cfg.get("rope_scaling")
    if not sc:
        return extra.astype(np.float32)
    if sc.get("type", sc.get("rope_type")) != "yarn":
        raise ValueError("only yarn rope_scaling is supported")
    factor = float(sc["factor"])
    orig = float(sc["original_max_position_embeddings"])

    def correction_dim(rotations):
        return (lanes * np.log(orig / (rotations * 2 * np.pi))
                / (2 * np.log(base)))

    low = max(np.floor(correction_dim(float(sc["beta_fast"]))), 0)
    high = min(np.ceil(correction_dim(float(sc["beta_slow"]))), lanes - 1)
    if low == high:
        high += 0.001
    ramp = np.clip((np.arange(lanes // 2) - low) / (high - low), 0, 1)
    return (extra / factor * ramp + extra * (1 - ramp)).astype(np.float32)


def softmax_scale(cfg) -> float:
    """``(nope + rope)^-0.5 * m^2`` with YaRN's ``m = 0.1 *
    mscale_all_dim * ln(factor) + 1`` (1 without ``rope_scaling``)."""
    width = int(cfg["qk_nope_head_dim"]) + int(cfg["qk_rope_head_dim"])
    sc = cfg.get("rope_scaling") or {}
    m = 1.0
    if sc.get("mscale_all_dim") and float(sc.get("factor", 1)) > 1:
        m = 0.1 * float(sc["mscale_all_dim"]) * np.log(float(sc["factor"])) + 1
    return float(width ** -0.5 * m * m)


def latent_attention_dims(cfg) -> SimpleNamespace:
    """What multi-head LATENT attention itself has, whatever follows it
    (the published key names of the DeepSeek-V3 lineage): the heads, the
    query's low rank (``q_lora_rank`` null: ``q_rank`` None, ONE query
    matrix and no ``q_a_norm``), the latent's widths, the rotary's
    frequencies, the softmax scale, and ``rotary`` — False where the
    configuration says ``mla_use_nope``: the lanes that would be rotated
    are carried and NOT rotated, on either side."""
    rank = cfg.get("q_lora_rank")
    o = SimpleNamespace(
        n_head=int(cfg["num_attention_heads"]),
        q_rank=None if rank is None else int(rank),
        d_c=int(cfg["kv_lora_rank"]),
        d_nope=int(cfg["qk_nope_head_dim"]),
        d_rope=int(cfg["qk_rope_head_dim"]), d_v=int(cfg["v_head_dim"]),
        rotary=not cfg.get("mla_use_nope", False),
        inv_freq=yarn_inv_freq(cfg), scale=softmax_scale(cfg))
    if cfg.get("attention_bias", False):
        raise ValueError("attention_bias is not supported")
    if o.d_rope % 2:
        raise ValueError("the rotated lanes must be even")
    o.d_latent = o.d_c + o.d_rope
    o.d_qk = o.d_nope + o.d_rope
    return o


def latent_dims(cfg) -> SimpleNamespace:
    """What every decoder of multi-head LATENT attention over a dense
    SwiGLU or routed experts beside a shared expert has (the published
    key names of the DeepSeek-V3 lineage), whatever reads its latent
    rows: :func:`latent_attention_dims`, the sizes and what
    ``routed_experts.route`` / ``expert_layer`` read.
    ``n_routed_experts`` may count the experts HELD here; the router's
    width is then ``n_routed_experts_all``.  :func:`dims` adds the
    lightning indexer's; ``latent_mtp_lm.dims`` the sandwich norms' and
    the drafting module's."""
    o = latent_attention_dims(cfg)
    vars(o).update(
        vocab=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        n_layer=int(cfg["num_hidden_layers"]),
        n_dense=int(cfg["first_k_dense_replace"]),
        d_mlp=int(cfg["intermediate_size"]),
        d_expert=int(cfg["moe_intermediate_size"]),
        n_expert=int(cfg.get("n_routed_experts_all",
                             cfg["n_routed_experts"])),
        top_k=int(cfg["num_experts_per_tok"]),
        n_shared=int(cfg.get("n_shared_experts", 0)),
        n_group=int(cfg.get("n_group", 1)),
        topk_group=int(cfg.get("topk_group", 1)),
        eps=float(cfg.get("rms_norm_eps", 1e-6)),
        norm_topk=bool(cfg.get("norm_topk_prob", True)),
        routed_scale=float(cfg.get("routed_scaling_factor", 1.0)),
        # what routed_experts.route / expert_layer read
        scoring=SIGMOID_BIAS, gate_act=SILU, expert_bias=True)
    if cfg.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("only scoring_func = sigmoid is supported")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("only hidden_act = silu is supported")
    if cfg.get("tie_word_embeddings", False):
        raise ValueError("a tied head is not supported")
    if int(cfg.get("moe_layer_freq", 1)) != 1:
        raise ValueError("only moe_layer_freq = 1 is supported")
    if o.n_expert % o.n_group or o.topk_group > o.n_group:
        raise ValueError("n_group must divide the experts and hold "
                         "topk_group")
    if o.n_group > 1 and o.n_expert // o.n_group < 2:
        raise ValueError("a group is scored by its two best experts")
    o.dense = tuple(i < o.n_dense for i in range(o.n_layer))
    o.expert_layers = tuple(i for i in range(o.n_layer) if not o.dense[i])
    return o


def dims(cfg) -> SimpleNamespace:
    """The block's sizes and scalars from a ``deepseek_v32`` config dict
    (the published key names): :func:`latent_dims` and the lightning
    indexer's."""
    o = latent_dims(cfg)
    o.n_index_head = int(cfg["index_n_heads"])
    o.d_index = int(cfg["index_head_dim"])
    o.index_topk = int(cfg["index_topk"])
    o.ln_eps = _LN_EPS
    if int(cfg.get("num_nextn_predict_layers", 0)):
        raise ValueError("a multi-token-prediction module is not held: "
                         "num_nextn_predict_layers must be 0")
    if o.q_rank is None:
        raise ValueError("the indexer reads the query's low rank: "
                         "q_lora_rank must be given")
    if o.d_rope > o.d_index:
        raise ValueError("the rotated lanes must fit an index head")
    return o


def param_shapes(cfg, name: str = "lm", held=None) -> dict:
    """Names and shapes of every weight the step reads: the one place
    the schema lives.  Matrices are ``[in, out]``; the latent's up
    projections are kept a head a batch, as multiplied: ``attn_uk``
    ``[heads, nope, kv_lora_rank]`` (``kC_i = c W_uk,i^T``; absorbed:
    ``qA_i = qC_i W_uk,i``) and ``attn_uv`` ``[heads, kv_lora_rank, v]``;
    an expert layer's gate and up matrices are ONE ``[held experts,
    d_model, 2 * width]`` (gate columns first), its shared expert's ONE
    ``[d_model, 2 * width]``; ``held = (lo, hi)``: the experts whose
    matrices are held (default all); the router and its bias keep their
    whole width."""
    d = dims(cfg)
    n_held = d.n_expert if held is None else int(held[1]) - int(held[0])
    out = {name + "_emb": (d.vocab, d.d_model),
           name + "_final_norm": (d.d_model,),
           name + "_head": (d.d_model, d.vocab)}
    for i in range(d.n_layer):
        p = "%s_l%d_" % (name, i)
        out.update({
            p + "input_norm": (d.d_model,), p + "ffn_norm": (d.d_model,),
            p + "attn_q_a": (d.d_model, d.q_rank),
            p + "q_a_norm": (d.q_rank,),
            p + "attn_q_b": (d.q_rank, d.n_head * d.d_qk),
            p + "attn_kv_a": (d.d_model, d.d_latent),
            p + "kv_a_norm": (d.d_c,),
            p + "attn_uk": (d.n_head, d.d_nope, d.d_c),
            p + "attn_uv": (d.n_head, d.d_c, d.d_v),
            p + "attn_o": (d.n_head * d.d_v, d.d_model),
            p + "index_q": (d.q_rank, d.n_index_head * d.d_index),
            p + "index_k": (d.d_model, d.d_index),
            p + "index_k_norm": (d.d_index,),
            p + "index_k_norm_bias": (d.d_index,),
            p + "index_w": (d.d_model, d.n_index_head)})
        if d.dense[i]:
            out.update({p + "ffn_gate": (d.d_model, d.d_mlp),
                        p + "ffn_up": (d.d_model, d.d_mlp),
                        p + "ffn_down": (d.d_mlp, d.d_model)})
            continue
        out.update({p + "router": (d.d_model, d.n_expert),
                    p + "expert_bias": (d.n_expert,),
                    p + "experts_w13": (n_held, d.d_model, 2 * d.d_expert),
                    p + "experts_w2": (n_held, d.d_expert, d.d_model)})
        if d.n_shared:
            out.update({p + "shared_w13": (d.d_model,
                                           2 * d.n_shared * d.d_expert),
                        p + "shared_w2": (d.n_shared * d.d_expert,
                                          d.d_model)})
    return out


def random_state(rng, cfg, name: str = "lm", std: float = 0.02,
                 dtype="float32", bias_range: float = 0.05,
                 held=None) -> dict:
    """Seeded random weights under :func:`param_shapes` (tests, tools):
    normal matrices in ``dtype``, norm weights near 1 and the indexer's
    LayerNorm bias NOT zero (a norm that is skipped shows), a float32
    router and an ``expert_bias`` uniform in ``+-bias_range``."""
    import jax.numpy as jnp

    w = {}
    for k, shp in param_shapes(cfg, name, held).items():
        if k.endswith("_norm"):
            w[k] = (1.0 + 0.1 * rng.randn(*shp)).astype("float32")
        elif k.endswith("_norm_bias"):
            w[k] = (0.1 * rng.randn(*shp)).astype("float32")
        elif k.endswith("expert_bias"):
            w[k] = rng.uniform(-bias_range, bias_range, shp).astype("float32")
        elif k.endswith("router"):
            w[k] = (rng.randn(*shp) * std).astype("float32")
        else:
            w[k] = jnp.asarray((rng.randn(*shp) * std).astype("float32"),
                               dtype)
    return w


def rotate(x, pos, inv_freq):
    """Rotate-half rotary over the WHOLE last axis of ``x`` ``[N, ...,
    2 * len(inv_freq)]`` at per-row positions ``pos`` ``[N]``, float32."""
    import jax.numpy as jnp

    f32 = jnp.float32
    half = x.shape[-1] // 2
    ang = pos.astype(f32)[:, None] * jnp.asarray(inv_freq, f32)[None, :]
    ang = ang.reshape((x.shape[0],) + (1,) * (x.ndim - 2) + (half,))
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)
    x = x.astype(f32)
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def latent_inputs(x, w, p: str, pos, d):
    """What attention takes of the normed rows ``x`` ``[N, d_model]`` at
    positions ``pos``: ``(cq [N, q_rank], qC [N, heads, nope], qR [N,
    heads, rope], row [N, kv_lora_rank + rope])`` float32 — ``row`` is
    the layer's cache row ``(c, kR)``: ``c`` normed, ``kR`` rotated.
    Without a query low rank (``d.q_rank`` None) the queries are ONE
    product ``x W_q`` (``attn_q``) and ``cq`` is None; without rotary
    (``d.rotary`` False: ``mla_use_nope``) ``qR`` and ``kR`` are carried
    as projected.

    The ``attn_q_b`` product is COMPLETE before the reshape by heads sees
    it (the barrier): laid by heads for the per-head ``attn_uk`` product
    behind it, it reads its matrix the other way round, and the compiler
    then copies the 75.5 MB ``bf16[1536,24576]`` whole inside the program
    (seen in the compiled round of ``openpangu_ultra_moe_718b``: six
    copies a round, 0.65 ms of 20.9; ten hoisted out of ``deepseek_v3_2``'s
    step loop with :func:`index_inputs`', 0.5 GB of temporaries; PR 64).
    Alone, the product reads the matrix as stored."""
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    if d.q_rank is None:
        cq, q = None, linear(x, w[p + "attn_q"])
    else:
        cq = rms_norm(linear(x, w[p + "attn_q_a"]), w[p + "q_a_norm"], d.eps)
        q = jax.lax.optimization_barrier(linear(cq, w[p + "attn_q_b"]))
    q = q.reshape(n, d.n_head, d.d_qk)
    ckr = linear(x, w[p + "attn_kv_a"])
    turn = ((lambda t: rotate(t, pos, d.inv_freq)) if d.rotary
            else (lambda t: t.astype(jnp.float32)))
    row = jnp.concatenate(
        [rms_norm(ckr[:, :d.d_c], w[p + "kv_a_norm"], d.eps),
         turn(ckr[:, d.d_c:])], axis=-1)
    return cq, q[..., :d.d_nope], turn(q[..., d.d_nope:]), row


def _rotate_head(x, pos, d):
    import jax.numpy as jnp

    return jnp.concatenate([rotate(x[..., :d.d_rope], pos, d.inv_freq),
                            x[..., d.d_rope:].astype(jnp.float32)], axis=-1)


def index_inputs(x, cq, w, p: str, pos, d):
    """The lightning indexer's inputs for the rows ``x`` (normed) and
    their query latents ``cq``: ``(qI [N, index heads, index dim], kI [N,
    index dim], wI [N, index heads])`` float32; ``kI`` is the row's INDEX
    KEY (LayerNorm with weight and bias, then the first rope lanes
    rotated), ``wI`` carries both ``^-0.5`` factors.  ``index_q``'s product
    is complete before its reshape by heads, as ``attn_q_b``'s in
    :func:`latent_inputs` and for its reason."""
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    q = jax.lax.optimization_barrier(linear(cq, w[p + "index_q"])).reshape(
        n, d.n_index_head, d.d_index)
    k = linear(x, w[p + "index_k"])
    mu = jnp.mean(k, axis=-1, keepdims=True)
    var = jnp.mean((k - mu) ** 2, axis=-1, keepdims=True)
    k = ((k - mu) / jnp.sqrt(var + d.ln_eps)
         * w[p + "index_k_norm"].astype(jnp.float32)
         + w[p + "index_k_norm_bias"].astype(jnp.float32))
    wi = linear(x, w[p + "index_w"]) * float(
        d.n_index_head ** -0.5 * d.d_index ** -0.5)
    return _rotate_head(q, pos, d), _rotate_head(k, pos, d), wi


def index_scores(qi, wi, keys):
    """``I[n, s] = sum_j wi[n, j] relu(qi[n, j] . keys[n, s])`` in
    float32: ``qi`` ``[N, heads, D]``, ``wi`` ``[N, heads]``, ``keys``
    ``[N, T, D]`` (one row's keys a query) or ``[T, D]`` (the same keys
    for every query) in their storage dtype (the products are taken in
    it, accumulated in float32).  Returns ``[N, T]``; the caller masks
    what is not live."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.decode_attention import pad_lanes

    f32 = jnp.float32
    form = "nhd,ntd->nht" if keys.ndim == 3 else "nhd,td->nht"
    # a leaf's row is whole lane tiles: the query is padded to match
    qi = pad_lanes(qi.astype(keys.dtype), keys.shape[-1])
    s = jnp.einsum(form, qi, keys, preferred_element_type=f32)
    return jnp.sum(jax.nn.relu(s) * wi.astype(f32)[:, :, None], axis=1)


#: positions a block of the selection's two-level count: a lane tile (128
#: of 128 / 256 on the chip: 0.106 / 0.120 ms a selection at ``[24,
#: 32768]``, ``tools/time_index_select.py``, PR 55); at most 256 — a
#: block's running counts pass through bf16, exact that far
_SELECT_BLOCK = 128
#: bits of a key settled by one pass of the threshold's search: a pass
#: counts ``2 ** _RADIX_BITS - 1`` candidate thresholds in one read (2 of
#: 1 / 2 / 4 on the chip: 0.125 / 0.105 / 0.162 ms)
_RADIX_BITS = 2


def _order_keys(scores):
    """``scores`` float32 as int32 keys whose integer order is the
    floats' TOTAL order (``-0.0`` under ``0.0``, as ``lax.top_k`` ranks
    them)."""
    import jax
    import jax.numpy as jnp

    bits = jax.lax.bitcast_convert_type(scores, jnp.int32)
    return jnp.where(bits < 0, bits ^ jnp.int32(0x7fffffff), bits)


def _kth_largest(keys, k: int):
    """The ``k``-th largest of each row of int32 ``keys`` ``[N, T]``
    (``k <= T``), ``[N, 1]``, without sorting: the largest ``t`` with
    ``count(keys >= t) >= k``, its bits settled from the top down, each
    pass one compare-and-count over the rows."""
    import jax.numpy as jnp

    u32 = jnp.uint32
    ukeys = keys.astype(u32) ^ u32(0x80000000)      # unsigned, same order
    least = jnp.zeros((keys.shape[0], 1), u32)
    steps = jnp.arange(1, 2 ** _RADIX_BITS, dtype=u32)[:, None, None]
    for shift in range(32 - _RADIX_BITS, -1, -_RADIX_BITS):
        # candidates least | j << shift, j = 1 ..: counts fall with j
        cands = least[None] | (steps << u32(shift))
        enough = jnp.sum(ukeys[None] >= cands, axis=-1,
                         dtype=jnp.int32) >= k           # [J, N]
        least = least | (jnp.sum(enough, axis=0, dtype=u32)[:, None]
                         << u32(shift))
    return (least ^ u32(0x80000000)).astype(jnp.int32)


def top_members(scores, live, k: int):
    """WHICH positions of each row are its ``min(k, live)`` of largest
    score — THE threshold and THE tie rule of this family, the step's
    list (:func:`select_positions`) and the chunk's mask
    (:func:`chunk_select`) alike: ``scores`` ``[N, T]`` float32, ``live``
    ``[N, T]`` bool (what is not live is never chosen), ``k <= T``.  A
    position is chosen if it is live and its score is above the row's
    ``k``-th largest live score, or equal to it and among the first such
    positions that still fit (ties: the LOWEST position first,
    ``lax.top_k``'s rule; scores compare in the floats' total order).  No
    sort: the threshold is searched by counting (:func:`_kth_largest`),
    the ties are ranked by a two-level running count.

    Returns ``(member, within, counts)`` over blocks of ``B`` positions
    (a divisor of ``T``, a lane tile where it divides): ``member`` ``[N,
    T / B, B]`` bool, ``within`` ``[N, T / B, B]`` int32 the chosen
    positions of a block up to and with each place, ``counts`` ``[N, T /
    B]`` int32 a block's chosen."""
    import jax.numpy as jnp

    n, t = scores.shape
    b = _blocks(t, _SELECT_BLOCK)
    keys = _order_keys(jnp.where(live, scores, -jnp.inf))
    least = _kth_largest(keys, k)
    above = (keys > least).reshape(n, t // b, b)
    ties = ((keys == least) & live).reshape(n, t // b, b)
    # both masks' running counts inside a block: one product with a
    # triangle of ones (0 / 1 in, integers <= B out: exact)
    upto = (jnp.arange(b)[:, None] <= jnp.arange(b)[None, :]).astype(
        jnp.bfloat16)
    above_run, ties_run = jnp.einsum(
        "cnbl,lm->cnbm", jnp.stack([above, ties]).astype(jnp.bfloat16),
        upto, preferred_element_type=jnp.float32).astype(jnp.int32)
    room = k - jnp.sum(above_run[..., -1], axis=-1)[:, None, None]
    ties_a_block = ties_run[..., -1]
    earlier = (jnp.cumsum(ties_a_block, axis=-1) - ties_a_block)[..., None]
    rank = earlier + ties_run           # a tie's place among its row's ties
    member = above | (ties & (rank <= room))
    within = (above_run + jnp.minimum(rank, room)
              - jnp.minimum(earlier, room))
    return member, within, within[..., -1]


def select_positions(scores, ts, top_k: int):
    """The positions a row at ``ts`` reads: ``(sel [N, k] int32, valid
    [N, k] bool)``, ``k = min(top_k, T)`` — the ``min(top_k, ts + 1)``
    positions ``<= ts`` of largest ``scores`` ``[N, T]`` (ties: the
    lowest position first), as a SET IN ASCENDING POSITION ORDER: the
    valid part of the list is strictly ascending, and the whole list is
    ascending, unique and in range whatever ``ts`` (a row with fewer
    than ``k`` live positions, an idle row at ``ts < 0`` among them,
    lists ``arange(k)``, the first ``ts + 1`` of them ``valid``).  The
    order is part of the contract: ``selected_latent_attention`` tells
    the compiler its list is sorted and unique.  It is NOT the order of
    the scores — nothing ranks the chosen (attention over a list does not
    depend on its order).  The set is :func:`top_members`'s, found by a
    threshold and listed by compaction, no sort and no scatter: a slot
    ``j`` of the list finds its block by comparing with the blocks'
    running counts, a one-hot product picks that block's running count,
    and a comparison over its places finds the position."""
    import jax.numpy as jnp

    from paddle_tpu.decode_attention import INDEX_SELECT_LOWERED

    INDEX_SELECT_LOWERED.labels(path="threshold").inc()
    i32 = jnp.int32
    t = scores.shape[1]
    k = min(int(top_k), t)
    live = jnp.arange(t)[None, :] <= ts[:, None]
    _, within, counts = top_members(scores, live, k)
    b = within.shape[2]
    listed = jnp.arange(k, dtype=i32)[None, :]                  # [1, k]
    slots = listed[..., None]
    ends = jnp.cumsum(counts, axis=-1)[:, None, :]              # [N, 1, nb]
    starts = ends - counts[:, None, :]
    mine = (starts <= slots) & (slots < ends)                   # [N, k, nb]
    block = jnp.sum(ends <= slots, axis=-1, dtype=i32)
    place = listed - jnp.max(jnp.where(mine, starts, 0), axis=-1)
    run = jnp.einsum("nkb,nbl->nkl", mine.astype(jnp.bfloat16),
                     within.astype(jnp.bfloat16),
                     preferred_element_type=jnp.float32).astype(i32)
    lane = jnp.sum(run <= place[..., None], axis=-1, dtype=i32)
    # at most k live: every live position, as they lie
    sel = jnp.where(ts[:, None] < k, listed, block * b + lane)
    return sel, listed <= ts[:, None]


def _per_head(form: str, x, w):
    """``einsum(form, x, w)`` a head a batch: ``x`` rounded to ``w``'s
    dtype, the products in it, float32 accumulation.  On the CPU both are
    first widened to float32 — the same products exactly (a bf16 pair's
    product is a float32), and XLA's CPU runtime has no bf16 x bf16 =
    f32 BATCHED dot outside a loop body (a K-row round's sixteen rows at
    the rehearsal's sizes: "Unsupported element type for DotThunk")."""
    import jax
    import jax.numpy as jnp

    x = x.astype(w.dtype)
    if jax.default_backend() == "cpu":
        x, w = x.astype(jnp.float32), w.astype(jnp.float32)
    return jnp.einsum(form, x, w, preferred_element_type=jnp.float32)


def absorb_queries(qc, qr, w, p: str, d):
    """The absorbed queries ``[N, heads, kv_lora_rank + rope]`` float32:
    ``qA_i = qC_i W_uk,i`` beside ``qR_i``, to be scored against cache
    rows ``(c, kR)`` as they lie."""
    import jax.numpy as jnp

    return jnp.concatenate(
        [_per_head("nhd,hdc->nhc", qc, w[p + "attn_uk"]), qr], axis=-1)


def attend_out(u, w, p: str, d):
    """``concat_i(u_i W_uv,i) W_o`` of the absorbed contexts ``u`` ``[N,
    heads, kv_lora_rank]``: ``[N, d_model]`` float32."""
    o = _per_head("nhc,hcd->nhd", u, w[p + "attn_uv"])
    return linear(o.reshape(u.shape[0], -1), w[p + "attn_o"])


def _blocks(n: int, block: int) -> int:
    from paddle_tpu.decode_attention import divisor_block

    return divisor_block(n, block)


def chunk_select(qi, wi, keys, q_pos, n_keys, top_k: int,
                 key_block: int = 1024):
    """The prefill chunk's selection: for ``C`` queries of ONE row (``qi``
    ``[C, heads, D]``, ``wi`` ``[C, heads]``, at positions ``q_pos``
    ``[C]``, ``< 0``: no query) over that row's index keys ``keys`` ``[T,
    D]`` (the first ``n_keys`` are scored, in whole blocks: no ``[C,
    heads, T]`` is ever held), WHICH positions each query reads: ``[C,
    T]`` bool — the ``min(top_k, q_pos + 1)`` positions ``<= q_pos`` of
    largest score, ties the lowest position first (what
    :func:`select_positions` lists, as a mask)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    c, t = qi.shape[0], keys.shape[0]
    kb = _blocks(t, key_block)

    def body(i, scores):
        block = jax.lax.dynamic_slice(keys, (i * kb, 0), (kb, keys.shape[1]))
        return jax.lax.dynamic_update_slice(
            scores, index_scores(qi, wi, block), (0, i * kb))

    scores = jax.lax.fori_loop(0, (n_keys + kb - 1) // kb, body,
                               jnp.full((c, t), -jnp.inf, f32))
    live = jnp.arange(t)[None, :] <= q_pos[:, None]
    return top_members(scores, live, min(int(top_k), t))[0].reshape(c, t)


def chunk_attend_expanded(qc, qr, rows, member, n_keys, w, p: str, d,
                          key_block: int = 512):
    """The prefill chunk's attend, EXPANDED: ``C`` queries of ONE row
    (``qc`` ``[C, heads, nope]``, ``qr`` ``[C, heads, rope]`` float32)
    against that row's cache rows ``rows`` ``[T, kv_lora_rank + rope]``
    (storage dtype; lanes past them, a leaf's padding, are not read),
    each query reading the positions ``member`` ``[C,
    T]`` names: a ``key_block`` of rows at a time is expanded to its
    heads' keys and values (``c W_uk``, ``c W_uv``) and met with an online
    softmax, so no expanded K/V of the history and no temporary that
    grows with the rung is held.  Returns ``[C, heads * v]`` float32 (a
    query that reads nothing: zeros)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    c, t = qc.shape[0], rows.shape[0]
    kb = _blocks(t, key_block)
    dt = rows.dtype
    uk, uv = w[p + "attn_uk"], w[p + "attn_uv"]
    qcs, qrs = (qc * d.scale).astype(dt), (qr * d.scale).astype(dt)

    def body(i, carry):
        m, l, acc = carry
        at = i * kb
        block = jax.lax.dynamic_slice(rows, (at, 0), (kb, rows.shape[1]))
        lat = block[:, :d.d_c].astype(uk.dtype)
        kc = jnp.einsum("kc,hdc->khd", lat, uk,
                        preferred_element_type=f32).astype(dt)
        vv = jnp.einsum("kc,hcd->khd", lat, uv,
                        preferred_element_type=f32).astype(dt)
        ok = jax.lax.dynamic_slice(member, (0, at), (c, kb))[:, None, :]
        s = (jnp.einsum("chd,khd->chk", qcs, kc, preferred_element_type=f32)
             + jnp.einsum("chr,kr->chk", qrs, block[:, d.d_c:d.d_latent],
                          preferred_element_type=f32))
        s = jnp.where(ok, s, _MASK)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        pr = jnp.exp(s - m_new[..., None]) * ok
        acc = alpha[..., None] * acc + jnp.einsum(
            "chk,khd->chd", pr.astype(dt), vv, preferred_element_type=f32)
        return m_new, alpha * l + pr.sum(axis=-1), acc

    h = d.n_head
    _, l, acc = jax.lax.fori_loop(
        0, (n_keys + kb - 1) // kb, body,
        (jnp.full((c, h), _MASK, f32), jnp.zeros((c, h), f32),
         jnp.zeros((c, h, d.d_v), f32)))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).reshape(c, -1)
