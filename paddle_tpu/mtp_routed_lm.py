"""The parts of a decoder whose blocks are grouped-query attention over
a short SLIDING WINDOW (rotary) or over the whole context (no positions),
then a dense SwiGLU (the leading layers) or a mixture of routed experts
BESIDE A SHARED EXPERT, and which carries a MULTI-TOKEN-PREDICTION module
that drafts for it (``model_type: exaone_moe``).  No bias anywhere; a
block has no norm BEFORE its branches, each branch is closed by one:

    q = RMS_head(h W_q; q_norm), k = RMS_head(h W_k; k_norm), v = h W_v
       window layer: q, k rotated at their positions (half-split over the
                     whole head); the query at p reads keys p - W + 1 .. p
       global layer: no rotary, no position of any kind; keys 0 .. p
    h = h + RMS(softmax(q k^T / sqrt(head_dim)) v W_o; post_attn_norm)
    dense layer:  y = W2 (silu(W1 h) * W3 h)
    sparse layer: s = sigmoid(h W_r)  in float32, over ALL the experts
                  sel = top_k(s + b)           b chooses, it does not weigh
                  g_e = s_e / (sum_{e in sel} s_e + 1e-6) * scaling
                  y = sum_{e in sel, e held here} g_e E_e(h) + E_shared(h)
    h = h + RMS(y; post_ffn_norm)
    logits = RMS(h; final_norm) W_head            (the head is untied)

The module (DeepSeek-V3's form; one of them), with ``h_i`` the last
block's output at position ``i`` and ``t_{i+1}`` the token after it:

    u_i = W_eh [RMS(E[t_{i+1}]; mtp_e_norm) ; RMS(h_i; mtp_h_norm)]
    u_i -> one GLOBAL sparse block with K/V leaves of its own
    logits for t_{i+2} = RMS(.; final_norm) W_head     (the model's own)

``decoding.make_mtp_routed_lm_pooled_step_fn`` strings them into the
slot-pooled step, the K-wide verify that also yields the last block's
hidden states, the module's K-wide pass and the chunked prefill; nothing
here knows a pool or a server.  The expert layer is
``routed_experts.expert_layer`` (``SIGMOID_BIAS`` scoring with a routed
scale, plus the shared term: :func:`dims` says so); the cache is
``decode_attention``'s: a window layer holds a RING leaf of ``window``
rows, a global layer and the module a sequence leaf of the length rung.
Weights are multiplied in the dtype they are given (bf16 as stored); the
router, its sigmoid and the selection run in float32 at "highest";
norms and rotary angles are float32.
"""
from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from paddle_tpu.hybrid_ssm import linear, rms_norm, rotary, swiglu
from paddle_tpu.routed_experts import SIGMOID_BIAS, SILU
from paddle_tpu.windowed_routed_lm import (GLOBAL, GLOBAL_ATTEND_SCOPE,
                                           PREFILL_CHUNK_SCOPE, WINDOW,
                                           WINDOW_ATTEND_SCOPE, chunk_attend)

__all__ = ["dims", "param_shapes", "random_state", "attention_inputs",
           "module_input", "layer_prefix", "MTP_LAYER", "GLOBAL", "WINDOW",
           "WINDOW_ATTEND_SCOPE", "GLOBAL_ATTEND_SCOPE",
           "PREFILL_CHUNK_SCOPE", "MTP_MODULE_SCOPE", "SPEC_VERIFY_SCOPE",
           "FLOAT32_PARAMS", "chunk_attend", "linear", "rms_norm", "rotary",
           "swiglu"]

#: ``jax.named_scope`` names, for the device trace
MTP_MODULE_SCOPE = "mtp_module"
SPEC_VERIFY_SCOPE = "spec_verify"

#: parameters kept float32 whatever the matrices' dtype, by name ending
FLOAT32_PARAMS = ("_norm", "router", "expert_bias")

#: the module's block among the layers' key prefixes
MTP_LAYER = "mtp"

_KINDS = {"sliding_attention": WINDOW, "full_attention": GLOBAL}


def layer_prefix(name: str, i) -> str:
    """The key prefix of layer ``i``'s weights (``MTP_LAYER``: the
    module's block)."""
    return "%s_%s_" % (name, i) if i == MTP_LAYER else "%s_l%d_" % (name, i)


def dims(cfg) -> SimpleNamespace:
    """The block's sizes and scalars from an ``exaone_moe`` config dict
    (the published key names).  ``num_experts`` may count the experts
    HELD here; the router's width is then ``num_experts_all``."""
    kinds = tuple(_KINDS.get(k) for k in cfg["layer_types"])
    dense = tuple(k == "dense" for k in cfg["mlp_layer_types"])
    o = SimpleNamespace(
        vocab=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        n_layer=int(cfg["num_hidden_layers"]), kinds=kinds, dense=dense,
        n_head=int(cfg["num_attention_heads"]),
        n_kv_head=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        d_mlp=int(cfg["intermediate_size"]),
        d_expert=int(cfg["moe_intermediate_size"]),
        n_expert=int(cfg.get("num_experts_all", cfg["num_experts"])),
        top_k=int(cfg["num_experts_per_tok"]),
        n_shared=int(cfg.get("num_shared_experts", 0)),
        window=int(cfg["sliding_window"]),
        eps=float(cfg.get("rms_norm_eps", 1e-5)),
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        norm_topk=bool(cfg.get("norm_topk_prob", True)),
        routed_scale=float(cfg.get("routed_scaling_factor", 1.0)),
        n_mtp=int(cfg.get("num_nextn_predict_layers", 0)),
        # what routed_experts.route / expert_layer read
        scoring=SIGMOID_BIAS, gate_act=SILU, expert_bias=True)
    if (len(kinds) != o.n_layer or len(dense) != o.n_layer
            or None in kinds):
        raise ValueError("layer_types and mlp_layer_types must name "
                         "num_hidden_layers layers, each sliding_attention "
                         "or full_attention, dense or sparse")
    if cfg.get("scoring_func", "sigmoid") != "sigmoid":
        raise ValueError("only scoring_func = sigmoid is supported")
    if int(cfg.get("n_group", 1)) != 1 or int(cfg.get("topk_group", 1)) != 1:
        raise ValueError("group-limited routing is not supported")
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("only hidden_act = silu is supported")
    if cfg.get("tie_word_embeddings", False):
        raise ValueError("a tied head is not supported")
    if o.n_mtp > 1 or (o.n_mtp and tuple(cfg.get(
            "mtp_layer_types", ["full_attention"])) != ("full_attention",)):
        raise ValueError("one full_attention multi-token-prediction module "
                         "is supported, no chain of them")
    if o.n_head % o.n_kv_head:
        raise ValueError("query heads must be a multiple of their KV heads")
    o.d_q, o.d_kv = o.n_head * o.head_dim, o.n_kv_head * o.head_dim
    o.expert_layers = tuple(i for i in range(o.n_layer) if not dense[i])
    o.window_layers = kinds.count(WINDOW)
    return o


def _block_shapes(d, p: str, dense: bool, n_held: int) -> dict:
    out = {p + "attn_q": (d.d_model, d.d_q),
           p + "attn_k": (d.d_model, d.d_kv),
           p + "attn_v": (d.d_model, d.d_kv),
           p + "attn_o": (d.d_q, d.d_model),
           p + "q_norm": (d.head_dim,), p + "k_norm": (d.head_dim,),
           p + "post_attn_norm": (d.d_model,),
           p + "post_ffn_norm": (d.d_model,)}
    if dense:
        out.update({p + "ffn_gate": (d.d_model, d.d_mlp),
                    p + "ffn_up": (d.d_model, d.d_mlp),
                    p + "ffn_down": (d.d_mlp, d.d_model)})
        return out
    out.update({p + "router": (d.d_model, d.n_expert),
                p + "expert_bias": (d.n_expert,),
                p + "experts_w13": (n_held, d.d_model, 2 * d.d_expert),
                p + "experts_w2": (n_held, d.d_expert, d.d_model)})
    if d.n_shared:
        out.update({p + "shared_w13": (d.d_model,
                                       2 * d.n_shared * d.d_expert),
                    p + "shared_w2": (d.n_shared * d.d_expert, d.d_model)})
    return out


def param_shapes(cfg, name: str = "lm", held=None) -> dict:
    """Names and shapes of every weight the step reads: the one place
    the schema lives.  Matrices are ``[in, out]``; an expert layer's gate
    and up matrices are ONE ``[held experts, d_model, 2 * width]`` (gate
    columns first) and its shared expert's ONE ``[d_model, 2 * width]``;
    ``held = (lo, hi)``: the experts whose matrices are held (default
    all); the router and its bias keep their whole width."""
    d = dims(cfg)
    n_held = d.n_expert if held is None else int(held[1]) - int(held[0])
    out = {name + "_emb": (d.vocab, d.d_model),
           name + "_final_norm": (d.d_model,),
           name + "_head": (d.d_model, d.vocab)}
    for i in range(d.n_layer):
        out.update(_block_shapes(d, layer_prefix(name, i), d.dense[i],
                                 n_held))
    if d.n_mtp:
        p = layer_prefix(name, MTP_LAYER)
        out.update(_block_shapes(d, p, False, n_held))
        out.update({p + "e_norm": (d.d_model,), p + "h_norm": (d.d_model,),
                    p + "eh": (2 * d.d_model, d.d_model)})
    return out


def random_state(rng, cfg, name: str = "lm", std: float = 0.02,
                 dtype="float32", bias_range: float = 0.05,
                 held=None) -> dict:
    """Seeded random weights under :func:`param_shapes` (tests, tools):
    normal matrices in ``dtype``, unit norms, a float32 router and an
    ``expert_bias`` uniform in ``+-bias_range`` (NOT zero: a bias that
    leaks into the weights, or is ignored, shows)."""
    import jax.numpy as jnp

    w = {}
    for k, shp in param_shapes(cfg, name, held).items():
        if k.endswith("_norm"):
            w[k] = np.ones(shp, "float32")
        elif k.endswith("expert_bias"):
            w[k] = rng.uniform(-bias_range, bias_range, shp).astype("float32")
        elif k.endswith("router"):
            w[k] = (rng.randn(*shp) * std).astype("float32")
        else:
            w[k] = jnp.asarray((rng.randn(*shp) * std).astype("float32"),
                               dtype)
    return w


def attention_inputs(x, w, p: str, kind: int, pos, d):
    """``(q [N, n_head, Dh], k [N, n_kv_head, Dh], v [N, d_kv])`` of the
    rows ``x`` (the residual itself: no norm before the branch) at
    positions ``pos``: q and k normed per head, then rotated in a window
    layer, bare in a global one.

    Both projections are COMPLETE before the per-head norm sees them
    (the barrier): fused with the norm's sum of squares the product is
    laid rows-minor, which reads its matrix the other way round, and the
    compiler then copies ``attn_q`` and ``attn_k`` whole inside the
    program (seen in the compiled round of ``k_exaone_236b_a23b``: four
    copies of a 100 MB ``bf16[8192,6144]`` and four of a
    ``bf16[1024,6144]`` a round, 1.4 ms of 21.6, in every layer fed by a
    block before it; PR 59).  Alone, the product reads both as stored."""
    import jax

    n = x.shape[0]
    q, k = jax.lax.optimization_barrier(
        (linear(x, w[p + "attn_q"]), linear(x, w[p + "attn_k"])))
    q = rms_norm(q.reshape(n, d.n_head, d.head_dim), w[p + "q_norm"], d.eps)
    k = rms_norm(k.reshape(n, d.n_kv_head, d.head_dim), w[p + "k_norm"],
                 d.eps)
    if kind == WINDOW:
        q, k = rotary(q, pos, d.rope_theta), rotary(k, pos, d.rope_theta)
    return q, k, linear(x, w[p + "attn_v"])


def module_input(hidden, emb_rows, w, p: str, d):
    """``u = W_eh [RMS(E[t_{i+1}]; e_norm) ; RMS(h_i; h_norm)]`` for rows
    ``hidden`` ``[N, d_model]`` (the last block's output at position
    ``i``) and ``emb_rows`` ``[N, d_model]`` (the embedding of the token
    at ``i + 1``); ``[N, d_model]`` float32."""
    import jax.numpy as jnp

    both = jnp.concatenate(
        [rms_norm(emb_rows, w[p + "e_norm"], d.eps),
         rms_norm(hidden, w[p + "h_norm"], d.eps)], axis=-1)
    return linear(both, w[p + "eh"])
