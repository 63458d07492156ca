"""The parts of a decoder whose layers are KIMI DELTA ATTENTION (a gated
delta rule whose decay is one factor a key CHANNEL; a recurrent state and
a conv window a slot) or multi-head LATENT attention read DENSELY and
position-free (one compressed row a position, no rotary on either side),
pre-norm, the leading layer's FFN a dense SwiGLU and every other layer's
routed experts beside a shared expert (``model_type: kimi_linear``; Kimi
Linear, arXiv:2510.26692).  Eps 1e-5, a final RMSNorm, an untied head, no
bias anywhere::

    h = x + mixer(RMS(x; mixer_norm));   y = h + ffn(RMS(h; ffn_norm))

    K layer: ``delta_hybrid_lm.kda_layer_step`` (a token a row) and
        ``kda_layer_chunk`` (C positions of one row: the rule's
        CHUNKWISE form), beta = sigmoid(W_b x) in (0, 1)
    M layer: q = x W_q -> [heads, nope + shared] = (qC, qR)     ONE matrix:
                                                   ``q_lora_rank`` null
             [c' ; kR] = x W_kva;  c = RMS(c'; kv_a_norm)
             the cached row a position is (c, kR); NO rotary
                                                   (``mla_use_nope``)
             kC_i = W_uk,i c;  v_i = W_uv,i c
             a_t,s,i = (nope + shared)^-0.5 (qC_t,i . kC_s,i + qR_t,i . kR_s)
             o_t = concat_i(sum_{s <= t} softmax_s(a_t,s,i) v_s,i) W_o
        ``latent_sparse_lm.latent_inputs`` / ``absorb_queries`` /
        ``attend_out`` / ``chunk_attend_expanded`` ARE the parts: absorbed
        in the step, expanded in the prefill
    FFN: layer 0 ``W2 (silu(W1 f) * W3 f)``; every other layer
        ``routed_experts.expert_layer``: sigmoid scores over all the
        experts in float32 + a bias that chooses, ONE group, the chosen
        normalised, x ``routed_scaling_factor``, + the shared expert

Nothing is copied: this file holds the sizes (:func:`dims`: the two
modules' own, side by side) and the schema (:func:`param_shapes`).
``decoding.make_kda_latent_lm_pooled_step_fn`` strings the parts into the
slot-pooled step and the chunked prefill; nothing here knows a pool or a
server.
"""
from __future__ import annotations

from types import SimpleNamespace

from paddle_tpu import delta_hybrid_lm as dh
from paddle_tpu import latent_sparse_lm as ls

__all__ = ["KDA", "LATENT", "FLOAT32_PARAMS", "dims", "param_shapes",
           "random_state"]

#: a layer's kind, as ``delta_hybrid_lm.kda_dims`` names the two
KDA, LATENT = dh.LINEAR, dh.FULL

#: parameters kept float32 whatever the matrices' dtype, by name ending
FLOAT32_PARAMS = dh.KDA_FLOAT32_PARAMS


def dims(cfg) -> SimpleNamespace:
    """The decoder's sizes from a ``kimi_linear`` config dict (the
    published key names): ``delta_hybrid_lm.kda_dims`` (the KDA layers,
    the layers' kinds, the FFNs and what ``routed_experts`` reads) beside
    ``latent_sparse_lm.latent_attention_dims`` (the layers that are not
    KDA)."""
    o = dh.kda_dims(cfg)
    vars(o).update(vars(ls.latent_attention_dims(cfg)))
    if cfg.get("moe_router_activation_func", "sigmoid") != "sigmoid":
        raise ValueError("only moe_router_activation_func = sigmoid is "
                         "supported")
    if (int(cfg.get("num_expert_group", 1)) != 1
            or int(cfg.get("topk_group", 1)) != 1):
        raise ValueError("the router is ungrouped: num_expert_group and "
                         "topk_group must be 1")
    if int(cfg.get("moe_layer_freq", 1)) != 1:
        raise ValueError("only moe_layer_freq = 1 is supported")
    if int(cfg.get("num_nextn_predict_layers", 0)):
        raise ValueError("a multi-token-prediction module is not held: "
                         "num_nextn_predict_layers must be 0")
    if o.q_rank is not None:
        raise ValueError("ONE query matrix: q_lora_rank must be null")
    if o.neg_eigval:
        raise ValueError("beta stays in (0, 1): no key of this release "
                         "asks for the factor 2")
    return o


def param_shapes(cfg, name: str = "lm", held=None) -> dict:
    """Names and shapes of every weight the step reads: the one place
    the schema lives.  A K layer's as ``delta_hybrid_lm.kda_mixer_shapes``,
    an M layer's ``attn_q`` ``[d_model, heads * (nope + shared)]``,
    ``attn_kv_a``, ``kv_a_norm`` and the latent's up projections a head a
    batch as ``latent_sparse_lm.param_shapes`` lays them, a layer's FFN
    as ``delta_hybrid_lm.kda_ffn_shapes``; ``held = (lo, hi)``: the
    experts whose matrices are held (default all)."""
    d = dims(cfg)
    n_held = d.n_expert if held is None else int(held[1]) - int(held[0])
    out = {name + "_emb": (d.vocab, d.d_model),
           name + "_final_norm": (d.d_model,),
           name + "_head": (d.d_model, d.vocab)}
    for i, kind in enumerate(d.kinds):
        p = "%s_l%d_" % (name, i)
        if kind == KDA:
            out.update(dh.kda_mixer_shapes(d, p))
        else:
            out.update({
                p + "attn_q": (d.d_model, d.n_head * d.d_qk),
                p + "attn_kv_a": (d.d_model, d.d_latent),
                p + "kv_a_norm": (d.d_c,),
                p + "attn_uk": (d.n_head, d.d_nope, d.d_c),
                p + "attn_uv": (d.n_head, d.d_c, d.d_v),
                p + "attn_o": (d.n_head * d.d_v, d.d_model)})
        out.update({p + "mixer_norm": (d.d_model,),
                    p + "ffn_norm": (d.d_model,)})
        out.update(dh.kda_ffn_shapes(d, p, d.dense[i], n_held))
    return out


def random_state(rng, cfg, name: str = "lm", std: float = 0.02,
                 dtype="float32", held=None, gate_std: float = 1.0,
                 bias_range: float = 0.05) -> dict:
    """Seeded random weights under :func:`param_shapes` (tests, tools):
    ``delta_hybrid_lm.kda_random_state``'s rules over this schema."""
    return dh.kda_random_weights(rng, param_shapes(cfg, name, held), std,
                                 dtype, gate_std, bias_range)
