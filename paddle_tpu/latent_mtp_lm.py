"""The parts of a decoder whose every block is multi-head LATENT
attention read DENSELY (every live position; no indexer), each branch
between TWO norms, then a dense SwiGLU (the leading layers) or routed
experts beside a shared expert under an UNGROUPED, bias-free sigmoid
router, and which carries ONE multi-token-prediction module that drafts
for it (``model_type: pangu_ultra_moe``, ``sandwich_norm: true``).  No
bias anywhere:

    x = RMS(h; input_norm)
    queries, cached row, expanded and absorbed forms: as
        ``paddle_tpu.latent_sparse_lm`` (its ``latent_inputs``,
        ``absorb_queries``, ``attend_out``, ``chunk_attend_expanded``,
        ``rotate`` ARE the parts; nothing is copied), with plain rotary
        (no ``rope_scaling``: ``yarn_inv_freq`` gives ``theta^(-2i/lanes)``,
        ``softmax_scale`` gives ``(nope + rope)^-0.5``) and the read over
        EVERY position ``<= t``
    h = h + RMS(o; post_attn_norm)
    f = RMS(h; pre_mlp_norm)
    dense layer:  y = W2 (silu(W1 f) * W3 f)
    sparse layer: s = sigmoid(f W_r)  in float32, over ALL the experts
                  sel = top_k(s)            no bias, ONE group
                  g_e = s_e / (sum_{e in sel} s_e + 1e-6) * scaling
                  y = sum_{e in sel, e held here} g_e E_e(f) + E_shared(f)
    h = h + RMS(y; post_mlp_norm)
    logits = RMS(h; final_norm) W_head            (the head is untied)

The module (DeepSeek-V3's form; ``mtp_routed_lm.module_input`` IS its
input), with ``h_i`` the last block's output at position ``i`` and
``t_{i+1}`` the token after it:

    u_i = W_eh [RMS(E[t_{i+1}]; mtp_e_norm) ; RMS(h_i; mtp_h_norm)]
    u_i -> one sandwich-normed SPARSE block with a latent leaf of its own
    logits for t_{i+2} = RMS(.; final_norm) W_head     (the model's own)

``decoding.make_latent_mtp_lm_pooled_step_fn`` strings them into the
slot-pooled step, the K-wide verify that also yields the last block's
hidden states, the module's K-wide pass and the chunked prefill; nothing
here knows a pool or a server.  The cache is ``decode_attention``'s
latent leaf ALONE (no index key) a layer and one for the module, read by
``decode_attention.dense_latent_attention``.  Weights are multiplied in
the dtype they are given (bf16 as stored), accumulated in float32; the
router, norms and rotary angles are float32.
"""
from __future__ import annotations

from types import SimpleNamespace

from paddle_tpu.latent_sparse_lm import (LATENT_ATTEND_SCOPE,
                                         LATENT_PROJECT_SCOPE,
                                         PREFILL_CHUNK_SCOPE, absorb_queries,
                                         attend_out, chunk_attend_expanded,
                                         latent_dims, latent_inputs, linear,
                                         rms_norm, swiglu)
from paddle_tpu.mtp_routed_lm import (MTP_LAYER, MTP_MODULE_SCOPE,
                                      SPEC_VERIFY_SCOPE, layer_prefix,
                                      module_input)

__all__ = ["dims", "param_shapes", "random_state", "close_attention",
           "ffn_branch", "latent_inputs", "absorb_queries", "attend_out",
           "chunk_attend_expanded", "module_input", "layer_prefix",
           "MTP_LAYER", "LATENT_PROJECT_SCOPE", "LATENT_ATTEND_SCOPE",
           "PREFILL_CHUNK_SCOPE", "MTP_MODULE_SCOPE", "SPEC_VERIFY_SCOPE",
           "FLOAT32_PARAMS", "linear", "rms_norm", "swiglu"]

#: parameters kept float32 whatever the matrices' dtype, by name ending
FLOAT32_PARAMS = ("_norm", "router")


def dims(cfg) -> SimpleNamespace:
    """The block's sizes and scalars from a ``pangu_ultra_moe`` config
    dict (the published key names): ``latent_sparse_lm.latent_dims`` with
    the router ungrouped and bias-free, and the module's count."""
    o = latent_dims(cfg)
    o.expert_bias = False           # what routed_experts.route reads
    o.n_mtp = int(cfg.get("num_nextn_predict_layers", 0))
    if not cfg.get("sandwich_norm", False):
        raise ValueError("every branch is closed by a second norm: "
                         "sandwich_norm must be true")
    if o.n_group != 1 or o.topk_group != 1:
        raise ValueError("the router is ungrouped: n_group and topk_group "
                         "must be 1")
    if cfg.get("rope_scaling"):
        raise ValueError("plain rotary: rope_scaling is not supported")
    if o.q_rank is None:
        raise ValueError("the queries pass a low rank: q_lora_rank must be "
                         "given")
    if o.n_mtp > 1:
        raise ValueError("one multi-token-prediction module is supported, "
                         "no chain of them")
    return o


def _block_shapes(d, p: str, dense: bool, n_held: int) -> dict:
    out = {p + "input_norm": (d.d_model,),
           p + "post_attn_norm": (d.d_model,),
           p + "pre_mlp_norm": (d.d_model,),
           p + "post_mlp_norm": (d.d_model,),
           p + "attn_q_a": (d.d_model, d.q_rank),
           p + "q_a_norm": (d.q_rank,),
           p + "attn_q_b": (d.q_rank, d.n_head * d.d_qk),
           p + "attn_kv_a": (d.d_model, d.d_latent),
           p + "kv_a_norm": (d.d_c,),
           p + "attn_uk": (d.n_head, d.d_nope, d.d_c),
           p + "attn_uv": (d.n_head, d.d_c, d.d_v),
           p + "attn_o": (d.n_head * d.d_v, d.d_model)}
    if dense:
        out.update({p + "ffn_gate": (d.d_model, d.d_mlp),
                    p + "ffn_up": (d.d_model, d.d_mlp),
                    p + "ffn_down": (d.d_mlp, d.d_model)})
        return out
    out.update({p + "router": (d.d_model, d.n_expert),
                p + "experts_w13": (n_held, d.d_model, 2 * d.d_expert),
                p + "experts_w2": (n_held, d.d_expert, d.d_model)})
    if d.n_shared:
        out.update({p + "shared_w13": (d.d_model,
                                       2 * d.n_shared * d.d_expert),
                    p + "shared_w2": (d.n_shared * d.d_expert, d.d_model)})
    return out


def param_shapes(cfg, name: str = "lm", held=None) -> dict:
    """Names and shapes of every weight the step reads: the one place
    the schema lives.  As ``latent_sparse_lm.param_shapes`` (matrices
    ``[in, out]``, the latent's up projections a head a batch, an expert
    layer's gate and up matrices ONE ``[held experts, d_model, 2 *
    width]``) without an indexer or a selection bias, with four norms a
    block, and the module's block under ``layer_prefix(name,
    MTP_LAYER)`` beside its two norms and ``eh`` ``[2 * d_model,
    d_model]``."""
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
                 dtype="float32", held=None) -> dict:
    """Seeded random weights under :func:`param_shapes` (tests, tools):
    normal matrices in ``dtype``, norm weights near 1 (a norm that is
    skipped shows) and a float32 router."""
    import jax.numpy as jnp

    w = {}
    for k, shp in param_shapes(cfg, name, held).items():
        if k.endswith("_norm"):
            w[k] = (1.0 + 0.1 * rng.randn(*shp)).astype("float32")
        elif k.endswith("router"):
            w[k] = (rng.randn(*shp) * std).astype("float32")
        else:
            w[k] = jnp.asarray((rng.randn(*shp) * std).astype("float32"),
                               dtype)
    return w


def close_attention(h, o, w, p: str, d):
    """The residual around the attention branch's output ``o`` ``[N,
    d_model]``, closed by its second norm: ``h + RMS(o; post_attn_norm)``.

    ``o`` — the ``attn_o`` product — is COMPLETE before the norm sees it
    (the barrier): fused with the norm's sum of squares that product
    streamed its ``bf16[16384,7680]`` at 661 GB/s in the compiled round of
    ``openpangu_ultra_moe_718b``, alone at 734 (0.381 -> 0.343 ms a
    block: PR 64).  The FFN's down products under ``post_mlp_norm`` and
    the module's ``eh`` read the same either way on the chip and keep
    their fusion (:func:`ffn_branch`)."""
    import jax

    return h + rms_norm(jax.lax.optimization_barrier(o),
                        w[p + "post_attn_norm"], d.eps)


def ffn_branch(h, w, p: str, dense: bool, ts, d, held=None):
    """The FFN branch between its two norms over the rows ``h`` ``[N,
    d_model]`` (``ts`` ``[N]``, ``< 0``: an idle row, routed nowhere):
    ``(h + RMS(FFN(RMS(h; pre_mlp_norm)); post_mlp_norm), stats)`` —
    ``stats`` the expert layer's counts, None for a dense layer.  The
    router reads the FFN's own normed input."""
    from paddle_tpu import routed_experts as rx

    f = rms_norm(h, w[p + "pre_mlp_norm"], d.eps)
    if dense:
        y, st = swiglu(f, w[p + "ffn_gate"], w[p + "ffn_up"],
                       w[p + "ffn_down"], 1.0, 1.0), None
    else:
        y, st = rx.expert_layer(f, w, p, ts, d, held)
    return h + rms_norm(y, w[p + "post_mlp_norm"], d.eps), st
