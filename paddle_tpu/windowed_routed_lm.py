"""The parts of a decoder whose every block is grouped-query attention —
over a SLIDING WINDOW with rotary positions, or over the whole context
with NO positions at all — then a mixture of routed experts chosen by a
router that reads the block's input BEFORE attention (``model_name:
smallthinker_*``).  Pre-norm, no bias anywhere:

    r = RMS(h; input_norm)
    router:    z = r W_r  in float32, over ALL the experts
               sel = top_k(z);  g = softmax(z[sel])  over the chosen
               (norm_topk_prob: g / sum g, a no-op after it, kept)
    attention: q, k, v = r W_q, r W_k, r W_v;  scores / sqrt(head_dim)
       window layer: q, k rotated at their positions (half-split over the
                     whole head); the query at p reads keys p - W + 1 .. p
       global layer: no rotary, no position of any kind; keys 0 .. p
    h = h + softmax(q k^T) v W_o;   f = RMS(h; ffn_norm)
    h = h + sum_{e in sel, e held here} g_e W2_e(relu(W1_e f) * W3_e f)
    logits = RMS(h; final_norm) W_head            (the head is untied)

``decoding.make_windowed_routed_lm_pooled_step_fn`` strings them into
the slot-pooled step and the chunked prefill; nothing here knows a pool
or a server.  The expert layer is ``routed_experts.expert_layer`` (the
scoring, the router's input and the gate's activation are what differ
from ``lfm2_moe``, and :func:`dims` says so); the cache is
``decode_attention``'s: a window layer holds a RING leaf of ``window``
rows, a global layer a sequence leaf of the length rung.  Weights are
multiplied in the dtype they are given (bf16 as stored); the router and
its softmax run in float32 at "highest"; norms and rotary angles are
float32.
"""
from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from paddle_tpu.hybrid_ssm import linear, rms_norm, rotary
from paddle_tpu.routed_experts import RELU, SOFTMAX_CHOSEN

__all__ = ["dims", "param_shapes", "random_state", "attention_inputs",
           "chunk_attend", "GLOBAL", "WINDOW", "WINDOW_ATTEND_SCOPE",
           "GLOBAL_ATTEND_SCOPE", "PREFILL_CHUNK_SCOPE", "FLOAT32_PARAMS",
           "linear", "rms_norm", "rotary"]

#: a layer's entry in ``sliding_window_layout`` / ``rope_layout``
GLOBAL, WINDOW = 0, 1

#: ``jax.named_scope`` names, for the device trace
WINDOW_ATTEND_SCOPE = "window_attend"
GLOBAL_ATTEND_SCOPE = "global_attend"
PREFILL_CHUNK_SCOPE = "prefill_chunk"

#: parameters kept float32 whatever the matrices' dtype, by name ending
FLOAT32_PARAMS = ("_norm", "router")


def dims(cfg) -> SimpleNamespace:
    """The block's sizes and scalars from a ``smallthinker`` config dict
    (the published key names)."""
    kinds = tuple(int(x) for x in cfg["sliding_window_layout"])
    o = SimpleNamespace(
        vocab=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        n_layer=int(cfg["num_hidden_layers"]), kinds=kinds,
        n_head=int(cfg["num_attention_heads"]),
        n_kv_head=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        d_expert=int(cfg["moe_ffn_hidden_size"]),
        n_expert=int(cfg["moe_num_primary_experts"]),
        top_k=int(cfg["moe_num_active_primary_experts"]),
        window=int(cfg["sliding_window_size"]),
        eps=float(cfg.get("rms_norm_eps", 1e-6)),
        rope_theta=float(cfg["rope_theta"]),
        norm_topk=bool(cfg.get("norm_topk_prob", True)),
        # what routed_experts.route / expert_layer read
        scoring=SOFTMAX_CHOSEN, gate_act=RELU, routed_scale=1.0,
        expert_bias=False)
    if len(kinds) != o.n_layer or set(kinds) - {GLOBAL, WINDOW}:
        raise ValueError("sliding_window_layout must name num_hidden_layers "
                         "layers, each %d (global) or %d (window)"
                         % (GLOBAL, WINDOW))
    if tuple(int(x) for x in cfg["rope_layout"]) != kinds:
        raise ValueError("rope_layout must equal sliding_window_layout: a "
                         "window layer is rotated, a global layer has no "
                         "positions")
    if not cfg.get("moe_primary_router_apply_softmax", False):
        raise ValueError("only moe_primary_router_apply_softmax = true is "
                         "supported")
    if cfg.get("rope_scaling") is not None:
        raise ValueError("rope_scaling is not supported")
    if cfg.get("tie_word_embeddings", False):
        raise ValueError("a tied head is not supported")
    if o.n_head % o.n_kv_head:
        raise ValueError("query heads must be a multiple of their KV heads")
    o.d_q, o.d_kv = o.n_head * o.head_dim, o.n_kv_head * o.head_dim
    o.expert_layers = tuple(range(o.n_layer))
    o.window_layers = kinds.count(WINDOW)
    return o


def param_shapes(cfg, name: str = "lm") -> dict:
    """Names and shapes of every weight the step reads: the one place
    the schema lives.  Matrices are ``[in, out]``; an expert layer's gate
    and up matrices are ONE ``[experts, d_model, 2 * width]`` (gate
    columns first), as ``routed_experts.param_shapes`` keeps them."""
    d = dims(cfg)
    out = {name + "_emb": (d.vocab, d.d_model),
           name + "_final_norm": (d.d_model,),
           name + "_head": (d.d_model, d.vocab)}
    for i in range(d.n_layer):
        p = "%s_l%d_" % (name, i)
        out.update({p + "input_norm": (d.d_model,),
                    p + "ffn_norm": (d.d_model,),
                    p + "attn_q": (d.d_model, d.d_q),
                    p + "attn_k": (d.d_model, d.d_kv),
                    p + "attn_v": (d.d_model, d.d_kv),
                    p + "attn_o": (d.d_q, d.d_model),
                    p + "router": (d.d_model, d.n_expert),
                    p + "experts_w13": (d.n_expert, d.d_model,
                                        2 * d.d_expert),
                    p + "experts_w2": (d.n_expert, d.d_expert, d.d_model)})
    return out


def random_state(rng, cfg, name: str = "lm", std: float = 0.02,
                 dtype="float32") -> dict:
    """Seeded random weights under :func:`param_shapes` (tests, tools):
    normal matrices in ``dtype``, unit norms, a float32 router."""
    import jax.numpy as jnp

    w = {}
    for k, shp in param_shapes(cfg, name).items():
        if k.endswith("_norm"):
            w[k] = np.ones(shp, "float32")
        elif k.endswith("router"):
            w[k] = (rng.randn(*shp) * std).astype("float32")
        else:
            w[k] = jnp.asarray((rng.randn(*shp) * std).astype("float32"),
                               dtype)
    return w


def attention_inputs(r, w, p: str, kind: int, pos, d):
    """``(q [N, n_head, Dh], k [N, n_kv_head, Dh], v [N, d_kv])`` of the
    normed rows ``r`` at positions ``pos``: rotated in a window layer,
    bare in a global one."""
    n = r.shape[0]
    q = linear(r, w[p + "attn_q"]).reshape(n, d.n_head, d.head_dim)
    k = linear(r, w[p + "attn_k"]).reshape(n, d.n_kv_head, d.head_dim)
    if kind == WINDOW:
        q, k = rotary(q, pos, d.rope_theta), rotary(k, pos, d.rope_theta)
    return q, k, linear(r, w[p + "attn_v"])


def chunk_attend(q, keys, vals, q_pos, key_pos, n_keys, d, window=None,
                 key_block: int = 2048):
    """The prefill chunk's attend: ``C`` queries of ONE row against that
    row's keys, a ``key_block`` at a time with an online softmax, so that
    no temporary grows with the rung.

    ``q`` ``[C, n_head, Dh]`` fp32; ``keys``, ``vals`` ``[N, d_kv]`` in
    the storage dtype; ``q_pos`` ``[C]`` the queries' positions (``< 0``:
    no query); ``key_pos`` ``[N]`` the position each key row holds (``<
    0``: nothing); ``n_keys`` the rows worth reading (the first
    ``n_keys`` are scored, in whole blocks).  A query reads the keys at
    positions ``<=`` its own and, with ``window``, less than ``window``
    behind it.  Returns ``[C, n_head * Dh]`` fp32."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    c = q.shape[0]
    g, rep, dh = d.n_kv_head, d.n_head // d.n_kv_head, d.head_dim
    n = keys.shape[0]
    kb = min(int(key_block), n)
    while n % kb:
        kb -= 1                        # tiny test rungs: a divisor
    dt = keys.dtype
    qs = (q / np.sqrt(dh).astype("float32")).astype(dt).reshape(c, g, rep, dh)

    def body(i, carry):
        m, l, acc = carry
        at = i * kb
        kk = jax.lax.dynamic_slice(keys, (at, 0), (kb, g * dh)).reshape(
            kb, g, dh)
        vv = jax.lax.dynamic_slice(vals, (at, 0), (kb, g * dh)).reshape(
            kb, g, dh)
        kp = jax.lax.dynamic_slice(key_pos, (at,), (kb,))
        ok = (kp[None, :] >= 0) & (kp[None, :] <= q_pos[:, None])
        if window is not None:
            ok = ok & (q_pos[:, None] - kp[None, :] < window)
        ok = ok[:, None, None, :]                           # [C, 1, 1, kb]
        s = jnp.einsum("cgrd,kgd->cgrk", qs, kk, preferred_element_type=f32)
        s = jnp.where(ok, s, -1e30)
        m_new = jnp.maximum(m, s.max(axis=-1))
        alpha = jnp.exp(m - m_new)
        pr = jnp.exp(s - m_new[..., None]) * ok
        acc = alpha[..., None] * acc + jnp.einsum(
            "cgrk,kgd->cgrd", pr.astype(dt), vv, preferred_element_type=f32)
        return m_new, alpha * l + pr.sum(axis=-1), acc

    m0 = jnp.full((c, g, rep), -1e30, f32)
    _, l, acc = jax.lax.fori_loop(
        0, (n_keys + kb - 1) // kb, body,
        (m0, jnp.zeros((c, g, rep), f32), jnp.zeros((c, g, rep, dh), f32)))
    return (acc / jnp.maximum(l, 1e-30)[..., None]).reshape(c, -1)
