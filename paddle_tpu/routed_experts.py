"""The parts of a decoder whose blocks are a gated short convolution or
grouped-query attention, then a dense SwiGLU or a mixture of routed
experts (``model_type: lfm2_moe``), as small functions of ONE token per
row.  Pre-norm, no bias anywhere:

    r = RMS(h; operator_norm)
    conv layer:  [B | C | x] = r W_in;  u = B * x
                 y_t = sum_j w[j] * u_{t - (L - 1) + j}    (depthwise, causal)
                 o = (C * y) W_out           state a row: the last L - 1 u
    attn layer:  q, k per-head RMS-normed, rotary, causal softmax, W_o
    h = h + o;   f = RMS(h; ffn_norm)
    dense layer:  h = h + W2 (silu(W1 f) * W3 f)
    expert layer: s = sigmoid(f W_r)  in float32, over ALL the experts
                  sel = top_k(s + b)            b chooses, it does not weigh
                  g_e = s_e / (sum_{e in sel} s_e + 1e-6) * scaling
                  h = h + sum_{e in sel, e held here} g_e W2_e(silu(W1_e f) * W3_e f)
    group-limited (``d.n_group`` > 1; ``paddle_tpu.latent_sparse_lm``):
                  the experts are n_group consecutive groups; a group is
                  scored by the sum of its two largest s + b, and top_k
                  runs inside the d.topk_group best groups alone

``decoding.make_routed_conv_lm_pooled_step_fn`` strings them into the
slot-pooled step; nothing here knows a pool or a server.  Weights are
multiplied in the dtype they are given (bf16 as stored); the router, its
sigmoid and the selection run in float32 (the router's matrix is kept
float32 and multiplied at precision "highest": a bf16 router picks other
experts); norms, rotary angles and the conv state are float32.

What differs between the families that share the expert layer is read
from ``d`` (each family's ``dims`` sets it from its configuration):
``d.scoring`` — :data:`SIGMOID_BIAS` (above) or :data:`SOFTMAX_CHOSEN`
(``sel = top_k(z)`` on the LOGITS, ``g = softmax(z[sel])`` over the
chosen alone; ``paddle_tpu.windowed_routed_lm``) — and ``d.gate_act``,
the gate's activation (:data:`SILU` | :data:`RELU`); the router may read
another input than the experts do (``expert_layer(..., router_input=)``:
a router placed before attention).

An expert layer is told which experts it HOLDS (``held``: a contiguous
range ``(lo, hi)`` of the published count): it routes over all of them,
computes what its own give for the rows routed to them and adds nothing
for the rest — the part of the result one chip of an expert-parallel
deployment computes.  The shares of disjoint ranges add up to the whole
layer (tests/test_routed_experts.py).  The experts' product is GROUPED:
the (row, choice) pairs sorted by expert, each expert's rows against its
own matrices once (``paddle_tpu.grouped_matmul``), never every row
against every expert.

A layer may also have a SHARED expert (``d.n_shared`` > 0;
``paddle_tpu.mtp_routed_lm``): a gated FFN of the same width that EVERY
row takes, unweighed, added after the routed sum — ``shared_w13``
``[d_model, 2 * n_shared * width]`` and ``shared_w2`` beside the routed
matrices (:func:`shared_expert`).  Every chip of an expert-parallel
deployment holds it and computes the same, so where shares are summed it
counts ONCE: ``expert_layer(..., shared=False)`` gives a share's routed
part alone.
"""
from __future__ import annotations

from types import SimpleNamespace

import numpy as np

from paddle_tpu.hybrid_ssm import (linear, rms_norm, rotary, starts_fresh,
                                   swiglu)

__all__ = ["dims", "param_shapes", "random_state", "route", "dispatch",
           "expert_layer", "shared_expert", "short_conv_step", "CONV",
           "ATTENTION", "ROUTE_SCOPE", "EXPERTS_SCOPE", "SHARED_EXPERT_SCOPE",
           "SHORT_CONV_SCOPE", "STAT_NAMES",
           "SIGMOID_BIAS", "SOFTMAX_CHOSEN", "SILU", "RELU",
           "linear", "rms_norm", "rotary", "swiglu", "starts_fresh"]

CONV, ATTENTION = "conv", "full_attention"

#: ``jax.named_scope`` names, for the device trace
ROUTE_SCOPE = "moe_route"
EXPERTS_SCOPE = "moe_experts"
SHARED_EXPERT_SCOPE = "shared_expert"
SHORT_CONV_SCOPE = "short_conv"

#: what :func:`expert_layer` counts of one step, in this order: (row,
#: choice) pairs of live rows routed to a held expert; held experts that
#: got at least one; the largest group; 1 if any row was live
STAT_NAMES = ("assignments", "experts_touched", "peak_load", "layer_steps")

#: ``d.scoring``: how the router's logits become a choice and its weights
SIGMOID_BIAS, SOFTMAX_CHOSEN = "sigmoid_bias", "softmax_chosen"
#: ``d.gate_act``: the activation of an expert's gate
SILU, RELU = "silu", "relu"

_WEIGHT_SUM_EPS = 1e-6


def dims(cfg) -> SimpleNamespace:
    """The block's sizes and scalars from an ``lfm2_moe`` config dict
    (the published key names)."""
    kinds = tuple(cfg["layer_types"])
    o = SimpleNamespace(
        vocab=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        n_layer=int(cfg["num_hidden_layers"]), kinds=kinds,
        n_dense=int(cfg["num_dense_layers"]),
        n_head=int(cfg["num_attention_heads"]),
        n_kv_head=int(cfg["num_key_value_heads"]),
        d_mlp=int(cfg["intermediate_size"]),
        d_expert=int(cfg["moe_intermediate_size"]),
        n_expert=int(cfg["num_experts"]),
        top_k=int(cfg["num_experts_per_tok"]),
        conv_len=int(cfg["conv_L_cache"]),
        eps=float(cfg.get("norm_eps", 1e-5)),
        rope_theta=float(cfg["rope_parameters"]["rope_theta"]),
        norm_topk=bool(cfg.get("norm_topk_prob", True)),
        routed_scale=float(cfg.get("routed_scaling_factor", 1.0)),
        expert_bias=bool(cfg.get("use_expert_bias", True)),
        scoring=SIGMOID_BIAS, gate_act=SILU)
    if len(kinds) != o.n_layer or set(kinds) - {CONV, ATTENTION}:
        raise ValueError("layer_types must name num_hidden_layers layers, "
                         "each %r or %r" % (CONV, ATTENTION))
    if cfg.get("conv_bias", False):
        raise ValueError("conv_bias is not supported")
    if o.n_head % o.n_kv_head or o.d_model % o.n_head:
        raise ValueError("heads must divide the width and their KV heads")
    o.head_dim = int(cfg.get("head_dim") or o.d_model // o.n_head)
    o.d_kv = o.n_kv_head * o.head_dim
    o.expert_layers = tuple(i for i in range(o.n_layer) if i >= o.n_dense)
    return o


def param_shapes(cfg, name: str = "lm") -> dict:
    """Names and shapes of every weight the step reads: the one place
    the schema lives.  Matrices are ``[in, out]``; the head is the
    embedding (tied); the depthwise conv kernel is ``[conv_L_cache,
    channels]``, oldest tap first; an expert layer's gate and up
    matrices are ONE ``[experts, d_model, 2 * width]`` (gate columns
    first) so that they are one grouped product, stored as multiplied."""
    d = dims(cfg)
    out = {name + "_emb": (d.vocab, d.d_model),
           name + "_embedding_norm": (d.d_model,)}
    for i, kind in enumerate(d.kinds):
        p = "%s_l%d_" % (name, i)
        out.update({p + "operator_norm": (d.d_model,),
                    p + "ffn_norm": (d.d_model,)})
        if kind == CONV:
            out.update({p + "conv_in": (d.d_model, 3 * d.d_model),
                        p + "conv_w": (d.conv_len, d.d_model),
                        p + "conv_out": (d.d_model, d.d_model)})
        else:
            out.update({p + "attn_q": (d.d_model, d.n_head * d.head_dim),
                        p + "attn_k": (d.d_model, d.d_kv),
                        p + "attn_v": (d.d_model, d.d_kv),
                        p + "attn_o": (d.n_head * d.head_dim, d.d_model),
                        p + "q_layernorm": (d.head_dim,),
                        p + "k_layernorm": (d.head_dim,)})
        if i < d.n_dense:
            out.update({p + "ffn_gate": (d.d_model, d.d_mlp),
                        p + "ffn_up": (d.d_model, d.d_mlp),
                        p + "ffn_down": (d.d_mlp, d.d_model)})
        else:
            out.update({p + "router": (d.d_model, d.n_expert),
                        p + "expert_bias": (d.n_expert,),
                        p + "experts_w13": (d.n_expert, d.d_model,
                                            2 * d.d_expert),
                        p + "experts_w2": (d.n_expert, d.d_expert,
                                           d.d_model)})
    return out


#: parameters kept float32 whatever the matrices' dtype, by name ending
FLOAT32_PARAMS = ("_norm", "_layernorm", "conv_w", "router", "expert_bias")


def random_state(rng, cfg, name: str = "lm", std: float = 0.02,
                 dtype="float32", bias_range: float = 0.05) -> dict:
    """Seeded random weights under :func:`param_shapes` (tests, tools):
    normal matrices in ``dtype``, unit norms, a float32 router, and an
    ``expert_bias`` uniform in ``+-bias_range`` — NOT zero, so that a
    bias that leaks into the weights, or is ignored, shows."""
    import jax.numpy as jnp

    w = {}
    for k, shp in param_shapes(cfg, name).items():
        if k.endswith(("_norm", "_layernorm")):
            w[k] = np.ones(shp, "float32")
        elif k.endswith("expert_bias"):
            w[k] = rng.uniform(-bias_range, bias_range, shp).astype("float32")
        elif k.endswith("conv_w"):
            w[k] = (rng.randn(*shp) * 0.5).astype("float32")
        elif k.endswith("router"):
            w[k] = (rng.randn(*shp) * std).astype("float32")
        else:
            w[k] = jnp.asarray((rng.randn(*shp) * std).astype("float32"),
                               dtype)
    return w


def route(f, w_router, bias, d):
    """Which experts each row chose and how it weighs them, over ALL
    ``d.n_expert``: ``(sel [N, top_k] int32, gate [N, top_k] float32)``,
    by ``d.scoring``.  :data:`SIGMOID_BIAS`: the bias enters the choice
    and never the weights; with ``d.n_group`` > 1 (absent: 1) the choice
    is made inside the ``d.topk_group`` best groups
    (:func:`_within_best_groups`).  :data:`SOFTMAX_CHOSEN`: the choice is
    made on the logits and the softmax runs over the chosen alone
    (``bias`` is not read)."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    if d.scoring == SOFTMAX_CHOSEN:
        z, sel = jax.lax.top_k(jnp.dot(
            f.astype(f32), w_router.astype(f32), precision="highest",
            preferred_element_type=f32), d.top_k)
        gate = jax.nn.softmax(z, axis=-1)
        if d.norm_topk:
            gate = gate / jnp.sum(gate, axis=-1, keepdims=True)
        return sel.astype(jnp.int32), gate * d.routed_scale
    s = jax.nn.sigmoid(jnp.dot(
        f.astype(f32), w_router.astype(f32), precision="highest",
        preferred_element_type=f32))
    chosen = s + bias.astype(f32) if d.expert_bias else s
    n_group = int(getattr(d, "n_group", 1))
    if n_group > 1:
        chosen = _within_best_groups(chosen, n_group, int(d.topk_group))
    _, sel = jax.lax.top_k(chosen, d.top_k)
    gate = jnp.take_along_axis(s, sel, axis=-1)
    if d.norm_topk:
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True)
                       + _WEIGHT_SUM_EPS)
    return sel.astype(jnp.int32), gate * d.routed_scale


def _within_best_groups(chosen, n_group: int, topk_group: int):
    """``chosen`` ``[N, n_expert]`` with every expert outside the row's
    ``topk_group`` best groups at ``-inf``: the experts are ``n_group``
    consecutive groups of equal size, a group is scored by the sum of
    its two largest ``chosen`` (ties: the lower group first)."""
    import jax
    import jax.numpy as jnp

    n = chosen.shape[0]
    by_group = chosen.reshape(n, n_group, -1)
    score = jnp.sum(jax.lax.top_k(by_group, 2)[0], axis=-1)
    _, best = jax.lax.top_k(score, topk_group)
    kept = jnp.any(best[:, :, None] == jnp.arange(n_group)[None, None, :],
                   axis=1)
    return jnp.where(kept[:, :, None], by_group, -jnp.inf).reshape(n, -1)


def dispatch(sel, live, held, n_expert: int):
    """Sort the (row, choice) pairs by expert.  ``sel`` ``[N, k]``,
    ``live`` ``[N]`` bool, ``held = (lo, hi)``.  Pairs of an idle row or
    of an expert not held sort last and belong to no group.  Returns
    ``(order [N * k], group_sizes [hi - lo], kept [N, k] bool)``:
    ``order[j]`` is the flat pair at sorted place ``j``."""
    import jax.numpy as jnp

    lo, hi = held
    kept = live[:, None] & (sel >= lo) & (sel < hi)
    key = jnp.where(kept, sel, n_expert).reshape(-1)
    order = jnp.argsort(key, stable=True).astype(jnp.int32)
    sizes = jnp.sum(key[:, None] == jnp.arange(lo, hi, dtype=jnp.int32),
                    axis=0, dtype=jnp.int32)
    return order, sizes, kept


def shared_expert(f, w, p: str, d):
    """The shared expert's term for every row of ``f`` ``[N, d_model]``:
    ``W2 (act(W1 f) * W3 f)`` with ``shared_w13`` / ``shared_w2``,
    unweighed; ``[N, d_model]`` float32."""
    import jax

    with jax.named_scope(SHARED_EXPERT_SCOPE):
        w13, w2 = w[p + "shared_w13"], w[p + "shared_w2"]
        gu = linear(f, w13)
        half = w13.shape[-1] // 2
        act_fn = jax.nn.relu if d.gate_act == RELU else jax.nn.silu
        return linear(act_fn(gu[:, :half]) * gu[:, half:], w2)


def expert_layer(f, w, p: str, ts, d, held=None, router_input=None,
                 shared: bool = True):
    """The held experts' part of a mixture layer for one token per row.

    ``f`` ``[N, d_model]`` (the normed residual); ``w`` the weight dict,
    ``p`` the layer's key prefix — ``experts_w13`` / ``experts_w2`` hold
    the HELD experts only, in order (``hi - lo`` of them), the router
    (and its bias, where ``d.expert_bias``) all ``d.n_expert``; ``ts``
    ``[N]`` (``< 0`` idle: routed nowhere, counted nowhere); ``held``
    ``(lo, hi)``, default all; ``router_input`` ``[N, d_model]``: what
    the router reads where that is not ``f`` (a router placed before
    attention reads the block's normed input); ``shared``: whether the
    layer's shared expert (``d.n_shared``; none: nothing to add) is
    added here — False for a share that is summed with others.  Returns
    ``(out [N, d_model] float32, stats [4] int32)`` with ``stats`` as
    :data:`STAT_NAMES`.

    The sorted pairs are padded to a whole ``grouped_matmul.ROW_TILE``
    (pairs of no group, as an idle row's are: 40 rows x 6 = 240 -> 256),
    so that a step whose pairs are not a multiple of it keeps the kernel
    instead of falling to ``ragged_dot``."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu import grouped_matmul as gm

    held = (0, d.n_expert) if held is None else tuple(held)
    n, k = f.shape[0], d.top_k
    live = ts >= 0
    with jax.named_scope(ROUTE_SCOPE):
        sel, gate = route(f if router_input is None else router_input,
                          w[p + "router"], w.get(p + "expert_bias"), d)
        order, sizes, kept = dispatch(sel, live, held, d.n_expert)
        w13, w2 = w[p + "experts_w13"], w[p + "experts_w2"]
        pad = -(n * k) % gm.ROW_TILE
        taken = order
        if pad:     # rows past the groups' total: multiplied by nothing
            taken = jnp.concatenate([order, jnp.zeros(pad, jnp.int32)])
        rows = f.astype(w13.dtype)[taken // k]
        visits = gm.plan(sizes, n * k + pad)
    with jax.named_scope(EXPERTS_SCOPE):
        gu = gm.grouped_matmul(rows, w13, visits)
        act_fn = jax.nn.relu if d.gate_act == RELU else jax.nn.silu
        act = act_fn(gu[:, :d.d_expert]) * gu[:, d.d_expert:]
        y = gm.grouped_matmul(act.astype(w2.dtype), w2, visits)
    with jax.named_scope(ROUTE_SCOPE):
        # back to (row, choice) order, weighed, summed over the choices
        place = jnp.zeros(n * k, jnp.int32).at[order].set(
            jnp.arange(n * k, dtype=jnp.int32))
        y = y[place].reshape(n, k, -1)
        out = jnp.sum(jnp.where(kept[..., None], gate[..., None] * y, 0.0),
                      axis=1)
        stats = jnp.stack([jnp.sum(sizes), jnp.sum(sizes > 0),
                           jnp.max(sizes), jnp.any(live).astype(jnp.int32)])
    if shared and getattr(d, "n_shared", 0):
        out = out + shared_expert(f, w, p, d)
    return out, stats.astype(jnp.int32)


def short_conv_step(r, w, p: str, conv, ts, d):
    """One token of the gated short convolution for every row.

    ``r`` ``[N, d_model]`` (the normed residual); ``conv`` ``[N,
    conv_L_cache - 1, d_model]`` float32, the row's last ``u``: RECURRENT
    state, read as zero for a row at ``ts == 0``
    (:func:`hybrid_ssm.starts_fresh`), kept for an idle row (``ts <
    0``).  Returns ``(out [N, d_model], conv)``."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    live, fresh = ts >= 0, starts_fresh(ts)
    with jax.named_scope(SHORT_CONV_SCOPE):
        b, c, x = jnp.split(linear(r, w[p + "conv_in"]), 3, axis=-1)
        u = b * x
        prev = jnp.where(fresh[:, None, None], 0.0, conv.astype(f32))
        window = jnp.concatenate([prev, u[:, None, :]], axis=1)
        y = jnp.sum(window * w[p + "conv_w"].astype(f32)[None], axis=1)
        conv_new = jnp.where(live[:, None, None], window[:, 1:],
                             conv.astype(f32)).astype(conv.dtype)
        return linear(c * y, w[p + "conv_out"]), conv_new
