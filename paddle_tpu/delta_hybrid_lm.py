"""The parts of a decoder whose layers are a GATED DELTA-RULE linear
attention or full multi-head attention, each followed by a SwiGLU
(``model_type: olmo_hybrid``), as small functions of ONE token per row.

    h = x + RMS(mixer(x))            # no norm BEFORE a branch: the
    h = h + RMS(SwiGLU(h))           # branch is closed by one

A *linear* layer, per head (``dk`` key lanes, ``dv`` value lanes, state
``S`` ``[dk, dv]``) and token::

    [q; k; v] = silu(depthwise causal conv, kernel K, of [W_q; W_k; W_v] x)
    q = q / ||q|| * dk^-1/2,   k = k / ||k||
    beta  = sigmoid(W_b x)      (x 2 where ``linear_allow_neg_eigval``)
    alpha = exp(-exp(A_log) * softplus(W_a x + dt_bias))
    S <- alpha S
    u  = S^T k                 # what the decayed state returns for k: READ
    S <- S + k (beta (v - u))^T                                   # WRITE
    o  = S^T q
    y  = RMS_dv(o) * silu(W_g x),   out = W_o [y of every head]

(Yang, Kautz, Hatamizadeh, "Gated Delta Networks", arXiv:2412.06464.)
Unlike a state that only decays and adds (``hybrid_ssm.mamba2_step``,
``sparse_linear_lm.lightning_step``) the write needs ``S^T k`` of the
WHOLE head state first.  A *full* layer is causal softmax attention of
``n_head`` heads over ``n_kv_head`` K/V heads, q and k RMS-normed over
the whole projection before the heads are split, rotary only where the
configuration gives a ``rope_theta`` (``olmo_hybrid`` gives ``null``:
positions enter through the linear layers' decay and convolution).

``decoding.make_delta_hybrid_lm_pooled_step_fn`` strings the parts into
the slot-pooled step; nothing here knows a pool or a server.  Weights
are multiplied in the dtype they are given (``hybrid_ssm.linear``);
norms, gates, the convolution and the recurrence run in float32.

Per linear layer a row carries two RECURRENT leaves, read as zero for a
row at ``ts == 0`` (``hybrid_ssm.starts_fresh``) and kept for an idle
row (``ts < 0``):

* ``state`` ``[N, H / g, dk, g * dv]`` float32: ``g`` heads side by side
  in the lane axis (:func:`heads_per_tile`), so that a row of the leaf
  is whole 128-lane tiles — ``dv`` = 192 alone would be padded to 256
  lanes in HBM, a third more of the largest thing a step moves after the
  weights; two heads are 384 = 3 tiles and nothing is padded;
* ``conv`` ``[N, K - 1, 2 H dk + H dv]`` float32: the last ``K - 1``
  projected rows ``[q; k; v]`` before the convolution.

The rule itself (:func:`gated_delta_step`) has two lowerings of its one
contract, chosen by :func:`lowering` from what the call can see (never a
flag):

* the Pallas TPU kernel (:func:`kernel_gated_delta_step`): a grid step
  brings a block of the state leaf — some slots of one row of its second
  axis, ``[bn, 1, dk, g * dv]``, as the leaf is declared — into VMEM
  ONCE, does decay, ``S^T k``, the write and ``S^T q`` on it there, on
  the vector unit in float32, and writes it back ONCE, in place.  ``k``
  and ``q`` go in as columns with the slot in the lane axis and the
  kernel makes the lane map of a slot by one gathered lane (and a select
  where a lane tile holds two heads); ``v`` as rows of the leaf's lane
  axis, the gates as scalars in SMEM: kilobytes a block, nothing the
  size of the state;
* the XLA form (:func:`xla_gated_delta_step`): two fusions, one reads
  the state and reduces to ``S^T k``, one reads it again, writes it and
  reduces to ``S^T q`` (XLA cannot fuse a reduction with the consumer of
  its own result) — three moves of the state for the rule's two.  The
  CPU, a leaf that is not float32, a leaf padded by the tiled layout.

``delta_update_lowered_total{path}`` (``kernel`` | ``xla``) counts the
updates traced, by the form taken, and ``delta_update_decay_total{decay}``
(``head`` | ``channel``) by the decay's contract.

KIMI DELTA ATTENTION beside gated position-free GQA and routed experts
(``model_type: solar_open2``; the ``kda_*`` functions below; Kimi Linear,
arXiv:2510.26692) is the same rule with a decay a CHANNEL.  Pre-norm,
eps 1e-5, a final RMSNorm, an untied head, no bias anywhere::

    h = x + mixer(RMS(x));   y = h + moe(RMS(h))

    K layer, per head (dk = dv), state S [dk, dv]:
      q, k, v = silu(causal depthwise conv, kernel K, of W_q x, W_k x, W_v x)
      q = q / ||q|| * dk^-1/2,   k = k / ||k||
      alpha = exp(-exp(A_log[h]) * softplus(W_fb (W_fa x) + dt_bias))
                                       # in (0, 1)^dk: one factor a CHANNEL
      beta  = sigmoid(W_b x)           (x 2 where ``kda_allow_neg_eigval``)
      S <- Diag(alpha) S               # row i of S times alpha_i
      u = S^T k;  S <- S + k (beta (v - u))^T;  o = S^T q
      out = W_o [RMS_dv(o) * sigmoid(W_gb (W_ga x))]
    G layer: q = W_q x (n_head x Dh), k, v = W_k x, W_v x (n_kv_head x Dh),
      NO rotary (``use_rope: false``), causal softmax at Dh^-1/2,
      out = W_o [attn * sigmoid(W_g x)]      (``use_gqa_gate``)
    MoE, after EVERY mixer: ``routed_experts.expert_layer`` (sigmoid
      scores, a selection bias, the top_k's scores normalised) beside ONE
      shared expert.

The two low-rank pairs (``W_fa``/``W_fb`` for the decay, ``W_ga``/``W_gb``
for the output gate: d_model -> head_dim -> H dk; ``kda_use_full_proj``
false) are two plain products each; the three convolutions are ONE
(:func:`qkv_conv_step`) over the concatenated channels.
``decoding.make_kda_routed_lm_pooled_step_fn`` strings these parts.

THE CHUNKWISE FORM (:func:`gated_delta_chunk`; the delta rule's WY / UT
transform): ``C`` positions of ONE row in matrix products and one
unit-lower-triangular solve a sub-chunk, equal to ``C`` calls of
:func:`gated_delta_step` at float32 rounding, for both decays.  Per
head, positions ``r = 1..C`` of a sub-chunk, state ``S_0`` in, ``g_r =
sum_{i<=r} log alpha_i`` (a vector over the key channels, or a scalar)::

    A[r, s] = beta_r sum_c k_r,c k_s,c e^{g_r,c - g_s,c}          (s < r)
    W = (I + A)^-1 Diag(beta) (V - (K * e^G) S_0)
    o_r = S_0^T (e^{g_r} * q_r) + sum_{s<=r} (sum_c q_r,c k_s,c e^{g_r,c - g_s,c}) w_s
    S_C = Diag(e^{g_C}) S_0 + sum_s (e^{g_C - g_s} * k_s) w_s^T

Every exponent is ``g_r - g_s`` with ``s <= r``: nothing is divided by a
cumulative decay, nothing overflows.  :func:`kda_layer_chunk` is a KDA
layer over a chunk (the convolution carried by the ``conv`` leaf);
``decoding.make_kda_latent_lm_pooled_step_fn`` (``model_type:
kimi_linear``: KDA beside multi-head LATENT attention) is the builder
that prefills with it.
"""
from __future__ import annotations

import functools
from types import SimpleNamespace

import numpy as np

from paddle_tpu.hybrid_ssm import (linear, rms_norm, rotary, starts_fresh,
                                   swiglu)
from paddle_tpu.monitor import registry as _registry
from paddle_tpu.routed_experts import SIGMOID_BIAS, SILU

__all__ = ["LINEAR", "FULL", "DELTA_UPDATE_SCOPE", "DELTA_CHUNK_SCOPE",
           "SHORT_CONV_SCOPE", "gated_delta_chunk", "qkv_conv_chunk",
           "kda_layer_chunk", "kda_mixer_shapes", "kda_ffn_shapes",
           "FLOAT32_PARAMS", "dims", "param_shapes", "random_state",
           "heads_per_tile", "qkv_conv_step", "l2_norm", "decay_and_step_gates",
           "gated_delta_step", "xla_gated_delta_step",
           "kernel_gated_delta_step", "lowering", "LOWERED", "KERNEL_NAME",
           "gated_output_norm", "conv_qkv", "delta_layer_step",
           "full_attention_rows", "DECAY", "CHANNEL_GATES_SCOPE",
           "FULL_ATTENTION_SCOPE", "KDA_FLOAT32_PARAMS", "kda_dims",
           "kda_param_shapes", "kda_random_state", "channel_decay",
           "kda_layer_step", "gated_attention_rows", "linear", "rms_norm", "rotary",
           "starts_fresh", "swiglu"]

#: ``layer_types`` entries
LINEAR, FULL = "linear_attention", "full_attention"

#: ``jax.named_scope`` names, for the device trace
DELTA_UPDATE_SCOPE = "delta_state_update"
DELTA_CHUNK_SCOPE = "delta_chunk_prefill"
SHORT_CONV_SCOPE = "delta_short_conv"
CHANNEL_GATES_SCOPE = "delta_channel_gates"
FULL_ATTENTION_SCOPE = "gated_full_attention"

#: endings of the parameters kept in float32 whatever the matrices are
FLOAT32_PARAMS = ("norm", "lin_conv_w", "lin_A_log", "lin_dt_bias")

_LANES = 128
_L2_EPS = 1e-6
#: the kernel's name in the device trace
KERNEL_NAME = "gated_delta_update"
#: most bytes of the state one buffer of a grid step holds
_BLOCK_BYTES = 5 << 19
#: positions of a sub-chunk of the chunkwise form: its pairwise decay is
#: ``[sub, sub, H, dk]`` float32 (64: 67 MB at 32 heads of 128 channels)
_SUB_CHUNK = 64
#: the least decay the chunkwise form takes the logarithm of
_ALPHA_FLOOR = 1e-37

LOWERED = _registry.REGISTRY.counter(
    "delta_update_lowered_total",
    "gated delta-rule state updates lowered (traced into a program or "
    "run eagerly), by the lowering chosen: kernel (Pallas TPU: each "
    "block of the state leaf read once and written once, in place) | "
    "xla (two fusions: the state read twice and written once) | chunk "
    "(the chunkwise form of a prefill: C positions of one slot)", ("path",))
DECAY = _registry.REGISTRY.counter(
    "delta_update_decay_total",
    "gated delta-rule state updates lowered, by the decay's contract: head "
    "(one factor a head, alpha [N, H]) | channel (one a key channel of a "
    "head, alpha [N, H, dk]: Kimi Delta Attention)", ("decay",))


def heads_per_tile(n_head: int, dv: int) -> int:
    """How many heads the state leaf lays side by side in its lane axis:
    the fewest whose ``dv`` lanes together are whole 128-lane tiles, if
    the head count divides into such groups, else 1 (the leaf is then
    padded by the tiled layout: tiny test sizes)."""
    g = _LANES // int(np.gcd(dv, _LANES))
    return g if n_head % g == 0 else 1


def dims(cfg) -> SimpleNamespace:
    """The decoder's sizes from an ``olmo_hybrid`` config dict (the
    published key names).  ``head_dim`` is absent there: ``hidden_size /
    num_attention_heads``."""
    g = cfg.get
    rope = (g("rope_parameters") or {}).get("rope_theta")
    o = SimpleNamespace(
        vocab=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        n_layer=int(cfg["num_hidden_layers"]),
        kinds=tuple(cfg["layer_types"]),
        n_head=int(cfg["num_attention_heads"]),
        n_kv_head=int(cfg["num_key_value_heads"]),
        d_mlp=int(cfg["intermediate_size"]),
        lin_heads=int(cfg["linear_num_value_heads"]),
        dk=int(cfg["linear_key_head_dim"]),
        dv=int(cfg["linear_value_head_dim"]),
        conv_len=int(cfg["linear_conv_kernel_dim"]),
        neg_eigval=bool(g("linear_allow_neg_eigval", False)),
        eps=float(g("rms_norm_eps", 1e-6)),
        rope_theta=None if rope is None else float(rope))
    o.head_dim = int(g("head_dim") or o.d_model // o.n_head)
    if len(o.kinds) != o.n_layer or set(o.kinds) - {LINEAR, FULL}:
        raise ValueError("layer_types must name %d layers as %r or %r"
                         % (o.n_layer, LINEAR, FULL))
    if int(cfg["linear_num_key_heads"]) != o.lin_heads:
        raise ValueError("linear_num_key_heads != linear_num_value_heads: "
                         "value heads grouped over key heads are not built")
    if o.n_head % o.n_kv_head:
        raise ValueError("heads must divide into their K/V heads")
    o.d_q, o.d_kv = o.n_head * o.head_dim, o.n_kv_head * o.head_dim
    o.d_key, o.d_value = o.lin_heads * o.dk, o.lin_heads * o.dv
    o.d_qkv = 2 * o.d_key + o.d_value
    o.tile_heads = heads_per_tile(o.lin_heads, o.dv)
    o.state_shape = (o.lin_heads // o.tile_heads, o.dk, o.tile_heads * o.dv)
    return o


def param_shapes(cfg, name: str = "lm") -> dict:
    """Names and shapes of every weight the step reads: the one place
    the schema lives.  Matrices are ``[in, out]``; the depthwise conv
    kernel is ``[K, channels]`` over ``[q; k; v]``, oldest tap first."""
    d = dims(cfg)
    out = {name + "_emb": (d.vocab, d.d_model),
           name + "_final_norm": (d.d_model,),
           name + "_head": (d.d_model, d.vocab)}
    for i, kind in enumerate(d.kinds):
        p = "%s_l%d_" % (name, i)
        if kind == LINEAR:
            out.update({
                p + "lin_q": (d.d_model, d.d_key),
                p + "lin_k": (d.d_model, d.d_key),
                p + "lin_v": (d.d_model, d.d_value),
                p + "lin_conv_w": (d.conv_len, d.d_qkv),
                p + "lin_a": (d.d_model, d.lin_heads),
                p + "lin_b": (d.d_model, d.lin_heads),
                p + "lin_A_log": (d.lin_heads,),
                p + "lin_dt_bias": (d.lin_heads,),
                p + "lin_g": (d.d_model, d.d_value),
                p + "lin_norm": (d.dv,),
                p + "lin_o": (d.d_value, d.d_model)})
        else:
            out.update({
                p + "attn_q": (d.d_model, d.d_q),
                p + "attn_k": (d.d_model, d.d_kv),
                p + "attn_v": (d.d_model, d.d_kv),
                p + "attn_q_norm": (d.d_q,), p + "attn_k_norm": (d.d_kv,),
                p + "attn_o": (d.d_q, d.d_model)})
        out.update({
            p + "mixer_norm": (d.d_model,), p + "mlp_norm": (d.d_model,),
            p + "mlp_gate": (d.d_model, d.d_mlp),
            p + "mlp_up": (d.d_model, d.d_mlp),
            p + "mlp_down": (d.d_mlp, d.d_model)})
    return out


def _random_vector(rng, key: str, shp):
    """A float32 parameter (:data:`FLOAT32_PARAMS`) of a random state:
    unit norms, the reference layer's ``A_log`` = log U(1e-3, 16) and
    ``dt_bias`` = inverse softplus of a step log-uniform in [1e-3, 1e-1],
    a conv kernel uniform in +-1 / sqrt(K)."""
    if key.endswith("norm"):
        return np.ones(shp, "float32")
    if key.endswith("lin_A_log"):
        return np.log(rng.uniform(1e-3, 16.0, shp)).astype("float32")
    if key.endswith("lin_dt_bias"):
        dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), shp))
        return (dt + np.log(-np.expm1(-dt))).astype("float32")
    return rng.uniform(-1, 1, shp).astype("float32") / np.sqrt(shp[0])


def random_state(rng, cfg, name: str = "lm", std: float = 0.02,
                 dtype="float32") -> dict:
    """Seeded random weights under :func:`param_shapes` (tests): normal
    matrices, a unit-variance embedding (every branch is closed by a
    norm of weight 1, so the stream the first layer reads has to be of
    that size too), unit norms, the reference layer's ``A_log`` = log
    U(1e-3, 16) and ``dt_bias`` = inverse softplus of a step log-uniform in
    [1e-3, 1e-1].  Vectors and the conv kernel stay fp32; matrices take
    ``dtype``."""
    import jax.numpy as jnp

    w = {}
    for k, shp in param_shapes(cfg, name).items():
        if k.endswith(FLOAT32_PARAMS):
            w[k] = _random_vector(rng, k, shp)
        else:
            s = 1.0 if k.endswith("_emb") else std
            w[k] = jnp.asarray((rng.randn(*shp) * s).astype("float32"), dtype)
    return w


def qkv_conv_step(x, w_conv, conv, ts):
    """The causal depthwise convolution over the projected row ``x``
    ``[N, C]`` (``[q; k; v]`` before it) and the row's window ``conv``
    ``[N, K - 1, C]``; SiLU after it, no bias.  Returns ``(activated
    [N, C], conv)``."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    live, fresh = ts >= 0, starts_fresh(ts)
    with jax.named_scope(SHORT_CONV_SCOPE):
        prev = jnp.where(fresh[:, None, None], 0.0, conv.astype(f32))
        window = jnp.concatenate([prev, x[:, None, :]], axis=1)
        y = jax.nn.silu(jnp.sum(window * w_conv.astype(f32)[None], axis=1))
        conv_new = jnp.where(live[:, None, None], window[:, 1:],
                             conv.astype(f32)).astype(conv.dtype)
    return y, conv_new


def qkv_conv_chunk(x, w_conv, conv, start, n_valid):
    """:func:`qkv_conv_step` over ``C`` positions of ONE row: ``x`` ``[C,
    channels]`` the projected rows at ``start .. start + C - 1`` (the
    first ``n_valid`` count), ``conv`` ``[K - 1, channels]`` the row's
    window (read as zero where the chunk starts a sequence).  Returns
    ``(activated [C, channels], conv)``, the window after ``n_valid``
    positions."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    c, taps = x.shape[0], w_conv.shape[0]
    with jax.named_scope(SHORT_CONV_SCOPE):
        prev = jnp.where(starts_fresh(start), 0.0, conv.astype(f32))
        ext = jnp.concatenate([prev, x], axis=0)        # [K - 1 + C, ch]
        wf = w_conv.astype(f32)
        y = jax.nn.silu(sum(ext[j:j + c] * wf[j] for j in range(taps)))
        conv_new = jax.lax.dynamic_slice_in_dim(
            ext, n_valid, taps - 1, axis=0).astype(conv.dtype)
    return y, conv_new


def l2_norm(x):
    """``x / ||x||_2`` over the last axis (a head's lanes)."""
    import jax
    import jax.numpy as jnp

    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True)
                             + _L2_EPS)


def decay_and_step_gates(x, w, p: str, d):
    """``(alpha, beta)`` ``[N, H]`` float32: the state's per-token decay
    in (0, 1) and the write's step in (0, 1), or (0, 2) where the
    configuration allows negative eigenvalues."""
    import jax
    import jax.numpy as jnp

    dt = jax.nn.softplus(linear(x, w[p + "lin_a"]) + w[p + "lin_dt_bias"])
    alpha = jnp.exp(-jnp.exp(w[p + "lin_A_log"]) * dt)
    beta = jax.nn.sigmoid(linear(x, w[p + "lin_b"]))
    return alpha, beta * 2.0 if d.neg_eigval else beta


def _over_lanes(x, g: int, dv: int):
    """Per-head values ``x`` ``[N, H, K]`` laid against the state leaf:
    ``[N, H / g, K, g * dv]`` holding head ``G * g + c // dv``'s value in
    lane ``c`` — broadcasts and selects on a constant lane map, so the
    state-sized product it feeds is made inside that product's fusion
    (a ``[.., g, dv] -> [.., g * dv]`` reshape of it would be a copy)."""
    import jax.numpy as jnp

    n, h, k = x.shape
    xg = x.reshape(n, h // g, g, k)
    out = xg[:, :, 0, :, None]
    head_of_lane = np.arange(g * dv) // dv
    for j in range(1, g):
        out = jnp.where(head_of_lane == j, xg[:, :, j, :, None], out)
    return out


def _block_slots(n: int, dk: int, lanes: int) -> int:
    """Slots a grid step of the kernel holds of a float32 leaf ``[n, .,
    dk, lanes]``: the most that divide both ``n`` and a lane tile (the
    block's columns of ``k`` and ``q`` lie in ONE tile of the
    slot-in-lanes operand), in whole sublane tiles (the block's rows of
    ``v`` and ``o``), within :data:`_BLOCK_BYTES` of state a buffer; 0 if
    none does."""
    return max((b for b in range(8, _LANES + 1, 8)
                if n % b == 0 and _LANES % b == 0
                and b * 4 * dk * lanes <= _BLOCK_BYTES), default=0)


def lowering(backend: str, s, dv: int) -> str:
    """``"kernel"`` or ``"xla"`` for one update of the state leaf ``s``
    ``[N, H / g, dk, g * dv]``.  The kernel needs a TPU, a float32 leaf
    whose rows are whole lane tiles (``g * dv``) of whole sublane tiles
    (``dk``), and a slot count that is a whole number of blocks."""
    import jax.numpy as jnp

    n, _, dk, lanes = s.shape
    ok = (backend == "tpu" and s.dtype == jnp.float32
          and lanes % _LANES == 0 and lanes % dv == 0 and dk % 8 == 0
          and _block_slots(n, dk, lanes))
    return "kernel" if ok else "xla"


def gated_delta_step(q, k, v, alpha, beta, s, ts):
    """One token of the gated delta rule for every row and head.

    ``q``, ``k`` ``[N, H, dk]`` (normalised), ``v`` ``[N, H, dv]``,
    ``beta`` ``[N, H]``, all float32; ``alpha`` the decay, ``[N, H]`` (one
    factor a head: ``S <- alpha S``) or ``[N, H, dk]`` (one a key channel:
    ``S <- Diag(alpha) S``, row ``i`` of a head's state times
    ``alpha_i``) — read from its shape, never a flag; ``s`` the state
    leaf ``[N, H / g, dk, g * dv]`` (``g`` read from its shape); ``ts``
    ``[N]`` (``< 0`` idle: state kept, ``0`` fresh: state read as zero).
    Returns ``(o [N, H, dv], s)``.  :func:`lowering` chooses the form."""
    import jax

    path = lowering(jax.default_backend(), s, v.shape[-1])
    LOWERED.labels(path=path).inc()
    DECAY.labels(decay="channel" if alpha.ndim == 3 else "head").inc()
    with jax.named_scope(DELTA_UPDATE_SCOPE):
        if path == "kernel":
            return kernel_gated_delta_step(q, k, v, alpha, beta, s, ts)
        return xla_gated_delta_step(q, k, v, alpha, beta, s, ts)


def xla_gated_delta_step(q, k, v, alpha, beta, s, ts):
    """The rule as XLA fuses it: two passes over the state (one reduces
    to ``S^T k``, one reads it again, writes it and reduces to ``S^T
    q``).  Every backend, every leaf; the kernel's reference."""
    import jax.numpy as jnp

    f32 = jnp.float32
    n, h, dv = v.shape
    g = s.shape[-1] // dv
    live, fresh = ts >= 0, starts_fresh(ts)
    wide = functools.partial(_over_lanes, g=g, dv=dv)
    kk, qq = wide(k), wide(q)
    s_prev = jnp.where(fresh[:, None, None, None], 0.0, s.astype(f32))
    s_dec = wide(alpha if alpha.ndim == 3 else alpha[..., None]) * s_prev
    u = jnp.sum(s_dec * kk, axis=2)                     # [N, H / g, g * dv]
    delta = wide(beta[..., None])[:, :, 0] * (v.reshape(u.shape) - u)
    s_new = s_dec + kk * delta[:, :, None, :]
    o = jnp.sum(s_new * qq, axis=2).reshape(n, h, dv)
    s_out = jnp.where(live[:, None, None, None], s_new,
                      s.astype(f32)).astype(s.dtype)
    return o, s_out


def kernel_gated_delta_step(q, k, v, alpha, beta, s, ts,
                            interpret: bool = False):
    """The rule as ONE Pallas TPU kernel: a block of the state leaf is
    brought into VMEM once, decayed, read for ``S^T k``, written and read
    for ``S^T q`` there, and goes back once, in place (the leaf is an
    ``input_output_aliases`` pair)."""
    import jax.numpy as jnp

    n, _, dk, lanes = s.shape
    # a row's kind by the one definition of each (here, not under the
    # shared trace): 0 fresh (its state is read as zero), 1 live, -1 idle
    # (its state is kept)
    kinds = jnp.where(starts_fresh(ts), 0, jnp.where(ts >= 0, 1, -1))
    return _kernel_call()(q, k, v, alpha, beta, s, kinds.astype(jnp.int32),
                          block=_block_slots(n, dk, lanes),
                          interpret=interpret)


@functools.lru_cache(maxsize=None)
def _kernel_call():
    """:func:`_delta_update` under ONE ``jax.jit`` (built once, jax
    imported late): the linear layers and the steps of a chunk program
    share one trace and one lowered function of the kernel
    (decode_attention._kernel_call)."""
    import jax

    return jax.jit(_delta_update, static_argnames=("block", "interpret"))


def _delta_update(q, k, v, alpha, beta, s, kinds, *, block, interpret):
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    f32 = jnp.float32
    n, h, dv = v.shape
    _, pairs, dk, lanes = s.shape
    g, bn = h // pairs, block
    # what a grid step needs besides its block of the state, in layouts
    # that cost kilobytes a block.  ``k`` and ``q`` are wanted with ``dk``
    # along SUBLANES: their columns go in with the slot in the lane axis,
    # [H / g, N / 128, 2 g, dk, 128] (a tile of 128 slots a block index),
    # and the kernel broadcasts a slot's lane over a tile.  ``v`` is a row
    # of the leaf's own lane axis, [H / g, N, g * dv]; the gates are
    # scalars a (slot, head), in SMEM beside the rows' kinds.  A decay a
    # CHANNEL is no scalar: it rides as a third column beside k and q
    # ([.., 3 g, dk, 128]) and is broadcast over a head's lanes as they are.
    channel = alpha.ndim == 3
    tiles = -(-n // _LANES)
    cols = jnp.concatenate(
        [x.reshape(n, pairs, g, dk)
         for x in ((k, q, alpha) if channel else (k, q))], axis=2)
    cols = jnp.pad(cols.transpose(1, 2, 3, 0),
                   ((0, 0),) * 3 + ((0, tiles * _LANES - n),))
    n_cols = cols.shape[1]
    cols = cols.reshape(pairs, n_cols, dk, tiles, _LANES).transpose(
        0, 3, 1, 2, 4)
    rows = v.reshape(n, pairs, lanes).transpose(1, 0, 2)

    def kernel(kind_ref, *refs):
        # the gates in SMEM (alpha where it is one a head, beta), then the
        # blocks
        gate_refs, (cols_ref, v_ref, s_ref, o_ref, s_out_ref) = (
            refs[:-5], refs[-5:])
        pair, first = pl.program_id(0), pl.program_id(1) * bn
        lane = jax.lax.broadcasted_iota(jnp.int32, (dk, _LANES), 1)
        row_lane = jax.lax.broadcasted_iota(jnp.int32, (1, lanes), 1)

        def gate_row(ref, m):
            """The (slot, head) scalars of ``ref`` against the leaf's
            row: ``[1, g * dv]``, head ``l // dv``'s in lane ``l``."""
            at = (first + m) * h + pair * g
            out = jnp.full((1, lanes), ref[at], f32)
            for head in range(1, g):
                out = jnp.where(row_lane >= head * dv, ref[at + head], out)
            return out

        def over_lanes(head0, at):
            """Heads ``head0 ..`` of ``cols_ref``, the slot in lane
            ``at``, against the leaf's row: ``[dk, g * dv]`` holding head
            ``l // dv``'s value in lane ``l`` — a lane tile at a time,
            one gathered lane, and a select where a tile holds the end
            of one head and the start of the next."""
            tile = []
            for j in range(lanes // _LANES):
                lo, hi = j * _LANES // dv, (j * _LANES + _LANES - 1) // dv
                out = jnp.take_along_axis(cols_ref[head0 + lo], at, axis=1)
                for head in range(lo + 1, hi + 1):
                    out = jnp.where(
                        lane >= head * dv - j * _LANES,
                        jnp.take_along_axis(cols_ref[head0 + head], at,
                                            axis=1), out)
                tile.append(out)
            return jnp.concatenate(tile, axis=1)

        def slot(m, carry):
            kind = kind_ref[first + m]
            at = jnp.full((dk, _LANES), first % _LANES + m, jnp.int32)
            kk, qq = over_lanes(0, at), over_lanes(g, at)
            if channel:
                a, b = over_lanes(2 * g, at), gate_row(gate_refs[0], m)
            else:
                a, b = gate_row(gate_refs[0], m), gate_row(gate_refs[1], m)
            s_old = s_ref[m]
            s_dec = a * jnp.where(kind == 0, 0.0, s_old)
            u = jnp.sum(s_dec * kk, axis=0, keepdims=True)
            s_new = s_dec + kk * (b * (v_ref[pl.ds(m, 1), :] - u))
            o_ref[pl.ds(m, 1), :] = jnp.sum(s_new * qq, axis=0,
                                            keepdims=True)
            s_out_ref[m] = jnp.where(kind >= 0, s_new, s_old)
            return carry

        jax.lax.fori_loop(0, bn, slot, 0)

    def leaf_block(j, i, *_):
        return (i, j, 0, 0)

    def row_block(j, i, *_):
        return (j, i, 0)

    scalars = ((kinds, beta.reshape(-1)) if channel
               else (kinds, alpha.reshape(-1), beta.reshape(-1)))

    o, s_out = pl.pallas_call(
        kernel,
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=len(scalars),
            # slot blocks innermost: a head group's columns of k and q
            # are fetched once for all of them
            grid=(pairs, n // bn),
            in_specs=[
                pl.BlockSpec((None, None, n_cols, dk, _LANES),
                             lambda j, i, *_: (j, i * bn // _LANES, 0, 0,
                                               0)),
                pl.BlockSpec((None, bn, lanes), row_block),
                pl.BlockSpec((bn, None, dk, lanes), leaf_block)],
            out_specs=[
                pl.BlockSpec((None, bn, lanes), row_block),
                pl.BlockSpec((bn, None, dk, lanes), leaf_block)]),
        out_shape=[jax.ShapeDtypeStruct((pairs, n, lanes), f32),
                   jax.ShapeDtypeStruct(s.shape, f32)],
        input_output_aliases={len(scalars) + 2: 1},
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # both buffers of the state block in and out, and as much
            # again for a slot's values
            vmem_limit_bytes=8 * bn * dk * lanes * 4 + (8 << 20)),
        name=KERNEL_NAME,
        interpret=interpret,
    )(*scalars, cols, rows, s)
    return o.transpose(1, 0, 2).reshape(n, h, dv), s_out


def _heads_apart(s, dv: int):
    """A state leaf's row ``[H / g, dk, g * dv]`` a head a matrix, ``[H,
    dk, dv]`` (what :func:`heads_per_tile` laid side by side)."""
    pairs, dk, lanes = s.shape
    g = lanes // dv
    if g == 1:
        return s
    return s.reshape(pairs, dk, g, dv).transpose(0, 2, 1, 3).reshape(
        pairs * g, dk, dv)


def _heads_beside(s, g: int):
    """:func:`_heads_apart` undone: ``[H, dk, dv]`` to the leaf's row."""
    h, dk, dv = s.shape
    if g == 1:
        return s
    return s.reshape(h // g, g, dk, dv).transpose(0, 2, 1, 3).reshape(
        h // g, dk, g * dv)


def _decayed(state, g_last):
    """``Diag(e^{g_C}) S_0``: the state a sub-chunk came in with, decayed
    through all of it (``g_last`` ``[H, dk]`` or ``[H, 1]``)."""
    import jax.numpy as jnp

    return jnp.exp(g_last)[..., None] * state


def gated_delta_chunk(q, k, v, alpha, beta, s, start, n_valid):
    """``C`` positions of ONE row through the gated delta rule, in the
    CHUNKWISE form (the module's docstring has the equations): equal to
    ``n_valid`` calls of :func:`gated_delta_step`, at float32 rounding.

    ``q``, ``k`` ``[C, H, dk]`` (normalised), ``v`` ``[C, H, dv]``,
    ``beta`` ``[C, H]``, float32; ``alpha`` ``[C, H]`` or ``[C, H, dk]``,
    the ONE contract :func:`gated_delta_step` has (read from its shape);
    ``s`` ``[H / g, dk, g * dv]`` the row of the state leaf before the
    chunk's first position (read as zero where ``start`` begins a
    sequence: ``starts_fresh``); the first ``n_valid`` positions count.
    Returns ``(o [C, H, dv], s)`` with ``s`` the state after ``n_valid``
    positions (rows of ``o`` past them mean nothing).

    Sub-chunks of at most :data:`_SUB_CHUNK` positions in sequence (their
    pairwise decays are ``[sub, sub, H, dk]``), each ONE forward
    substitution; plain ``jax.numpy``, the products at "highest" so that
    a prefilled state is the stepped one."""
    import jax
    import jax.numpy as jnp

    f32, hi = jnp.float32, jax.lax.Precision.HIGHEST
    c, h, dv = v.shape
    g = s.shape[-1] // dv
    LOWERED.labels(path="chunk").inc()
    DECAY.labels(decay="channel" if alpha.ndim == 3 else "head").inc()
    with jax.named_scope(DELTA_CHUNK_SCOPE):
        valid = jnp.arange(c) < n_valid
        # a position that does not count neither decays nor writes
        log_a = jnp.where(
            valid[:, None, None], jnp.log(jnp.maximum(
                alpha if alpha.ndim == 3 else alpha[..., None],
                _ALPHA_FLOOR)), 0.0)                    # [C, H, dk | 1]
        beta = jnp.where(valid[:, None], beta, 0.0)
        sub = min(_SUB_CHUNK, c)
        pad = -c % sub

        def blocks(x):
            x = jnp.pad(x.astype(f32), ((0, pad),) + ((0, 0),) * (x.ndim - 1))
            return x.reshape((-1, sub) + x.shape[1:])

        at = jnp.arange(sub)
        upto = (at[:, None] >= at[None, :])[..., None, None]        # s <= r

        def one(state, xs):
            qb, kb, vb, lb, bb = xs
            gs = jnp.cumsum(lb, axis=0)                 # [sub, H, dk | 1]
            # e^{g_r - g_s} for s <= r alone: no exponent above 0
            pair = jnp.exp(jnp.where(upto, gs[:, None] - gs[None, :],
                                     -jnp.inf))         # [r, s, H, dk | 1]
            kk = jnp.sum(kb[:, None] * kb[None, :] * pair, axis=-1)
            qk = jnp.sum(qb[:, None] * kb[None, :] * pair, axis=-1)
            # A: what lies under the diagonal (the solve reads nothing else)
            a = bb[:, None, :] * kk                                 # [r, s, H]
            grow = jnp.exp(gs)
            rhs = bb[..., None] * (vb - jnp.einsum(
                "rhc,hcd->rhd", kb * grow, state, precision=hi))
            w = jax.lax.linalg.triangular_solve(
                a.transpose(2, 0, 1), rhs.transpose(1, 0, 2), left_side=True,
                lower=True, unit_diagonal=True)                     # [H, r, dv]
            o = (jnp.einsum("rhc,hcd->rhd", qb * grow, state, precision=hi)
                 + jnp.einsum("rsh,hsd->rhd", qk, w, precision=hi))
            left = jnp.exp(gs[-1][None] - gs)           # e^{g_C - g_s}
            state = (_decayed(state, gs[-1])
                     + jnp.einsum("shc,hsd->hcd", kb * left, w, precision=hi))
            return state, o

        s0 = jnp.where(starts_fresh(start), 0.0,
                       _heads_apart(s.astype(f32), dv))
        s_out, o = jax.lax.scan(one, s0, tuple(
            blocks(x) for x in (q, k, v, log_a, beta)))
        return (o.reshape(-1, h, dv)[:c],
                _heads_beside(s_out, g).astype(s.dtype))


def gated_output_norm(o, gate, w_norm, eps: float, act=None):
    """``RMSNorm_dv(o) * act(gate)`` per head: ``o``, ``gate`` ``[N, H,
    dv]``, ``w_norm`` ``[dv]`` (one weight for every head); ``act`` the
    gate's activation, SiLU where none is given (``olmo_hybrid``; KDA
    gives a sigmoid)."""
    import jax

    return rms_norm(o, w_norm, eps) * (act or jax.nn.silu)(gate)


def conv_qkv(x, w, p: str, conv, ts, d):
    """The rule's ``(q [N, H, dk], k [N, H, dk], v [N, H, dv], conv)`` of
    a linear layer's input rows ``x``: the three projections through the
    ONE short convolution, q and k L2-normed per head, q scaled by
    ``dk^-1/2`` (both kinds of delta-rule layer)."""
    qkv, conv = qkv_conv_step(_project_qkv(x, w, p), w[p + "lin_conv_w"],
                              conv, ts)
    return _rule_inputs(qkv, d) + (conv,)


def _project_qkv(x, w, p: str):
    """``[W_q x; W_k x; W_v x]`` ``[N, 2 H dk + H dv]``: what the short
    convolution runs over."""
    import jax.numpy as jnp

    return jnp.concatenate([linear(x, w[p + "lin_q"]), linear(x, w[p + "lin_k"]),
                            linear(x, w[p + "lin_v"])], axis=-1)


def _rule_inputs(qkv, d):
    """The convolved ``[q; k; v]`` rows ``[N, 2 H dk + H dv]`` as the rule
    takes them: ``(q, k [N, H, dk], v [N, H, dv])``, q and k L2-normed per
    head, q scaled by ``dk^-1/2``."""
    n = qkv.shape[0]
    q = l2_norm(qkv[:, :d.d_key].reshape(n, d.lin_heads, d.dk)) \
        * float(d.dk) ** -0.5
    k = l2_norm(qkv[:, d.d_key:2 * d.d_key].reshape(n, d.lin_heads, d.dk))
    v = qkv[:, 2 * d.d_key:].reshape(n, d.lin_heads, d.dv)
    return q, k, v


def delta_layer_step(x, w, p: str, state, conv, ts, d):
    """One token of a linear layer for every row: ``x`` ``[N, d_model]``
    (the residual as it stands), ``state`` / ``conv`` the row's leaves.
    Returns ``(out [N, d_model], state, conv)``."""
    n = x.shape[0]
    q, k, v, conv = conv_qkv(x, w, p, conv, ts, d)
    alpha, beta = decay_and_step_gates(x, w, p, d)
    o, state = gated_delta_step(q, k, v, alpha, beta, state, ts)
    gate = linear(x, w[p + "lin_g"]).reshape(n, d.lin_heads, d.dv)
    y = gated_output_norm(o, gate, w[p + "lin_norm"], d.eps)
    return linear(y.reshape(n, d.d_value), w[p + "lin_o"]), state, conv


def full_attention_rows(x, w, p: str, pos, d):
    """The fresh ``(q, k, v)`` rows of a full layer, ``[N, heads *
    head_dim]`` float32: q and k RMS-normed over the whole projection,
    rotated at ``pos`` only where the configuration has a ``rope_theta``."""
    n = x.shape[0]
    q = rms_norm(linear(x, w[p + "attn_q"]), w[p + "attn_q_norm"], d.eps)
    k = rms_norm(linear(x, w[p + "attn_k"]), w[p + "attn_k_norm"], d.eps)
    if d.rope_theta is not None:
        q = rotary(q.reshape(n, d.n_head, d.head_dim), pos,
                   d.rope_theta).reshape(n, d.d_q)
        k = rotary(k.reshape(n, d.n_kv_head, d.head_dim), pos,
                   d.rope_theta).reshape(n, d.d_kv)
    return q, k, linear(x, w[p + "attn_v"])


# --- Kimi Delta Attention beside gated GQA and routed experts
#     (``model_type: solar_open2``) ------------------------------------

#: endings of the ``solar_open2`` parameters kept in float32
KDA_FLOAT32_PARAMS = FLOAT32_PARAMS + ("router", "expert_bias")


def _first(cfg, *keys, default=None):
    """The value under the first of ``keys`` the config has (two
    releases name the same thing differently)."""
    for key in keys:
        if cfg.get(key) is not None:
            return cfg[key]
    return default


def kda_dims(cfg) -> SimpleNamespace:
    """The decoder's sizes from a config dict of a Kimi-Delta-Attention
    decoder with routed experts, under either release's published key
    names: ``solar_open2`` (``gqa_layers`` 0-indexed, ``n_routed_experts``,
    ``num_experts_per_tok``, ``n_shared_experts``, ``norm_topk_prob``) or
    ``kimi_linear`` (``linear_attn_config.kda_layers`` /
    ``full_attn_layers`` 1-INDEXED, ``num_experts``,
    ``num_experts_per_token``, ``num_shared_experts``,
    ``moe_renormalize``; ``first_k_dense_replace`` leading layers whose
    FFN is a dense SwiGLU of ``intermediate_size``).  The count of
    experts may be of those HELD here; the router's width is then
    ``n_routed_experts_all`` / ``num_experts_all``.  Carries what
    ``routed_experts.route`` / ``expert_layer`` / ``shared_expert`` read
    of a ``dims``.  What the layers that are not KDA are (gated GQA, or
    latent attention: ``kda_latent_lm.dims``) is the builder's."""
    g, lin = cfg.get, cfg["linear_attn_config"]
    n_layer = int(cfg["num_hidden_layers"])
    if lin.get("kda_layers") is not None:
        kda = set(int(i) - 1 for i in lin["kda_layers"])
        full = set(int(i) - 1 for i in lin["full_attn_layers"])
        if kda & full or kda | full != set(range(n_layer)):
            raise ValueError(
                "kda_layers and full_attn_layers (1-indexed) must name each "
                "of the %d layers once" % n_layer)
    else:
        full = set(int(i) for i in cfg["gqa_layers"])
        if full - set(range(n_layer)):
            raise ValueError("gqa_layers names a layer past "
                             "num_hidden_layers")
    n_dense = int(g("first_k_dense_replace", 0))
    o = SimpleNamespace(
        vocab=int(cfg["vocab_size"]), d_model=int(cfg["hidden_size"]),
        n_layer=n_layer,
        kinds=tuple(FULL if i in full else LINEAR for i in range(n_layer)),
        n_head=int(cfg["num_attention_heads"]),
        n_kv_head=int(cfg["num_key_value_heads"]),
        head_dim=int(cfg["head_dim"]),
        lin_heads=int(lin["num_heads"]), dk=int(lin["head_dim"]),
        dv=int(lin["head_dim"]),
        conv_len=int(lin["short_conv_kernel_size"]),
        neg_eigval=bool(g("kda_allow_neg_eigval", False)),
        attn_gate=bool(g("use_gqa_gate", False)),
        eps=float(g("rms_norm_eps", 1e-5)),
        rope_theta=float(cfg["rope_theta"]) if g("use_rope", True) else None,
        n_dense=n_dense, d_mlp=int(g("intermediate_size", 0)),
        d_expert=int(cfg["moe_intermediate_size"]),
        n_expert=int(_first(cfg, "n_routed_experts_all", "num_experts_all",
                            "n_routed_experts", "num_experts")),
        top_k=int(_first(cfg, "num_experts_per_tok",
                         "num_experts_per_token")),
        n_shared=int(_first(cfg, "n_shared_experts", "num_shared_experts",
                            default=0)),
        norm_topk=bool(_first(cfg, "norm_topk_prob", "moe_renormalize",
                              default=True)),
        routed_scale=float(g("routed_scaling_factor", 1.0)),
        scoring=SIGMOID_BIAS, gate_act=SILU, expert_bias=True)
    if lin.get("num_kv_heads") not in (None, o.lin_heads):
        raise ValueError("linear_attn_config.num_kv_heads: value heads "
                         "grouped over key heads are not built")
    if g("kda_use_full_proj", False):
        raise ValueError("kda_use_full_proj: only the low-rank gate "
                         "projections are built")
    if g("tie_word_embeddings", False):
        raise ValueError("a tied head is not supported")
    if float(g("partial_rotary_factor", 1)) != 1 and o.rope_theta is not None:
        raise ValueError("a partial rotary is not supported")
    if o.n_head % o.n_kv_head:
        raise ValueError("query heads must be a multiple of their KV heads")
    if not 0 <= n_dense <= n_layer:
        raise ValueError("first_k_dense_replace past num_hidden_layers")
    o.d_q, o.d_kv = o.n_head * o.head_dim, o.n_kv_head * o.head_dim
    o.d_key = o.d_value = o.lin_heads * o.dk
    o.d_qkv = 2 * o.d_key + o.d_value
    o.d_rank = o.dk            # the low rank of both gate pairs: head_dim
    o.tile_heads = heads_per_tile(o.lin_heads, o.dv)
    o.state_shape = (o.lin_heads // o.tile_heads, o.dk, o.tile_heads * o.dv)
    o.dense = tuple(i < n_dense for i in range(n_layer))
    o.expert_layers = tuple(i for i in range(n_layer) if not o.dense[i])
    return o


def kda_mixer_shapes(d, p: str) -> dict:
    """Names and shapes of ONE KDA layer's mixer under the prefix ``p``
    (both builders of such layers hold them alike)."""
    return {
        p + "lin_q": (d.d_model, d.d_key),
        p + "lin_k": (d.d_model, d.d_key),
        p + "lin_v": (d.d_model, d.d_value),
        p + "lin_conv_w": (d.conv_len, d.d_qkv),
        p + "lin_fa": (d.d_model, d.d_rank),
        p + "lin_fb": (d.d_rank, d.d_key),
        p + "lin_b": (d.d_model, d.lin_heads),
        p + "lin_A_log": (d.lin_heads,),
        p + "lin_dt_bias": (d.d_key,),
        p + "lin_ga": (d.d_model, d.d_rank),
        p + "lin_gb": (d.d_rank, d.d_value),
        p + "lin_norm": (d.dv,),
        p + "lin_o": (d.d_value, d.d_model)}


def kda_ffn_shapes(d, p: str, dense: bool, n_held: int) -> dict:
    """Names and shapes of ONE layer's FFN under the prefix ``p``: a
    dense SwiGLU, or the router (and its bias) at its whole width, the
    ``n_held`` held experts and the shared expert."""
    if dense:
        return {p + "ffn_gate": (d.d_model, d.d_mlp),
                p + "ffn_up": (d.d_model, d.d_mlp),
                p + "ffn_down": (d.d_mlp, d.d_model)}
    out = {p + "router": (d.d_model, d.n_expert),
           p + "expert_bias": (d.n_expert,),
           p + "experts_w13": (n_held, d.d_model, 2 * d.d_expert),
           p + "experts_w2": (n_held, d.d_expert, d.d_model)}
    if d.n_shared:
        out.update({
            p + "shared_w13": (d.d_model, 2 * d.n_shared * d.d_expert),
            p + "shared_w2": (d.n_shared * d.d_expert, d.d_model)})
    return out


def kda_param_shapes(cfg, name: str = "lm", held=None) -> dict:
    """Names and shapes of every weight the ``solar_open2`` step reads.
    Matrices are ``[in, out]``; the conv kernel and the experts' matrices
    as :func:`param_shapes` / ``routed_experts.param_shapes`` lay them;
    ``held = (lo, hi)``: the experts whose matrices are held (default
    all); the router and its bias keep their whole width."""
    d = kda_dims(cfg)
    n_held = d.n_expert if held is None else int(held[1]) - int(held[0])
    out = {name + "_emb": (d.vocab, d.d_model),
           name + "_final_norm": (d.d_model,),
           name + "_head": (d.d_model, d.vocab)}
    for i, kind in enumerate(d.kinds):
        p = "%s_l%d_" % (name, i)
        if kind == LINEAR:
            out.update(kda_mixer_shapes(d, p))
        else:
            out.update({
                p + "attn_q": (d.d_model, d.d_q),
                p + "attn_k": (d.d_model, d.d_kv),
                p + "attn_v": (d.d_model, d.d_kv),
                p + "attn_o": (d.d_q, d.d_model)})
            if d.attn_gate:
                out[p + "attn_gate"] = (d.d_model, d.d_q)
        out.update({
            p + "mixer_norm": (d.d_model,), p + "ffn_norm": (d.d_model,)})
        out.update(kda_ffn_shapes(d, p, d.dense[i], n_held))
    return out


def kda_random_state(rng, cfg, name: str = "lm", std: float = 0.02,
                     dtype="float32", held=None, gate_std: float = 1.0,
                     bias_range: float = 0.05) -> dict:
    """Seeded random weights under :func:`kda_param_shapes` (tests): as
    :func:`random_state`, with a float32 router, a selection bias uniform
    in ``+-bias_range`` (NOT zero) and the decay pair's second matrix
    ``lin_fb`` normal(0, ``gate_std``) so that the channels of ONE head
    decay differently (else a decay a channel cannot be told from a decay
    a head)."""
    return kda_random_weights(rng, kda_param_shapes(cfg, name, held), std,
                              dtype, gate_std, bias_range)


def kda_random_weights(rng, shapes, std, dtype, gate_std, bias_range) -> dict:
    """:func:`kda_random_state`'s rules over ``shapes`` (name -> shape):
    any schema whose mixers are KDA layers."""
    import jax.numpy as jnp

    w = {}
    for k, shp in shapes.items():
        if k.endswith(FLOAT32_PARAMS):
            w[k] = _random_vector(rng, k, shp)
        elif k.endswith("expert_bias"):
            w[k] = rng.uniform(-bias_range, bias_range, shp).astype("float32")
        elif k.endswith("router"):
            w[k] = (rng.randn(*shp) * std).astype("float32")
        else:
            s = (1.0 if k.endswith("_emb") else gate_std
                 if k.endswith("lin_fb") else std)
            w[k] = jnp.asarray((rng.randn(*shp) * s).astype("float32"), dtype)
    return w


def channel_decay(x, w, p: str, d):
    """``(alpha [N, H, dk], beta [N, H])`` float32 of a KDA layer: the
    decay a key CHANNEL through the low-rank pair ``lin_fa`` / ``lin_fb``,
    ``A_log`` a head and ``dt_bias`` a channel, and the write's step in
    (0, 1), or (0, 2) where negative eigenvalues are allowed."""
    import jax
    import jax.numpy as jnp

    n = x.shape[0]
    a = linear(linear(x, w[p + "lin_fa"]), w[p + "lin_fb"])
    dt = jax.nn.softplus(a + w[p + "lin_dt_bias"]).reshape(
        n, d.lin_heads, d.dk)
    alpha = jnp.exp(-jnp.exp(w[p + "lin_A_log"])[None, :, None] * dt)
    beta = jax.nn.sigmoid(linear(x, w[p + "lin_b"]))
    return alpha, beta * 2.0 if d.neg_eigval else beta


def kda_layer_step(x, w, p: str, state, conv, ts, d):
    """One token of a KDA layer for every row: ``x`` ``[N, d_model]``
    (the NORMED residual), ``state`` / ``conv`` the row's leaves (as
    :func:`delta_layer_step`'s).  Returns ``(out [N, d_model], state,
    conv)``."""
    import jax

    n = x.shape[0]
    q, k, v, conv = conv_qkv(x, w, p, conv, ts, d)
    with jax.named_scope(CHANNEL_GATES_SCOPE):
        alpha, beta = channel_decay(x, w, p, d)
        gate = linear(linear(x, w[p + "lin_ga"]), w[p + "lin_gb"]).reshape(
            n, d.lin_heads, d.dv)
    o, state = gated_delta_step(q, k, v, alpha, beta, state, ts)
    y = gated_output_norm(o, gate, w[p + "lin_norm"], d.eps, jax.nn.sigmoid)
    return linear(y.reshape(n, d.d_value), w[p + "lin_o"]), state, conv


def kda_layer_chunk(x, w, p: str, state, conv, start, n_valid, d):
    """:func:`kda_layer_step` over ``C`` positions of ONE row, the rule in
    its chunkwise form: ``x`` ``[C, d_model]`` (the NORMED residual at
    ``start .. start + C - 1``, the first ``n_valid`` count), ``state``
    ``[H / g, dk, g * dv]`` / ``conv`` ``[K - 1, channels]`` the row's
    leaves before the chunk.  Returns ``(out [C, d_model], state, conv)``,
    the leaves after ``n_valid`` positions."""
    import jax

    c = x.shape[0]
    qkv, conv = qkv_conv_chunk(_project_qkv(x, w, p), w[p + "lin_conv_w"],
                               conv, start, n_valid)
    q, k, v = _rule_inputs(qkv, d)
    with jax.named_scope(CHANNEL_GATES_SCOPE):
        alpha, beta = channel_decay(x, w, p, d)
        gate = linear(linear(x, w[p + "lin_ga"]), w[p + "lin_gb"]).reshape(
            c, d.lin_heads, d.dv)
    o, state = gated_delta_chunk(q, k, v, alpha, beta, state, start, n_valid)
    y = gated_output_norm(o, gate, w[p + "lin_norm"], d.eps, jax.nn.sigmoid)
    return linear(y.reshape(c, d.d_value), w[p + "lin_o"]), state, conv


def gated_attention_rows(x, w, p: str, pos, d):
    """``(q, k, v, gate)`` of a G layer for the rows ``x`` (the NORMED
    residual): the fresh rows ``[N, heads * head_dim]`` float32, rotated
    at ``pos`` only where the configuration uses rotary (``solar_open2``:
    ``use_rope`` false), and the sigmoid gate over the attention's output
    lanes (None where ``use_gqa_gate`` is false)."""
    import jax

    n = x.shape[0]
    q, k = linear(x, w[p + "attn_q"]), linear(x, w[p + "attn_k"])
    if d.rope_theta is not None:
        q = rotary(q.reshape(n, d.n_head, d.head_dim), pos,
                   d.rope_theta).reshape(n, d.d_q)
        k = rotary(k.reshape(n, d.n_kv_head, d.head_dim), pos,
                   d.rope_theta).reshape(n, d.d_kv)
    gate = (jax.nn.sigmoid(linear(x, w[p + "attn_gate"])) if d.attn_gate
            else None)
    return q, k, linear(x, w[p + "attn_v"]), gate
