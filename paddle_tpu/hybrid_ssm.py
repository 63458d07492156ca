"""The parts of a hybrid decoder block: a Mamba-2 mixer beside
grouped-query attention on the same normed input, then a SwiGLU MLP
(``model_type: falcon_h1``), as small functions of ONE token per row.

    u = RMS_1(h)
    h = h + ssm_out_multiplier * Mamba(ssm_in_multiplier * u)
          + attention_out_multiplier * Attn(attention_in_multiplier * u)
    h = h + mlp_multipliers[1] * W_down(silu(mlp_multipliers[0] * W_gate v)
                                        * W_up v),      v = RMS_2(h)

``decoding.make_hybrid_ssm_lm_pooled_step_fn`` strings them into the
slot-pooled step ``step_fn(cache, tokens [N], ts [N])``; nothing here
knows a pool or a server.  Weights are multiplied in the dtype they are
given (bf16 as stored: bf16 products, fp32 accumulation through
``preferred_element_type``) — no weight is converted per step.  Norms,
rotary angles, softmax and the SSM recurrence run in fp32.

Per layer a row carries two kinds of state:

* ``k`` / ``v`` ``[N, T, n_kv_head * head_dim]`` — positions, appended
  in place at ``ts[n]`` and read ``0..ts[n]`` through
  ``decode_attention``'s contract (write-before-read: a row reads only
  what it wrote itself);
* ``ssm`` ``[N, n_heads, d_head, d_state]`` and ``conv`` ``[N, d_conv -
  1, d_ssm + 2 * n_groups * d_state]`` — RECURRENT: read and re-written
  whole every step, so nothing protects a row from its slot's previous
  occupant.  :func:`mamba2_step` therefore reads zeros where
  :func:`starts_fresh` says so (``ts == 0``), whoever held the row
  before, and leaves an idle row (``ts < 0``) as it was.
"""
from __future__ import annotations

from types import SimpleNamespace

import numpy as np

__all__ = ["dims", "param_shapes", "random_state", "rms_norm", "rotary",
           "swiglu", "mamba2_step", "starts_fresh", "linear",
           "SSM_UPDATE_SCOPE"]

#: the ``jax.named_scope`` around the state update, for the device trace
SSM_UPDATE_SCOPE = "ssm_state_update"


def dims(cfg) -> SimpleNamespace:
    """The block's sizes and scalars from a ``falcon_h1`` config dict
    (the published key names)."""
    g = lambda k, d=None: cfg[k] if d is None else cfg.get(k, d)
    o = SimpleNamespace(
        vocab=int(g("vocab_size")), d_model=int(g("hidden_size")),
        n_layer=int(g("num_hidden_layers")),
        n_head=int(g("num_attention_heads")),
        n_kv_head=int(g("num_key_value_heads")),
        head_dim=int(g("head_dim")), d_mlp=int(g("intermediate_size")),
        d_ssm=int(g("mamba_d_ssm")), ssm_heads=int(g("mamba_n_heads")),
        ssm_head_dim=int(g("mamba_d_head")),
        d_state=int(g("mamba_d_state")), n_groups=int(g("mamba_n_groups")),
        d_conv=int(g("mamba_d_conv")), eps=float(g("rms_norm_eps", 1e-5)),
        rope_theta=float(g("rope_theta")),
        embedding_multiplier=float(g("embedding_multiplier", 1.0)),
        lm_head_multiplier=float(g("lm_head_multiplier", 1.0)),
        attention_in_multiplier=float(g("attention_in_multiplier", 1.0)),
        attention_out_multiplier=float(g("attention_out_multiplier", 1.0)),
        key_multiplier=float(g("key_multiplier", 1.0)),
        ssm_in_multiplier=float(g("ssm_in_multiplier", 1.0)),
        ssm_out_multiplier=float(g("ssm_out_multiplier", 1.0)),
        ssm_multipliers=tuple(float(x) for x in g(
            "ssm_multipliers", [1.0] * 5)),
        mlp_multipliers=tuple(float(x) for x in g(
            "mlp_multipliers", [1.0, 1.0])))
    if o.ssm_heads * o.ssm_head_dim != o.d_ssm:
        raise ValueError("mamba_n_heads * mamba_d_head != mamba_d_ssm")
    if o.n_head % o.n_kv_head or o.ssm_heads % o.n_groups:
        raise ValueError("heads must divide into their KV heads / groups")
    o.d_kv = o.n_kv_head * o.head_dim
    o.d_xbc = o.d_ssm + 2 * o.n_groups * o.d_state
    o.d_in_proj = o.d_ssm + o.d_xbc + o.ssm_heads
    return o


def param_shapes(cfg, name: str = "lm") -> dict:
    """Names and shapes of every weight the step reads: the one place
    the schema lives.  Matrices are ``[in, out]``; the depthwise conv
    kernel is ``[d_conv, channels]``, oldest tap first."""
    d = dims(cfg)
    out = {name + "_emb": (d.vocab, d.d_model),
           name + "_final_norm": (d.d_model,),
           name + "_head": (d.d_model, d.vocab)}
    for i in range(d.n_layer):
        p = "%s_l%d_" % (name, i)
        out.update({
            p + "norm1": (d.d_model,), p + "norm2": (d.d_model,),
            p + "attn_q": (d.d_model, d.n_head * d.head_dim),
            p + "attn_k": (d.d_model, d.d_kv),
            p + "attn_v": (d.d_model, d.d_kv),
            p + "attn_o": (d.n_head * d.head_dim, d.d_model),
            p + "ssm_in": (d.d_model, d.d_in_proj),
            p + "ssm_conv_w": (d.d_conv, d.d_xbc),
            p + "ssm_conv_b": (d.d_xbc,),
            p + "ssm_dt_bias": (d.ssm_heads,),
            p + "ssm_A_log": (d.ssm_heads,), p + "ssm_D": (d.ssm_heads,),
            p + "ssm_norm": (d.d_ssm,),
            p + "ssm_out": (d.d_ssm, d.d_model),
            p + "mlp_gate": (d.d_model, d.d_mlp),
            p + "mlp_up": (d.d_model, d.d_mlp),
            p + "mlp_down": (d.d_mlp, d.d_model)})
    return out


def random_state(rng, cfg, name: str = "lm", std: float = 0.02,
                 dtype="float32") -> dict:
    """Seeded random weights under :func:`param_shapes` (tests, benches):
    normal matrices, unit norms, zero conv bias, Mamba-2's published
    ``A_log`` / ``D`` / ``dt_bias`` initialisation.  Vectors stay fp32;
    matrices take ``dtype``."""
    import jax.numpy as jnp

    w = {}
    for k, shp in param_shapes(cfg, name).items():
        if k.endswith(("norm1", "norm2", "_final_norm", "ssm_norm", "ssm_D")):
            w[k] = np.ones(shp, "float32")
        elif k.endswith("ssm_conv_b"):
            w[k] = np.zeros(shp, "float32")
        elif k.endswith("ssm_A_log"):
            w[k] = np.log(rng.uniform(1.0, 16.0, shp)).astype("float32")
        elif k.endswith("ssm_dt_bias"):
            dt = np.exp(rng.uniform(np.log(1e-3), np.log(1e-1), shp))
            w[k] = (dt + np.log(-np.expm1(-dt))).astype("float32")
        elif k.endswith("ssm_conv_w"):
            w[k] = (rng.randn(*shp) * 0.3).astype("float32")
        else:
            w[k] = jnp.asarray((rng.randn(*shp) * std).astype("float32"),
                               dtype)
    return w


def linear(x, w):
    """``x @ w`` in ``w``'s dtype, accumulated in fp32."""
    import jax.numpy as jnp

    return jnp.dot(x.astype(w.dtype), w, preferred_element_type=jnp.float32)


def rms_norm(x, w, eps: float, groups: int = 1):
    """RMSNorm with weight, in fp32; ``groups`` > 1 normalises each of
    that many equal slices of the last axis by its own mean square."""
    import jax.numpy as jnp

    x = x.astype(jnp.float32)
    shp = x.shape
    xg = x.reshape(shp[:-1] + (groups, shp[-1] // groups))
    ms = jnp.mean(xg * xg, axis=-1, keepdims=True)
    return (xg / jnp.sqrt(ms + eps)).reshape(shp) * w.astype(jnp.float32)


def rotary(x, pos, theta: float):
    """Rotate-half rotary embedding over the whole head: ``x`` ``[N,
    heads, head_dim]`` at per-row positions ``pos`` ``[N]``."""
    import jax.numpy as jnp

    half = x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]       # [N, half]
    cos = jnp.concatenate([jnp.cos(ang)] * 2, axis=-1)[:, None, :]
    sin = jnp.concatenate([jnp.sin(ang)] * 2, axis=-1)[:, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def swiglu(v, w_gate, w_up, w_down, gate_mult: float, down_mult: float):
    import jax

    h = jax.nn.silu(gate_mult * linear(v, w_gate)) * linear(v, w_up)
    return down_mult * linear(h, w_down)


def starts_fresh(ts):
    """Rows whose recurrent state must be taken as zero: a row at
    position 0 begins a sequence, whatever its slot held before."""
    return ts == 0


def mamba2_step(x, w, p: str, ssm, conv, ts, d):
    """One token of the Mamba-2 mixer for every row.

    ``x`` ``[N, d_model]`` (already scaled by ``ssm_in_multiplier``);
    ``w`` the weight dict, ``p`` the layer's key prefix; ``ssm`` ``[N,
    heads, d_head, d_state]`` and ``conv`` ``[N, d_conv - 1, d_xbc]`` the
    row's recurrent state; ``ts`` ``[N]`` (``< 0`` idle, ``0`` a fresh
    sequence); ``d`` from :func:`dims`.  Returns ``(out [N, d_model],
    ssm, conv)``.  The recurrence is computed in fp32 whatever dtype the
    state is stored in."""
    import jax
    import jax.numpy as jnp

    f32 = jnp.float32
    n = x.shape[0]
    live, fresh = ts >= 0, starts_fresh(ts)
    m = np.repeat(np.asarray(d.ssm_multipliers, "float32"),
                  [d.d_ssm, d.d_ssm, d.n_groups * d.d_state,
                   d.n_groups * d.d_state, d.ssm_heads])
    zxbcdt = linear(x, w[p + "ssm_in"]) * m
    z = zxbcdt[:, :d.d_ssm]
    xbc = zxbcdt[:, d.d_ssm:d.d_ssm + d.d_xbc]
    dt = zxbcdt[:, d.d_ssm + d.d_xbc:]
    # causal depthwise conv over the last d_conv pre-conv rows
    prev = jnp.where(fresh[:, None, None], 0.0, conv.astype(f32))
    window = jnp.concatenate([prev, xbc[:, None, :]], axis=1)
    conv_new = jnp.where(live[:, None, None], window[:, 1:], conv.astype(f32))
    xbc = jax.nn.silu(jnp.sum(window * w[p + "ssm_conv_w"][None], axis=1)
                      + w[p + "ssm_conv_b"])
    gn = d.n_groups * d.d_state
    xs = xbc[:, :d.d_ssm].reshape(n, d.ssm_heads, d.ssm_head_dim)
    per = d.ssm_heads // d.n_groups
    b = jnp.repeat(xbc[:, d.d_ssm:d.d_ssm + gn].reshape(
        n, d.n_groups, d.d_state), per, axis=1)               # [N, H, Nst]
    c = jnp.repeat(xbc[:, d.d_ssm + gn:].reshape(
        n, d.n_groups, d.d_state), per, axis=1)
    dt = jax.nn.softplus(dt + w[p + "ssm_dt_bias"])             # [N, H]
    decay = jnp.exp(dt * -jnp.exp(w[p + "ssm_A_log"]))
    with jax.named_scope(SSM_UPDATE_SCOPE):
        s_prev = jnp.where(fresh[:, None, None, None], 0.0, ssm.astype(f32))
        s_new = (decay[:, :, None, None] * s_prev
                 + (dt[:, :, None] * xs)[..., None] * b[:, :, None, :])
        y = jnp.sum(s_new * c[:, :, None, :], axis=-1)
        ssm_new = jnp.where(live[:, None, None, None], s_new,
                            ssm.astype(f32)).astype(ssm.dtype)
    y = (y + w[p + "ssm_D"][None, :, None] * xs).reshape(n, d.d_ssm)
    y = rms_norm(y * jax.nn.silu(z), w[p + "ssm_norm"], d.eps, d.n_groups)
    return linear(y, w[p + "ssm_out"]), ssm_new, conv_new.astype(conv.dtype)
