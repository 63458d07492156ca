"""Plain reference for ``olmo_hybrid_7b``: the full causal forward of an
``olmo_hybrid`` decoder in float32 ``jax.numpy`` at matmul precision
"highest".  No cache, no conv state, no kernels, no batching tricks,
nothing from ``paddle_tpu``: the short convolution is a padded causal
convolution over the whole sequence, the gated delta rule a ``lax.scan``
over time with one ``[dk, dv]`` state a head, full attention a masked
softmax over the whole sequence.

The equations (``x`` the residual, ``RMS`` RMSNorm with weight, eps from
the config, no bias anywhere; ``H`` heads of ``dk`` key and ``dv`` value
lanes in a linear layer)::

    h0     = E[ids]
    x      = x + RMS(mixer(x))           # no norm before a branch
    x      = x + RMS(W_down(silu(W_gate x) * W_up x))
    logits = RMS_f(x) @ W_head

    linear layer, per head and token t:
      [q; k; v]_t = silu(sum_j w_conv[j] * ([W_q; W_k; W_v] x)_{t-(K-1)+j})
      q = q / sqrt(|q|^2 + 1e-6) / sqrt(dk),   k = k / sqrt(|k|^2 + 1e-6)
      beta_t  = sigmoid(W_b x)  (x 2: linear_allow_neg_eigval)
      alpha_t = exp(-exp(A_log) * softplus(W_a x + dt_bias))
      S <- alpha_t S;  u = S^T k_t;  S <- S + k_t (beta_t (v_t - u))^T
      o_t = S^T q_t;   y_t = RMS_dv(o_t) * silu(W_g x);   out = W_o [y_t]
    full layer:
      q = RMS(W_q x), k = RMS(W_k x) over the whole projection, v = W_v x;
      causal softmax at 1 / sqrt(head_dim), head j of n_head reads K/V head
      j // (n_head / n_kv_head); rotate-half rotary over the whole head
      ONLY where ``rope_parameters.rope_theta`` is a number (the published
      file gives null: none).

Departures from the published description, each an assumption the
catalog's row is silent on (``assumed`` in the configuration file says
why): ``head_dim`` = hidden / heads; the block is the family's (OLMo 2 /
3: a branch is closed by its norm and nothing norms its input, q and k
normed before the heads are split); ``rope_theta: null`` read as it
stands; the initial ``A_log`` / ``dt_bias`` are the reference layer's
(``fla.layers.GatedDeltaNet``); one output-norm weight ``[dv]`` for
every head.

Weights come in under the names the served program uses (``lm_emb``,
``lm_l<i>_lin_q`` ...; matrices ``[in, out]``, the conv kernel ``[K,
channels]`` over ``[q; k; v]``, oldest tap first) and in the dtype it
serves them in (bf16): they are upcast here, one layer at a time
(``block`` takes one layer's weights), the SwiGLU in slices of its width
and the head in slices of the vocabulary (``head_stats``), so the check
fits beside 6.5 GB of served weights.  That naming is the only thing
shared with the system under test.

Tolerance (``check.mean_gap_share`` 0.00003 and ``check.worst_gap_share``
0.03 in the config).  The served step rounds each matmul's activations
to bf16 and keeps K/V in bf16; this forward keeps them in float32 (a
dense model has no marginal expert to flip, so the operands are NOT
rounded here).  With random weights the top logits of 100,352 sit closer
than that rounding, so tokens cannot be compared; logits can: a served
token's gap is how far its reference logit lies under the position's
maximum, as a share of that position's logit range (max - min).  One
bound cannot do both jobs: the WORST of some 3,500 tokens is one near
tie the two programs break differently, a maximum that grows with the
sample, while a lower precision moves EVERY token a little.  So the mean
is held tight and the worst loose.  Both lie between readings taken on
the chip at the published widths (PR 50; the configuration's README and
PERF.md section 4 have them all), mean / worst: the largest the served
path gave over 24 runs of the cell and the test (0.0000068 / 0.0020),
and what the same path gives with every matrix rounded to int8, the
nearest precision below the bf16 the configuration states (0.00016 /
0.0083) — which has to come out as not correct, and does, by the mean
and not by the worst, as do (by both) a step that skips the state reset
of a reused slot, a conv window read one position late, ``beta``
without its factor 2, the delta term or the decay left out, the L2
norms or the output gate left out, and rotary in the full layers:
``benchmark/tests/test_delta_hybrid_check.py``.  What the bounds cannot
hold is the dtype of the delta-rule state: rounded to bf16 after every
step it reads 0.000011 / 0.0024, twice the unharmed mean and inside
both limits (Falcon's SSM state is not told either).

Two more conditions ride with them (the family's ``check_against_
reference``): at least half of the sampled requests sat in a slot
another request had left, and every branch of every block is at least 1%
of the residual it is added to by ``block``'s own measure (on the chip
13-100%: a branch is closed by a norm of weight 1).
"""
import jax
import jax.numpy as jnp

F32 = jnp.float32
LINEAR, FULL = "linear_attention", "full_attention"


def _f(a):
    return jnp.asarray(a).astype(F32)


def _rms(x, w, eps):
    return x / jnp.sqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * _f(w)


def _l2(x):
    return x / jnp.sqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def _rope(x, theta):
    """x [B, S, H, D] at positions 0..S-1, rotate-half over all of D."""
    s, half = x.shape[1], x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def full_attention(w, p, x, cfg):
    b, s, d = x.shape
    nh, nkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    dh = int(cfg.get("head_dim") or d // nh)
    eps = float(cfg["rms_norm_eps"])
    q = _rms(x @ _f(w[p + "attn_q"]), w[p + "attn_q_norm"], eps)
    k = _rms(x @ _f(w[p + "attn_k"]), w[p + "attn_k_norm"], eps)
    q, k = q.reshape(b, s, nh, dh), k.reshape(b, s, nkv, dh)
    v = (x @ _f(w[p + "attn_v"])).reshape(b, s, nkv, dh)
    theta = (cfg.get("rope_parameters") or {}).get("rope_theta")
    if theta is not None:
        q, k = _rope(q, float(theta)), _rope(k, float(theta))
    k, v = (jnp.repeat(t, nh // nkv, axis=2) for t in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(dh))
    causal = jnp.tril(jnp.ones((s, s), bool))[None, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e9), axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, nh * dh)
    return ctx @ _f(w[p + "attn_o"])


def gated_delta_net(w, p, x, cfg):
    b, s, _ = x.shape
    heads, dk, dv, kc = (int(cfg[k]) for k in (
        "linear_num_value_heads", "linear_key_head_dim",
        "linear_value_head_dim", "linear_conv_kernel_dim"))
    qkv = jnp.concatenate([x @ _f(w[p + "lin_q"]), x @ _f(w[p + "lin_k"]),
                           x @ _f(w[p + "lin_v"])], axis=-1)
    # causal depthwise conv: y_t = sum_j w[j] * x_{t - (kc - 1) + j}
    padded = jnp.pad(qkv, ((0, 0), (kc - 1, 0), (0, 0)))
    cw = _f(w[p + "lin_conv_w"])
    qkv = jax.nn.silu(sum(padded[:, j:j + s] * cw[j] for j in range(kc)))
    q = _l2(qkv[..., :heads * dk].reshape(b, s, heads, dk)) / jnp.sqrt(F32(dk))
    k = _l2(qkv[..., heads * dk:2 * heads * dk].reshape(b, s, heads, dk))
    v = qkv[..., 2 * heads * dk:].reshape(b, s, heads, dv)
    beta = jax.nn.sigmoid(x @ _f(w[p + "lin_b"]))              # [B, S, H]
    if cfg.get("linear_allow_neg_eigval"):
        beta = 2.0 * beta
    dt = jax.nn.softplus(x @ _f(w[p + "lin_a"]) + _f(w[p + "lin_dt_bias"]))
    alpha = jnp.exp(-jnp.exp(_f(w[p + "lin_A_log"])) * dt)

    def step(state, inp):                       # state [B, H, dk, dv]
        q_t, k_t, v_t, a_t, b_t = inp
        state = a_t[:, :, None, None] * state
        u = jnp.einsum("bhkv,bhk->bhv", state, k_t)
        state = state + k_t[..., None] * (b_t[..., None] * (v_t - u))[
            :, :, None, :]
        return state, jnp.einsum("bhkv,bhk->bhv", state, q_t)

    t_first = lambda t: jnp.moveaxis(t, 1, 0)                  # noqa: E731
    _, o = jax.lax.scan(step, jnp.zeros((b, heads, dk, dv), F32),
                        tuple(t_first(t) for t in (q, k, v, alpha, beta)))
    o = jnp.moveaxis(o, 0, 1)                                  # [B, S, H, dv]
    gate = (x @ _f(w[p + "lin_g"])).reshape(b, s, heads, dv)
    y = _rms(o, w[p + "lin_norm"], float(cfg["rms_norm_eps"])) \
        * jax.nn.silu(gate)
    return y.reshape(b, s, heads * dv) @ _f(w[p + "lin_o"])


def _size(t):
    return jnp.sqrt(jnp.mean(t * t))


def embed(w, tokens, cfg, name="lm"):
    return _f(w[name + "_emb"][tokens])


def mlp(w, p, v, blocks=1):
    """SwiGLU.  ``blocks`` > 1 takes the intermediate width in that many
    equal slices, one after another (each slice's down-projection adds
    into the same sum), so that only one slice of the three matrices is
    upcast at a time: the same products, a smaller footprint."""
    wg, wu, wd = w[p + "mlp_gate"], w[p + "mlp_up"], w[p + "mlp_down"]
    n = wg.shape[1] // blocks
    assert n * blocks == wg.shape[1], "blocks must divide the MLP width"

    out = jnp.zeros_like(v)
    for j in range(blocks):   # static slices: no copy of a whole matrix
        cols = slice(j * n, (j + 1) * n)
        out = out + (jax.nn.silu(v @ _f(wg[:, cols]))
                     * (v @ _f(wu[:, cols]))) @ _f(wd[cols, :])
    return out


def block(w, i, h, cfg, kind, name="lm", mlp_blocks=1):
    """One block of ``kind`` over ``h`` [B, S, D]; ``w`` needs only layer
    ``i``'s weights.  Returns ``(h, shares)``: the rms of the mixer's and
    of the MLP's contribution over the rms of the residual each is added
    to."""
    with jax.default_matmul_precision("highest"):
        p = "%s_l%d_" % (name, i)
        eps = float(cfg["rms_norm_eps"])
        mixer = gated_delta_net if kind == LINEAR else full_attention
        mix = _rms(mixer(w, p, h, cfg), w[p + "mixer_norm"], eps)
        mid = h + mix
        out = _rms(mlp(w, p, mid, mlp_blocks), w[p + "mlp_norm"], eps)
        shares = jnp.stack([_size(mix) / _size(h), _size(out) / _size(mid)])
        return mid + out, shares


def head(w, h, cfg, name="lm"):
    """All logits [B, S, V] (small vocabularies: the CPU tests)."""
    with jax.default_matmul_precision("highest"):
        x = _rms(h, w[name + "_final_norm"], float(cfg["rms_norm_eps"]))
        return x @ _f(w[name + "_head"])


def head_stats(w, h, targets, cfg, blocks, name="lm"):
    """What the check needs of the logits at every position without
    holding them: ``(max, min, argmax, logit of targets)``, each [B, S],
    the head taken in ``blocks`` equal slices of the vocabulary."""
    with jax.default_matmul_precision("highest"):
        x = _rms(h, w[name + "_final_norm"], float(cfg["rms_norm_eps"]))
        wh = w[name + "_head"]
        vb = wh.shape[1] // blocks
        assert vb * blocks == wh.shape[1], "blocks must divide the vocabulary"

        shp = targets.shape
        hi, lo = jnp.full(shp, -jnp.inf, F32), jnp.full(shp, jnp.inf, F32)
        arg, got = jnp.zeros(shp, jnp.int32), jnp.zeros(shp, F32)
        for j in range(blocks):   # static slices: no copy of the head
            lg = x @ _f(wh[:, j * vb:(j + 1) * vb])             # [B, S, vb]
            bmax = lg.max(-1)
            arg = jnp.where(bmax > hi, j * vb + lg.argmax(-1), arg)
            local = targets - j * vb
            picked = jnp.take_along_axis(
                lg, jnp.clip(local, 0, vb - 1)[..., None], -1)[..., 0]
            got = jnp.where((local >= 0) & (local < vb), picked, got)
            hi, lo = jnp.maximum(hi, bmax), jnp.minimum(lo, lg.min(-1))
        return hi, lo, arg, got


def forward(w, tokens, cfg, name="lm"):
    """tokens [B, S] int32 -> logits [B, S, V]; position s sees
    positions <= s."""
    h = embed(w, tokens, cfg, name)
    for i, kind in enumerate(cfg["layer_types"]):
        h, _ = block(w, i, h, cfg, kind, name)
    return head(w, h, cfg, name)
