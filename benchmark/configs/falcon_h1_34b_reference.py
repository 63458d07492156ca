"""Plain reference for ``falcon_h1_34b``: the full causal forward of a
``falcon_h1`` decoder in float32 ``jax.numpy`` at matmul precision
"highest".  No cache, no chunked scan, no batching tricks, nothing from
``paddle_tpu``: attention is a masked softmax over the whole sequence and
the Mamba-2 mixer a ``lax.scan`` over time, one token a step.

The equations (``h`` the residual, ``RMS`` RMSNorm with weight, eps from
the config, no bias but the conv's):

    h0     = E[ids] * embedding_multiplier
    u      = RMS_1(h)
    h      = h + ssm_out_multiplier * Mamba(ssm_in_multiplier * u)
               + attention_out_multiplier * Attn(attention_in_multiplier * u)
    v      = RMS_2(h)
    h      = h + mlp_multipliers[1] * W_down(silu(mlp_multipliers[0] * W_gate v)
                                             * W_up v)
    logits = (RMS_f(h) @ W_head) * lm_head_multiplier

    Attn:  q = W_q x, k = key_multiplier * W_k x, v = W_v x; rotary
           (rotate-half over the whole head, theta from the config) on q
           and k; causal softmax at 1 / sqrt(head_dim); query head j reads
           KV head j // (n_head / n_kv_head).
    Mamba: [z | xBC | dt] = (W_in x) * m, m scaling the segments z, x, B,
           C, dt by ssm_multipliers[0..4]; xBC = silu(causal depthwise
           conv1d(xBC) + bias); dt = softplus(dt + dt_bias), A = -exp(A_log);
           per head i of group g:  S_t = exp(dt A) S_{t-1} + dt x_t (x) B_t[g],
           y_t = S_t C_t[g] + D x_t;  y = RMS_grouped(y * silu(z));  W_out y.

Weights come in under the names the served program uses (``lm_emb``,
``lm_l<i>_attn_q`` ...; matrices ``[in, out]``, the conv kernel ``[d_conv,
channels]`` oldest tap first) and in the dtype it serves them in (bf16):
they are upcast here, one layer at a time (``block`` takes one layer's
weights), and the head is taken in vocabulary blocks (``head_stats``), so
the check fits beside 10.5 GB of served weights.  That naming is the only
thing shared with the system under test.

Where the multipliers sit is an assumption (the catalog gives the
scalars, not their place): see ``assumed.multiplier_placement`` in the
config file.

Tolerance (``check.logit_gap_share`` in the config: 0.01).  The served
step rounds each matmul's activations to bf16 and keeps K/V in bf16; this
forward keeps them in float32.  With random weights the top logits of
261,120 sit closer than that rounding, so tokens cannot be compared;
logits can: each served token's reference logit must lie within the
stated share of that position's logit range (max - min) of the
position's maximum.  The share lies between two readings taken on the
chip at the published widths (PERF.md section 4, PR 27): the largest the
served path gave over its seeds (0.002), and what the same path gives
with every matrix rounded to int8, the nearest precision below the bf16
the configuration states (0.064) — which has to come out as not correct,
and does, as do a step that skips the state reset of a reused slot (0.09)
and a conv window read one position late (0.7):
``benchmark/tests/test_hybrid_ssm_check.py``.  What the bound cannot
hold is the dtype of the SSM state: kept in bf16 it reads 0.0007-0.0012,
as the float32 state does, because rounding the state each step is an
error of the size the configured path makes at every matmul input.

Two more conditions ride with it (the family's ``check_against_
reference``): at least half of the sampled requests sat in a slot
another request had left, and every branch of every block is at least 1%
of the residual it is added to by ``block``'s own measure (a smaller
branch the comparison could not see).  For the second the x, B, C
columns of the mixer's in-projection are drawn wider than
``initializer_range`` (``assumed.ssm_in_xbc_std``): at 0.02 the state's
path S.C is 0.08% of the skip path D.x and no served token depends on the
recurrent state at all.
"""
import jax
import jax.numpy as jnp

F32 = jnp.float32


def _f(a):
    return jnp.asarray(a).astype(F32)


def _rms(x, w, eps, groups=1):
    shp = x.shape
    xg = x.reshape(shp[:-1] + (groups, shp[-1] // groups))
    xg = xg / jnp.sqrt(jnp.mean(xg * xg, axis=-1, keepdims=True) + eps)
    return xg.reshape(shp) * _f(w)


def _rope(x, theta):
    """x [B, S, H, D] at positions 0..S-1, rotate-half over all of D."""
    s, half = x.shape[1], x.shape[-1] // 2
    inv = theta ** (-jnp.arange(half, dtype=F32) / half)
    ang = jnp.arange(s, dtype=F32)[:, None] * inv[None, :]
    cos = jnp.concatenate([jnp.cos(ang), jnp.cos(ang)], -1)[None, :, None, :]
    sin = jnp.concatenate([jnp.sin(ang), jnp.sin(ang)], -1)[None, :, None, :]
    rot = jnp.concatenate([-x[..., half:], x[..., :half]], axis=-1)
    return x * cos + rot * sin


def attention(w, p, x, cfg):
    b, s, _ = x.shape
    nh, nkv, dh = (int(cfg[k]) for k in (
        "num_attention_heads", "num_key_value_heads", "head_dim"))
    q = (x @ _f(w[p + "attn_q"])).reshape(b, s, nh, dh)
    k = (float(cfg["key_multiplier"])
         * (x @ _f(w[p + "attn_k"]))).reshape(b, s, nkv, dh)
    v = (x @ _f(w[p + "attn_v"])).reshape(b, s, nkv, dh)
    q, k = _rope(q, float(cfg["rope_theta"])), _rope(k, float(cfg["rope_theta"]))
    k, v = (jnp.repeat(t, nh // nkv, axis=2) for t in (k, v))
    scores = jnp.einsum("bqhd,bkhd->bhqk", q, k) / jnp.sqrt(F32(dh))
    causal = jnp.tril(jnp.ones((s, s), bool))[None, None]
    probs = jax.nn.softmax(jnp.where(causal, scores, -1e9), axis=-1)
    ctx = jnp.einsum("bhqk,bkhd->bqhd", probs, v).reshape(b, s, nh * dh)
    return ctx @ _f(w[p + "attn_o"])


def mamba(w, p, x, cfg):
    b, s, _ = x.shape
    d_ssm, heads, dh, n, g, kc = (int(cfg[k]) for k in (
        "mamba_d_ssm", "mamba_n_heads", "mamba_d_head", "mamba_d_state",
        "mamba_n_groups", "mamba_d_conv"))
    mz, mx, mb, mc, mdt = (float(v) for v in cfg["ssm_multipliers"])
    m = jnp.concatenate([jnp.full((d_ssm,), mz), jnp.full((d_ssm,), mx),
                         jnp.full((g * n,), mb), jnp.full((g * n,), mc),
                         jnp.full((heads,), mdt)]).astype(F32)
    zxbcdt = (x @ _f(w[p + "ssm_in"])) * m
    z = zxbcdt[..., :d_ssm]
    xbc = zxbcdt[..., d_ssm:2 * d_ssm + 2 * g * n]
    dt = zxbcdt[..., 2 * d_ssm + 2 * g * n:]
    # causal depthwise conv: y_t = sum_j w[j] * x_{t - (kc - 1) + j}
    padded = jnp.pad(xbc, ((0, 0), (kc - 1, 0), (0, 0)))
    cw = _f(w[p + "ssm_conv_w"])
    xbc = jax.nn.silu(sum(padded[:, j:j + s] * cw[j] for j in range(kc))
                      + _f(w[p + "ssm_conv_b"]))
    xs = xbc[..., :d_ssm].reshape(b, s, heads, dh)
    bm = xbc[..., d_ssm:d_ssm + g * n].reshape(b, s, g, n)
    cm = xbc[..., d_ssm + g * n:].reshape(b, s, g, n)
    dt = jax.nn.softplus(dt + _f(w[p + "ssm_dt_bias"]))       # [B, S, H]
    a = -jnp.exp(_f(w[p + "ssm_A_log"]))
    dskip = _f(w[p + "ssm_D"])

    def step(state, inp):
        x_t, b_t, c_t, dt_t = inp
        bh = jnp.repeat(b_t, heads // g, axis=1)               # [B, H, N]
        ch = jnp.repeat(c_t, heads // g, axis=1)
        state = (jnp.exp(dt_t * a)[:, :, None, None] * state
                 + (dt_t[:, :, None] * x_t)[..., None] * bh[:, :, None, :])
        y = jnp.sum(state * ch[:, :, None, :], axis=-1)
        return state, y + dskip[None, :, None] * x_t

    t_first = lambda t: jnp.moveaxis(t, 1, 0)
    _, y = jax.lax.scan(step, jnp.zeros((b, heads, dh, n), F32),
                        (t_first(xs), t_first(bm), t_first(cm), t_first(dt)))
    y = jnp.moveaxis(y, 0, 1).reshape(b, s, d_ssm)
    y = _rms(y * jax.nn.silu(z), w[p + "ssm_norm"],
             float(cfg["rms_norm_eps"]), groups=g)
    return y @ _f(w[p + "ssm_out"])


def _size(t):
    return jnp.sqrt(jnp.mean(t * t))


def embed(w, tokens, cfg, name="lm"):
    return _f(w[name + "_emb"][tokens]) * float(cfg["embedding_multiplier"])


def mlp(w, p, v, cfg, blocks=1):
    """SwiGLU.  ``blocks`` > 1 takes the intermediate width in that many
    equal slices, one after another (each slice's down-projection adds
    into the same sum), so that only one slice of the three matrices is
    upcast at a time: the same products, a smaller footprint."""
    g_mult, d_mult = (float(x) for x in cfg["mlp_multipliers"])
    wg, wu, wd = w[p + "mlp_gate"], w[p + "mlp_up"], w[p + "mlp_down"]
    n = wg.shape[1] // blocks
    assert n * blocks == wg.shape[1], "blocks must divide the MLP width"

    out = jnp.zeros_like(v)
    for j in range(blocks):   # static slices: no copy of a whole matrix
        cols = slice(j * n, (j + 1) * n)
        out = out + (jax.nn.silu(g_mult * (v @ _f(wg[:, cols])))
                     * (v @ _f(wu[:, cols]))) @ _f(wd[cols, :])
    return d_mult * out


def block(w, i, h, cfg, name="lm", mlp_blocks=1):
    """One block over ``h`` [B, S, D]; ``w`` needs only layer ``i``'s
    weights.  Returns ``(h, shares)``: the rms of the mixer's, the
    attention's and the MLP's contribution over the rms of the residual
    each is added to."""
    with jax.default_matmul_precision("highest"):
        p = "%s_l%d_" % (name, i)
        eps = float(cfg["rms_norm_eps"])
        u = _rms(h, w[p + "norm1"], eps)
        mix = float(cfg["ssm_out_multiplier"]) * mamba(
            w, p, float(cfg["ssm_in_multiplier"]) * u, cfg)
        att = float(cfg["attention_out_multiplier"]) * attention(
            w, p, float(cfg["attention_in_multiplier"]) * u, cfg)
        mid = h + mix + att
        out = mlp(w, p, _rms(mid, w[p + "norm2"], eps), cfg, mlp_blocks)
        shares = jnp.stack([_size(mix) / _size(h), _size(att) / _size(h),
                            _size(out) / _size(mid)])
        return mid + out, shares


def head(w, h, cfg, name="lm"):
    """All logits [B, S, V] (small vocabularies: the CPU tests)."""
    with jax.default_matmul_precision("highest"):
        x = _rms(h, w[name + "_final_norm"], float(cfg["rms_norm_eps"]))
        return (x @ _f(w[name + "_head"])) * float(cfg["lm_head_multiplier"])


def head_stats(w, h, targets, cfg, blocks, name="lm"):
    """What the check needs of the logits at every position without
    holding them: ``(max, min, argmax, logit of targets)``, each [B, S],
    the head taken in ``blocks`` equal slices of the vocabulary."""
    with jax.default_matmul_precision("highest"):
        x = _rms(h, w[name + "_final_norm"], float(cfg["rms_norm_eps"]))
        wh = w[name + "_head"]
        vb = wh.shape[1] // blocks
        assert vb * blocks == wh.shape[1], "blocks must divide the vocabulary"
        mult = float(cfg["lm_head_multiplier"])

        shp = targets.shape
        hi, lo = jnp.full(shp, -jnp.inf, F32), jnp.full(shp, jnp.inf, F32)
        arg, got = jnp.zeros(shp, jnp.int32), jnp.zeros(shp, F32)
        for j in range(blocks):   # static slices: no copy of the head
            lg = (x @ _f(wh[:, j * vb:(j + 1) * vb])) * mult    # [B, S, vb]
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
    for i in range(int(cfg["num_hidden_layers"])):
        h, _ = block(w, i, h, cfg, name)
    return head(w, h, cfg, name)
