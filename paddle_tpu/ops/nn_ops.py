"""NN ops: activations, conv/pool, normalization, losses, dropout, softmax.

Reference kernels: operators/activation_op.cc, conv_op.cc (cuDNN/gemm),
pool_op.cc, batch_norm_op.cc, layer_norm_op.cc, softmax_op.cc,
cross_entropy_op.cc, softmax_with_cross_entropy_op.cc, dropout_op.cc.
Convs lower to lax.conv_general_dilated in NCHW — XLA tiles them onto the
MXU; there is no cuDNN-style algo selection because XLA owns codegen.
"""
from __future__ import annotations

import numpy as np

from paddle_tpu.core.registry import register_op
from paddle_tpu.ops.common import maybe, one, prng


def _jnp():
    import jax.numpy as jnp

    return jnp


def _jax():
    import jax

    return jax


# ---------------------------------------------------------------------------
# activations
# ---------------------------------------------------------------------------
def _act(name, fn):
    @register_op(name)
    def kernel(inputs, attrs, _fn=fn):
        return {"Out": _fn(one(inputs, "X"), attrs)}

    return kernel


_act("relu", lambda x, a: _jax().nn.relu(x))
_act("relu6", lambda x, a: _jnp().clip(x, 0.0, a.get("threshold", 6.0)))
_act("sigmoid", lambda x, a: _jax().nn.sigmoid(x))
_act("tanh", lambda x, a: _jnp().tanh(x))
_act("gelu", lambda x, a: _jax().nn.gelu(x, approximate=a.get("approximate", False)))
_act("leaky_relu", lambda x, a: _jax().nn.leaky_relu(x, a.get("alpha", 0.02)))
_act("elu", lambda x, a: _jax().nn.elu(x, a.get("alpha", 1.0)))
_act("softplus", lambda x, a: _jax().nn.softplus(x))
_act("softsign", lambda x, a: x / (1 + _jnp().abs(x)))
_act("swish", lambda x, a: x * _jax().nn.sigmoid(a.get("beta", 1.0) * x))
_act("hard_sigmoid", lambda x, a: _jnp().clip(a.get("slope", 0.2) * x + a.get("offset", 0.5), 0.0, 1.0))
_act("hard_swish", lambda x, a: x * _jnp().clip(x + a.get("offset", 3.0), 0.0, a.get("threshold", 6.0)) / a.get("scale", 6.0))
_act("thresholded_relu", lambda x, a: _jnp().where(x > a.get("threshold", 1.0), x, 0.0))
_act("stanh", lambda x, a: a.get("scale_b", 1.7159) * _jnp().tanh(a.get("scale_a", 0.67) * x))
_act("soft_relu", lambda x, a: _jnp().log1p(_jnp().exp(_jnp().clip(x, -a.get("threshold", 40.0), a.get("threshold", 40.0)))))
_act("brelu", lambda x, a: _jnp().clip(x, a.get("t_min", 0.0), a.get("t_max", 24.0)))
_act("prelu_channel", lambda x, a: x)  # placeholder; prelu op below


@register_op("prelu")
def prelu(inputs, attrs):
    jnp = _jnp()
    x = one(inputs, "X")
    alpha = one(inputs, "Alpha")
    mode = attrs.get("mode", "all")
    if mode == "channel":
        alpha = alpha.reshape((1, -1) + (1,) * (x.ndim - 2))
    return {"Out": jnp.where(x > 0, x, alpha * x)}


@register_op("softmax")
def softmax(inputs, attrs):
    jax = _jax()
    x = one(inputs, "X")
    return {"Out": jax.nn.softmax(x, axis=attrs.get("axis", -1))}


@register_op("log_softmax")
def log_softmax(inputs, attrs):
    jax = _jax()
    return {"Out": jax.nn.log_softmax(one(inputs, "X"), axis=attrs.get("axis", -1))}


# ---------------------------------------------------------------------------
# conv / pool
# ---------------------------------------------------------------------------
def _pair(v):
    if isinstance(v, (list, tuple)):
        return tuple(int(x) for x in v)
    return (int(v), int(v))


@register_op("conv2d")
def conv2d(inputs, attrs):
    """reference: conv_op.cc.  ``data_format``: NCHW (reference default)
    or NHWC — the TPU-preferred channels-last layout (weights stay OIHW
    in both; XLA relayouts internally either way, but NHWC activations
    skip the boundary transposes)."""
    jax = _jax()
    x = one(inputs, "Input")
    w = one(inputs, "Filter")
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    dilations = _pair(attrs.get("dilations", [1, 1]))
    groups = attrs.get("groups", 1)
    fmt = attrs.get("data_format", "NCHW")
    out = jax.lax.conv_general_dilated(
        x,
        w,
        window_strides=strides,
        padding=[(pads[0], pads[0]), (pads[1], pads[1])],
        rhs_dilation=dilations,
        feature_group_count=groups,
        dimension_numbers=(fmt, "OIHW", fmt),
    )
    b = one(inputs, "Bias")
    if b is not None:
        out = out + b.reshape((1, -1, 1, 1) if fmt == "NCHW" else (1, 1, 1, -1))
    return {"Output": out}


@register_op("depthwise_conv2d")
def depthwise_conv2d(inputs, attrs):
    attrs = dict(attrs)
    x = one(inputs, "Input")
    fmt = attrs.get("data_format", "NCHW")
    attrs["groups"] = x.shape[1] if fmt == "NCHW" else x.shape[-1]
    return conv2d(inputs, attrs)


@register_op("conv2d_transpose")
def conv2d_transpose(inputs, attrs):
    """reference: conv_transpose_op.cc — out = (in-1)*stride - 2*pad +
    k_eff.  jax.lax.conv_transpose's explicit padding pads the
    stride-dilated input before a VALID conv, so paddle padding p maps
    to (k_eff - 1 - p) per side."""
    jax = _jax()
    x = one(inputs, "Input")
    w = one(inputs, "Filter")  # reference layout: [in_c, out_c/groups, kh, kw]
    strides = _pair(attrs.get("strides", [1, 1]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    dilations = _pair(attrs.get("dilations", [1, 1]))
    keff = [
        (w.shape[2 + i] - 1) * dilations[i] + 1 for i in range(2)
    ]
    jpad = [(keff[i] - 1 - pads[i], keff[i] - 1 - pads[i]) for i in range(2)]
    # OIHW + transpose_kernel: jax flips the spatial taps and swaps
    # in/out channels — the true gradient-of-conv the reference computes
    out = jax.lax.conv_transpose(
        x,
        w,
        strides=strides,
        padding=jpad,
        rhs_dilation=dilations,
        dimension_numbers=("NCHW", "OIHW", "NCHW"),
        transpose_kernel=True,
    )
    return {"Output": out}


@register_op("pool2d")
def pool2d(inputs, attrs):
    jax = _jax()
    jnp = _jnp()
    x = one(inputs, "X")
    ptype = attrs.get("pooling_type", "max")
    ksize = _pair(attrs.get("ksize", [2, 2]))
    strides = _pair(attrs.get("strides", [2, 2]))
    pads = _pair(attrs.get("paddings", [0, 0]))
    fmt = attrs.get("data_format", "NCHW")
    sp = (2, 3) if fmt == "NCHW" else (1, 2)  # spatial axes
    if attrs.get("global_pooling", False) or attrs.get("adaptive", False) and tuple(attrs.get("ksize")) == (1, 1):
        if ptype == "max":
            return {"Out": jnp.max(x, axis=sp, keepdims=True)}
        return {"Out": jnp.mean(x, axis=sp, keepdims=True)}
    # ceil_mode rounds partial windows IN (reference pool_op.h
    # PoolOutputSize with ceil): realized as extra high-side padding so
    # reduce_window emits the ceil-count windows; avg-exclusive counts
    # only real cells either way (padding contributes zeros)
    extra = [0, 0]
    if attrs.get("ceil_mode", False):
        hw = (x.shape[2], x.shape[3]) if fmt == "NCHW" else (x.shape[1], x.shape[2])
        for d in range(2):
            num = hw[d] + 2 * pads[d] - ksize[d]
            o_ceil = -(-num // strides[d]) + 1
            extra[d] = (o_ceil - 1) * strides[d] + ksize[d] - hw[d] - 2 * pads[d]
    if fmt == "NCHW":
        window = (1, 1) + ksize
        strides4 = (1, 1) + strides
        padding = ((0, 0), (0, 0), (pads[0], pads[0] + extra[0]),
                   (pads[1], pads[1] + extra[1]))
    else:
        window = (1,) + ksize + (1,)
        strides4 = (1,) + strides + (1,)
        padding = ((0, 0), (pads[0], pads[0] + extra[0]),
                   (pads[1], pads[1] + extra[1]), (0, 0))
    if ptype == "max":
        init = -jnp.inf
        out = jax.lax.reduce_window(x, init, jax.lax.max, window, strides4, padding)
    else:
        summed = jax.lax.reduce_window(x, 0.0, jax.lax.add, window, strides4, padding)
        if attrs.get("exclusive", True) and (pads[0] or pads[1] or extra[0] or extra[1]):
            ones = jnp.ones_like(x)
            counts = jax.lax.reduce_window(ones, 0.0, jax.lax.add, window, strides4, padding)
            out = summed / counts
        else:
            out = summed / float(ksize[0] * ksize[1])
    return {"Out": out}


# ---------------------------------------------------------------------------
# normalization
# ---------------------------------------------------------------------------
@register_op("batch_norm", no_grad_set={"Mean", "Variance"})
def batch_norm(inputs, attrs):
    """reference: operators/batch_norm_op.cc.  Outputs MeanOut/VarianceOut
    alias the running stats vars; SavedMean/SavedVariance feed the grad."""
    jnp = _jnp()
    x = one(inputs, "X")
    scale = one(inputs, "Scale")
    bias = one(inputs, "Bias")
    mean = one(inputs, "Mean")
    var = one(inputs, "Variance")
    eps = attrs.get("epsilon", 1e-5)
    momentum = attrs.get("momentum", 0.9)
    is_test = attrs.get("is_test", False)
    layout = attrs.get("data_layout", "NCHW")
    axes = tuple(i for i in range(x.ndim) if i != (1 if layout == "NCHW" else x.ndim - 1))
    cshape = tuple(-1 if i == (1 if layout == "NCHW" else x.ndim - 1) else 1 for i in range(x.ndim))
    # Statistics and the normalize math run in fp32 regardless of x's
    # dtype (AMP feeds bf16 activations; running stats / affine params
    # stay fp32 — contrib/mixed_precision _KEEP_FP32_IN).  XLA fuses the
    # casts into the surrounding elementwise chain, so activation HBM
    # traffic stays bf16 while accumulation is exact.
    stat_dtype = jnp.promote_types(x.dtype, jnp.float32)
    xf = x.astype(stat_dtype) if x.dtype != stat_dtype else x
    if is_test:
        use_mean, use_var = mean, var
        saved_mean, saved_var = mean, var
        new_mean, new_var = mean, var
    else:
        use_mean = jnp.mean(xf, axis=axes)
        use_var = jnp.var(xf, axis=axes)
        if attrs.get("sync_bn", False):
            # SyncBatchNorm (reference: sync_batch_norm_op.cu — NCCL
            # stat exchange): global batch statistics via psum over the
            # active dp axis; E[x^2]-E[x]^2 so one reduce round trip
            from paddle_tpu.parallel import env as penv

            ax = attrs.get("axis_name") or penv.axis_for_ring(attrs.get("ring_id", 0))
            if penv.axis_active(ax):
                import jax as _jaxmod

                n = _jaxmod.lax.psum(1, axis_name=ax)
                mean_sq = jnp.mean(xf * xf, axis=axes)
                use_mean = _jaxmod.lax.psum(use_mean, axis_name=ax) / n
                use_var = _jaxmod.lax.psum(mean_sq, axis_name=ax) / n - use_mean * use_mean
        saved_mean, saved_var = use_mean, use_var
        new_mean = momentum * mean + (1 - momentum) * use_mean
        new_var = momentum * var + (1 - momentum) * use_var
    inv = 1.0 / jnp.sqrt(use_var + eps)
    y = (xf - use_mean.reshape(cshape)) * inv.reshape(cshape) * scale.reshape(cshape) + bias.reshape(cshape)
    y = y.astype(x.dtype)
    return {
        "Y": y,
        "MeanOut": new_mean,
        "VarianceOut": new_var,
        "SavedMean": saved_mean,
        "SavedVariance": saved_var,
    }


@register_op("layer_norm")
def layer_norm(inputs, attrs):
    jnp = _jnp()
    x = one(inputs, "X")
    scale = one(inputs, "Scale")
    bias = one(inputs, "Bias")
    eps = attrs.get("epsilon", 1e-5)
    begin = attrs.get("begin_norm_axis", 1)
    axes = tuple(range(begin, x.ndim))
    stat_dtype = jnp.promote_types(x.dtype, jnp.float32)
    xf = x.astype(stat_dtype) if x.dtype != stat_dtype else x
    mean = jnp.mean(xf, axis=axes, keepdims=True)
    var = jnp.var(xf, axis=axes, keepdims=True)
    y = (xf - mean) / jnp.sqrt(var + eps)
    norm_shape = x.shape[begin:]
    if scale is not None:
        y = y * scale.reshape(norm_shape)
    if bias is not None:
        y = y + bias.reshape(norm_shape)
    return {"Y": y.astype(x.dtype), "Mean": mean.squeeze(axes), "Variance": var.squeeze(axes)}


@register_op("group_norm")
def group_norm(inputs, attrs):
    jnp = _jnp()
    x = one(inputs, "X")  # NCHW
    scale = one(inputs, "Scale")
    bias = one(inputs, "Bias")
    g = attrs.get("groups", 1)
    eps = attrs.get("epsilon", 1e-5)
    n, c = x.shape[0], x.shape[1]
    xg = x.reshape((n, g, c // g) + x.shape[2:])
    stat_dtype = jnp.promote_types(xg.dtype, jnp.float32)
    if xg.dtype != stat_dtype:
        xg = xg.astype(stat_dtype)
    axes = tuple(range(2, xg.ndim))
    mean = jnp.mean(xg, axis=axes, keepdims=True)
    var = jnp.var(xg, axis=axes, keepdims=True)
    y = ((xg - mean) / jnp.sqrt(var + eps)).reshape(x.shape)
    cshape = (1, c) + (1,) * (x.ndim - 2)
    if scale is not None:
        y = y * scale.reshape(cshape)
    if bias is not None:
        y = y + bias.reshape(cshape)
    return {"Y": y.astype(x.dtype), "Mean": mean.reshape((n, g)), "Variance": var.reshape((n, g))}


# ---------------------------------------------------------------------------
# dropout
# ---------------------------------------------------------------------------
@register_op("dropout")
def dropout(inputs, attrs):
    jax = _jax()
    jnp = _jnp()
    x = one(inputs, "X")
    p = attrs.get("dropout_prob", 0.5)
    if attrs.get("is_test", False) or p == 0.0:
        impl = attrs.get("dropout_implementation", "downgrade_in_infer")
        out = x * (1.0 - p) if impl == "downgrade_in_infer" and not attrs.get("is_test", False) else x
        if attrs.get("is_test", False) and impl == "downgrade_in_infer":
            out = x * (1.0 - p)
        elif attrs.get("is_test", False):
            out = x
        return {"Out": out, "Mask": jnp.ones_like(x)}
    key = prng(attrs.get("seed", 0))
    mask = jax.random.bernoulli(key, 1.0 - p, x.shape)
    impl = attrs.get("dropout_implementation", "downgrade_in_infer")
    if impl == "upscale_in_train":
        out = jnp.where(mask, x / (1.0 - p), 0.0)
    else:
        out = jnp.where(mask, x, 0.0)
    return {"Out": out.astype(x.dtype), "Mask": mask.astype(x.dtype)}


# ---------------------------------------------------------------------------
# losses
# ---------------------------------------------------------------------------
@register_op("cross_entropy", no_grad_set={"Label"})
def cross_entropy(inputs, attrs):
    jnp = _jnp()
    x = one(inputs, "X")  # probabilities [..., C]
    label = one(inputs, "Label")
    eps = 1e-8
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * jnp.log(x + eps), axis=-1, keepdims=True)
    else:
        if label.ndim == x.ndim and label.shape[-1] == 1:
            lbl = label.squeeze(-1)
        else:
            lbl = label
        picked = jnp.take_along_axis(x, lbl[..., None].astype("int32"), axis=-1)
        loss = -jnp.log(picked + eps)
    return {"Y": loss}


@register_op("softmax_with_cross_entropy", no_grad_set={"Label"})
def softmax_with_cross_entropy(inputs, attrs):
    jax = _jax()
    jnp = _jnp()
    logits = one(inputs, "Logits")
    label = one(inputs, "Label")
    axis = attrs.get("axis", -1)
    logp = jax.nn.log_softmax(logits, axis=axis)
    softmax_out = jnp.exp(logp)
    if attrs.get("soft_label", False):
        loss = -jnp.sum(label * logp, axis=axis, keepdims=True)
    else:
        if label.ndim == logits.ndim and label.shape[axis] == 1:
            lbl = label.squeeze(axis)
        else:
            lbl = label
        picked = jnp.take_along_axis(logp, lbl[..., None].astype("int32"), axis=axis)
        loss = -picked
        if attrs.get("ignore_index", -100) >= 0:
            ig = attrs["ignore_index"]
            loss = jnp.where(lbl[..., None] == ig, 0.0, loss)
    return {"Softmax": softmax_out, "Loss": loss}


@register_op("sigmoid_cross_entropy_with_logits", no_grad_set={"Label"})
def sigmoid_cross_entropy_with_logits(inputs, attrs):
    jnp = _jnp()
    x = one(inputs, "X")
    label = one(inputs, "Label")
    loss = jnp.maximum(x, 0) - x * label + jnp.log1p(jnp.exp(-jnp.abs(x)))
    ignore = attrs.get("ignore_index", -100)
    loss = jnp.where(label == ignore, 0.0, loss)
    if attrs.get("normalize", False):
        norm = jnp.maximum(jnp.sum(jnp.where(label != ignore, 1.0, 0.0)), 1.0)
        loss = loss / norm
    return {"Out": loss}


@register_op("square_error_cost", no_grad_set={"Y"})
def square_error_cost(inputs, attrs):
    x, y = one(inputs, "X"), one(inputs, "Y")
    d = x - y
    return {"Out": d * d}


@register_op("huber_loss", no_grad_set={"Y"})
def huber_loss(inputs, attrs):
    jnp = _jnp()
    x, y = one(inputs, "X"), one(inputs, "Y")
    delta = attrs.get("delta", 1.0)
    r = y - x
    ar = jnp.abs(r)
    loss = jnp.where(ar <= delta, 0.5 * r * r, delta * (ar - 0.5 * delta))
    return {"Out": loss, "Residual": r}


@register_op("smooth_l1_loss", no_grad_set={"Y"})
def smooth_l1_loss(inputs, attrs):
    jnp = _jnp()
    x, y = one(inputs, "X"), one(inputs, "Y")
    sigma2 = attrs.get("sigma", 1.0) ** 2
    d = x - y
    ad = jnp.abs(d)
    out = jnp.where(ad < 1.0 / sigma2, 0.5 * d * d * sigma2, ad - 0.5 / sigma2)
    return {"Out": jnp.sum(out, axis=tuple(range(1, out.ndim)), keepdims=True).reshape((x.shape[0], 1)), "Diff": d}


@register_op("log_loss", no_grad_set={"Labels"})
def log_loss(inputs, attrs):
    jnp = _jnp()
    p = one(inputs, "Predicted")
    y = one(inputs, "Labels")
    eps = attrs.get("epsilon", 1e-4)
    return {"Loss": -y * jnp.log(p + eps) - (1 - y) * jnp.log(1 - p + eps)}


# ---------------------------------------------------------------------------
# matmul-adjacent nn pieces
# ---------------------------------------------------------------------------
@register_op("l2_normalize")
def l2_normalize(inputs, attrs):
    jnp = _jnp()
    x = one(inputs, "X")
    axis = attrs.get("axis", -1)
    eps = attrs.get("epsilon", 1e-10)
    norm = jnp.sqrt(jnp.sum(x * x, axis=axis, keepdims=True) + eps)
    return {"Out": x / norm, "Norm": norm}


@register_op("norm")
def norm(inputs, attrs):
    return l2_normalize(inputs, attrs)


@register_op("maxout")
def maxout(inputs, attrs):
    jnp = _jnp()
    x = one(inputs, "X")
    g = attrs["groups"]
    n, c, h, w = x.shape
    return {"Out": jnp.max(x.reshape(n, c // g, g, h, w), axis=2)}


@register_op("im2sequence")
def im2sequence(inputs, attrs):
    # simplified patch-extraction (reference: operators/im2sequence_op.cc)
    jax = _jax()
    x = one(inputs, "X")
    kh, kw = _pair(attrs.get("kernels", [1, 1]))
    sh, sw = _pair(attrs.get("strides", [1, 1]))
    patches = jax.lax.conv_general_dilated_patches(
        x, (kh, kw), (sh, sw), padding="VALID", dimension_numbers=("NCHW", "OIHW", "NCHW")
    )
    n, c, oh, ow = patches.shape
    return {"Out": patches.transpose(0, 2, 3, 1).reshape(n * oh * ow, c)}


# ---------------------------------------------------------------------------
# CTC loss (reference: operators/warpctc_op.cc — wraps warp-ctc; here the
# standard log-space alpha recursion compiles into the step via lax.scan,
# differentiable through autodiff)
# ---------------------------------------------------------------------------
@register_op("warpctc", no_grad_set={"Label", "LogitsLength", "LabelLength"})
def warpctc(inputs, attrs):
    """Logits [B, T, C] padded batch-major, Label [B, L] int (padded),
    LogitsLength/LabelLength [B].  Returns Loss [B, 1] (negative log
    likelihood; norm_by_times divides by the logit length)."""
    jax = _jax()
    jnp = _jnp()
    from paddle_tpu.ops.common import maybe

    logits = one(inputs, "Logits")
    label = one(inputs, "Label").astype(jnp.int32)
    B, T, C = logits.shape
    L = label.shape[1]
    logit_len = maybe(inputs, "LogitsLength")
    label_len = maybe(inputs, "LabelLength")
    logit_len = (
        jnp.full((B,), T, jnp.int32) if logit_len is None else logit_len.reshape(B).astype(jnp.int32)
    )
    label_len = (
        jnp.full((B,), L, jnp.int32) if label_len is None else label_len.reshape(B).astype(jnp.int32)
    )
    blank = int(attrs.get("blank", 0))
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)

    S = 2 * L + 1
    ext = jnp.full((B, S), blank, jnp.int32)
    ext = ext.at[:, 1::2].set(label)
    prev2 = jnp.concatenate([jnp.full((B, 2), -1, jnp.int32), ext[:, :-2]], axis=1)
    skip_ok = (ext != blank) & (ext != prev2)

    NEG = -1e30
    alpha = jnp.full((B, S), NEG, jnp.float32)
    alpha = alpha.at[:, 0].set(logp[:, 0, blank])
    if S > 1:
        first_lbl = jnp.take_along_axis(logp[:, 0, :], ext[:, 1:2], axis=1)[:, 0]
        alpha = alpha.at[:, 1].set(first_lbl)

    def shift(a, k):
        return jnp.concatenate([jnp.full((B, k), NEG, jnp.float32), a[:, :-k]], axis=1)

    def step(alpha, t):
        lp_t = jnp.take_along_axis(logp[:, t, :], ext, axis=1)  # [B, S]
        m = jnp.logaddexp(alpha, shift(alpha, 1))
        m = jnp.where(skip_ok, jnp.logaddexp(m, shift(alpha, 2)), m)
        new = m + lp_t
        active = (t < logit_len)[:, None]
        return jnp.where(active, new, alpha), None

    alpha, _ = jax.lax.scan(step, alpha, jnp.arange(1, T))
    last = (2 * label_len)[:, None]
    a_last = jnp.take_along_axis(alpha, last, axis=1)[:, 0]
    a_prev = jnp.take_along_axis(alpha, jnp.maximum(last - 1, 0), axis=1)[:, 0]
    ll = jnp.where(label_len > 0, jnp.logaddexp(a_last, a_prev), a_last)
    loss = -ll
    if attrs.get("norm_by_times", False):
        loss = loss / jnp.maximum(logit_len.astype(jnp.float32), 1.0)
    return {"Loss": loss.reshape(B, 1).astype(logits.dtype)}


# ---------------------------------------------------------------------------
# RNN cell units (reference: operators/lstm_unit_op.cc, gru_unit_op.cc)
# ---------------------------------------------------------------------------
@register_op("lstm_unit")
def lstm_unit(inputs, attrs):
    """X = pre-activation gates [B, 4H] (i, f, c, o packed), C_prev [B, H];
    returns C [B, H], H (hidden) [B, H]."""
    jax = _jax()
    jnp = _jnp()
    x = one(inputs, "X")
    c_prev = one(inputs, "C_prev")
    forget_bias = attrs.get("forget_bias", 0.0)
    H = c_prev.shape[-1]
    i, f, c_hat, o = jnp.split(x, 4, axis=-1)
    c = jax.nn.sigmoid(f + forget_bias) * c_prev + jax.nn.sigmoid(i) * jnp.tanh(c_hat)
    h = jax.nn.sigmoid(o) * jnp.tanh(c)
    return {"C": c, "H": h}


@register_op("gru_unit")
def gru_unit(inputs, attrs):
    """Input [B, 3H] (update, reset, candidate-input packed),
    HiddenPrev [B, H], Weight [H, 3H] (reference layout: first 2H for
    update/reset, last H for candidate), Bias [1, 3H] optional."""
    jax = _jax()
    jnp = _jnp()
    from paddle_tpu.ops.common import maybe

    x = one(inputs, "Input")
    h_prev = one(inputs, "HiddenPrev")
    w = one(inputs, "Weight")
    b = maybe(inputs, "Bias")
    H = h_prev.shape[-1]
    if b is not None:
        x = x + b.reshape(1, 3 * H)
    xu, xr, xc = x[:, :H], x[:, H : 2 * H], x[:, 2 * H :]
    wu, wr = w[:, :H], w[:, H : 2 * H]
    wc = w[:, 2 * H :]
    u = jax.nn.sigmoid(xu + h_prev @ wu)
    r = jax.nn.sigmoid(xr + h_prev @ wr)
    c = jnp.tanh(xc + (r * h_prev) @ wc)
    h = u * h_prev + (1.0 - u) * c
    return {"Gate": jnp.concatenate([u, r, c], axis=-1), "ResetHiddenPrev": r * h_prev, "Hidden": h}


# ---------------------------------------------------------------------------
# sequence_conv (reference: operators/sequence_ops/sequence_conv_op.cc) —
# context-window conv over padded sequences
# ---------------------------------------------------------------------------
@register_op("sequence_conv", no_grad_set={"SeqLen"})
def sequence_conv(inputs, attrs):
    """X [B, T, D] padded, Filter [ctx_len*D, F]; out [B, T, F].  Rows
    outside a sequence contribute zeros (LoD boundary semantics)."""
    jnp = _jnp()
    from paddle_tpu.ops.common import maybe

    x = one(inputs, "X")
    w = one(inputs, "Filter")
    seq_len = maybe(inputs, "SeqLen")
    ctx_start = int(attrs.get("contextStart", attrs.get("context_start", -1)))
    ctx_len = int(attrs.get("contextLength", attrs.get("context_length", 3)))
    B, T, D = x.shape
    if seq_len is not None:
        t_idx = jnp.arange(T)[None, :, None]
        x = jnp.where(t_idx < seq_len.reshape(B, 1, 1), x, 0.0)
    cols = []
    for j in range(ctx_start, ctx_start + ctx_len):
        if j < 0:
            shifted = jnp.pad(x, ((0, 0), (-j, 0), (0, 0)))[:, :T]
        elif j > 0:
            shifted = jnp.pad(x, ((0, 0), (0, j), (0, 0)))[:, j:]
        else:
            shifted = x
        cols.append(shifted)
    ctx = jnp.concatenate(cols, axis=-1)  # [B, T, ctx_len*D]
    out = ctx @ w
    if seq_len is not None:
        out = jnp.where(t_idx < seq_len.reshape(B, 1, 1), out, 0.0)
    return {"Out": out}


def _fused_attention_infer(op, block):
    """Out is Q's shape and dtype, Lse its leading ``[N, H, S]`` in
    float32: written out, so that appending the op never traces a
    lowering (and never counts one)."""
    q = block.var(op.input("Q")[0])
    for slot, shape, dtype in (("Out", q.shape, q.dtype),
                               ("Lse", q.shape and q.shape[:3], "float32")):
        for n in op.outputs.get(slot, ()):
            v = block._find_var_recursive(n)
            if v is not None:
                v.shape, v.dtype = shape, dtype


def _fused_attention_grad_maker(op, block, out_grad_names, req):
    """ONE ``fused_attention_grad`` op that reads the forward's own
    context and row statistic, so the compiled step runs the forward
    kernel once per layer (a ``jax.vjp`` over the forward inside the
    grad op would be a second, different custom call that XLA cannot
    merge with the first)."""
    from paddle_tpu import unique_name
    from paddle_tpu.framework import grad_var_name

    dout = out_grad_names.get(op.output("Out")[0])
    if dout is None:
        return []
    inputs = {slot: list(names) for slot, names in op.inputs.items()}
    inputs.update(Out=op.output("Out"), Lse=op.output("Lse"))
    inputs["Out@GRAD"] = [dout]
    outputs = {}
    for slot in ("Q", "K", "V"):
        name = op.input(slot)[0]
        if name not in req:
            continue
        fwd = block._find_var_recursive(name)
        grad = unique_name.generate(grad_var_name(name) + "@RENAME@att")
        block.create_var(name=grad, shape=fwd.shape, dtype=fwd.dtype,
                         stop_gradient=True)
        outputs[slot + "@GRAD"] = [grad]
    if not outputs:
        return []
    return [dict(type="fused_attention_grad", inputs=inputs, outputs=outputs,
                 attrs=dict(op.attrs, op_role="backward"))]


def _fused_attention_args(inputs, attrs):
    """``(q, k, v, mask, causal, scale, path, act)`` of the op or its
    grad: ``path`` (``"ring"`` | ``"kernel"`` | ``"xla"``) is the
    lowering for this trace, the same in both, ``act`` the activation
    context the ring needs."""
    import jax

    from paddle_tpu.fused_attention import attention_lowering
    from paddle_tpu.sharding import activations as _sh_act

    q, k, v = one(inputs, "Q"), one(inputs, "K"), one(inputs, "V")
    mask = maybe(inputs, "Mask")
    act = _sh_act.current()
    path = attention_lowering(
        jax.default_backend(), int(q.shape[2]), int(k.shape[2]),
        int(q.shape[1]), int(q.shape[3]), q.dtype,
        partitioned=_sh_act.partitioned())
    if act is not None and act.sp_axis is not None and mask is None:
        n_sp = int(act.axis_sizes.get(act.sp_axis, 1))
        if (n_sp > 1 and int(q.shape[2]) % n_sp == 0
                and tuple(k.shape) == tuple(q.shape)):
            path = "ring"
    return (q, k, v, mask, bool(attrs.get("causal", False)),
            float(attrs.get("scale", 1.0)), path, act)


def _ring_attention(act, causal, scale):
    import jax
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.parallel.ring_attention import ring_attention

    sp = act.sp_axis
    spec = P(None, None, sp, None)
    return jax.shard_map(
        lambda qq, kk, vv: ring_attention(
            qq, kk, vv, axis_name=sp, causal=causal, scale=scale),
        mesh=act.mesh, in_specs=(spec, spec, spec), out_specs=spec)


@register_op("fused_attention", no_grad_set={"Mask"},
             infer_shape=_fused_attention_infer,
             grad_maker=_fused_attention_grad_maker)
def fused_attention(inputs, attrs):
    """Fused scaled-dot-product self-attention: Q/K/V [N, H, S, D] ->
    ``Out`` [N, H, S, D] and ``Lse`` [N, H, S] (float32, the per-row
    log-sum-exp of the biased scores: what ``fused_attention_grad``
    rebuilds the probabilities from).

    What ``models.transformer.multi_head_attention`` emits whenever no
    dropout sits inside attention.  Padding comes in as ``Mask`` [N, S]
    (1 = token) and masks KEYS only, exactly as the additive
    ``(mask - 1) * 1e9`` bias of the four-op build: a padded query row
    still attends every real key.  Scores and softmax are float32;
    probabilities take V's dtype for the context product.

    The lowering is chosen per trace from what the op can see
    (``paddle_tpu.fused_attention.attention_lowering`` states the rule
    and the chip numbers that set it) and counted in
    ``fused_attention_lowered_total{path}``:

    * ``kernel`` — the Pallas TPU pair of ``paddle_tpu/fused_attention.py``:
      no score-shaped tensor is written to HBM, forward or backward;
    * ``xla`` — plain XLA ops, on every backend (``xla_attention``);
    * ``ring`` — traced under a sequence-parallel activation context (a
      CompiledProgram whose rules carry sp activation rules —
      sharding/activations.py) with no ``Mask`` and a sequence the sp
      axis divides: ``parallel/ring_attention.py``, blockwise exact
      attention with K/V rotating around the ring, O(S/sp) activation
      memory per chip.  Padding masks and non-divisible lengths take the
      gathered ``xla`` path (GSPMD inserts the collectives).  ``Lse`` is
      zeros there: the ring keeps its own residuals.
    """
    import jax
    jnp = _jnp()

    from paddle_tpu import fused_attention as fa

    q, k, v, mask, causal, scale, path, act = _fused_attention_args(
        inputs, attrs)
    fa.LOWERED.labels(path=path).inc()
    with jax.named_scope("fused_attention"):
        if path == "ring":
            return {"Out": _ring_attention(act, causal, scale)(q, k, v),
                    "Lse": jnp.zeros(q.shape[:3], jnp.float32)}
        attend = fa.kernel_attention if path == "kernel" else fa.xla_attention
        out, lse = attend(q, k, v, mask, causal, scale)
    return {"Out": out, "Lse": lse}


def _fused_attention_grad_infer(op, block):
    """Nothing to infer: the grad maker made each gradient in its
    forward input's shape and dtype (and appending the op must not
    trace a kernel)."""


@register_op("fused_attention_grad", infer_shape=_fused_attention_grad_infer)
def fused_attention_grad(inputs, attrs):
    """dQ, dK, dV of ``fused_attention`` from its inputs, ``Out``,
    ``Lse`` and ``Out@GRAD``, by the lowering the forward took: the
    backward kernel, else ``jax.vjp`` over the XLA or ring form (inside
    one module XLA merges that forward with the op's own)."""
    import jax

    from paddle_tpu import fused_attention as fa

    q, k, v, mask, causal, scale, path, act = _fused_attention_args(
        inputs, attrs)
    dout = one(inputs, "Out@GRAD").astype(q.dtype)
    with jax.named_scope("fused_attention_grad"):
        if path == "kernel":
            grads = fa.kernel_attention_grad(
                q, k, v, mask, one(inputs, "Out"), one(inputs, "Lse"), dout,
                causal, scale)
        else:
            attend = (_ring_attention(act, causal, scale) if path == "ring"
                      else lambda a, b, c: fa.xla_attention(
                          a, b, c, mask, causal, scale)[0])
            grads = jax.vjp(attend, q, k, v)[1](dout)
    return {slot + "@GRAD": g for slot, g in zip("QKV", grads)}


# ---------------------------------------------------------------------------
# NCE (reference: operators/nce_op.cc) — noise-contrastive estimation with
# a uniform sampler compiled into the step
# ---------------------------------------------------------------------------
@register_op("nce", no_grad_set={"Label", "SampleWeight"})
def nce(inputs, attrs):
    """Input [B, D], Label [B, 1], Weight [V, D], Bias [V] optional,
    SampleWeight [B, 1] optional (per-example cost scale).  Uniform,
    log_uniform, or custom (attr ``custom_dist``, a length-V probability
    vector — the reference's CustomSampler, operators/math/sampler.cc)
    negative sampler (num_neg_samples), logistic NCE loss with the
    log(k*P) correction.  Cost [B, 1]."""
    jax = _jax()
    jnp = _jnp()
    from paddle_tpu.ops.common import maybe, prng

    x = one(inputs, "Input")
    label = one(inputs, "Label").reshape(-1).astype(jnp.int32)
    w = one(inputs, "Weight")
    b = maybe(inputs, "Bias")
    sw = maybe(inputs, "SampleWeight")
    V = w.shape[0]
    k = int(attrs.get("num_neg_samples", 10))
    sampler = attrs.get("sampler", "uniform")
    # fresh negatives per distinct batch: fold the labels into the key
    # (a constant key would reuse the same k negatives forever; identical
    # repeated batches still get identical draws — deterministic)
    key = jax.random.fold_in(
        prng(int(attrs.get("seed", 0))), jnp.sum(label).astype(jnp.uint32)
    )
    if sampler == "custom_dist":
        # inverse-CDF draw from the user distribution; alias-free and
        # static-shape (the reference builds an alias table host-side)
        probs = jnp.asarray(attrs["custom_dist"], dtype=jnp.float32).reshape(-1)
        probs = probs / jnp.sum(probs)
        cdf = jnp.cumsum(probs)
        u = jax.random.uniform(key, (k,))
        neg = jnp.clip(jnp.searchsorted(cdf, u), 0, V - 1).astype(jnp.int32)
        logp_all = jnp.log(jnp.maximum(probs, 1e-30))
        log_kp_true = jnp.log(float(k)) + logp_all[label]
        log_kp_neg = jnp.log(float(k)) + logp_all[neg]
    elif sampler == "log_uniform":
        # Zipfian P(c) = log((c+2)/(c+1)) / log(V+1); inverse-CDF draw
        # c = floor(exp(u*log(V+1))) - 1 (the reference's LogUniformSampler,
        # operators/math/sampler.cc)
        u = jax.random.uniform(key, (k,))
        neg = jnp.clip(
            jnp.exp(u * jnp.log(float(V + 1))).astype(jnp.int32) - 1, 0, V - 1
        )

        def logp(c):
            # log1p keeps precision at large class ids (log((c+2)/(c+1))
            # rounds to log(1.0) = 0 in fp32 once c+1 >= 2^24)
            cf = c.astype(jnp.float32)
            return jnp.log(jnp.log1p(1.0 / (cf + 1.0)) / jnp.log(float(V + 1)))

        log_kp_true = jnp.log(float(k)) + logp(label)      # [B]
        log_kp_neg = jnp.log(float(k)) + logp(neg)         # [k]
    else:  # uniform
        neg = jax.random.randint(key, (k,), 0, V)
        log_kp_true = jnp.full((label.shape[0],), jnp.log(k / V))
        log_kp_neg = jnp.full((k,), jnp.log(k / V))

    true_logit = jnp.sum(x * w[label], axis=-1)
    neg_logit = x @ w[neg].T  # [B, k]
    if b is not None:
        true_logit = true_logit + b.reshape(-1)[label]
        neg_logit = neg_logit + b.reshape(-1)[neg][None, :]
    pos_cost = jax.nn.softplus(-(true_logit - log_kp_true))
    neg_cost = jnp.sum(jax.nn.softplus(neg_logit - log_kp_neg[None, :]), axis=-1)
    cost = pos_cost + neg_cost
    if sw is not None:
        cost = cost * sw.reshape(-1)
    return {"Cost": cost.reshape(-1, 1)}


# ---------------------------------------------------------------------------
# Hierarchical sigmoid (reference: operators/hierarchical_sigmoid_op.cc)
# over the default complete binary tree
# ---------------------------------------------------------------------------
@register_op("hierarchical_sigmoid", no_grad_set={"Label", "PathTable", "PathCode"})
def hierarchical_sigmoid(inputs, attrs):
    """X [B, D], Label [B, 1], W [num_classes-1, D] (default tree) or
    [non_leaf_num, D] (custom), Bias optional.

    Default: complete-binary-tree paths like the reference (heap
    indexing: leaf code = label + num_classes; internal node id =
    code//2 - 1 at each level).  Custom (reference:
    hierarchical_sigmoid_op.cc custom-tree path via MatrixBitCodeFunctor
    CustomCode): PathTable [B, L] holds each sample's leaf->root
    non-leaf row indices (-1 padding), PathCode [B, L] the 0/1 branch
    labels; Label is unused for path construction."""
    jax = _jax()
    jnp = _jnp()
    from paddle_tpu.ops.common import maybe

    x = one(inputs, "X")
    w = one(inputs, "W")
    b = maybe(inputs, "Bias")
    ptable = maybe(inputs, "PathTable")
    pcode = maybe(inputs, "PathCode")

    if ptable is not None:
        if pcode is None:
            raise ValueError("hierarchical_sigmoid: PathTable without PathCode")
        valid = ptable >= 0  # [B, L]
        node = jnp.maximum(ptable, 0).astype(jnp.int32)
        bit = pcode.astype(jnp.float32)
        logit = jnp.einsum("bd,bld->bl", x, w[node])
        if b is not None:
            logit = logit + b.reshape(-1)[node]
        sign = 2.0 * bit - 1.0
        total = jnp.sum(
            jnp.where(valid, jax.nn.softplus(-sign * logit), 0.0), axis=1
        )
        return {"Out": total.reshape(-1, 1), "PreOut": total.reshape(-1, 1)}

    label = one(inputs, "Label").reshape(-1).astype(jnp.int32)
    K = int(attrs["num_classes"])
    depth = max(1, int(np.ceil(np.log2(K))) + 1)

    code = label + K  # heap leaf code
    total = jnp.zeros(x.shape[0], jnp.float32)
    for _ in range(depth):
        valid = code > 1
        node = jnp.maximum(code // 2 - 1, 0)
        bit = (code % 2).astype(jnp.float32)  # 1 = right child
        logit = jnp.sum(x * w[node], axis=-1)
        if b is not None:
            logit = logit + b.reshape(-1)[node]
        # p(bit) = sigmoid(logit) for bit 1 else sigmoid(-logit)
        sign = 2.0 * bit - 1.0
        total = total + jnp.where(valid, jax.nn.softplus(-sign * logit), 0.0)
        code = code // 2
    return {"Out": total.reshape(-1, 1), "PreOut": total.reshape(-1, 1)}


# ---------------------------------------------------------------------------
# Image resize (reference: operators/interpolate_op.cc bilinear_interp /
# nearest_interp) and pixel reorganization ops
# ---------------------------------------------------------------------------
def _interp(inputs, attrs, method):
    jax = _jax()
    jnp = _jnp()
    from paddle_tpu.ops.common import maybe

    x = one(inputs, "X")  # NCHW
    out_size = maybe(inputs, "OutSize")
    if out_size is not None:
        raise NotImplementedError("dynamic OutSize tensor; pass out_h/out_w attrs")
    out_h = int(attrs.get("out_h", 0))
    out_w = int(attrs.get("out_w", 0))
    scale = attrs.get("scale", 0)
    n, c, h, w = x.shape
    if out_h <= 0 or out_w <= 0:
        if not scale:
            raise ValueError("interpolate needs out_h/out_w or scale")
        out_h, out_w = int(h * scale), int(w * scale)
    if attrs.get("align_corners", True):
        # fluid default: corners map to corners — src = dst*(in-1)/(out-1).
        # A degenerate axis (out==1) samples coordinate 0 (ratio 0, like
        # the reference's ratio_h/w = 0 branch) — per-axis, NOT a
        # whole-op fallback to half-pixel sampling (ADVICE r2).
        ratio_h = (h - 1) / (out_h - 1) if out_h > 1 else 0.0
        ratio_w = (w - 1) / (out_w - 1) if out_w > 1 else 0.0
        ys = jnp.arange(out_h, dtype=jnp.float32) * ratio_h
        xs = jnp.arange(out_w, dtype=jnp.float32) * ratio_w
        if method == "nearest":
            yi = jnp.round(ys).astype(int)
            xi = jnp.round(xs).astype(int)
            out = x[:, :, yi][:, :, :, xi]
        else:
            y0 = jnp.clip(jnp.floor(ys).astype(int), 0, h - 1)
            x0 = jnp.clip(jnp.floor(xs).astype(int), 0, w - 1)
            y1 = jnp.clip(y0 + 1, 0, h - 1)
            x1 = jnp.clip(x0 + 1, 0, w - 1)
            wy = (ys - y0).reshape(1, 1, -1, 1)
            wx = (xs - x0).reshape(1, 1, 1, -1)
            v00 = x[:, :, y0][:, :, :, x0]
            v01 = x[:, :, y0][:, :, :, x1]
            v10 = x[:, :, y1][:, :, :, x0]
            v11 = x[:, :, y1][:, :, :, x1]
            out = (
                v00 * (1 - wy) * (1 - wx)
                + v01 * (1 - wy) * wx
                + v10 * wy * (1 - wx)
                + v11 * wy * wx
            )
    else:
        out = jax.image.resize(x, (n, c, out_h, out_w), method=method)
    return {"Out": out.astype(x.dtype)}


@register_op("bilinear_interp")
def bilinear_interp(inputs, attrs):
    return _interp(inputs, attrs, "bilinear")


@register_op("nearest_interp")
def nearest_interp(inputs, attrs):
    return _interp(inputs, attrs, "nearest")


@register_op("pixel_shuffle")
def pixel_shuffle(inputs, attrs):
    """reference: operators/pixel_shuffle_op.cc — [N, C*r^2, H, W] ->
    [N, C, H*r, W*r]."""
    x = one(inputs, "X")
    r = int(attrs.get("upscale_factor", 1))
    n, c, h, w = x.shape
    oc = c // (r * r)
    out = x.reshape(n, oc, r, r, h, w).transpose(0, 1, 4, 2, 5, 3).reshape(n, oc, h * r, w * r)
    return {"Out": out}


@register_op("shuffle_channel")
def shuffle_channel(inputs, attrs):
    """reference: operators/shuffle_channel_op.cc."""
    x = one(inputs, "X")
    g = int(attrs.get("group", 1))
    n, c, h, w = x.shape
    out = x.reshape(n, g, c // g, h, w).transpose(0, 2, 1, 3, 4).reshape(n, c, h, w)
    return {"Out": out}


@register_op("spectral_norm", no_grad_set={"U", "V"})
def spectral_norm(inputs, attrs):
    """reference: operators/spectral_norm_op.h CalcMatrixSigmaAndNormWeight —
    power iteration v = W^T u / ||.||, u = W v / ||.||, sigma = u^T W v,
    Out = W / sigma.  U/V are persistent buffers treated as constants for
    the gradient (stop_gradient), matching the reference grad kernel which
    differentiates only through Weight."""
    import jax

    jnp = _jnp()
    w = one(inputs, "Weight")
    u = one(inputs, "U").reshape(-1)
    v = one(inputs, "V").reshape(-1)
    dim = int(attrs.get("dim", 0))
    power_iters = int(attrs.get("power_iters", 1))
    eps = attrs.get("eps", 1e-12)
    perm = (dim,) + tuple(i for i in range(w.ndim) if i != dim)
    h = w.shape[dim]
    wmat = jnp.transpose(w, perm).reshape(h, -1)
    u = jax.lax.stop_gradient(u)
    v = jax.lax.stop_gradient(v)
    for _ in range(power_iters):
        v = wmat.T @ u
        v = jax.lax.stop_gradient(v / (jnp.linalg.norm(v) + eps))
        u = wmat @ v
        u = jax.lax.stop_gradient(u / (jnp.linalg.norm(u) + eps))
    sigma = u @ (wmat @ v)
    out = wmat / sigma
    inv_perm = tuple(np.argsort(perm))
    out = jnp.transpose(out.reshape(tuple(w.shape[p] for p in perm)), inv_perm)
    return {"Out": out}


@register_op("data_norm")
def data_norm(inputs, attrs):
    """reference: operators/data_norm_op.cc — CTR data normalization.

    Y = (X - mean) * scale with mean = BatchSum/BatchSize and
    scale = sqrt(BatchSize/BatchSquareSum).  The reference routes *stat
    updates* through the gradient channel (DataNormGradKernel sets
    dBatchSize=N, dBatchSum=sum(x), dBatchSquareSum=sum((x-mean)^2)+N*eps
    so plain SGD with lr folds fresh batch stats into the accumulators);
    jax.custom_vjp reproduces exactly those cotangents."""
    import jax

    jnp = _jnp()
    x = one(inputs, "X")
    bsize = one(inputs, "BatchSize")
    bsum = one(inputs, "BatchSum")
    bsqsum = one(inputs, "BatchSquareSum")
    eps = attrs.get("epsilon", 1e-4)
    layout = attrs.get("data_layout", "NCHW")
    caxis = 1 if (layout == "NCHW" and x.ndim > 2) else x.ndim - 1
    cshape = tuple(-1 if i == caxis else 1 for i in range(x.ndim))
    n = x.shape[0]
    red = tuple(i for i in range(x.ndim) if i != caxis)

    @jax.custom_vjp
    def _dn(xv, bsz, bsm, bss):
        means = bsm / bsz
        scales = jnp.sqrt(bsz / bss)
        return (xv - means.reshape(cshape)) * scales.reshape(cshape)

    def _dn_fwd(xv, bsz, bsm, bss):
        means = bsm / bsz
        scales = jnp.sqrt(bsz / bss)
        y = (xv - means.reshape(cshape)) * scales.reshape(cshape)
        return y, (xv, means, scales)

    def _dn_bwd(res, gy):
        xv, means, scales = res
        dx = gy * scales.reshape(cshape)
        d_bsz = jnp.full(means.shape, float(n), dtype=xv.dtype)
        d_bsm = jnp.sum(xv, axis=red)
        d_bss = jnp.sum(jnp.square(xv - means.reshape(cshape)), axis=red) + d_bsz * eps
        return dx, d_bsz, d_bsm, d_bss

    _dn.defvjp(_dn_fwd, _dn_bwd)
    means = bsum / bsize
    scales = jnp.sqrt(bsize / bsqsum)
    return {"Y": _dn(x, bsize, bsum, bsqsum), "Means": means, "Scales": scales}


@register_op("row_conv", no_grad_set={"SeqLen"})
def row_conv(inputs, attrs):
    """reference: operators/row_conv_op.h — lookahead convolution (Deep
    Speech 2): out[t] = sum_{j=0..k-1} x[t+j] * filter[j], future context
    zero beyond each sequence's end.  Padded [B, T, D] + SeqLen encoding;
    the k shifted adds stay fused elementwise on TPU (k is tiny)."""
    jnp = _jnp()
    x = one(inputs, "X")  # [B, T, D]
    filt = one(inputs, "Filter")  # [k, D]
    seq_len = maybe(inputs, "SeqLen")
    k = filt.shape[0]
    B, T, D = x.shape
    if seq_len is not None:
        m = (jnp.arange(T)[None, :] < seq_len.reshape(-1)[:, None]).astype(x.dtype)
        x = x * m[:, :, None]
    xpad = jnp.pad(x, ((0, 0), (0, k), (0, 0)))
    out = jnp.zeros_like(x)
    for j in range(k):
        out = out + xpad[:, j : j + T, :] * filt[j][None, None, :]
    return {"Out": out}


@register_op("bilinear_tensor_product")
def bilinear_tensor_product(inputs, attrs):
    """reference: operators/bilinear_tensor_product_op.h —
    out[b,k] = x[b]^T W[k] y[b] (+ bias).  One einsum -> two MXU matmuls."""
    jnp = _jnp()
    x = one(inputs, "X")  # [B, M]
    y = one(inputs, "Y")  # [B, N]
    w = one(inputs, "Weight")  # [K, M, N]
    bias = maybe(inputs, "Bias")  # [1, K]
    out = jnp.einsum("bm,kmn,bn->bk", x, w, y)
    if bias is not None:
        out = out + bias.reshape(1, -1)
    return {"Out": out}
