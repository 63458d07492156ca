"""Calibration yardstick: a hand-written pure-JAX ResNet-50 train step.

This is the framework-free reference point for bench.py: the same model
(ResNet-50 v1.5, NCHW, batch-stat BN, momentum SGD, bf16 activations)
written directly in jax/lax with no paddle_tpu machinery.  The measured
`pure_jax_step_ms` bounds what XLA can do for this model on this chip;
`framework_overhead_pct = (framework - pure) / pure` is then a measured,
driver-visible fact instead of a docstring claim.

Last recorded (BENCH_r05.json, one v5e chip, NHWC bs256 chunk10 fresh):
framework 120.92 ms/step vs this yardstick 119.83 ms — 0.91% overhead at
13.2% MFU.  Where the other 87% goes is for a trace to say (ROADMAP
Queue 1 item 3).
"""
import functools
import time

import numpy as np

BN_MOMENTUM = 0.9
BN_EPS = 1e-5


def _he(key, shape):
    import jax

    fan_in = int(np.prod(shape[1:]))
    return jax.random.normal(key, shape, "float32") * np.sqrt(2.0 / fan_in)


def init_params(seed=0):
    import jax

    key = jax.random.PRNGKey(seed)
    params, stats = {}, {}

    def conv(name, cout, cin, k):
        nonlocal key
        key, sub = jax.random.split(key)
        params[name + "_w"] = _he(sub, (cout, cin, k, k))

    def bn(name, c):
        params[name + "_scale"] = np.ones((c,), np.float32)
        params[name + "_bias"] = np.zeros((c,), np.float32)
        stats[name + "_mean"] = np.zeros((c,), np.float32)
        stats[name + "_var"] = np.ones((c,), np.float32)

    conv("stem", 64, 3, 7)
    bn("stem_bn", 64)
    cin = 64
    for si, (n_blocks, width) in enumerate([(3, 64), (4, 128), (6, 256), (3, 512)]):
        cout = width * 4
        for bi in range(n_blocks):
            p = "s%d_b%d" % (si, bi)
            conv(p + "_c1", width, cin, 1)
            bn(p + "_bn1", width)
            conv(p + "_c2", width, width, 3)
            bn(p + "_bn2", width)
            conv(p + "_c3", cout, width, 1)
            bn(p + "_bn3", cout)
            if bi == 0:
                conv(p + "_ds", cout, cin, 1)
                bn(p + "_dsbn", cout)
            cin = cout
    key, sub = jax.random.split(key)
    params["fc_w"] = _he(sub, (2048, 1000))
    params["fc_b"] = np.zeros((1000,), np.float32)
    return params, stats


def _conv(x, w, stride=1, layout="NCHW"):
    import jax

    k = w.shape[2]
    pad = (k - 1) // 2
    if layout == "NHWC":
        w = w.transpose(2, 3, 1, 0)  # OIHW -> HWIO
        dn = ("NHWC", "HWIO", "NHWC")
    else:
        dn = ("NCHW", "OIHW", "NCHW")
    return jax.lax.conv_general_dilated(
        x, w.astype(x.dtype), (stride, stride), [(pad, pad), (pad, pad)],
        dimension_numbers=dn,
    )


def _bn_train(x, params, stats, name, new_stats, layout="NCHW"):
    import jax.numpy as jnp

    xf = x.astype(jnp.float32)
    axes = (0, 1, 2) if layout == "NHWC" else (0, 2, 3)
    mean = jnp.mean(xf, axis=axes)
    var = jnp.var(xf, axis=axes)
    new_stats[name + "_mean"] = (
        stats[name + "_mean"] * BN_MOMENTUM + mean * (1 - BN_MOMENTUM)
    )
    new_stats[name + "_var"] = (
        stats[name + "_var"] * BN_MOMENTUM + var * (1 - BN_MOMENTUM)
    )
    inv = (params[name + "_scale"] / jnp.sqrt(var + BN_EPS)).astype(x.dtype)
    shift = (params[name + "_bias"] - mean * params[name + "_scale"]
             / jnp.sqrt(var + BN_EPS)).astype(x.dtype)
    if layout == "NHWC":
        return x * inv[None, None, None, :] + shift[None, None, None, :]
    return x * inv[None, :, None, None] + shift[None, :, None, None]


def forward(params, stats, images, layout="NCHW"):
    import jax
    import jax.numpy as jnp

    new_stats = {}
    x = images.astype(jnp.bfloat16)
    x = _conv(x, params["stem_w"], 2, layout=layout)
    x = _bn_train(x, params, stats, "stem_bn", new_stats, layout=layout)
    x = jax.nn.relu(x)
    if layout == "NHWC":
        win, strides = (1, 3, 3, 1), (1, 2, 2, 1)
        pads = [(0, 0), (1, 1), (1, 1), (0, 0)]
    else:
        win, strides = (1, 1, 3, 3), (1, 1, 2, 2)
        pads = [(0, 0), (0, 0), (1, 1), (1, 1)]
    x = jax.lax.reduce_window(x, -jnp.inf, jax.lax.max, win, strides, pads)
    for si, (n_blocks, width) in enumerate([(3, 64), (4, 128), (6, 256), (3, 512)]):
        for bi in range(n_blocks):
            p = "s%d_b%d" % (si, bi)
            stride = 2 if (bi == 0 and si > 0) else 1
            y = jax.nn.relu(_bn_train(_conv(x, params[p + "_c1_w"], layout=layout), params, stats, p + "_bn1", new_stats, layout=layout))
            # v1.5: the stride lives on the 3x3
            y = jax.nn.relu(_bn_train(_conv(y, params[p + "_c2_w"], stride, layout=layout), params, stats, p + "_bn2", new_stats, layout=layout))
            y = _bn_train(_conv(y, params[p + "_c3_w"], layout=layout), params, stats, p + "_bn3", new_stats, layout=layout)
            if bi == 0:
                x = _bn_train(_conv(x, params[p + "_ds_w"], stride, layout=layout), params, stats, p + "_dsbn", new_stats, layout=layout)
            x = jax.nn.relu(x + y)
    pool_axes = (1, 2) if layout == "NHWC" else (2, 3)
    x = jnp.mean(x.astype(jnp.float32), axis=pool_axes)  # [N, 2048]
    logits = x @ params["fc_w"] + params["fc_b"]
    return logits, new_stats


def loss_fn(params, stats, images, labels, layout="NCHW"):
    import jax

    logits, new_stats = forward(params, stats, images, layout=layout)
    logp = jax.nn.log_softmax(logits)
    nll = -jax.numpy.take_along_axis(logp, labels, axis=1)
    return jax.numpy.mean(nll), new_stats


def make_train_step(lr=0.1, momentum=0.9, n_steps=1, layout="NCHW",
                    fresh=False):
    """One jitted call = ``n_steps`` momentum-SGD steps (fori_loop).

    ``fresh=True``: images/labels carry a leading ``n_steps`` axis and
    each iteration consumes its own slice — the same fresh-batch regime
    as the framework path's ``per_step_feed`` (bench.py), so the
    overhead comparison stays apples-to-apples."""
    import functools as _ft

    import jax

    grad_fn = jax.value_and_grad(
        _ft.partial(loss_fn, layout=layout), has_aux=True)

    def one(carry, images, labels):
        params, vel, stats, _ = carry
        (loss, new_stats), grads = grad_fn(params, stats, images, labels)
        new_vel = jax.tree.map(lambda v, g: momentum * v + g, vel, grads)
        new_params = jax.tree.map(lambda p, v: p - lr * v, params, new_vel)
        return new_params, new_vel, new_stats, loss

    @functools.partial(jax.jit, donate_argnums=(0, 1, 2))
    def train_step(params, vel, stats, images, labels):
        def batch(i):
            if not fresh:
                return images, labels
            return (
                jax.lax.dynamic_index_in_dim(images, i, 0, keepdims=False),
                jax.lax.dynamic_index_in_dim(labels, i, 0, keepdims=False),
            )

        carry = one((params, vel, stats, np.float32(0)), *batch(0))
        if n_steps > 1:
            carry = jax.lax.fori_loop(
                1, n_steps, lambda i, c: one(c, *batch(i)), carry
            )
        return carry

    return train_step


def measure(batch=256, steps=20, chunk=10, seed=0, layout="NCHW",
            fresh=False):
    """Returns (step_time_ms, final_loss) for the pure-JAX yardstick,
    timed exactly like bench.py's framework path: ``chunk`` steps per
    jitted call, a d2h sync per chunk; ``fresh=True`` feeds ``chunk``
    distinct batches per call (matching per_step_feed)."""
    import jax

    dev = jax.devices()[0]
    params, stats = init_params(seed)
    params = jax.device_put(params, dev)
    stats = jax.device_put(stats, dev)
    vel = jax.tree.map(lambda p: np.zeros(p.shape, p.dtype), params)
    vel = jax.device_put(vel, dev)
    rng = np.random.RandomState(0)
    shape = (batch, 224, 224, 3) if layout == "NHWC" else (batch, 3, 224, 224)
    fresh = bool(fresh) and chunk > 1
    n_b = chunk if fresh else 1
    imgs = rng.uniform(-1, 1, (n_b,) + shape).astype(np.float32)
    lbls = rng.randint(0, 1000, (n_b, batch, 1)).astype(np.int32)
    images = jax.device_put(imgs if fresh else imgs[0], dev)
    labels = jax.device_put(lbls if fresh else lbls[0], dev)
    images1 = jax.device_put(imgs[0], dev)
    labels1 = jax.device_put(lbls[0], dev)

    step1 = make_train_step(n_steps=1, layout=layout)
    stepN = make_train_step(n_steps=chunk, layout=layout, fresh=fresh)
    for _ in range(2):  # warmup/compile the single-step path
        params, vel, stats, loss = step1(params, vel, stats, images1, labels1)
    np.asarray(loss)
    params, vel, stats, loss = stepN(params, vel, stats, images, labels)
    np.asarray(loss)  # compile + warm the chunked path

    done = 0
    t0 = time.perf_counter()
    while done < steps:
        params, vel, stats, loss = stepN(params, vel, stats, images, labels)
        done += chunk
        lv = np.asarray(loss)
    dt = time.perf_counter() - t0
    return dt * 1e3 / done, float(lv)


if __name__ == "__main__":
    ms, loss = measure()
    print({"pure_jax_step_ms": round(ms, 2), "loss": loss})
