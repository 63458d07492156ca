"""The gated delta-rule hybrid decoder (``model_type: olmo_hybrid``) on
the pooled decode path: ``decoding.make_delta_hybrid_lm_pooled_step_fn``
-> ``KVSlotPool`` -> ``DecodeServer``, at tiny sizes on the CPU (seeded:
``dk != dv``, a head count that is no power of two), against the
benchmark's plain reference (``benchmark/configs/
olmo_hybrid_7b_reference.py``: float32, full forward, no cache, the rule
a scan over time).

What is new under the pool: a recurrent state that is READ (``S^T k``)
before it is written, per-token decay and step gates, one short
convolution over ``[q; k; v]``, a state leaf that lays several heads in
one row of lanes, and full attention of one query head a K/V head.
"""
import functools
import importlib.util
import os

import numpy as np
import pytest

from conftest import WAIT
from test_routed_conv_lm import _staggered

from paddle_tpu import decode_attention as da
from paddle_tpu import decoding
from paddle_tpu import delta_hybrid_lm as dh
from paddle_tpu.serving.decode import DecodeServer
from paddle_tpu.serving.kv_pool import KVSlotPool

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _load(path, name):
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


ref = _load(os.path.join(ROOT, "benchmark", "configs",
                         "olmo_hybrid_7b_reference.py"), "olmo_reference")

V = 211
#: (linear heads, dk, dv): two heads a lane tile (64 + 64 lanes, as the
#: published 192 + 192 are three), and one head a (padded) row
LAYOUTS = {"two_heads_a_row": (6, 24, 64), "one_head_a_row": (3, 12, 20)}


def tiny_cfg(layout="two_heads_a_row", **over):
    heads, dk, dv = LAYOUTS[layout]
    cfg = dict(
        model_type="olmo_hybrid", vocab_size=V, hidden_size=64,
        intermediate_size=96, num_hidden_layers=4, num_attention_heads=4,
        num_key_value_heads=4, rms_norm_eps=1e-6,
        layer_types=[dh.LINEAR] * 3 + [dh.FULL],
        linear_num_key_heads=heads, linear_num_value_heads=heads,
        linear_key_head_dim=dk, linear_value_head_dim=dv,
        linear_conv_kernel_dim=4, linear_allow_neg_eigval=True,
        rope_parameters={"rope_theta": None})
    cfg.update(over)
    return cfg


def weights(cfg, seed=0, dtype="float32"):
    return dh.random_state(np.random.RandomState(seed), cfg, std=0.1,
                           dtype=dtype)


def _reference_leaves(w, toks, cfg):
    """What the pool's recurrent leaves must hold after ``toks`` [S]: per
    linear layer the rule's state ``[H, dk, dv]`` and the last three
    projected rows, by the reference's own equations (a second scan that
    keeps the state the reference's ``gated_delta_net`` throws away)."""
    import jax
    import jax.numpy as jnp

    out, h = [], ref.embed(w, jnp.asarray(toks)[None], cfg)
    heads, dk, dv = (cfg["linear_num_value_heads"],
                     cfg["linear_key_head_dim"], cfg["linear_value_head_dim"])
    with jax.default_matmul_precision("highest"):
        for i, kind in enumerate(cfg["layer_types"]):
            p = "lm_l%d_" % i
            if kind == dh.LINEAR:
                x = h[0]
                qkv = jnp.concatenate([x @ w[p + "lin_q"], x @ w[p + "lin_k"],
                                       x @ w[p + "lin_v"]], -1)
                pad = jnp.pad(qkv, ((3, 0), (0, 0)))
                act = jax.nn.silu(sum(pad[j:j + len(toks)]
                                      * w[p + "lin_conv_w"][j]
                                      for j in range(4)))
                k = ref._l2(act[:, heads * dk:2 * heads * dk].reshape(
                    -1, heads, dk))
                v = act[:, 2 * heads * dk:].reshape(-1, heads, dv)
                beta = 2 * jax.nn.sigmoid(x @ w[p + "lin_b"])
                alpha = jnp.exp(-jnp.exp(w[p + "lin_A_log"]) * jax.nn.softplus(
                    x @ w[p + "lin_a"] + w[p + "lin_dt_bias"]))
                s = jnp.zeros((heads, dk, dv))
                for t in range(len(toks)):
                    s = alpha[t][:, None, None] * s
                    u = jnp.einsum("hkv,hk->hv", s, k[t])
                    s = s + k[t][..., None] * (
                        beta[t][:, None] * (v[t] - u))[:, None, :]
                out.append((np.asarray(s), np.asarray(qkv[-3:])))
            h, _ = ref.block(w, i, h, cfg, kind)
    return out


def _state_by_head(leaf, d):
    """A row of the pool's state leaf ``[H / g, dk, g * dv]`` as the
    rule's ``[H, dk, dv]``."""
    g = d.tile_heads
    return np.asarray(leaf).reshape(d.lin_heads // g, d.dk, g, d.dv) \
        .transpose(0, 2, 1, 3).reshape(d.lin_heads, d.dk, d.dv)


# fp32: the step and the reference differ in the order of float32 sums.
# bf16: the step multiplies bf16 weights by activations rounded to bf16
# and keeps K/V in bf16 (relative 2**-9 an operand, twelve products
# deep); the reference upcasts the same weights and keeps everything
# else in float32.
@pytest.mark.parametrize("layout", sorted(LAYOUTS))
@pytest.mark.parametrize("dtype,kv_dtype,tol", [
    ("float32", "fp32", 2e-5), ("bfloat16", "bf16", 3e-2)])
def test_prefill_then_decode_equals_the_full_forward(layout, dtype, kv_dtype,
                                                     tol):
    import jax.numpy as jnp

    cfg = tiny_cfg(layout)
    d = dh.dims(cfg)
    assert d.tile_heads == (2 if layout == "two_heads_a_row" else 1)
    w = weights(cfg, seed=3, dtype=dtype)
    step, make_cache = decoding.make_delta_hybrid_lm_pooled_step_fn(
        w, cfg, kv_dtype=kv_dtype)
    toks = np.random.RandomState(5).randint(0, V, (3, 12)).astype(np.int32)
    want = np.asarray(ref.forward(w, jnp.asarray(toks), cfg))
    got, cache = _staggered(step, make_cache, toks)
    assert np.abs(got - want).max() <= tol * (want.max() - want.min())
    # the row that was idle throughout was neither written nor started
    for layer in cache:
        for leaf in layer.values():
            assert float(jnp.abs(leaf[3].astype("float32")).max()) == 0.0
    assert [sorted(layer) for layer in cache] == (
        [["conv", "state"]] * 3 + [["k", "v"]])
    assert cache[3]["k"].dtype == jnp.dtype(
        {"fp32": "float32", "bf16": "bfloat16"}[kv_dtype])
    assert cache[0]["state"].dtype == cache[0]["conv"].dtype == jnp.float32
    assert cache[0]["state"].shape == (4,) + d.state_shape


@pytest.mark.parametrize("layout", sorted(LAYOUTS))
def test_every_leaf_holds_what_the_references_equations_leave(layout):
    """After a row's twelve tokens its state leaf is the rule's ``S`` of
    every head (whichever way the heads share a row of lanes), its conv
    leaf the last three projected rows, its K/V rows the normed keys and
    the values of every position."""
    import jax.numpy as jnp

    cfg = tiny_cfg(layout)
    d = dh.dims(cfg)
    w = weights(cfg, seed=3)
    step, make_cache = decoding.make_delta_hybrid_lm_pooled_step_fn(
        w, cfg, kv_dtype="fp32")
    toks = np.random.RandomState(5).randint(0, V, (3, 12)).astype(np.int32)
    _, cache = _staggered(step, make_cache, toks)
    for b in range(3):
        for i, (s, conv) in enumerate(_reference_leaves(w, toks[b], cfg)):
            got = _state_by_head(cache[i]["state"][b], d)
            assert np.abs(got - s).max() <= 1e-5 * np.abs(s).max()
            assert np.abs(np.asarray(cache[i]["conv"][b]) - conv).max() \
                <= 1e-5 * np.abs(conv).max()
    # the full layer's rows: k after its norm, v as projected
    h = ref.embed(w, jnp.asarray(toks), cfg)
    for i in range(3):
        h, _ = ref.block(w, i, h, cfg, dh.LINEAR)
    k = ref._rms(h @ w["lm_l3_attn_k"], w["lm_l3_attn_k_norm"], 1e-6)
    for name, rows in (("k", k), ("v", h @ w["lm_l3_attn_v"])):
        rows = np.asarray(rows)
        assert np.abs(np.asarray(cache[3][name])[:3, :12] - rows).max() \
            <= 2e-5 * np.abs(rows).max()


def test_the_step_gate_reaches_past_one_in_these_weights():
    """``beta = 2 sigmoid(W_b x)``: were it never above 1 here, a step
    without the factor 2 could not be told from one with it — and it is
    told: served with ``linear_allow_neg_eigval`` false the logits leave
    the reference's."""
    import jax.numpy as jnp

    cfg = tiny_cfg()
    w = weights(cfg, seed=3)
    toks = np.random.RandomState(5).randint(0, V, (3, 12)).astype(np.int32)
    x = ref.embed(w, jnp.asarray(toks), cfg)
    _, beta = dh.decay_and_step_gates(x.reshape(-1, 64), w, "lm_l0_",
                                      dh.dims(cfg))
    beta = np.asarray(beta)
    assert beta.max() > 1.3 and beta.min() < 0.7
    assert (beta > 1).mean() > 0.25
    want = np.asarray(ref.forward(w, jnp.asarray(toks), cfg))
    step, make_cache = decoding.make_delta_hybrid_lm_pooled_step_fn(
        w, dict(cfg, linear_allow_neg_eigval=False), kv_dtype="fp32")
    got, _ = _staggered(step, make_cache, toks)
    assert np.abs(got - want).max() > 1e-2 * (want.max() - want.min())


def test_gated_delta_step_reads_the_state_before_it_writes_it():
    """One head, one row, by hand: with ``alpha`` = 1 and ``beta`` = 1
    the rule REPLACES what the state returns for ``k`` by ``v`` (the
    delta rule), where a state that only adds would return their sum."""
    import jax.numpy as jnp

    k = jnp.asarray([[[1.0, 0.0]]])                  # [N=1, H=1, dk=2]
    v0 = jnp.asarray([[[1.0, 2.0, 3.0]]])
    v1 = jnp.asarray([[[5.0, 5.0, 5.0]]])
    one = jnp.ones((1, 1))
    s = jnp.zeros((1, 1, 2, 3))
    ts = jnp.asarray([1], jnp.int32)
    o, s = dh.gated_delta_step(k, k, v0, one, one, s, ts)
    assert np.allclose(o, v0)
    o, s = dh.gated_delta_step(k, k, v1, one, one, s, ts)
    assert np.allclose(o, v1) and np.allclose(s[0, 0, 0], v1[0, 0])
    # beta = 2 overshoots to the other side (a negative eigenvalue)
    o, s = dh.gated_delta_step(k, k, v0, one, 2 * one, s, ts)
    assert np.allclose(o, 2 * v0 - v1)
    # a decay of 1/2 halves what is read before the write
    o, _ = dh.gated_delta_step(k, k, jnp.zeros_like(v0), 0.5 * one,
                               0 * one, s, ts)
    assert np.allclose(o, 0.5 * (2 * v0 - v1))


# ---------------------------------------------------------------------------
# the rule's two lowerings: the Pallas kernel (interpret mode here) and
# the XLA form, against the rule written out in numpy
# ---------------------------------------------------------------------------
#: Olmo-Hybrid-7B's head shape (96 key lanes, 192 value lanes: two heads
#: a row of 384 lanes), few heads and slots
OLMO_HEAD = (4, 96, 192)


def _numpy_rule(q, k, v, alpha, beta, s, ts):
    """One token of the rule, head by head and row by row, over the
    state as ``[N, H, dk, dv]`` float64."""
    q, k, v, alpha, beta = (np.asarray(a, np.float64)
                            for a in (q, k, v, alpha, beta))
    s = np.array(s, np.float64)
    o = np.zeros(v.shape)
    for n in range(s.shape[0]):
        for h in range(s.shape[1]):
            prev = s[n, h] if ts[n] != 0 else np.zeros_like(s[n, h])
            dec = alpha[n, h] * prev
            u = dec.T @ k[n, h]
            new = dec + np.outer(k[n, h], beta[n, h] * (v[n, h] - u))
            o[n, h] = new.T @ q[n, h]
            if ts[n] >= 0:
                s[n, h] = new
    return o, s


def _leaf_by_head(leaf, heads, dk, dv):
    g = dh.heads_per_tile(heads, dv)
    return np.asarray(leaf).reshape(-1, heads // g, dk, g, dv) \
        .transpose(0, 1, 3, 2, 4).reshape(-1, heads, dk, dv)


def _rule_inputs(rng, n, heads, dk, dv):
    import jax.numpy as jnp

    q = dh.l2_norm(jnp.asarray(rng.randn(n, heads, dk), jnp.float32)) \
        * dk ** -0.5
    k = dh.l2_norm(jnp.asarray(rng.randn(n, heads, dk), jnp.float32))
    return (q, k, jnp.asarray(rng.randn(n, heads, dv), jnp.float32),
            jnp.asarray(rng.uniform(0.5, 1.0, (n, heads)), jnp.float32),
            jnp.asarray(rng.uniform(0.0, 2.0, (n, heads)), jnp.float32))


@pytest.mark.parametrize("case,slots,first_ts,steps", [
    ("olmo_head_shape", 8, [3, 9, 1, 500, 17, 2, 64, 5], 1),
    ("fresh_rows", 8, [0, 4, 0, 0, 7, 0, 1, 0], 1),
    ("idle_rows", 8, [-1, 4, -1, 0, -1, -1, 2, -1], 1),
    ("mixed_over_steps", 16, [0, 3, -1, 5] * 4, 4)])
def test_the_kernel_is_the_xla_form_and_the_rule(case, slots, first_ts, steps):
    """The Pallas kernel (interpret mode) against the XLA form and the
    rule in numpy at Olmo-Hybrid-7B's head shape: live rows, fresh rows
    (a state of garbage read as zero), idle rows (their state bit-equal
    afterwards, whatever the step computed), and a mixed batch whose
    written state feeds the next steps' reads."""
    import jax.numpy as jnp

    heads, dk, dv = OLMO_HEAD
    g = dh.heads_per_tile(heads, dv)
    assert g == 2
    rng = np.random.RandomState(len(case))
    start = jnp.asarray(rng.randn(slots, heads // g, dk, g * dv),
                        jnp.float32)
    assert dh.lowering("tpu", start, dv) == "kernel"
    ts = np.asarray(first_ts, np.int32)
    by_kernel = by_xla = start
    by_hand = _leaf_by_head(start, heads, dk, dv)
    for _ in range(steps):
        args = _rule_inputs(rng, slots, heads, dk, dv)
        o_k, by_kernel = dh.kernel_gated_delta_step(
            *args, by_kernel, jnp.asarray(ts), interpret=True)
        o_x, by_xla = dh.xla_gated_delta_step(*args, by_xla, jnp.asarray(ts))
        o_n, by_hand = _numpy_rule(*args, by_hand, ts)
        live = ts >= 0
        np.testing.assert_allclose(np.asarray(o_k)[live],
                                   np.asarray(o_x)[live], rtol=0, atol=2e-6)
        np.testing.assert_allclose(np.asarray(o_k)[live], o_n[live], rtol=0,
                                   atol=5e-6)
        np.testing.assert_allclose(by_kernel, by_xla, rtol=0, atol=5e-6)
        np.testing.assert_allclose(
            _leaf_by_head(by_kernel, heads, dk, dv), by_hand, rtol=0,
            atol=1e-5)
        # an idle row's state is the bits it was
        assert np.array_equal(np.asarray(by_kernel)[~live],
                              np.asarray(start)[~live])
        ts = np.where(live, ts + 1, ts)


#: Solar-Open2's head shape: dk = dv = 128, one head a row of lanes
KDA_HEAD = (3, 128, 128)


@pytest.mark.parametrize("case,layout,slots,first_ts,steps", [
    ("kda_head_shape", KDA_HEAD, 8, [3, 9, 1, 500, 17, 2, 64, 5], 1),
    ("fresh_and_idle_rows", KDA_HEAD, 8, [0, 4, -1, 0, -1, 0, 1, -1], 1),
    ("mixed_over_steps", KDA_HEAD, 16, [0, 3, -1, 5] * 4, 3),
    ("two_heads_a_row", OLMO_HEAD, 8, [0, 4, -1, 7, 2, 0, 1, -1], 2)])
def test_a_decay_a_channel_is_the_kernel_the_xla_form_and_the_rule(
        case, layout, slots, first_ts, steps):
    """``alpha [N, H, dk]`` (``S <- Diag(alpha) S``) through both
    lowerings of the one contract and the rule in numpy: the kernel takes
    the decay as a third column beside k and q."""
    import jax.numpy as jnp

    heads, dk, dv = layout
    g = dh.heads_per_tile(heads, dv)
    rng = np.random.RandomState(len(case))
    start = jnp.asarray(rng.randn(slots, heads // g, dk, g * dv),
                        jnp.float32)
    assert dh.lowering("tpu", start, dv) == "kernel"
    ts = np.asarray(first_ts, np.int32)
    by_kernel = by_xla = start
    by_hand = _leaf_by_head(start, heads, dk, dv)
    for _ in range(steps):
        q, k, v, _, beta = _rule_inputs(rng, slots, heads, dk, dv)
        alpha = jnp.asarray(rng.uniform(0.3, 1.0, (slots, heads, dk)),
                            jnp.float32)
        args = (q, k, v, alpha, beta)
        o_k, by_kernel = dh.kernel_gated_delta_step(
            *args, by_kernel, jnp.asarray(ts), interpret=True)
        o_x, by_xla = dh.xla_gated_delta_step(*args, by_xla, jnp.asarray(ts))
        o_n, by_hand = _numpy_rule(q, k, v, np.asarray(alpha)[..., None],
                                   beta, by_hand, ts)
        live = ts >= 0
        np.testing.assert_allclose(np.asarray(o_k)[live],
                                   np.asarray(o_x)[live], rtol=0, atol=2e-6)
        np.testing.assert_allclose(np.asarray(o_k)[live], o_n[live], rtol=0,
                                   atol=5e-6)
        np.testing.assert_allclose(by_kernel, by_xla, rtol=0, atol=5e-6)
        np.testing.assert_allclose(
            _leaf_by_head(by_kernel, heads, dk, dv), by_hand, rtol=0,
            atol=1e-5)
        assert np.array_equal(np.asarray(by_kernel)[~live],
                              np.asarray(start)[~live])
        ts = np.where(live, ts + 1, ts)


@pytest.mark.parametrize("form", ["xla", "kernel"])
def test_one_value_a_head_in_every_channel_is_the_decay_a_head(form):
    """The two contracts meet: ``alpha [N, H, dk]`` whose channels all
    carry their head's one value gives what ``alpha [N, H]`` gives, bit
    for bit in the XLA form (the same products in the same order) and to
    a rounding in the kernel."""
    import jax.numpy as jnp

    heads, dk, dv = KDA_HEAD
    rng = np.random.RandomState(9)
    s = jnp.asarray(rng.randn(8, heads, dk, dv), jnp.float32)
    ts = jnp.asarray([0, 4, -1, 7, 2, 0, 1, 30], jnp.int32)
    q, k, v, alpha, beta = _rule_inputs(rng, 8, heads, dk, dv)
    wide = jnp.broadcast_to(alpha[..., None], (8, heads, dk))
    step = (dh.xla_gated_delta_step if form == "xla" else functools.partial(
        dh.kernel_gated_delta_step, interpret=True))
    o_head, s_head = step(q, k, v, alpha, beta, s, ts)
    o_chan, s_chan = step(q, k, v, wide, beta, s, ts)
    tol = 0 if form == "xla" else 1e-6
    np.testing.assert_allclose(o_chan, o_head, rtol=0, atol=tol)
    np.testing.assert_allclose(s_chan, s_head, rtol=0, atol=tol)


def test_solar_widths_take_thirty_two_slots_a_block():
    """At the ``solar_open2_250b`` cell's leaf (256 slots of 64 KB a
    head) a block is 32 slots, 2 MB a buffer."""
    assert dh._block_slots(256, 128, 128) == 32
    assert dh.heads_per_tile(64, 128) == 1



@pytest.mark.parametrize("why,backend,dtype,layout,slots,want", [
    ("a_tpu_and_whole_tiles", "tpu", "float32", "two_heads_a_row", 8,
     "kernel"),
    ("a_cpu_backend", "cpu", "float32", "two_heads_a_row", 8, "xla"),
    ("a_leaf_that_is_not_float32", "tpu", "bfloat16", "two_heads_a_row", 8,
     "xla"),
    ("one_head_a_padded_row", "tpu", "float32", "one_head_a_row", 8, "xla"),
    ("slots_that_are_no_whole_block", "tpu", "float32", "two_heads_a_row", 6,
     "xla")])
def test_lowering_chooses_from_what_the_call_can_see(why, backend, dtype,
                                                     layout, slots, want):
    import jax
    import jax.numpy as jnp

    d = dh.dims(tiny_cfg(layout))
    leaf = jax.ShapeDtypeStruct((slots,) + d.state_shape, jnp.dtype(dtype))
    assert dh.lowering(backend, leaf, d.dv) == want
    assert (d.tile_heads == 1) == (layout == "one_head_a_row")


def test_olmo_widths_take_sixteen_slots_a_block():
    """The block is a rule on the shapes: at the cell's leaf (80 slots of
    147 KB a head pair) the most slots that divide the slots and a lane
    tile within the budget of a buffer."""
    assert dh._block_slots(80, 96, 384) == 16
    assert dh._block_slots(8, 96, 384) == 8
    assert dh._block_slots(80, 128, 4096) == 0      # a state too large
    assert dh._block_slots(12, 96, 384) == 0        # no whole sublanes


@pytest.mark.parametrize("backend,path", [("cpu", "xla"), ("tpu", "kernel")])
def test_delta_update_lowered_total_counts_the_form_taken(backend, path,
                                                          monkeypatch):
    """A traced step bumps ``delta_update_lowered_total{path}`` once a
    linear layer, by the form :func:`lowering` chose — and on a TPU at
    whole tiles the traced program holds the kernel's call."""
    import jax

    cfg = tiny_cfg()
    step, make_cache = decoding.make_delta_hybrid_lm_pooled_step_fn(
        weights(cfg), cfg, kv_dtype="fp32")
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    count = lambda p: dh.LOWERED.labels(path=p).value
    other = {"xla": "kernel", "kernel": "xla"}[path]
    before = count(path), count(other)
    jaxpr = jax.make_jaxpr(step)(make_cache(8, 16), np.zeros(8, np.int32),
                                 np.zeros(8, np.int32))
    assert (count(path) - before[0], count(other) - before[1]) == (3, 0)
    assert (dh.KERNEL_NAME in str(jaxpr)) == (path == "kernel")


#: equations of the kernel's body at the cell's shapes: 139 as written,
#: half as many again allowed (tests/test_decode_attention.py says why a
#: body is held by a count: what a trace and a lowering cost a process
#: grows with it, compile cache hit or not)
_DELTA_KERNEL_EQUATIONS_MAX = 208


def test_the_kernel_is_traced_once_and_its_body_stays_small():
    """A step's linear layers share ONE traced function of the kernel,
    and its body holds one loop over a block's slots."""
    import importlib.util

    import jax

    spec = importlib.util.spec_from_file_location(
        "time_delta_update", os.path.join(ROOT, "tools",
                                          "time_delta_update.py"))
    tool = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(tool)
    f, args = tool.two_layer_program(dh, tool.SHAPE, interpret=False)
    calls = [e for e in jax.make_jaxpr(f)(*args).jaxpr.eqns
             if "jaxpr" in e.params
             and e.params.get("name") == "_delta_update"]
    assert len(calls) == 2      # one a layer ...
    # ... of one function traced once
    assert calls[0].params["jaxpr"] is calls[1].params["jaxpr"]
    kernels = [e for e in calls[0].params["jaxpr"].jaxpr.eqns
               if e.primitive.name == "pallas_call"]
    assert len(kernels) == 1
    body = tool.equations(kernels[0].params["jaxpr"])
    assert 40 < body <= _DELTA_KERNEL_EQUATIONS_MAX, body


# ---------------------------------------------------------------------------
# the pool: different leaves a layer, a reused slot, refused tiers
# ---------------------------------------------------------------------------
def _pool(cfg, w, len_ladder, **kw):
    step, make_cache = decoding.make_delta_hybrid_lm_pooled_step_fn(
        w, cfg, kv_dtype="fp32")
    return KVSlotPool(step, make_cache, eos_id=V, max_slots=2,
                      max_seq_len=len_ladder[-1], slot_ladder=[2],
                      len_ladder=len_ladder, steps=2, kv_dtype="fp32",
                      **kw), make_cache


def _serve(pool, state, slot, prompt, n_new):
    state = pool.admit(state, slot, prompt, len(prompt), len(prompt) + n_new)
    while not bool(np.asarray(state["finished"])[slot]):
        state = pool.chunk(state)
    toks = np.asarray(state["tokens"])[slot]
    return state, toks[len(prompt):len(prompt) + n_new].copy()


@pytest.mark.parametrize("reset", [True, False])
def test_a_reused_slot_starts_its_state_and_conv_window_from_zero(
        reset, monkeypatch):
    """Request B in the slot request A left gets B's tokens and leaves
    exactly as a pool that never held A gives them; with the reset taken
    out of the step it starts from A's state and conv window and does
    not."""
    import jax.numpy as jnp

    if not reset:
        monkeypatch.setattr(dh, "starts_fresh",
                            lambda ts: jnp.zeros(ts.shape, bool))
    cfg = tiny_cfg()
    w = weights(cfg, seed=11)
    rng = np.random.RandomState(2)
    a, b = (rng.randint(0, V, n).astype(np.int32) for n in (9, 4))
    pool, _ = _pool(cfg, w, [16])
    virgin, want_toks = _serve(pool, pool.alloc(2, 16), 0, b, 8)
    used, _ = _serve(pool, pool.alloc(2, 16), 0, a, 7)
    for name in ("state", "conv"):
        assert np.abs(np.asarray(used["cache"][0][name])[0]).max() > 0
    used, got_toks = _serve(pool, used, 0, b, 8)
    same = all(np.array_equal(np.asarray(u[name])[0], np.asarray(v[name])[0])
               for u, v in zip(used["cache"], virgin["cache"])
               for name in ("state", "conv") if name in u)
    if reset:
        assert same and np.array_equal(got_toks, want_toks)
    else:
        assert not same


def test_an_idle_row_keeps_its_state_and_its_conv_window():
    """A slot that finished sits idle while its neighbour runs on: the
    steps it does not take leave both of its recurrent leaves as they
    were."""
    cfg = tiny_cfg()
    w = weights(cfg, seed=11)
    rng = np.random.RandomState(4)
    a, b = (rng.randint(0, V, n).astype(np.int32) for n in (5, 6))
    pool, _ = _pool(cfg, w, [32])
    state, _ = _serve(pool, pool.alloc(2, 32), 0, a, 4)
    before = [{k: np.asarray(v)[0].copy() for k, v in layer.items()}
              for layer in state["cache"]]
    assert np.abs(before[0]["state"]).max() > 0
    state, _ = _serve(pool, state, 1, b, 20)
    for layer, was in zip(state["cache"], before):
        for name in ("state", "conv"):
            if name in was:
                assert np.array_equal(np.asarray(layer[name])[0], was[name])


def test_layers_that_hold_different_leaves_are_declared_leaf_by_leaf():
    """A full layer holds k and v and no state, a linear layer a state
    and a conv window and no K/V: ``spec_of(make_cache)`` reads the
    declarations, ``resize`` keeps the
    recurrent leaves whatever the length rung, and the bytes follow."""
    import jax

    cfg = tiny_cfg()
    w = weights(cfg, seed=4)
    pool, make_cache = _pool(cfg, w, [16, 32])
    d = dh.dims(cfg)
    leaves = jax.tree.leaves(jax.eval_shape(lambda: make_cache(2, 16)))
    with pytest.raises(ValueError, match="make_cache declares nothing"):
        decoding.spec_of(lambda s, t: make_cache(s, t))
    spec = decoding.spec_of(make_cache)
    assert len(spec.flat) == len(leaves)
    # flattened: (conv, state) x 3, then k, v
    assert [leaf.seq_axis for leaf in spec.flat] == (
        [None, None] * 3 + [1, 1])
    assert pool.recurrent_leaves == spec.names(lambda leaf: leaf.seq_axis is None) == [
        "[%d]['%s']" % (i, n) for i in range(3) for n in ("conv", "state")]

    rng = np.random.RandomState(9)
    p0, p1 = (rng.randint(0, V, n).astype(np.int32) for n in (5, 3))
    want = pool.admit(pool.alloc(2, 32), 0, p0, 5, 24)
    want = pool.chunk(pool.chunk(want))
    want = pool.admit(want, 1, p1, 3, 12)
    for _ in range(12):
        want = pool.chunk(want)
    state = pool.admit(pool.alloc(2, 16), 0, p0, 5, 24)
    state = pool.chunk(pool.chunk(state))
    held = np.asarray(state["cache"][0]["state"])
    state = pool.resize(state, 2, 32)
    assert np.array_equal(np.asarray(state["cache"][0]["state"]), held)
    state = pool.admit(state, 1, p1, 3, 12)
    for _ in range(12):
        state = pool.chunk(state)
    assert np.array_equal(np.asarray(state["tokens"]),
                          np.asarray(want["tokens"]))
    # bytes: K/V of the ONE full layer scale with the rung; the three
    # states and conv windows do not
    assert pool.kv_rung_bytes(2, 32) == 2 * pool.kv_rung_bytes(2, 16) == (
        2 * 2 * 32 * d.d_kv * 4)
    assert pool.recurrent_rung_bytes(2, 32) == pool.recurrent_rung_bytes(
        2, 16) == 3 * 2 * 4 * (d.lin_heads * d.dk * d.dv
                               + (d.conv_len - 1) * d.d_qkv)


@pytest.mark.parametrize("tier", ["prefix", "speculative"])
def test_prefix_and_speculation_are_refused_over_this_builder(tier):
    cfg = tiny_cfg()
    w = weights(cfg)
    if tier == "prefix":
        kw = {"prefix": True}
    else:
        from paddle_tpu.serving.speculative import SpeculativeConfig

        step, make_cache = decoding.make_delta_hybrid_lm_pooled_step_fn(
            w, cfg, kv_dtype="fp32")
        kw = {"speculative": SpeculativeConfig(
            lambda c, t, ts: (None, c), step, make_cache, k=2)}
    with pytest.raises(ValueError, match=r"recurrent leaves .*a recurrent "
                       r"state has no"):
        _pool(cfg, w, [16], **kw)
    if tier == "prefix":
        step, make_cache = decoding.make_delta_hybrid_lm_pooled_step_fn(
            w, cfg, kv_dtype="fp32")
        with pytest.raises(ValueError, match="recurrent leaves"):
            DecodeServer(step, make_cache, eos_id=V, max_seq_len=16,
                         max_slots=2, prefix_cache=1 << 20)


def test_a_config_this_builder_cannot_serve_is_refused_by_name():
    with pytest.raises(ValueError, match="layer_types"):
        dh.dims(tiny_cfg(layer_types=[dh.LINEAR, "sliding_attention"] * 2))
    with pytest.raises(ValueError, match="linear_num_key_heads"):
        dh.dims(tiny_cfg(linear_num_key_heads=3))


# ---------------------------------------------------------------------------
# the chooser's count of the form one query head a K/V head lowered to
# ---------------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["cpu", "tpu"])
def test_the_full_layers_count_the_form_they_lowered(backend, monkeypatch):
    """``make_decode_attention`` counts a step of one query head a K/V
    head over sequence leaves by the form it took, once a full layer of
    a traced step, and the read rule the builder declares for the
    server's counter follows the same choice: on the CPU the XLA form
    and the whole rung; built for a TPU over bf16 leaves of
    whole-lane-tile heads the grouped kernel (a head one row of a unit)
    and its rounding — a rung of one block in classes of an eighth."""
    import jax


    tpu = backend == "tpu"
    if tpu:     # no chip here: the answer is given for the backend built for
        monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    cfg = tiny_cfg(layer_types=[dh.LINEAR, dh.FULL] * 2,
                   **(dict(head_dim=128) if tpu else {}))
    w = weights(cfg)
    step, make_cache = decoding.make_delta_hybrid_lm_pooled_step_fn(
        w, cfg, kv_dtype="bf16")
    count = lambda path: da.UNGROUPED_LOWERED.labels(path=path).value
    before = count("xla"), count("kernel")
    rung = 128 if tpu else 16
    jax.eval_shape(step, make_cache(2, rung), np.zeros(2, np.int32),
                   np.zeros(2, np.int32))
    assert (count("xla") - before[0], count("kernel") - before[1]) == (
        (0, 2) if tpu else (2, 0))
    read = decoding.spec_of(make_cache).reads[0].rule(
        np.array([0, 7, 15, rung - 1]), rung)
    assert read.tolist() == ([16, 16, 16, 128] if tpu else [rung] * 4)


# ---------------------------------------------------------------------------
# the server
# ---------------------------------------------------------------------------
def test_decode_server_end_to_end_with_slot_reuse():
    """Six requests through two slots: every one gets the tokens the
    reference's full forward ranks first (greedy, fp32), each reused
    slot's state was started from zero, and the server counted one reset
    an admission."""
    import jax.numpy as jnp

    cfg = tiny_cfg()
    w = weights(cfg, seed=21)
    step, make_cache = decoding.make_delta_hybrid_lm_pooled_step_fn(
        w, cfg, kv_dtype="fp32")
    srv = DecodeServer(step, make_cache, eos_id=V, max_seq_len=32,
                       max_slots=2, slot_ladder=[2], len_ladder=[32],
                       steps_per_tick=2, kv_dtype="fp32", name="delta-e2e")
    try:
        srv.warmup()
        rng = np.random.RandomState(8)
        prompts = [rng.randint(0, V, n).astype(np.int32)
                   for n in (5, 9, 3, 7, 4, 6)]
        reqs = [srv.submit({"tokens": p}, max_new_tokens=6 + i)
                for i, p in enumerate(prompts)]
        outs = [r.result(timeout=WAIT)[0] for r in reqs]
        m = srv.metrics()["decode"]
    finally:
        srv.stop(drain=False, timeout=30)
    for i, (p, out) in enumerate(zip(prompts, outs)):
        assert len(out) == 6 + i
        full = np.concatenate([p, out])[None, :]
        logits = np.asarray(ref.forward(w, jnp.asarray(full), cfg))[0]
        for j, tok in enumerate(out):
            row = logits[len(p) + j - 1]
            assert row.max() - row[tok] <= 1e-5 * (row.max() - row.min())
    assert m["state_resets"] == len(prompts)
    # an fp32 pool without the builder's rule would count the ragged
    # kernel's rounding; with it, the whole rung a step and active slot
    assert m["kv_positions_read"] == 32 * sum(
        len(p) + len(o) - 1 for p, o in zip(prompts, outs))
