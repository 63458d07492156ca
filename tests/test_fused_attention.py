"""paddle_tpu.fused_attention and the ``fused_attention`` op.

The Pallas TPU kernel pair runs here under interpret mode at toy widths
(one lane tile of two 64-wide heads), the XLA form directly; both are
held to a float32 softmax reference on sequences padded to unequal
lengths, at EVERY position: a padded query row attends the real keys.
(The kernels' compile for a described v5e at the BERT cells' widths is
in tests/test_v5e_compile.py, the one file that loads the chip's
compiler.)

Tolerances, from the dtypes.  Both forms take bf16 operands, keep scores
and softmax in float32 and round the probabilities and the results to
bf16 (eps 2^-8): a result of magnitude ``a`` may sit 2^-8 ``a`` off the
float32 reference for the output rounding alone, a few times that with
the probabilities': ``BF16_ULPS`` of them, of the reference's largest
entry.  The XLA form on float32 operands differs from the reference by
the order of its sums only: 1e-5.
"""
import numpy as np
import pytest

from paddle_tpu import fused_attention as fa

N, H, S, D = 3, 2, 128, 64
SCALE = 1.0 / np.sqrt(D)
LENS = (S, S - 37, 5)       # padded QUERY rows in two of three sequences
BF16_EPS = 2.0 ** -8
BF16_ULPS = 4


def _inputs(dtype, seed=0, masked=True):
    import jax.numpy as jnp

    rng = np.random.RandomState(seed)
    q, k, v, dout = (jnp.asarray(rng.randn(N, H, S, D), dtype)
                     for _ in range(4))
    mask = None
    if masked:
        mask = jnp.asarray((np.arange(S)[None, :]
                            < np.asarray(LENS)[:, None]).astype(np.float32))
    return q, k, v, mask, dout


def _reference(q, k, v, mask, causal):
    """float32 softmax attention with the four-op build's additive
    biases, at matmul precision "highest"."""
    import jax
    import jax.numpy as jnp

    q, k, v = (x.astype(jnp.float32) for x in (q, k, v))
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, precision="highest") * SCALE
    if causal:
        s = s + jnp.where(jnp.arange(S)[None, :] <= jnp.arange(S)[:, None],
                          0.0, -1e9)
    if mask is not None:
        s = s + ((mask - 1.0) * 1e9)[:, None, None, :]
    return jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, axis=-1), v,
                      precision="highest")


def _reference_all(q, k, v, mask, dout, causal):
    import jax
    import jax.numpy as jnp

    out, vjp = jax.vjp(lambda a, b, c: _reference(a, b, c, mask, causal),
                       q, k, v)
    return (out,) + vjp(dout.astype(jnp.float32))


def _xla_all(q, k, v, mask, dout, causal):
    import jax

    out, vjp = jax.vjp(
        lambda a, b, c: fa.xla_attention(a, b, c, mask, causal, SCALE)[0],
        q, k, v)
    return (out,) + vjp(dout)


def _kernel_all(q, k, v, mask, dout, causal):
    out, lse = fa.kernel_attention(q, k, v, mask, causal, SCALE,
                                   interpret=True)
    return (out,) + fa.kernel_attention_grad(
        q, k, v, mask, out, lse, dout, causal, SCALE, interpret=True)


def _close(got, want, ulps, names=("context", "dQ", "dK", "dV")):
    for name, g, w in zip(names, got, want):
        g, w = np.asarray(g, np.float32), np.asarray(w, np.float32)
        assert g.shape == w.shape and np.isfinite(g).all(), name
        np.testing.assert_allclose(g, w, rtol=0, err_msg=name,
                                   atol=ulps * BF16_EPS * np.abs(w).max())


MASKS = [(False, False), (True, False), (False, True), (True, True)]
MASK_IDS = ["plain", "padded", "causal", "padded-causal"]


@pytest.mark.parametrize("masked,causal", MASKS, ids=MASK_IDS)
@pytest.mark.parametrize("form", [_kernel_all, _xla_all],
                         ids=["kernel", "xla"])
def test_forward_and_gradients_against_float32_reference(form, masked,
                                                         causal):
    import jax.numpy as jnp

    args = _inputs(jnp.bfloat16, masked=masked)
    _close(form(*args, causal), _reference_all(*args, causal), BF16_ULPS)


@pytest.mark.parametrize("masked,causal", MASKS, ids=MASK_IDS)
def test_kernel_against_xla_form_on_the_same_inputs(masked, causal):
    import jax.numpy as jnp

    args = _inputs(jnp.bfloat16, seed=1, masked=masked)
    _close(_kernel_all(*args, causal), _xla_all(*args, causal), BF16_ULPS)
    q, k, v, mask, _ = args
    _, lse_k = fa.kernel_attention(q, k, v, mask, causal, SCALE,
                                   interpret=True)
    _, lse_x = fa.xla_attention(q, k, v, mask, causal, SCALE)
    # the XLA form's scores pass through bf16 (|s| up to ~6 here)
    np.testing.assert_allclose(np.asarray(lse_k), np.asarray(lse_x),
                               rtol=0, atol=8 * BF16_EPS)


@pytest.mark.parametrize("masked,causal", MASKS, ids=MASK_IDS)
def test_xla_form_in_float32_is_the_reference(masked, causal):
    import jax.numpy as jnp

    args = _inputs(jnp.float32, seed=2, masked=masked)
    got, want = _xla_all(*args, causal), _reference_all(*args, causal)
    for g, w in zip(got, want):
        np.testing.assert_allclose(np.asarray(g), np.asarray(w), rtol=0,
                                   atol=1e-5 * np.abs(np.asarray(w)).max())


@pytest.mark.parametrize("form", [_kernel_all, _xla_all],
                         ids=["kernel", "xla"])
def test_a_padded_query_row_attends_every_real_key(form):
    """Key-only masking: the context of a PAD position is the softmax
    over the real keys of its own scores — not zero, not uniform, not a
    pad-only segment's answer — and moving a pad key's K/V changes
    nothing anywhere."""
    import jax.numpy as jnp

    q, k, v, mask, dout = _inputs(jnp.bfloat16, seed=3)
    out = np.asarray(form(q, k, v, mask, dout, False)[0], np.float32)
    want = np.asarray(_reference(q, k, v, mask, False))
    pad_rows = np.asarray(mask) == 0
    assert pad_rows.sum() > 0
    np.testing.assert_allclose(
        out.transpose(0, 2, 1, 3)[pad_rows],
        want.transpose(0, 2, 1, 3)[pad_rows], rtol=0,
        atol=BF16_ULPS * BF16_EPS * np.abs(want).max())
    pad_keys = jnp.asarray(pad_rows)[:, None, :, None]
    moved = form(q, jnp.where(pad_keys, 7.0, k).astype(k.dtype),
                 jnp.where(pad_keys, -7.0, v).astype(v.dtype), mask, dout,
                 False)[0]
    assert np.array_equal(np.asarray(moved, np.float32), out)


def test_kernel_takes_several_batch_rows_a_grid_step():
    """8 short sequences a grid step (``_batch_rows``), a batch the
    block does not divide falling back to a divisor."""
    assert fa._batch_rows(128, 128) == 8 and fa._batch_rows(32, 512) == 2
    assert fa._batch_rows(6, 128) == 6 and fa._batch_rows(7, 128) == 7
    assert fa._batch_rows(9, 256) == 3 and fa._batch_rows(5, 2048) == 1


RULE = [
    # backend, S_q, S_k, heads, d_head, dtype, partitioned -> path
    ("tpu", 512, 512, 12, 64, "bfloat16", False, "kernel"),   # pretrain_s512
    ("tpu", 128, 128, 12, 64, "bfloat16", False, "kernel"),   # pretrain_s128
    ("tpu", 2048, 2048, 8, 128, "bfloat16", False, "kernel"),
    ("tpu", 4096, 4096, 12, 64, "bfloat16", False, "xla"),    # K/V tile + scores past VMEM
    ("tpu", 96, 96, 12, 64, "bfloat16", False, "xla"),        # not whole 128-row tiles
    ("tpu", 512, 512, 12, 64, "float32", False, "xla"),       # no AMP: float32 products
    ("tpu", 512, 512, 12, 64, "bfloat16", True, "xla"),       # GSPMD cannot split a Mosaic call
    ("tpu", 128, 512, 12, 64, "bfloat16", False, "xla"),      # cross-attention
    ("tpu", 512, 512, 12, 32, "bfloat16", False, "xla"),      # four heads a lane tile: not built
    ("tpu", 512, 512, 3, 64, "bfloat16", False, "xla"),       # heads do not fill lane tiles
    ("tpu", 512, 512, 3, 128, "bfloat16", False, "kernel"),
    ("cpu", 512, 512, 12, 64, "bfloat16", False, "xla"),
    ("gpu", 512, 512, 12, 64, "bfloat16", False, "xla"),
]


@pytest.mark.parametrize("case", RULE, ids=lambda c: "-".join(map(str, c)))
def test_lowering_rule(case):
    *seen, want = case
    assert fa.attention_lowering(*seen) == want


# ---------------------------------------------------------------------------
# the op in a Program
# ---------------------------------------------------------------------------
def _attention_program(with_mask=True, amp=False):
    import paddle_tpu as fluid
    from paddle_tpu import framework, models

    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 5
    with framework.program_guard(prog, startup):
        x = fluid.layers.data("x", [16, 32])
        mask = fluid.layers.data("mask", [16]) if with_mask else None
        y = models.transformer.multi_head_attention(
            x, x, 32, 4, dropout_rate=0.0, mask=mask, name="att")
        loss = fluid.layers.mean(y * y)
        opt = fluid.optimizer.SGDOptimizer(0.1)
        if amp:
            opt = fluid.contrib.mixed_precision.decorate(opt)
        opt.minimize(loss)
    return prog, startup, loss


def test_grad_op_reads_the_forwards_context_and_row_statistic():
    """ONE grad op per forward op, fed ``Out`` and ``Lse``: the compiled
    step has no reason to run the forward kernel a second time."""
    prog, _, _ = _attention_program()
    ops = prog.global_block().ops
    fwd = [op for op in ops if op.type == "fused_attention"]
    bwd = [op for op in ops if op.type == "fused_attention_grad"]
    assert len(fwd) == len(bwd) == 1
    assert bwd[0].input("Out") == fwd[0].output("Out")
    assert bwd[0].input("Lse") == fwd[0].output("Lse")
    assert bwd[0].input("Mask") == fwd[0].input("Mask")
    assert sorted(bwd[0].outputs) == ["K@GRAD", "Q@GRAD", "V@GRAD"]
    lse = prog.global_block().var(fwd[0].output("Lse")[0])
    assert tuple(lse.shape) == (-1, 4, 16) and lse.dtype == "float32"


def test_amp_keeps_the_row_statistic_float32():
    prog, _, _ = _attention_program(amp=True)
    block = prog.global_block()
    fwd = [op for op in block.ops if op.type == "fused_attention"][0]
    assert block.var(fwd.output("Out")[0]).dtype == "bfloat16"
    assert block.var(fwd.output("Lse")[0]).dtype == "float32"
    assert block.var(fwd.input("Q")[0]).dtype == "bfloat16"


def _train(prog, startup, loss, steps=3):
    import paddle_tpu as fluid

    rng = np.random.RandomState(0)
    feed = {"x": rng.randn(4, 16, 32).astype("float32"),
            "mask": (np.arange(16)[None, :]
                     < np.array([16, 9, 3, 12])[:, None]).astype("float32")}
    exe = fluid.Executor(fluid.CPUPlace())
    with fluid.scope_guard(fluid.Scope()):
        exe.run(startup)
        return [float(np.asarray(exe.run(prog, feed=feed,
                                         fetch_list=[loss])[0]))
                for _ in range(steps)]


def test_lowering_is_counted_by_path_and_no_flag_changes_it(monkeypatch):
    """Off a TPU every fused_attention op lowers to the XLA form, one
    count per op lowered; building the program counts nothing; the
    removed flash-attention flag in the environment changes neither the
    path nor a single bit of the losses."""
    def counts():
        return {p: fa.LOWERED.labels(path=p).value
                for p in ("kernel", "xla", "ring")}

    before = counts()
    prog, startup, loss = _attention_program()
    assert counts() == before
    plain = _train(prog, startup, loss)
    after = counts()
    assert after["xla"] == before["xla"] + 1
    assert after["kernel"] == before["kernel"]
    assert after["ring"] == before["ring"]
    assert plain[-1] < plain[0]
    monkeypatch.setenv("PADDLE_TPU_" + "FLASH_ATTENTION", "1")
    flagged = _train(*_attention_program())
    assert flagged == plain
    assert counts()["kernel"] == before["kernel"]
