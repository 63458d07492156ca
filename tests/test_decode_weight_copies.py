"""The pooled transformer-LM builder holds each matrix in the dtype its
products are taken in (``decoding._pooled_lm_parts``, ``_fc``).

Where the backend rounds an fp32 matmul operand to bf16 anyway (a TPU
at the default precision) the builder copies every matrix the forward
multiplies to bf16 ONCE, and ``_fc`` multiplies a matrix as stored, so
no step casts 0.47 GB of weights again (``gpt1_117m``: 15% of the
chip's busy time at 10 live slots).  No TPU is attached here: the
observation is forced through the builder's private helper, and what
the CPU does — no copy, the parent's arithmetic — is held too."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from paddle_tpu import decoding, monitor

DIMS = dict(vocab=37, d_model=32, n_layer=2, n_head=4, d_inner=64)
MAX_POS = 16
COUNTER = "decode_weight_copies_total"


@pytest.fixture(scope="module")
def lm_state():
    return decoding.random_transformer_lm_state(
        np.random.RandomState(3), *DIMS.values(), MAX_POS)


@pytest.fixture
def products_are_bf16(monkeypatch):
    """The builder sees a backend whose fp32 products are bf16."""
    monkeypatch.setattr(decoding, "_products_are_bf16", lambda b, p: True)


def _matrices():
    return set(decoding._multiplied_matrices("lm", DIMS["n_layer"]))


def _parts(state):
    return decoding._pooled_lm_parts(
        state, DIMS["d_model"], DIMS["n_layer"], DIMS["n_head"], "lm", "fp32")


@pytest.mark.parametrize("backend,precision,expected", [
    ("tpu", None, True),
    ("tpu", "default", True),
    ("tpu", "highest", False),
    ("tpu", "float32", False),
    ("cpu", None, False),
    ("gpu", None, False),
])
def test_which_backends_round_fp32_products_to_bf16(backend, precision,
                                                    expected):
    assert decoding._products_are_bf16(backend, precision) is expected


def test_the_precision_read_is_the_process_setting():
    """What the builder hands the helper follows
    ``jax.default_matmul_precision``."""
    assert jax.config.jax_default_matmul_precision is None
    with jax.default_matmul_precision("highest"):
        assert not decoding._products_are_bf16(
            "tpu", jax.config.jax_default_matmul_precision)


def test_fc_over_a_bf16_matrix_takes_bf16_products_in_fp32():
    rng = np.random.RandomState(0)
    x = jnp.asarray(rng.randn(5, 48), jnp.float32)
    w = jnp.asarray(rng.randn(48, 24), jnp.float32)
    b = jnp.asarray(rng.randn(24), jnp.float32)
    got = decoding._fc({"p_w": w.astype(jnp.bfloat16), "p_b": b}, x, "p")
    assert got.dtype == jnp.float32

    def rounded(a):
        return np.asarray(a.astype(jnp.bfloat16).astype(jnp.float32),
                          np.float64)

    want = rounded(x) @ rounded(w) + np.asarray(b, np.float64)
    np.testing.assert_allclose(np.asarray(got), want, rtol=0, atol=2e-5)
    fp32 = np.asarray(x, np.float64) @ np.asarray(w, np.float64) + np.asarray(
        b, np.float64)
    assert np.abs(np.asarray(got) - fp32).max() > 5e-3


def test_fc_over_an_fp32_matrix_is_the_plain_product():
    rng = np.random.RandomState(1)
    x = jnp.asarray(rng.randn(3, 2, 16), jnp.float32)
    w = jnp.asarray(rng.randn(16, 8), jnp.float32)
    b = jnp.asarray(rng.randn(8), jnp.float32)
    got = decoding._fc({"p_w": w, "p_b": b}, x, "p")
    assert np.array_equal(np.asarray(got), np.asarray(x @ w + b))


def test_the_builder_copies_exactly_the_multiplied_matrices(
        lm_state, products_are_bf16):
    before = monitor.counter_value(COUNTER)
    _, _, W, _ = _parts(lm_state)
    copied = {k for k, v in W.items() if v.dtype == jnp.bfloat16}
    assert copied == _matrices()
    assert len(copied) == 6 * DIMS["n_layer"] + 1
    for k, v in W.items():
        assert isinstance(v, jax.Array)  # the pool binds it as it is
        if k not in copied:  # biases, LayerNorm vectors, both embeddings
            assert v.dtype == jnp.float32, k
        assert np.array_equal(
            np.asarray(v), np.asarray(jnp.asarray(lm_state[k]).astype(
                v.dtype))), k
    assert monitor.counter_value(COUNTER) - before == len(copied)
    # the caller's fp32 state is its own (a benchmark's reference reads it)
    assert all(v.dtype == np.float32 for v in lm_state.values())


def test_a_matrix_that_is_not_fp32_is_taken_as_stored(lm_state,
                                                      products_are_bf16):
    state = dict(lm_state)
    state["lm_head_w"] = jnp.asarray(state["lm_head_w"], jnp.bfloat16)
    before = monitor.counter_value(COUNTER)
    _, _, W, _ = _parts(state)
    assert W["lm_head_w"] is state["lm_head_w"]
    assert monitor.counter_value(COUNTER) - before == 6 * DIMS["n_layer"]


def _eqns(jaxpr):
    for eqn in jaxpr.eqns:
        yield eqn
        for sub in jax.core.jaxprs_in_params(eqn.params):
            yield from _eqns(sub)


@pytest.mark.parametrize("builder", ["step", "verify"])
def test_the_traced_step_casts_no_weight(lm_state, products_are_bf16,
                                         builder):
    """Neither the pooled step nor the K-wide verify forward converts
    an operand shaped like a matrix it multiplies, and every product
    over one takes bf16 operands into fp32."""
    args = (lm_state, *DIMS.values())
    if builder == "step":
        fn, make_cache = decoding.make_transformer_lm_pooled_step_fn(*args)
        tokens = jnp.zeros((3,), jnp.int32)
    else:
        fn = decoding.make_transformer_lm_pooled_verify_fn(*args)
        _, make_cache = decoding.make_transformer_lm_pooled_step_fn(*args)
        tokens = jnp.zeros((3, 2), jnp.int32)
    shapes = {lm_state[k].shape for k in _matrices()}
    closed = jax.make_jaxpr(fn)(make_cache(3, MAX_POS), tokens,
                                jnp.array([0, 4, -1], jnp.int32))
    products = 0
    for eqn in _eqns(closed.jaxpr):
        if eqn.primitive.name == "convert_element_type":
            assert eqn.invars[0].aval.shape not in shapes, eqn
        if (eqn.primitive.name == "dot_general"
                and eqn.invars[1].aval.shape in shapes):
            products += 1
            assert [v.aval.dtype for v in eqn.invars] == [jnp.bfloat16] * 2
            assert eqn.outvars[0].aval.dtype == jnp.float32
    assert products == 6 * DIMS["n_layer"] + 1


def test_bf16_products_stay_close_to_the_fp32_step(lm_state, monkeypatch):
    """Same tokens in, logits within bf16 rounding of the fp32 step's:
    the copies change the products' precision (to the TPU's own), not
    the model."""
    args = (lm_state, *DIMS.values())
    plain, make_cache = decoding.make_transformer_lm_pooled_step_fn(*args)
    monkeypatch.setattr(decoding, "_products_are_bf16", lambda b, p: True)
    held, _ = decoding.make_transformer_lm_pooled_step_fn(*args)
    tokens = jnp.array([5, 9, 11], jnp.int32)
    ts = jnp.array([0, 0, -1], jnp.int32)
    a, _ = jax.jit(plain)(make_cache(3, MAX_POS), tokens, ts)
    b, _ = jax.jit(held)(make_cache(3, MAX_POS), tokens, ts)
    a, b = np.asarray(a)[:2], np.asarray(b)[:2]
    assert not np.array_equal(a, b)
    assert np.abs(a - b).max() <= 0.02 * (a.max() - a.min())


def test_on_the_cpu_the_builder_makes_no_copy(lm_state):
    """fp32 products are fp32 here: the step closes over the weights as
    given and computes what it computed before the copies existed."""
    before = monitor.counter_value(COUNTER)
    step_fn, make_cache = decoding.make_transformer_lm_pooled_step_fn(
        lm_state, *DIMS.values())
    _, _, W, _ = _parts(lm_state)
    assert {str(v.dtype) for v in W.values()} == {"float32"}
    assert monitor.counter_value(COUNTER) == before
    tokens = jnp.array([5, 9], jnp.int32)
    ts = jnp.array([0, 0], jnp.int32)
    got, _ = jax.jit(step_fn)(make_cache(2, MAX_POS), tokens, ts)
    scalar, scalar_cache = decoding.make_transformer_lm_step_fn(
        lm_state, *DIMS.values(), MAX_POS)
    want, _ = jax.jit(scalar)(scalar_cache(2), tokens, 0)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=0, atol=1e-5)
