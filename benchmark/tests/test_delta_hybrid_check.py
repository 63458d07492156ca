"""The comparison that decides ``correct`` for ``olmo_hybrid_7b`` has to
fail what it exists to catch.  Requests are served through the program's
own pool (``KVSlotPool`` over the delta-rule hybrid step, eight slots,
two waves, so that every sampled request sits in a slot another request
left) and held to the two bounds COMMITTED in the config's ``check`` by
the family's own ``check_against_reference``.  The unharmed program must
pass; served with

* every matrix rounded to int8 (the nearest precision below the bf16
  the configuration states),
* the step's state reset taken out (a reused slot starts from its
  predecessor's delta-rule state and conv window),
* the conv window read one position late,
* ``beta`` without its factor 2,
* the delta term left out (``S <- alpha S + beta k v^T``: a state that
  only decays and adds),
* the decay left out (``alpha = 1``),
* the q / k L2 norms left out,
* the output gate left out,
* a full layer given rotary positions,

it must fail, by the mean bound or by the worst-token bound.  A
delta-rule state rounded to bf16 after every step is served too and its
reading recorded: where the bounds cannot tell it from the configured
float32 state the case is an ``xfail`` with the reading, not a looser
bound (the configuration's README and PERF.md section 4
have the chip's numbers).

Where a TPU is attached (``chiprun --timeout 2400 -- python -m pytest
benchmark/tests/test_delta_hybrid_check.py``; no ``-x``: a variant that
fails to fail must not hide the others' readings) the sizes are the
configuration's own: every published width, twelve layers, the whole
vocabulary.  On the CPU they are its ``rehearse`` sizes and prove the
mechanism only.  The readings go to
``chiprun_out/delta_hybrid_check.json``.
"""
import gc
import json
import os
import types

import numpy as np
import pytest

from benchmark.lib import harness

CONF = os.path.join(harness.BENCH, "configs", "olmo_hybrid_7b.json")
SLOTS = 8


@pytest.fixture(scope="module")
def setting():
    import jax

    on_chip = jax.default_backend() == "tpu"
    cfg = harness.load_config(CONF, rehearse=not on_chip)
    fam = harness.load_py(os.path.join(
        harness.BENCH, "families", cfg["family"] + ".py"), cfg["family"])
    build, dh = fam.builder()
    state = fam.make_weights(cfg, jax.devices()[0], dh)
    rng = np.random.RandomState(2 ** 31 - 9 & 0x7fffffff)
    rung, p_len, o_len = ((256, (16, 48), (96, 160)) if on_chip
                          else (64, (4, 12), (16, 30)))
    waves = [[(rng.randint(0, int(cfg["vocab_size"]),
                           rng.randint(*p_len)).astype(np.int32),
               int(rng.randint(*o_len))) for _ in range(SLOTS)]
             for _ in range(2)]
    cfg = dict(cfg, check=dict(cfg["check"], sample_max_total=rung))
    ctx = types.SimpleNamespace(cfg=cfg, device=jax.devices()[0])
    readings = {"device": jax.devices()[0].device_kind,
                "sizes": "configuration" if on_chip else "rehearse"}
    yield cfg, fam, build, dh, state, waves, rung, ctx, readings
    out = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "delta_hybrid_check.json"), "w") as f:
        json.dump(readings, f, indent=1)


def serve(setting, weights=None, **cfg_over):
    """Both waves through one pool; the second wave's (prompt, tokens).
    ``cfg_over``: config keys the SERVED step is built with (the
    reference keeps the configuration's)."""
    from paddle_tpu.serving.kv_pool import KVSlotPool

    cfg, fam, build, dh, state, waves, rung, ctx, _ = setting
    sv = cfg["serving"]
    step, make_cache = build(weights if weights is not None else state,
                             dict(cfg, **cfg_over), kv_dtype=sv["kv_dtype"])
    pool = KVSlotPool(step, make_cache, eos_id=int(cfg["vocab_size"]),
                      max_slots=SLOTS, max_seq_len=rung, slot_ladder=[SLOTS],
                      len_ladder=[rung], steps=8, kv_dtype=sv["kv_dtype"])
    st = pool.alloc(SLOTS, rung)
    for wave in waves:
        for i, (prompt, n_new) in enumerate(wave):
            st = pool.admit(st, i, prompt, len(prompt), len(prompt) + n_new)
        while not np.asarray(st["finished"]).all():
            st = pool.chunk(st)
        toks = np.asarray(st["tokens"])
    return [(p, toks[i, len(p):len(p) + n].copy(), SLOTS)
            for i, (p, n) in enumerate(waves[1])]


def verdict(setting, name, kept):
    cfg, fam, _, _, state, _, _, ctx, readings = setting
    ok, info = fam.check_against_reference(ctx, state, kept, SLOTS)
    readings[name] = {k: info[k] for k in (
        "mean_logit_gap_share", "mean_gap_share_allowed",
        "worst_logit_gap_share", "worst_gap_share_allowed",
        "gap_share_quantiles", "argmax_agreement", "in_reused_slots",
        "smallest_branch_share")}
    readings[name]["ok"] = ok
    return ok, info


def test_the_unharmed_program_passes(setting):
    ok, info = verdict(setting, "unharmed", serve(setting))
    assert ok, info
    assert info["in_reused_slots"] == SLOTS
    # every branch is something the comparison can see
    assert info["smallest_branch_share"] >= 0.01, info


def test_a_step_that_skips_the_state_reset_fails(setting, monkeypatch):
    import jax.numpy as jnp

    monkeypatch.setattr(setting[3], "starts_fresh",
                        lambda ts: jnp.zeros(ts.shape, bool))
    ok, info = verdict(setting, "no_state_reset", serve(setting))
    assert not ok, info


def test_a_conv_window_one_position_late_fails(setting):
    import jax.numpy as jnp

    state = setting[4]
    late = {k: (jnp.concatenate([v[1:], jnp.zeros_like(v[:1])])
                if k.endswith("lin_conv_w") else v)
            for k, v in state.items()}
    ok, info = verdict(setting, "conv_window_late", serve(setting, late))
    assert not ok, info


def test_beta_without_its_factor_two_fails(setting):
    ok, info = verdict(setting, "beta_without_factor_2",
                       serve(setting, linear_allow_neg_eigval=False))
    assert not ok, info


def test_the_delta_term_left_out_fails(setting, monkeypatch):
    """``S <- alpha S + beta k v^T``: the write without what the state
    already returns for this key (a state that only decays and adds)."""
    import jax.numpy as jnp

    dh = setting[3]
    whole = dh.gated_delta_step

    def no_delta(q, k, v, alpha, beta, s, ts):
        # u = (alpha S)^T k is what the rule itself returns for a query
        # k when it writes nothing; v + u makes beta (v' - u) = beta v
        u, _ = whole(k, k, jnp.zeros_like(v), alpha, jnp.zeros_like(beta),
                     s, ts)
        return whole(q, k, v + u, alpha, beta, s, ts)

    monkeypatch.setattr(dh, "gated_delta_step", no_delta)
    ok, info = verdict(setting, "no_delta_term", serve(setting))
    assert not ok, info


def test_the_decay_left_out_fails(setting, monkeypatch):
    import jax.numpy as jnp

    dh = setting[3]
    gates = dh.decay_and_step_gates

    def no_decay(x, w, p, d):
        alpha, beta = gates(x, w, p, d)
        return jnp.ones_like(alpha), beta

    monkeypatch.setattr(dh, "decay_and_step_gates", no_decay)
    ok, info = verdict(setting, "no_decay", serve(setting))
    assert not ok, info


def test_qk_l2_norms_left_out_fail(setting, monkeypatch):
    monkeypatch.setattr(setting[3], "l2_norm", lambda x: x)
    ok, info = verdict(setting, "no_qk_l2_norms", serve(setting))
    assert not ok, info


def test_the_output_gate_left_out_fails(setting, monkeypatch):
    dh = setting[3]
    monkeypatch.setattr(
        dh, "gated_output_norm",
        lambda o, gate, w_norm, eps: dh.rms_norm(o, w_norm, eps))
    ok, info = verdict(setting, "no_output_gate", serve(setting))
    assert not ok, info


def test_a_full_layer_given_rotary_positions_fails(setting):
    ok, info = verdict(setting, "rotary_in_full_layers", serve(
        setting, rope_parameters={"rope_theta": 10000.0}))
    assert not ok, info


def test_a_bf16_delta_state_is_read_and_recorded(setting, monkeypatch):
    """The configuration states a float32 state.  Rounded to bf16 after
    every step the reading is recorded; where the bounds cannot tell it
    from the float32 state the case is an xfail with the reading."""
    import jax

    dh = setting[3]
    whole = dh.gated_delta_step

    def rounded(q, k, v, alpha, beta, s, ts):
        # not a cast pair: XLA:TPU drops astype(bf16).astype(f32)
        o, s_new = whole(q, k, v, alpha, beta, s, ts)
        return o, jax.lax.reduce_precision(s_new, exponent_bits=8,
                                           mantissa_bits=7)

    monkeypatch.setattr(dh, "gated_delta_step", rounded)
    ok, info = verdict(setting, "bf16_delta_state", serve(setting))
    assert np.isfinite(info["worst_logit_gap_share"])
    if ok:
        pytest.xfail("the bounds cannot tell a bf16 state: mean gap share "
                     "%.7f under %.7f, worst %.5f under %.5f" % (
                         info["mean_logit_gap_share"],
                         info["mean_gap_share_allowed"],
                         info["worst_logit_gap_share"],
                         info["worst_gap_share_allowed"]))


def test_int8_rounded_weights_fail(setting):
    """Last in the file: the chip cannot hold the weights twice, so the
    served copy is rounded IN PLACE (donated), its tokens taken, and the
    unrounded weights made again from their seed for the reference."""
    import jax
    import jax.numpy as jnp

    def rounded(a):
        f = a.astype(jnp.float32)
        scale = jnp.abs(f).max() / 127.0
        return (jnp.round(f / scale) * scale).astype(a.dtype)

    cfg, fam, build, dh, state, waves, rung, ctx, readings = setting
    in_place = jax.jit(rounded, donate_argnums=0)
    for k in list(state):
        if state[k].ndim == 2 and state[k].dtype == jnp.bfloat16:
            state[k] = in_place(state[k])
    kept = serve(setting)
    state.clear()      # the rounded copy goes before the other comes
    gc.collect()
    state.update(fam.make_weights(cfg, ctx.device, dh))
    ok, info = verdict(setting, "int8_rounded_weights", kept)
    assert not ok, info
