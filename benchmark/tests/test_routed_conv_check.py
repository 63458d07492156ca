"""The comparison that decides ``correct`` for ``lfm2_24b_a2b`` has to
fail what it exists to catch.  Requests are served through the program's
own pool (``KVSlotPool`` over the routed-experts step, eight slots, two
waves, so that every sampled request sits in a slot another request
left) and held to the two bounds COMMITTED in the config's ``check`` by
the family's own ``check_against_reference``.  The unharmed program must
pass; served with

* every matrix rounded to int8 (the nearest precision below the bf16
  the configuration states),
* the selection bias added to the weights (``g ~ s + b``),
* no top-k normalisation of the weights,
* three experts a token for four,
* the conv window read one position late,
* the step's state reset taken out (a reused slot starts from its
  predecessor's conv window),
* the per-head q/k norms left out,

it must fail, by the mean bound or by the worst-token bound.  A bias
that is ignored in the CHOICE is served too (it must fail as well: the
configuration's bias is not zero so that it does).

Where a TPU is attached (``chiprun -- python -m pytest
benchmark/tests/test_routed_conv_check.py``) the sizes are the
configuration's own: every published width, nine layers, 64 experts, the
whole vocabulary.  On the CPU they are its ``rehearse`` sizes and prove
the mechanism only.  The readings go to
``chiprun_out/routed_conv_check.json``.
"""
import gc
import json
import os
import types

import numpy as np
import pytest

from benchmark.lib import harness

CONF = os.path.join(harness.BENCH, "configs", "lfm2_24b_a2b.json")
SLOTS = 8


@pytest.fixture(scope="module")
def setting():
    import jax

    on_chip = jax.default_backend() == "tpu"
    cfg = harness.load_config(CONF, rehearse=not on_chip)
    fam = harness.load_py(os.path.join(
        harness.BENCH, "families", cfg["family"] + ".py"), cfg["family"])
    build, rx = fam.builder()
    state = fam.make_weights(cfg, jax.devices()[0], rx)
    rng = np.random.RandomState(2 ** 31 - 7 & 0x7fffffff)
    rung, p_len, o_len = ((512, (16, 48), (330, 450)) if on_chip
                          else (64, (4, 12), (30, 50)))
    waves = [[(rng.randint(0, int(cfg["vocab_size"]),
                           rng.randint(*p_len)).astype(np.int32),
               int(rng.randint(*o_len))) for _ in range(SLOTS)]
             for _ in range(2)]
    cfg = dict(cfg, check=dict(cfg["check"], sample_max_total=rung))
    ctx = types.SimpleNamespace(cfg=cfg, device=jax.devices()[0])
    readings = {"device": jax.devices()[0].device_kind,
                "sizes": "configuration" if on_chip else "rehearse"}
    yield cfg, fam, build, rx, state, waves, rung, ctx, readings
    out = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "routed_conv_check.json"), "w") as f:
        json.dump(readings, f, indent=1)


def serve(setting, weights=None, **cfg_over):
    """Both waves through one pool; the second wave's (prompt, tokens).
    ``cfg_over``: config keys the SERVED step is built with (the
    reference keeps the configuration's)."""
    from paddle_tpu.serving.kv_pool import KVSlotPool

    cfg, fam, build, rx, state, waves, rung, ctx, _ = setting
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
        "argmax_agreement", "gap_share_quantiles", "in_reused_slots",
        "smallest_branch_share")}
    readings[name]["ok"] = ok
    return ok, info


def test_the_unharmed_program_passes(setting):
    ok, info = verdict(setting, "unharmed", serve(setting))
    assert ok, info
    assert info["in_reused_slots"] == SLOTS
    # every branch is something the comparison can see
    assert info["smallest_branch_share"] >= 0.01, info


def test_the_bias_added_to_the_weights_fails(setting, monkeypatch):
    import jax
    import jax.numpy as jnp

    rx = setting[3]

    def route(f, w_router, bias, d):
        s = jax.nn.sigmoid(jnp.dot(
            f.astype(jnp.float32), w_router, precision="highest"))
        chosen = s + bias
        _, sel = jax.lax.top_k(chosen, d.top_k)
        gate = jnp.take_along_axis(chosen, sel, axis=-1)   # the fault
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-6)
        return sel.astype(jnp.int32), gate * d.routed_scale

    monkeypatch.setattr(rx, "route", route)
    ok, info = verdict(setting, "bias_in_the_weights", serve(setting))
    assert not ok, info


def test_a_bias_ignored_in_the_choice_fails(setting):
    ok, info = verdict(setting, "bias_ignored",
                       serve(setting, use_expert_bias=False))
    assert not ok, info


def test_no_top_k_normalisation_fails(setting):
    ok, info = verdict(setting, "no_normalisation",
                       serve(setting, norm_topk_prob=False))
    assert not ok, info


def test_three_experts_for_four_fails(setting):
    cfg = setting[0]
    ok, info = verdict(setting, "top_k_minus_one", serve(
        setting, num_experts_per_tok=int(cfg["num_experts_per_tok"]) - 1))
    assert not ok, info


def test_a_conv_window_one_position_late_fails(setting):
    import jax.numpy as jnp

    state = setting[4]
    late = {k: (jnp.concatenate([v[1:], jnp.zeros_like(v[:1])])
                if k.endswith("conv_w") else v) for k, v in state.items()}
    ok, info = verdict(setting, "conv_window_late", serve(setting, late))
    assert not ok, info


def test_a_step_that_skips_the_state_reset_fails(setting, monkeypatch):
    import jax.numpy as jnp

    monkeypatch.setattr(setting[3], "starts_fresh",
                        lambda ts: jnp.zeros(ts.shape, bool))
    ok, info = verdict(setting, "no_state_reset", serve(setting))
    assert not ok, info


def test_qk_norms_left_out_fail(setting, monkeypatch):
    rx = setting[3]
    head_dim = rx.dims(setting[0]).head_dim
    normed = rx.rms_norm
    monkeypatch.setattr(rx, "rms_norm", lambda x, w, eps, groups=1: (
        x.astype("float32") if w.shape == (head_dim,)
        else normed(x, w, eps, groups)))
    ok, info = verdict(setting, "no_qk_norms", serve(setting))
    assert not ok, info


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

    cfg, fam, build, rx, state, waves, rung, ctx, readings = setting
    in_place = jax.jit(rounded, donate_argnums=0)
    for k in list(state):
        if state[k].ndim >= 2 and state[k].dtype == jnp.bfloat16:
            state[k] = in_place(state[k])
    kept = serve(setting)
    state.clear()      # the rounded copy goes before the other comes
    gc.collect()
    state.update(fam.make_weights(cfg, ctx.device, rx))
    ok, info = verdict(setting, "int8_rounded_weights", kept)
    assert not ok, info
