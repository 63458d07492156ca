"""The comparison that decides ``correct`` for ``falcon_h1_34b`` has to
fail what it exists to catch.  Requests are served through the
program's own pool (``KVSlotPool`` over the hybrid step, eight slots,
two waves, so that every sampled request sits in a slot another request
left) and held to the bound COMMITTED in the config's ``check`` by the
family's own ``check_against_reference``.  The unharmed program must
pass; served with

* the step's state reset taken out (a reused slot starts from its
  predecessor's SSM and conv state),
* the conv window read one position late,
* every matrix rounded to int8 (the nearest precision below the bf16 the
  configuration states),

it must fail.  An SSM state kept in bf16 instead of the configuration's
float32 is served too and its reading recorded: rounding the state to
bf16 each step is an error of the same size as the rounding of every
matmul input that the configured path already makes, so this check
cannot hold the state's dtype and does not pretend to (PERF.md section
4 has the chip's numbers).

Where a TPU is attached (``chiprun -- python -m pytest
benchmark/tests/test_hybrid_ssm_check.py``) the sizes are the
configuration's own: every published width, 6 layers, the whole
vocabulary.  On the CPU they are its ``rehearse`` sizes and prove the
mechanism only.  The readings go to ``chiprun_out/hybrid_ssm_check.json``.
"""
import gc
import json
import os
import types

import numpy as np
import pytest

from benchmark.lib import harness

CONF = os.path.join(harness.BENCH, "configs", "falcon_h1_34b.json")
SLOTS = 8


@pytest.fixture(scope="module")
def setting():
    import jax

    on_chip = jax.default_backend() == "tpu"
    cfg = harness.load_config(CONF, rehearse=not on_chip)
    fam = harness.load_py(os.path.join(
        harness.BENCH, "families", cfg["family"] + ".py"), cfg["family"])
    build, hybrid_ssm = fam.builder()
    state = fam.make_weights(cfg, jax.devices()[0], hybrid_ssm)
    rng = np.random.RandomState(2 ** 31 - 5 & 0x7fffffff)
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
    yield cfg, fam, build, hybrid_ssm, state, waves, rung, ctx, readings
    out = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "hybrid_ssm_check.json"), "w") as f:
        json.dump(readings, f, indent=1)


def serve(setting, weights=None, ssm_state_dtype=None):
    """Both waves through one pool; the second wave's (prompt, tokens)."""
    from paddle_tpu.serving.kv_pool import KVSlotPool

    cfg, fam, build, hs, state, waves, rung, ctx, _ = setting
    sv = cfg["serving"]
    step, make_cache = build(
        weights if weights is not None else state, cfg,
        kv_dtype=sv["kv_dtype"], ssm_state_dtype=ssm_state_dtype
        or cfg["assumed"]["ssm_state_dtype"])
    pool = KVSlotPool(step, make_cache, eos_id=int(cfg["vocab_size"]),
                      max_slots=SLOTS, max_seq_len=rung, slot_ladder=[SLOTS],
                      len_ladder=[rung], steps=4, kv_dtype=sv["kv_dtype"])
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
        "worst_logit_gap_share", "logit_gap_share_allowed",
        "argmax_agreement", "in_reused_slots", "smallest_branch_share")}
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

    hs = setting[3]
    monkeypatch.setattr(hs, "starts_fresh",
                        lambda ts: jnp.zeros(ts.shape, bool))
    ok, info = verdict(setting, "no_state_reset", serve(setting))
    assert not ok, info


def test_a_conv_window_one_position_late_fails(setting):
    import jax.numpy as jnp

    state = setting[4]
    late = {k: (jnp.concatenate([v[1:], jnp.zeros_like(v[:1])])
                if k.endswith("ssm_conv_w") else v)
            for k, v in state.items()}
    ok, info = verdict(setting, "conv_window_late", serve(setting, late))
    assert not ok, info


def test_a_bf16_ssm_state_is_read_and_recorded(setting):
    """Not held to fail (see the module's docstring): recorded, and it
    must at least still serve finite logits."""
    ok, info = verdict(setting, "bf16_ssm_state",
                       serve(setting, ssm_state_dtype="bfloat16"))
    assert np.isfinite(info["worst_logit_gap_share"])


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

    cfg, fam, build, hs, state, waves, rung, ctx, readings = setting
    in_place = jax.jit(rounded, donate_argnums=0)
    for k in list(state):
        if state[k].ndim == 2 and state[k].dtype == jnp.bfloat16:
            state[k] = in_place(state[k])
    kept = serve(setting)
    state.clear()      # the rounded copy goes before the other comes
    gc.collect()
    state.update(fam.make_weights(cfg, ctx.device, hs))
    ok, info = verdict(setting, "int8_rounded_weights", kept)
    assert not ok, info
