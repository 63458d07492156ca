"""The comparison that decides ``correct`` for ``kimi_linear_48b_a3b`` has
to fail what it exists to catch.  Requests are served through the
program's own server (``DecodeServer`` over the KDA-and-latent step with a
prefix cache: a pilot prefills the shortest document in chunks — the
delta rule in its CHUNKWISE form — and leaves its snapshot, then three
requests are seated over it, one after another, so that each sits in a
slot another request left) and held to the two bounds COMMITTED in the
config's ``check`` by the family's own ``check_against_reference``.  The
unharmed program must pass; served with

* THE DECAY AVERAGED OVER A HEAD'S CHANNELS (a decay a head for a decay a
  channel, in the step and in the chunk form),
* the chunk form with ``A = 0`` (the triangular solve dropped: every
  position of a sub-chunk writes as if the others had not),
* the chunk's state carried without ``Diag(e^{g_C})`` (the state a
  sub-chunk came in with is not decayed through it),
* a snapshot installed WITHOUT ITS DELTA STATE (the latent rows and the
  conv window alone), and one without its conv window,
* routed experts in place of the leading dense FFN,
* every matrix rounded to int8 (the nearest precision below the bf16 the
  configuration states),

or compared with a reference that is told

* to ROTATE the 64 shared lanes (``mla_use_nope`` false),
* ``beta`` with a factor 2 (``kda_allow_neg_eigval``),
* ``routed_scaling_factor`` 1 for 2.446,
* no shared expert

(the first and the third also SERVED so, against the reference as the
configuration has it: the same harm seen from its other side), it must
fail, by the mean bound or by the worst-token bound.  Every serve here
has ONE live slot (a pilot, then three requests one after another); the
cell holds the same two bounds over 96 live slots.  A variant
the bounds cannot tell is an ``xfail`` with its reading, not a looser
bound and not a dropped case (the configuration's README and PERF.md
section 4 have the chip's numbers).

Where a TPU is attached (``chiprun --timeout 3400 -- python -m pytest
benchmark/tests/test_kda_latent_check.py``; no ``-x``: a variant that
fails to fail must not hide the others' readings) the sizes are the
configuration's own: every published width, eight layers, 16 held experts
of 256, an eighth of the vocabulary, 96 slots at rung 32768, the
16,384-token document.  On the CPU they are its ``rehearse`` sizes and
prove the mechanism only.  The readings go to
``chiprun_out/kda_latent_check.json``.
"""
import gc
import json
import os
import types

import numpy as np
import pytest

from benchmark.lib import harness, traffic

CONF = os.path.join(harness.BENCH, "configs", "kimi_linear_48b_a3b.json")
KEPT = {}       # the unharmed program's tokens, served once a seed
KEYS = ("mean_logit_gap_share", "mean_gap_share_allowed",
        "worst_logit_gap_share", "worst_gap_share_allowed",
        "gap_share_quantiles", "tokens", "argmax_agreement",
        "distinct_tokens_per_answer", "in_reused_slots",
        "contexts_past_minimum", "smallest_branch_share",
        "branch_share_of_residual")


@pytest.fixture(scope="module")
def setting():
    import jax

    on_chip = jax.default_backend() == "tpu"
    cfg = harness.load_config(CONF, rehearse=not on_chip)
    mix = traffic.load_mix("shared_docs_qa_wide_32k", rehearse=not on_chip)
    fam = harness.load_py(os.path.join(
        harness.BENCH, "families", cfg["family"] + ".py"), cfg["family"])
    build, parts = fam.builder()
    state = fam.make_weights(cfg, jax.devices()[0], parts)
    vocab = int(cfg["vocab_size"])
    q_len, n_new = ((48, 96), 128) if on_chip else ((4, 9), 12)

    def prompts_of(seed):
        rng = np.random.RandomState(seed)
        doc = rng.randint(0, vocab, min(mix["documents"])).astype(np.int32)
        return [np.concatenate([doc, rng.randint(
            0, vocab, rng.randint(*q_len)).astype(np.int32)])
            for _ in range(4)]

    ctx = types.SimpleNamespace(cfg=cfg, device=jax.devices()[0])
    readings = {"device": jax.devices()[0].device_kind,
                "sizes": "configuration" if on_chip else "rehearse",
                "document_tokens": int(min(mix["documents"]))}
    yield cfg, fam, build, parts, state, prompts_of, n_new, ctx, readings
    out = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "kda_latent_check.json"), "w") as f:
        json.dump(readings, f, indent=1)


def serve(setting, seed=2147483604, weights=None, **cfg_over):
    """A pilot on the document, then three requests over its snapshot,
    one at a time: ``[(prompt, tokens, requests before it)]``."""
    base, fam, build, _, state, prompts_of, n_new, _, _ = setting
    prompts = prompts_of(seed)
    srv = fam.make_server(dict(base, **cfg_over),
                          state if weights is None else weights, build)
    slots = int(base["serving"]["slot_ladder"][-1])
    try:
        srv.warmup()
        srv.submit({"tokens": prompts[0]}, max_new_tokens=2).result(1800)
        kept = []
        for p in prompts[1:]:
            got = srv.submit({"tokens": p}, max_new_tokens=n_new).result(900)
            kept.append((p, np.asarray(got[0], np.int32), slots))
        stats = srv.metrics()["decode"]
        assert stats["prefix_cache"]["hits"] == len(prompts) - 1, stats
        assert stats["prefill_chunks"] == len(prompts[0]) // int(
            base["serving"]["prefill_tokens"]), stats
        assert stats["latent_positions_selected"] == stats[
            "index_positions_scored"] > 0, stats
    finally:
        srv.stop(drain=False, timeout=60.0)
    return kept


def verdict(setting, name, kept, **reference_differs):
    """The family's check of ``kept``; ``reference_differs``: keys of the
    configuration the REFERENCE is given otherwise."""
    cfg, fam, _, _, state, _, _, ctx, readings = setting
    if reference_differs:
        ctx = types.SimpleNamespace(cfg=dict(cfg, **reference_differs),
                                    device=ctx.device)
    ok, info = fam.check_against_reference(ctx, state, kept, kept[0][2])
    readings[name] = dict({k: info[k] for k in KEYS}, ok=ok)
    return ok, info


def must_fail(setting, ok, info):
    """A harmed variant has to fail at the configuration's sizes; the
    rehearsal's (documents of 64 positions in chunks of 16) prove the
    mechanism and record the reading."""
    if ok and setting[8]["sizes"] == "rehearse":
        pytest.xfail("at the rehearsal's sizes a few dozen tokens over a "
                     "64-position document do not tell it (reading "
                     "recorded)")
    assert not ok, info


def unharmed(setting, seed=2147483604):
    if seed not in KEPT:
        KEPT[seed] = serve(setting, seed)
    return KEPT[seed]


@pytest.mark.parametrize("seed", [2147483604, 1500450271])
def test_the_unharmed_program_passes(setting, seed):
    ok, info = verdict(setting, "unharmed_%d" % seed, unharmed(setting, seed))
    assert ok, info
    # every branch is something the comparison can see
    assert info["smallest_branch_share"] >= 0.01, info


@pytest.mark.parametrize("name,differs,served", [
    ("rotary_on_the_shared_lanes", {"mla_use_nope": False}, False),
    ("rotary_on_the_shared_lanes_served", {"mla_use_nope": False}, True),
    ("beta_with_a_factor_two", {"kda_allow_neg_eigval": True}, False),
    ("routed_scaling_factor_dropped", {"routed_scaling_factor": 1.0}, False),
    ("routed_scaling_factor_dropped_served",
     {"routed_scaling_factor": 1.0}, True),
    ("shared_expert_left_out", {"num_shared_experts": 0}, False)])
def test_one_side_told_otherwise_fails(setting, name, differs, served):
    """ONE key of the configuration given otherwise to the reference over
    the unharmed program's tokens, or (``served``: two of the four BOTH
    ways, their readings side by side in the file) to the PROGRAM, which
    serves its own tokens against the reference as the configuration has
    it."""
    if served:
        ok, info = verdict(setting, name, serve(setting, **differs))
    else:
        ok, info = verdict(setting, name, unharmed(setting), **differs)
    must_fail(setting, ok, info)


def test_a_decay_averaged_over_a_heads_channels_fails(setting, monkeypatch):
    import jax.numpy as jnp

    dh = setting[3][1]
    real = dh.channel_decay

    def per_head(x, w_, p, d):
        alpha, beta = real(x, w_, p, d)
        return jnp.broadcast_to(alpha.mean(-1, keepdims=True),
                                alpha.shape), beta

    monkeypatch.setattr(dh, "channel_decay", per_head)
    ok, info = verdict(setting, "decay_averaged_over_a_head", serve(setting))
    must_fail(setting, ok, info)


def test_the_chunk_form_without_its_triangular_solve_fails(setting,
                                                           monkeypatch):
    """``A = 0``: ``W = Diag(beta) (V - (K * e^G) S_0)``, every position
    of a sub-chunk written as if the others had not been."""
    import jax

    monkeypatch.setattr(jax.lax.linalg, "triangular_solve",
                        lambda a, b, **kw: b)
    ok, info = verdict(setting, "chunk_form_A_zero", serve(setting))
    must_fail(setting, ok, info)


def test_the_chunk_state_carried_undecayed_fails(setting, monkeypatch):
    dh = setting[3][1]
    monkeypatch.setattr(dh, "_decayed", lambda state, g_last: state)
    ok, info = verdict(setting, "chunk_state_without_its_decay",
                       serve(setting))
    must_fail(setting, ok, info)


@pytest.mark.parametrize("leaf", ["state", "conv"])
def test_a_snapshot_installed_without_a_recurrent_leaf_fails(
        setting, monkeypatch, leaf):
    """The snapshot's latent rows arrive; the delta states (or the conv
    windows) are zeros, as a pool that snapshots sequence leaves alone
    would install them."""
    import jax.numpy as jnp

    from paddle_tpu.serving.kv_pool import KVSlotPool

    d = setting[3][0].dims(setting[0])
    shape = {"state": tuple(d.state_shape),
             "conv": (d.conv_len - 1, d.d_qkv)}[leaf]
    real = KVSlotPool.snapshot

    def without(self, state, slot):
        snap = real(self, state, slot)
        assert sum(tuple(x.shape) == shape for x in snap) == sum(
            kind == setting[3][0].KDA for kind in d.kinds)
        return [jnp.zeros_like(x) if tuple(x.shape) == shape else x
                for x in snap]

    monkeypatch.setattr(KVSlotPool, "snapshot", without)
    ok, info = verdict(setting, "snapshot_without_its_%s" % leaf,
                       serve(setting))
    must_fail(setting, ok, info)


def test_experts_in_place_of_the_leading_dense_ffn_fail(setting):
    """Served with ``first_k_dense_replace`` 0: layer 0's FFN is routed
    experts beside a shared expert (weights of its own, made here at the
    other layers' scales); the reference keeps the dense one."""
    import jax
    import jax.numpy as jnp

    cfg, fam, _, parts, state, _, _, ctx, _ = setting
    d = parts[0].dims(cfg)
    held = fam.held_of(cfg)
    a = cfg["assumed"]
    extra = {}
    for i, (n, shp) in enumerate(sorted(parts[1].kda_ffn_shapes(
            d, "lm_l0_", False, held[1] - held[0]).items())):
        k = jax.random.fold_in(jax.random.PRNGKey(63), i)
        if n.endswith("expert_bias"):
            extra[n] = jnp.zeros(shp, jnp.float32)
        elif n.endswith("router"):
            extra[n] = jax.random.normal(k, shp, jnp.float32) * float(
                a["router_std"])
        else:
            extra[n] = (jax.random.normal(k, shp, jnp.bfloat16) * float(
                a["initializer_range"])).astype(jnp.bfloat16)
    kept = serve(setting, weights=dict(state, **extra),
                 first_k_dense_replace=0)
    ok, info = verdict(setting, "experts_for_the_leading_dense_ffn", kept)
    must_fail(setting, ok, info)


def test_int8_rounded_weights_fail(setting):
    """Last in the file: the served copy is rounded IN PLACE (donated),
    its tokens taken, and the unrounded weights made again from their
    seed for the reference."""
    import jax
    import jax.numpy as jnp

    def rounded(a):
        f = a.astype(jnp.float32)
        scale = jnp.abs(f).max() / 127.0
        return (jnp.round(f / scale) * scale).astype(a.dtype)

    cfg, fam, _, parts, state, _, _, ctx, _ = setting
    in_place = jax.jit(rounded, donate_argnums=0)
    for k in list(state):
        if state[k].ndim >= 2 and state[k].dtype == jnp.bfloat16:
            state[k] = in_place(state[k])
    kept = serve(setting)
    state.clear()      # the rounded copy goes before the other comes
    gc.collect()
    state.update(fam.make_weights(cfg, ctx.device, parts))
    ok, info = verdict(setting, "int8_rounded_weights", kept)
    must_fail(setting, ok, info)
