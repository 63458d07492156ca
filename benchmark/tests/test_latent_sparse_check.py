"""The comparison that decides ``correct`` for ``deepseek_v3_2`` has to
fail what it exists to catch.  Requests are served the way the cell
serves them — the family's own ``DecodeServer`` (``make_server``): a
pilot request prefills a document in chunks and leaves its snapshot,
then sampled requests are seated over it, one after another in the slot
the last one left, every one at a context 8 times ``index_topk`` — and
held to the bounds COMMITTED in the config's ``check`` by the family's
own ``check_against_reference``, the reference given the whole prompt,
expanded, with its own float32 indexer and top-k, no cache.  The
unharmed program must pass, on two seeds of documents and questions; it
must fail when served with

* the indexer's rotary dropped (index queries and keys left bare),
* the indexer's ReLU dropped,
* the indexer's head weights ``w_t`` dropped (every head weighs 1),
* a router whose matrix and input are rounded to bf16 (*),
* every matrix rounded to int8 (the nearest precision below the bf16 the
  configuration states),

and when the unharmed tokens are held to a reference that differs from
the configuration by

* the top 1024 for the top 2048,
* no selection at all (a dense read of every earlier position),
* YaRN's scale dropped from the softmax (``mscale_all_dim`` 0).

(*) 8 of 256 experts are held here: a marginal expert that flips is a
held one a thirty-second of the time, which is why this cell is not the
expert layer's yardstick.  On the chip that variant is served, read and
RECORDED, and where it passes the check it is an expected failure
(``xfail``) and PERF.md says so; at the rehearsal's sizes it is recorded
the same way.

Where a TPU is attached (``chiprun --timeout 3400 -- python -m pytest
benchmark/tests/test_latent_sparse_check.py``) the sizes are the
configuration's own: every published width, 5 layers, 8 held experts of
256, the shortest document of the cell's corpus (16,384 positions).  On
the CPU they are its ``rehearse`` sizes and prove the mechanism only
(there int8-rounded weights move the mean less than one near-tied token
of ~36 does: recorded, an expected failure where they pass).
The readings go to ``chiprun_out/latent_sparse_check.json``.
"""
import gc
import json
import os
import types

import numpy as np
import pytest

from benchmark.lib import harness, traffic

CONF = os.path.join(harness.BENCH, "configs", "deepseek_v3_2.json")
KEPT = {}       # the unharmed program's tokens, served once a seed
KEYS = ("mean_logit_gap_share", "mean_gap_share_allowed",
        "worst_logit_gap_share", "worst_gap_share_allowed",
        "gap_share_quantiles", "index_score_worst_difference",
        "index_score_tolerance", "index_selected_worst_below_kth",
        "index_rank_margin", "tokens", "argmax_agreement",
        "distinct_tokens_per_answer", "in_reused_slots",
        "smallest_branch_share", "branch_share_of_residual")


@pytest.fixture(scope="module")
def setting():
    import jax

    on_chip = jax.default_backend() == "tpu"
    cfg = harness.load_config(CONF, rehearse=not on_chip)
    mix = traffic.load_mix("shared_docs_qa_32k", rehearse=not on_chip)
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
                "document_tokens": int(min(mix["documents"])),
                "index_topk": int(cfg["index_topk"])}
    yield cfg, fam, build, parts, state, prompts_of, n_new, ctx, readings
    out = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "latent_sparse_check.json"), "w") as f:
        json.dump(readings, f, indent=1)


def serve(setting, seed=2147483604):
    """A pilot on the document, then three requests over its snapshot,
    one at a time: ``[(prompt, tokens, requests before it)]``."""
    base, fam, build, _, state, prompts_of, n_new, _, _ = setting
    prompts = prompts_of(seed)
    srv = fam.make_server(base, state, build)
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
        assert 0 < stats["latent_positions_selected"] < stats[
            "index_positions_scored"], stats
    finally:
        srv.stop(drain=False, timeout=60.0)
    return kept


def drop_program_pieces(fam):
    """Forget the family's compiled copy of the PROGRAM's indexer (the
    reference's compiled pieces stay)."""
    for key in [k for k in fam._PROGRAMS if k.startswith(fam.PROGRAM_KEY)]:
        del fam._PROGRAMS[key]


def verdict(setting, name, kept, program_harmed=False, **reference_differs):
    """The family's check of ``kept``; ``reference_differs``: keys of the
    configuration the REFERENCE is given otherwise; ``program_harmed``:
    the program's own indexer piece is rebuilt (it is patched)."""
    cfg, fam, _, _, state, _, _, ctx, readings = setting
    if reference_differs:
        ctx = types.SimpleNamespace(cfg=dict(cfg, **reference_differs),
                                    device=ctx.device)
    if program_harmed:
        drop_program_pieces(fam)
    ok, info = fam.check_against_reference(ctx, state, kept, kept[0][2])
    readings[name] = dict({k: info[k] for k in KEYS}, ok=ok)
    return ok, info


def unharmed(setting, seed=2147483604):
    if seed not in KEPT:
        KEPT[seed] = serve(setting, seed)
    return KEPT[seed]


@pytest.mark.parametrize("seed", [2147483604, 1500450271])
def test_the_unharmed_program_passes(setting, seed):
    ok, info = verdict(setting, "unharmed_%d" % seed, unharmed(setting, seed),
                       program_harmed=True)
    assert ok, info
    # every branch is something the comparison can see
    assert info["smallest_branch_share"] >= 0.01, info


@pytest.mark.parametrize("name,differs", [
    ("top_half_for_top_k", lambda c: {
        "index_topk": int(c["index_topk"]) // 2}),
    ("selection_ignored_dense_read", lambda c: {
        "index_topk": int(c["check"]["reference_len"])}),
    ("yarn_scale_dropped", lambda c: {
        "rope_scaling": dict(c["rope_scaling"], mscale_all_dim=0)})])
def test_a_reference_that_differs_from_the_configuration_fails(
        setting, name, differs):
    """The served tokens are the unharmed program's; the reference is
    told to select half as many, to select everything, or to scale its
    softmax without YaRN's factor."""
    ok, info = verdict(setting, name, unharmed(setting),
                       **differs(setting[0]))
    assert not ok, info


def _served_harmed(setting, name):
    ok, info = verdict(setting, name, serve(setting), program_harmed=True)
    drop_program_pieces(setting[1])  # no harmed piece for the next test
    return ok, info


def test_the_indexers_rotary_dropped_fails(setting, monkeypatch):
    parts = setting[3]
    monkeypatch.setattr(parts, "_rotate_head", lambda x, pos, d: x)
    ok, info = _served_harmed(setting, "indexer_rotary_dropped")
    assert not ok, info


def test_the_indexers_relu_dropped_fails(setting, monkeypatch):
    import jax.numpy as jnp

    parts = setting[3]

    def no_relu(qi, wi, keys):
        form = "nhd,ntd->nht" if keys.ndim == 3 else "nhd,td->nht"
        qi = qi.astype(keys.dtype)
        qi = jnp.pad(qi, ((0, 0), (0, 0),
                          (0, keys.shape[-1] - qi.shape[-1])))
        s = jnp.einsum(form, qi, keys, preferred_element_type=jnp.float32)
        return jnp.sum(s * wi.astype(jnp.float32)[:, :, None], axis=1)

    monkeypatch.setattr(parts, "index_scores", no_relu)
    ok, info = _served_harmed(setting, "indexer_relu_dropped")
    assert not ok, info


def test_the_indexers_head_weights_dropped_fail(setting, monkeypatch):
    import jax.numpy as jnp

    parts = setting[3]
    inputs = parts.index_inputs

    def unweighed(x, cq, w, p, pos, d):
        qi, ki, wi = inputs(x, cq, w, p, pos, d)
        return qi, ki, jnp.full_like(wi, float(
            d.n_index_head ** -0.5 * d.d_index ** -0.5))

    monkeypatch.setattr(parts, "index_inputs", unweighed)
    ok, info = _served_harmed(setting, "indexer_head_weights_dropped")
    assert not ok, info


def test_a_bf16_router_is_recorded(setting, monkeypatch):
    """8 of 256 experts held: a flipped marginal expert is rarely a held
    one.  Recorded; an expected failure where the check cannot see it."""
    import jax.numpy as jnp

    from paddle_tpu import routed_experts as rx

    route = rx.route

    def rounded(f, w_router, bias, d):
        bf = lambda a: a.astype(jnp.bfloat16).astype(jnp.float32)
        return route(bf(f), bf(w_router), bias, d)

    monkeypatch.setattr(rx, "route", rounded)
    ok, info = _served_harmed(setting, "bf16_router")
    if ok:
        pytest.xfail("a bf16 router under a held share of 8 in 256: under "
                     "what the comparison sees (reading recorded)")
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

    cfg, fam, _, parts, state, _, _, ctx, _ = setting
    in_place = jax.jit(rounded, donate_argnums=0)
    for k in list(state):
        if state[k].ndim >= 2 and state[k].dtype == jnp.bfloat16:
            state[k] = in_place(state[k])
    kept = serve(setting)
    state.clear()      # the rounded copy goes before the other comes
    gc.collect()
    state.update(fam.make_weights(cfg, ctx.device, parts))
    ok, info = verdict(setting, "int8_rounded_weights", kept,
                       program_harmed=True)
    if ok and setting[8]["sizes"] == "rehearse":
        pytest.xfail("at the rehearsal's widths one near-tied token of ~36 "
                     "moves the mean more than int8 does (reading recorded)")
    assert not ok, info
