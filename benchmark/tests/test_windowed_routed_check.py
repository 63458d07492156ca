"""The comparison that decides ``correct`` for ``smallthinker_21b_a3b``
has to fail what it exists to catch.  Requests are served the way the
cell serves them — the family's own ``DecodeServer`` (``make_server``):
a pilot request prefills a document in chunks and leaves its snapshot,
then sampled requests are seated over it, one after another in the slot
the last one left, every one past the window — and held to the bounds
COMMITTED in the config's ``check`` by the family's own
``check_against_reference``, the reference given the whole prompt, no
cache and no ring.  The unharmed program must pass; it must fail when
served with

* a row of the ring written one place off (*),
* the router fed the post-attention input (the experts' own),
* SiLU gates for ReLU,
* a softmax over all the experts before the top-k (gates that no longer
  sum to one),
* a snapshot taken one chunk early,
* every matrix rounded to int8 (the nearest precision below the bf16 the
  configuration states),

and when the unharmed tokens are held to a reference that differs from
the configuration by

* a window of one position fewer, or one more (*),
* window layers that read everything (as global layers do),
* rotary applied in a global layer.

(*) ONE key of the window's 4,096 at the configuration's sizes: each
moves a window layer's output by ~1/sqrt(4096) of itself, under the floor
at which two bf16 programs of eight layers part whatever their operands
(PERF.md section 6: a difference of 1e-5 before a rounding is the
geometric mean of that and an ulp after it).  On the chip these three
are served, read and RECORDED, and expected to pass the check
(``xfail``); at the rehearsal's window of 16 they must fail like the
rest.

Where a TPU is attached (``chiprun --timeout 2400 -- python -m pytest
benchmark/tests/test_windowed_routed_check.py``) the sizes are the
configuration's own: every published width, 8 layers, 64 experts, the
whole vocabulary, the shortest document of the cell's corpus (6,144
positions: past the window of 4,096).  On the CPU they are its
``rehearse`` sizes and prove the mechanism only.  The readings go to
``chiprun_out/windowed_routed_check.json``.
"""
import gc
import json
import os
import types

import numpy as np
import pytest

from benchmark.lib import harness, traffic

CONF = os.path.join(harness.BENCH, "configs", "smallthinker_21b_a3b.json")
KEPT = {}       # the unharmed program's tokens, served once


@pytest.fixture(scope="module")
def setting():
    import jax

    on_chip = jax.default_backend() == "tpu"
    cfg = harness.load_config(CONF, rehearse=not on_chip)
    mix = traffic.load_mix("shared_docs_qa_16k", rehearse=not on_chip)
    fam = harness.load_py(os.path.join(
        harness.BENCH, "families", cfg["family"] + ".py"), cfg["family"])
    build, parts = fam.builder()
    state = fam.make_weights(cfg, jax.devices()[0], parts)
    rng = np.random.RandomState(2 ** 31 - 43 & 0x7fffffff)
    vocab = int(cfg["vocab_size"])
    doc = rng.randint(0, vocab, min(mix["documents"])).astype(np.int32)
    q_len, n_new = ((48, 96), 128) if on_chip else ((4, 9), 12)
    prompts = [np.concatenate([doc, rng.randint(
        0, vocab, rng.randint(*q_len)).astype(np.int32)]) for _ in range(5)]
    ctx = types.SimpleNamespace(cfg=cfg, device=jax.devices()[0])
    readings = {"device": jax.devices()[0].device_kind,
                "sizes": "configuration" if on_chip else "rehearse",
                "document_tokens": int(len(doc)),
                "window": int(cfg["sliding_window_size"])}
    yield cfg, fam, build, parts, state, prompts, n_new, ctx, readings
    out = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "windowed_routed_check.json"), "w") as f:
        json.dump(readings, f, indent=1)


def serve(setting):
    """A pilot on the document, then four requests over its snapshot,
    one at a time: ``[(prompt, tokens, requests before it)]``."""
    base, fam, build, _, state, prompts, n_new, _, _ = setting
    srv = fam.make_server(base, state, build)
    slots = int(base["serving"]["slot_ladder"][-1])
    try:
        srv.warmup()
        srv.submit({"tokens": prompts[0]}, max_new_tokens=2).result(900)
        kept = []
        for p in prompts[1:]:
            got = srv.submit({"tokens": p}, max_new_tokens=n_new).result(900)
            kept.append((p, np.asarray(got[0], np.int32), slots))
        stats = srv.metrics()["decode"]
        assert stats["prefix_cache"]["hits"] == len(prompts) - 1, stats
        assert stats["prefill_chunks"] == len(prompts[0]) // int(
            base["serving"]["prefill_tokens"]), stats
    finally:
        srv.stop(drain=False, timeout=60.0)
    return kept


def one_key_of_the_window(setting, ok, info):
    """A variant that differs by one key of the window must fail where
    the window is 16; at 4,096 the check is known not to see it."""
    if ok and setting[8]["sizes"] == "configuration":
        pytest.xfail("one key of 4,096: under the floor of a bf16 "
                     "comparison (reading recorded)")
    assert not ok, info


def verdict(setting, name, kept, **reference_differs):
    """The family's check of ``kept``; ``reference_differs``: keys of the
    configuration the REFERENCE is given otherwise."""
    cfg, fam, _, _, state, _, _, ctx, readings = setting
    if reference_differs:
        ctx = types.SimpleNamespace(cfg=dict(cfg, **reference_differs),
                                    device=ctx.device)
    ok, info = fam.check_against_reference(ctx, state, kept, kept[0][2])
    readings[name] = {k: info[k] for k in (
        "mean_logit_gap_share", "mean_gap_share_allowed",
        "worst_logit_gap_share", "worst_gap_share_allowed",
        "gap_share_quantiles", "tokens", "argmax_agreement",
        "distinct_tokens_per_answer", "in_reused_slots",
        "smallest_branch_share", "branch_share_of_residual")}
    readings[name]["ok"] = ok
    return ok, info


def unharmed(setting):
    if "kept" not in KEPT:
        KEPT["kept"] = serve(setting)
    return KEPT["kept"]


def test_the_unharmed_program_passes(setting):
    ok, info = verdict(setting, "unharmed", unharmed(setting))
    assert ok, info
    # every branch is something the comparison can see
    assert info["smallest_branch_share"] >= 0.01, info


@pytest.mark.parametrize("name,differs", [
    ("window_one_fewer", lambda c: {
        "sliding_window_size": int(c["sliding_window_size"]) - 1}),
    ("window_one_more", lambda c: {
        "sliding_window_size": int(c["sliding_window_size"]) + 1}),
    ("window_layers_read_as_global", lambda c: {
        "sliding_window_size": int(c["max_position_embeddings"])}),
    ("rotary_in_a_global_layer", lambda c: {
        "rope_layout": [1] * len(c["rope_layout"])})])
def test_a_reference_that_differs_from_the_configuration_fails(
        setting, name, differs):
    """The served tokens are the unharmed program's; the reference is
    told a window one position off (the served window is then one too
    many, or one too few), no window, or rotary everywhere."""
    ok, info = verdict(setting, name, unharmed(setting),
                       **differs(setting[0]))
    if name.startswith("window_one"):
        one_key_of_the_window(setting, ok, info)
    assert not ok, info


def test_a_ring_row_written_one_place_off_fails(setting, monkeypatch):
    from paddle_tpu import decode_attention as da

    window = int(setting[0]["sliding_window_size"])
    append = da._append

    def one_off(kv, name, new, rows, at, heads):
        import jax.numpy as jnp

        t = kv[name].shape[1]
        if t == window:      # a ring leaf: the step's row lands one on
            at = jnp.where(at < t, (at + 1) % t, at)
        return append(kv, name, new, rows, at, heads)

    monkeypatch.setattr(da, "_append", one_off)
    ok, info = verdict(setting, "ring_row_one_place_off", serve(setting))
    one_key_of_the_window(setting, ok, info)


def test_a_router_fed_the_post_attention_input_fails(setting, monkeypatch):
    from paddle_tpu import routed_experts as rx

    layer = rx.expert_layer
    monkeypatch.setattr(
        rx, "expert_layer",
        lambda f, w, p, ts, d, held=None, router_input=None: layer(
            f, w, p, ts, d, held))
    ok, info = verdict(setting, "router_reads_post_attention_input",
                       serve(setting))
    assert not ok, info


def test_silu_gates_for_relu_fail(setting, monkeypatch):
    from paddle_tpu import routed_experts as rx

    parts = setting[3]
    dims = parts.dims

    def silu(cfg):
        d = dims(cfg)
        d.gate_act = rx.SILU
        return d

    monkeypatch.setattr(parts, "dims", silu)
    ok, info = verdict(setting, "silu_for_relu", serve(setting))
    assert not ok, info


def test_a_softmax_over_all_the_experts_before_the_top_k_fails(
        setting, monkeypatch):
    from paddle_tpu import routed_experts as rx

    def softmax_first(f, w_router, bias, d):
        import jax
        import jax.numpy as jnp

        f32 = jnp.float32
        every = jax.nn.softmax(jnp.dot(
            f.astype(f32), w_router.astype(f32), precision="highest",
            preferred_element_type=f32), axis=-1)
        gate, sel = jax.lax.top_k(every, d.top_k)
        return sel.astype(jnp.int32), gate

    monkeypatch.setattr(rx, "route", softmax_first)
    ok, info = verdict(setting, "softmax_over_all_before_top_k",
                       serve(setting))
    assert not ok, info


def test_a_snapshot_taken_one_chunk_early_fails(setting, monkeypatch):
    from paddle_tpu.serving.kv_pool import KVSlotPool

    early = {}
    prefill, snapshot = KVSlotPool.prefill, KVSlotPool.snapshot

    def prefill_and_remember(self, state, slot, start, activate):
        if activate:   # the slot's row BEFORE its last whole chunk
            early[slot] = snapshot(self, state, slot)
        return prefill(self, state, slot, start, activate)

    monkeypatch.setattr(KVSlotPool, "prefill", prefill_and_remember)
    monkeypatch.setattr(KVSlotPool, "snapshot",
                        lambda self, state, slot: early[slot])
    ok, info = verdict(setting, "snapshot_one_chunk_early", serve(setting))
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
    ok, info = verdict(setting, "int8_rounded_weights", kept)
    assert not ok, info
