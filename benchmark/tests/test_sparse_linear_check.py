"""The comparison that decides ``correct`` for ``minicpm_sala`` has to
fail what it exists to catch.  Requests are served the way the cell
serves them — the family's own ``DecodeServer`` (``make_server``): a
pilot request prefills a document in chunks and leaves its snapshot,
then sampled requests are seated over it, one after another in the slot
the last one left — and held to the bound COMMITTED in the config's
``check`` by the family's own ``check_against_reference``, the reference
given the whole prompt and no cache.  The unharmed program must pass;
served with

* every matrix rounded to int8 (the nearest precision below the bf16 the
  configuration states),
* a selection that ignores the scores (the most recent ``topk`` unforced
  blocks),
* a snapshot whose lightning state was taken one chunk early,
* dense attention past ``dense_len``,
* one decay rate for every lightning head,

it must fail.

Where a TPU is attached (``chiprun -- python -m pytest
benchmark/tests/test_sparse_linear_check.py``) the sizes are the
configuration's own: every published width, 8 layers, the whole
vocabulary, the shortest document of the cell's corpus.  On the CPU they
are its ``rehearse`` sizes and prove the mechanism only.  The readings go
to ``chiprun_out/sparse_linear_check.json``.
"""
import gc
import json
import os
import types

import numpy as np
import pytest

from benchmark.lib import harness, traffic

CONF = os.path.join(harness.BENCH, "configs", "minicpm_sala.json")


@pytest.fixture(scope="module")
def setting():
    import jax

    on_chip = jax.default_backend() == "tpu"
    cfg = harness.load_config(CONF, rehearse=not on_chip)
    mix = traffic.load_mix("shared_docs_qa", rehearse=not on_chip)
    fam = harness.load_py(os.path.join(
        harness.BENCH, "families", cfg["family"] + ".py"), cfg["family"])
    build, parts = fam.builder()
    state = fam.make_weights(cfg, jax.devices()[0], parts)
    rng = np.random.RandomState(2 ** 31 - 7 & 0x7fffffff)
    vocab = int(cfg["vocab_size"])
    doc = rng.randint(0, vocab, min(mix["documents"])).astype(np.int32)
    q_len, n_new = ((48, 96), 128) if on_chip else ((4, 9), 12)
    prompts = [np.concatenate([doc, rng.randint(
        0, vocab, rng.randint(*q_len)).astype(np.int32)]) for _ in range(7)]
    ctx = types.SimpleNamespace(cfg=cfg, device=jax.devices()[0])
    readings = {"device": jax.devices()[0].device_kind,
                "sizes": "configuration" if on_chip else "rehearse",
                "document_tokens": int(len(doc))}
    yield cfg, fam, build, parts, state, prompts, n_new, ctx, readings
    out = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    with open(os.path.join(out, "sparse_linear_check.json"), "w") as f:
        json.dump(readings, f, indent=1)


def serve(setting, cfg=None):
    """A pilot on the document, then six requests over its snapshot,
    one at a time: ``[(prompt, tokens, requests before it)]``."""
    base, fam, build, _, state, prompts, n_new, _, _ = setting
    srv = fam.make_server(cfg or base, state, build)
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


def verdict(setting, name, kept):
    _, fam, _, _, state, _, _, ctx, readings = setting
    ok, info = fam.check_against_reference(ctx, state, kept, kept[0][2])
    readings[name] = {k: info[k] for k in (
        "mean_logit_gap_share", "mean_logit_gap_share_allowed",
        "p99_logit_gap_share", "tokens",
        "worst_logit_gap_share", "logit_gap_share_allowed",
        "argmax_agreement", "in_reused_slots", "smallest_branch_share",
        "branch_share_of_residual")}
    readings[name]["ok"] = ok
    return ok, info


def test_the_unharmed_program_passes(setting):
    ok, info = verdict(setting, "unharmed", serve(setting))
    assert ok, info
    # every branch is something the comparison can see
    assert info["smallest_branch_share"] >= 0.01, info


def test_a_selection_that_ignores_the_scores_fails(setting, monkeypatch):
    import jax.numpy as jnp

    parts = setting[3]
    rule = parts.select_blocks

    def most_recent(q, ck, ts, d):
        blocks, valid, dense = rule(q, ck, ts, d)
        k = min(d.topk, blocks.shape[-1])
        win_lo = jnp.maximum((ts + 1 - d.window_size) // d.block_size, 0)
        recent = win_lo[:, None] - 1 - jnp.arange(k)[None, :]
        recent = jnp.broadcast_to(recent[:, None, :], blocks.shape[:2] + (k,))
        return (jnp.concatenate([blocks[..., :-k],
                                 jnp.maximum(recent, 0)], axis=-1),
                jnp.concatenate([valid[..., :-k],
                                 recent >= d.init_blocks], axis=-1), dense)

    monkeypatch.setattr(parts, "select_blocks", most_recent)
    ok, info = verdict(setting, "selection_ignores_scores", serve(setting))
    assert not ok, info


def test_a_snapshot_with_the_state_one_chunk_early_fails(setting,
                                                         monkeypatch):
    from paddle_tpu.serving.kv_pool import KVSlotPool

    early = {}
    prefill, snapshot = KVSlotPool.prefill, KVSlotPool.snapshot

    def prefill_and_remember(self, state, slot, start, activate):
        if activate:   # the slot's row BEFORE its last whole chunk
            early[slot] = snapshot(self, state, slot)
        return prefill(self, state, slot, start, activate)

    def stale_state(self, state, slot):
        return [now if ax is not None else then for now, then, ax in zip(
            snapshot(self, state, slot), early[slot],
            self._kv_seq_axes(state))]

    monkeypatch.setattr(KVSlotPool, "prefill", prefill_and_remember)
    monkeypatch.setattr(KVSlotPool, "snapshot", stale_state)
    ok, info = verdict(setting, "snapshot_state_one_chunk_early",
                       serve(setting))
    assert not ok, info


def test_dense_attention_past_dense_len_fails(setting):
    cfg = setting[0]
    sparse = dict(cfg["assumed"]["sparse_config"],
                  dense_len=int(cfg["serving"]["max_seq_len"]))
    dense = dict(cfg, assumed=dict(cfg["assumed"], sparse_config=sparse))
    ok, info = verdict(setting, "dense_past_dense_len", serve(setting, dense))
    assert not ok, info


def test_one_decay_for_every_head_fails(setting, monkeypatch):
    parts = setting[3]
    dims = parts.dims

    def one_decay(cfg):
        d = dims(cfg)
        d.slopes = np.full_like(d.slopes, d.slopes.mean())
        return d

    monkeypatch.setattr(parts, "dims", one_decay)
    ok, info = verdict(setting, "one_decay_for_every_head", serve(setting))
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
        if state[k].ndim == 2 and state[k].dtype == jnp.bfloat16:
            state[k] = in_place(state[k])
    kept = serve(setting)
    state.clear()      # the rounded copy goes before the other comes
    gc.collect()
    state.update(fam.make_weights(cfg, ctx.device, parts))
    ok, info = verdict(setting, "int8_rounded_weights", kept)
    assert not ok, info
