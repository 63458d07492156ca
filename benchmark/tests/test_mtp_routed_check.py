"""The comparison that decides ``correct`` for ``k_exaone_236b_a23b`` has
to fail what it exists to catch.  Requests are served the way the cell
serves them — the family's own ``DecodeServer`` (``make_server``) with
the model's multi-token-prediction module drafting, every request
speculative, prompts walking the rounds two at a time, every context
past the window — and held to the bounds COMMITTED in the config's
``check`` by the family's own ``check_against_reference``: the served
tokens against the reference's logits AND the module's proposals against
the reference's module logits.  The unharmed program must pass; it must
fail when served with

* the selection bias added to the weights (B),
* q/k norms left out,
* rotary in the global layer (and the module's),
* a row of the ring written one place off (*),
* the module fed the hidden state of the neighbouring position (D),
* the module's ``RMSNorm_h`` left out (D),
* a rejected proposal kept,
* every matrix rounded to int8 (the nearest precision below the bf16 the
  configuration states),

and when the unharmed tokens and proposals are held to a reference that
differs from the configuration by

* no shared expert,
* ``routed_scaling_factor`` 1,
* seven experts a token,
* a held range shifted by one expert,
* a window of one position fewer, or one more (*),
* window layers that read everything (as global layers do).

(D) harms the module alone: the served tokens stay exact (speculation is
greedy-exact) and only the DRAFTS' limits can fail it — without them a
module that computes nothing would be a speed-up at this acceptance.
(*) ONE key of the window's 128: a sixtieth of a window layer's output —
on the chip they read a mean of 0.013-0.026, ten times the limit, and
FAIL (PR 47); should a reading ever sit inside the unharmed range the
test records it and expects a pass (``xfail``), as
``smallthinker_21b_a3b``'s does at its window of 4,096.
(B) cannot be told at the configuration's sizes: the choice is
unchanged, a bias of +-0.05 moves a gate of ~0.31 by a tenth, one chosen
expert in eight is held here and it stands beside a shared expert of
weight 1 — 3% of the FFN branch, a second-order effect on a logit's
place: 0.00021 / 0.027 against the unharmed 0.00014 / 0.027 (chip,
PR 47; the rehearsal reads 0.00073 against 0.00055).  Recorded and
expected to pass (``xfail``) at both sizes; a reading past the limits
would be welcome and fails nothing.

Where a TPU is attached (``chiprun --timeout 3000 -- python -m pytest
benchmark/tests/test_mtp_routed_check.py``) the sizes are the
configuration's own; on the CPU they are its ``rehearse`` sizes and prove
the mechanism only.  The readings go to
``chiprun_out/mtp_routed_check.json``.
"""
import gc
import json
import os
import types

import numpy as np
import pytest

from benchmark.lib import harness

CONF = os.path.join(harness.BENCH, "configs", "k_exaone_236b_a23b.json")
KEPT = {}       # the unharmed program's tokens and proposals, served once


@pytest.fixture(scope="module")
def setting():
    import jax

    on_chip = jax.default_backend() == "tpu"
    cfg = harness.load_config(CONF, rehearse=not on_chip)
    fam = harness.load_py(os.path.join(
        harness.BENCH, "families", cfg["family"] + ".py"), cfg["family"])
    build, parts = fam.builder()
    state = fam.make_weights(cfg, jax.devices()[0], parts)
    rng = np.random.RandomState(2 ** 31 - 47 & 0x7fffffff)
    vocab = int(cfg["vocab_size"])
    p_len, n_new = ((96, 224), 384) if on_chip else ((4, 10), 30)
    prompts = [rng.randint(0, vocab, rng.randint(*p_len)).astype(np.int32)
               for _ in range(4)]
    ctx = types.SimpleNamespace(cfg=cfg, device=jax.devices()[0])
    readings = {"device": jax.devices()[0].device_kind,
                "sizes": "configuration" if on_chip else "rehearse",
                "window": int(cfg["sliding_window"])}
    yield cfg, fam, build, parts, state, prompts, n_new, ctx, readings
    out = os.path.join(harness.ROOT, "chiprun_out")
    os.makedirs(out, exist_ok=True)
    # a CPU rehearsal must not overwrite a chip run's readings
    name = "mtp_routed_check%s.json" % ("" if on_chip else ".rehearse")
    with open(os.path.join(out, name), "w") as f:
        json.dump(readings, f, indent=1)


def serve(setting, build=None):
    """Four speculative requests at once, each keeping its proposals:
    ``[(prompt, tokens, requests before it, proposals)]``."""
    base, fam, own, _, state, prompts, n_new, _, _ = setting
    srv = fam.make_server(base, state, build or own)
    slots = int(base["serving"]["slot_ladder"][-1])
    try:
        srv.warmup()
        reqs = [srv.submit({"tokens": p}, max_new_tokens=n_new,
                           speculative=True, keep_drafts=True)
                for p in prompts]
        kept = [(p, np.asarray(r.result(1800)[0], np.int32), slots,
                 r.draft_tokens) for p, r in zip(prompts, reqs)]
        spec = srv.metrics()["decode"]["speculative"]
        assert spec["rounds"] == srv.metrics()["decode"]["ticks"] > 0, spec
    finally:
        srv.stop(drain=False, timeout=60.0)
    return kept


def one_key_of_the_window(setting, ok, info):
    """A variant that differs by one key of the window must fail where
    the window is 8; at 128 the reading is recorded."""
    if ok and setting[8]["sizes"] == "configuration":
        pytest.xfail("one key of 128: inside the unharmed range of a bf16 "
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
        "draft_mean_gap_share", "draft_mean_gap_share_allowed",
        "draft_worst_gap_share", "draft_worst_gap_share_allowed",
        "gap_share_quantiles", "draft_gap_share_quantiles", "tokens",
        "argmax_agreement", "draft_argmax_agreement", "drafts_equal_served",
        "distinct_tokens_per_answer", "smallest_branch_share",
        "branch_share_of_residual")}
    readings[name]["ok"] = ok
    return ok, info


def tokens_pass(info):
    return (info["mean_logit_gap_share"] <= info["mean_gap_share_allowed"]
            and info["worst_logit_gap_share"]
            <= info["worst_gap_share_allowed"])


def unharmed(setting):
    if "kept" not in KEPT:
        KEPT["kept"] = serve(setting)
    return KEPT["kept"]


def test_the_unharmed_program_passes(setting):
    ok, info = verdict(setting, "unharmed", unharmed(setting))
    assert ok, info
    # every branch is something the comparison can see
    assert info["smallest_branch_share"] >= 0.01, info


def test_plain_requests_are_served_as_faithfully_as_speculative_ones(setting):
    """The same prompts submitted WITHOUT ``speculative=True`` go through
    the one-token ``chunk`` (on the chip: the grouped kernel over the
    global leaf, where the round reads it through the XLA form).  In
    float32 on the CPU the tokens are the speculative requests' token
    for token (``tests/test_self_draft_round.py``).  In bf16 they are
    two programs that sum in another order: where two logits lie within
    a rounding of each other they choose differently — one position in
    ~30 of a random-weight decoder — and go their own ways from there
    (chip, PR 47: the four requests first part at tokens 27, 1, 7 and 95
    of 384).  So what is HELD is that the plain tokens meet the
    reference under the same two limits; where each request first parts
    is recorded."""
    base, fam, own, _, state, prompts, n_new, _, readings = setting
    kept = unharmed(setting)
    srv = fam.make_server(base, state, own)
    try:
        srv.warmup()
        reqs = [srv.submit({"tokens": p}, max_new_tokens=n_new)
                for p in prompts]
        plain = [np.asarray(r.result(1800)[0], np.int32) for r in reqs]
        assert srv.metrics()["decode"]["speculative"]["rounds"] == 0
    finally:
        srv.stop(drain=False, timeout=60.0)
    first = []
    for (_, spec, _, _), got in zip(kept, plain):
        differ = np.nonzero(spec != got)[0]
        first.append(int(differ[0]) if differ.size else None)
    # the proposals are another sequence's: only the tokens' limits
    # are read
    _, info = verdict(setting, "plain_requests", [
        (p, got, before, drafts)
        for (p, _, before, drafts), got in zip(kept, plain)])
    readings["plain_requests"]["first_token_that_differs"] = first
    assert tokens_pass(info), info
    if readings["sizes"] == "rehearse":
        assert first == [None] * len(first), first


@pytest.mark.parametrize("name,differs", [
    ("no_shared_expert", lambda c: {"num_shared_experts": 0}),
    ("routed_scaling_factor_1", lambda c: {"routed_scaling_factor": 1.0}),
    ("top_7", lambda c: {
        "num_experts_per_tok": int(c["num_experts_per_tok"]) - 1}),
    ("held_range_shifted_by_one", lambda c: {
        "experts_held": [x + 1 for x in c["experts_held"]]}),
    ("window_one_fewer", lambda c: {
        "sliding_window": int(c["sliding_window"]) - 1}),
    ("window_one_more", lambda c: {
        "sliding_window": int(c["sliding_window"]) + 1}),
    ("window_layers_read_as_global", lambda c: {
        "sliding_window": int(c["max_position_embeddings"])})])
def test_a_reference_that_differs_from_the_configuration_fails(
        setting, name, differs):
    """The served tokens and proposals are the unharmed program's; the
    reference is told another model."""
    ok, info = verdict(setting, name, unharmed(setting),
                       **differs(setting[0]))
    if name.startswith("window_one"):
        one_key_of_the_window(setting, ok, info)
    assert not ok, info


def test_the_selection_bias_added_to_the_weights_fails(setting, monkeypatch):
    from paddle_tpu import routed_experts as rx

    def biased(f, w_router, bias, d):
        import jax
        import jax.numpy as jnp

        f32 = jnp.float32
        s = jax.nn.sigmoid(jnp.dot(
            f.astype(f32), w_router.astype(f32), precision="highest",
            preferred_element_type=f32)) + bias.astype(f32)
        gate, sel = jax.lax.top_k(s, d.top_k)
        gate = gate / (jnp.sum(gate, axis=-1, keepdims=True) + 1e-6)
        return sel.astype(jnp.int32), gate * d.routed_scale

    monkeypatch.setattr(rx, "route", biased)
    ok, info = verdict(setting, "bias_added_to_the_weights", serve(setting))
    if ok:
        pytest.xfail("a tenth of one gate in eight beside a shared expert: "
                     "second order at these sizes (reading recorded)")


def _attention_inputs(parts, normed: bool, rotate_all: bool):
    def harmed(x, w, p, kind, pos, d):
        n = x.shape[0]
        q = parts.linear(x, w[p + "attn_q"]).reshape(n, d.n_head, d.head_dim)
        k = parts.linear(x, w[p + "attn_k"]).reshape(n, d.n_kv_head,
                                                     d.head_dim)
        if normed:
            q = parts.rms_norm(q, w[p + "q_norm"], d.eps)
            k = parts.rms_norm(k, w[p + "k_norm"], d.eps)
        if rotate_all or kind == parts.WINDOW:
            q = parts.rotary(q, pos, d.rope_theta)
            k = parts.rotary(k, pos, d.rope_theta)
        return q, k, parts.linear(x, w[p + "attn_v"])
    return harmed


def test_qk_norms_left_out_fail(setting, monkeypatch):
    parts = setting[3]
    monkeypatch.setattr(parts, "attention_inputs",
                        _attention_inputs(parts, False, False))
    ok, info = verdict(setting, "qk_norms_left_out", serve(setting))
    assert not ok, info


def test_rotary_in_the_global_layer_fails(setting, monkeypatch):
    parts = setting[3]
    monkeypatch.setattr(parts, "attention_inputs",
                        _attention_inputs(parts, True, True))
    ok, info = verdict(setting, "rotary_in_the_global_layer", serve(setting))
    assert not ok, info


def test_a_ring_row_written_one_place_off_fails(setting, monkeypatch):
    from paddle_tpu import decode_attention as da

    window = int(setting[0]["sliding_window"])
    append = da._append

    def one_off(kv, name, new, rows, at, heads):
        import jax.numpy as jnp

        t = kv[name].shape[1]
        if t == window:      # a ring leaf: the round's rows land one on
            at = jnp.where(at < t, (at + 1) % t, at)
        return append(kv, name, new, rows, at, heads)

    monkeypatch.setattr(da, "_append", one_off)
    ok, info = verdict(setting, "ring_row_one_place_off", serve(setting))
    one_key_of_the_window(setting, ok, info)


def _with_module(setting, harm_module=None, harm_verify=None):
    """The family's ``build`` with the self-draft's two functions
    wrapped."""
    make_step, make_self_draft = setting[2]

    def harmed(make_cache):
        cfg = make_self_draft(make_cache)
        if harm_module is not None:
            cfg.module_fn = harm_module(cfg.module_fn)
        if harm_verify is not None:
            cfg.verify_fn = harm_verify(cfg.verify_fn)
        return cfg

    return make_step, harmed


def test_the_module_fed_the_neighbouring_hidden_state_fails_by_its_drafts(
        setting):
    def neighbour(module_fn):
        return lambda cache, hidden, nxt, ts: module_fn(
            cache, hidden[:, ::-1], nxt, ts)

    ok, info = verdict(setting, "module_fed_the_neighbouring_hidden_state",
                       serve(setting, _with_module(setting, neighbour)))
    assert not ok and tokens_pass(info), info


def test_the_modules_hidden_norm_left_out_fails_by_its_drafts(
        setting, monkeypatch):
    parts = setting[3]

    def no_hnorm(hidden, emb_rows, w, p, d):
        import jax.numpy as jnp

        both = jnp.concatenate(
            [parts.rms_norm(emb_rows, w[p + "e_norm"], d.eps),
             hidden.astype(jnp.float32)], axis=-1)
        return parts.linear(both, w[p + "eh"])

    monkeypatch.setattr(parts, "module_input", no_hnorm)
    ok, info = verdict(setting, "module_hidden_norm_left_out", serve(setting))
    assert not ok and tokens_pass(info), info


def test_a_rejected_proposal_kept_fails(setting):
    """The verify made to agree with whatever was proposed: every draft
    is 'accepted' and served."""
    def agreeable(verify_fn):
        def verify(cache, tokens, ts):
            import jax

            logits, hidden, cache = verify_fn(cache, tokens, ts)
            bump = 1e4 * jax.nn.one_hot(tokens[:, 1], logits.shape[-1])
            return logits.at[:, 0].add(bump), hidden, cache
        return verify

    ok, info = verdict(
        setting, "rejected_proposal_kept",
        serve(setting, _with_module(setting, harm_verify=agreeable)))
    assert not ok and not tokens_pass(info), info


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
