"""Speculative decoding: draft-then-verify rounds in the slot pool.

One token per target step is the autoregressive tax.  Speculative
decoding (Leviathan et al., ICML 2023) pays it with a SMALL draft
model: per round the draft proposes ``k - 1`` tokens one at a time,
then the target verifies all ``k`` consumed positions in ONE K-wide
forward (``decoding.make_transformer_lm_pooled_verify_fn``) — exactly
the prefill-shaped call the rung ladder already compiles, so the whole
round is one warmed ``spec_chunk`` executable per (slot, length) rung
pair and zero new shapes.

Acceptance is **greedy-exact**: a drafted token is accepted iff it
equals the target's own greedy argmax at that position, so the emitted
sequence is bit-identical to non-speculative greedy decode no matter
how bad the draft is (parity-pinned; a weak draft only costs speed).
The round's algebra, per slot (``pos`` = tokens consumed so far):

* consumption ``j`` eats position ``q_j = pos + j``: the stored prompt
  token while ``q_j < prompt_len`` (teacher forcing — prefill runs
  K-wide through the same call), else the draft's proposal;
* the chain stays alive through ``j`` iff every consumed draft token so
  far matched the target's prediction for its position; the target's
  ``argmax(logits[:, j])`` is the (verified) token for ``q_j + 1`` and
  is emitted while the chain is alive and past the prompt;
* ``pos`` advances by the accepted length (1..k): rejected positions'
  cache rows are simply re-written next round — the pool's
  write-before-read invariant makes rollback free, for the target AND
  the draft cache (both are state leaves the executables thread
  through).

Non-speculative slots sharing the pool degrade to one exact token per
round (their chain dies at ``j = 1`` by construction); the scheduler
only dispatches ``spec_chunk`` on ticks where some active slot opted
in, so a pool with speculation enabled but unused runs plain chunks.

A SECOND kind of draft is the target's own multi-token-prediction
module (:class:`SelfDraftConfig`, DeepSeek-V3's form;
``decoding.make_mtp_routed_lm_pooled_step_fn``).  It has no token-only
step and no cache of its own to keep position-synced: it consumes the
target's last hidden state ``h_i`` with the token ``t_{i+1}`` of
positions the target has VERIFIED, keeps K/V rows for them among the
target's own cache leaves, and its output at the last kept position is
the NEXT round's proposal, carried in the pool state.  The round (``k``
= 2), per slot with ``pos`` tokens consumed and ``d`` last round's
proposal:

* consume ``c0 = tokens[pos]`` and ``c1`` = the stored prompt token while
  ``pos + 1 < prompt_len``, else ``d``; verify ``[c0, c1]`` at ``pos,
  pos + 1`` in one 2-wide target forward -> ``g0, g1, h0, h1``;
* ``c1`` is kept iff it is a prompt token or (a speculative slot's)
  ``d == g0``; emit ``g0``, and ``g1`` if ``c1`` was kept; advance by 1
  or 2 — the served tokens are the plain step's, token for token;
* run the module on ``(h0, t_{pos+1})`` and ``(h1, t_{pos+2})`` (the
  second row is garbage after a rejection: its K/V row is re-written
  next round before anything reads it); the new ``d`` is its argmax at
  the last kept row.  Both rows' proposals are also written, by the
  position they predict, into the state's ``proposals`` ``[S, T]``
  buffer, which nothing fetches unless a request asked to keep them.

Over RING leaves a rejected round must leave every row a later query
reads unchanged: a round writes ring rows ``pos .. pos + k - 1`` over
positions ``pos - W ..``; after a rejection at ``j = 1`` the next query,
at ``pos + 1``, reads ``pos + 2 - W ..``: with ``k = 2`` nothing it reads
was overwritten, with ``k >= 3`` position ``pos + 2 - W`` was.
``KVSlotPool`` therefore carries ``k <= 2`` over a ring and refuses more.

Telemetry: ``serving_spec_tokens_{proposed,accepted}_total`` counters
(labeled like the decode series), ``serving_spec_rounds_total`` /
``serving_spec_row_rounds_total`` (rounds dispatched; slots that
advanced in one) and the per-server accepted-length histogram in
``DecodeServer.metrics()``.
"""
from __future__ import annotations

from typing import Callable, Dict, Optional

import numpy as np

from paddle_tpu import monitor

__all__ = ["SpeculativeConfig", "SelfDraftConfig", "make_lm_speculative",
           "make_self_draft", "make_spec_chunk_fn",
           "make_self_draft_chunk_fn", "dispatch_spec_chunk",
           "SPEC_PROPOSED", "SPEC_ACCEPTED", "SPEC_ROUNDS",
           "SPEC_ROW_ROUNDS"]

_LABELS = ("server", "instance")
SPEC_PROPOSED = monitor.counter(
    "serving_spec_tokens_proposed_total",
    "draft tokens proposed per speculative decode round (k - 1 per "
    "round per opted-in slot in its decode phase)", _LABELS)
SPEC_ACCEPTED = monitor.counter(
    "serving_spec_tokens_accepted_total",
    "draft tokens accepted by greedy-exact verification (acceptance "
    "rate = accepted / proposed; the speculative speedup lever)",
    _LABELS)
SPEC_ROUNDS = monitor.counter(
    "serving_spec_rounds_total",
    "speculative rounds dispatched (one spec_chunk executable call: "
    "every active slot's k rows verified at once)", _LABELS)
SPEC_ROW_ROUNDS = monitor.counter(
    "serving_spec_row_rounds_total",
    "slots that advanced in a speculative round, summed over rounds "
    "(generated tokens over this = tokens a row a round)", _LABELS)


class SpeculativeConfig:
    """Everything a slot pool needs to run draft-then-verify rounds.

    ``verify_fn(cache, tokens [S, K], ts [S]) -> (logits [S, K, V],
    cache)``: the target's K-wide teacher-forced forward, exact-parity
    with its sequential step.  ``draft_step_fn``/``draft_make_cache``:
    the draft model in the same slot-pooled step contract — its cache
    rides the pool state as ``draft_cache`` so both models stay
    position-synced.  ``k``: consumed positions per round (>= 2; the
    draft proposes ``k - 1``).  ``draft_meta``: manifest fields for
    ``save_decode_endpoint`` (the per-endpoint ``draft`` block).
    """

    #: what drafts: a separate model with a cache of its own
    kind = "model"

    def __init__(self, verify_fn: Callable, draft_step_fn: Callable,
                 draft_make_cache: Callable, k: int = 4,
                 draft_meta: Optional[Dict[str, object]] = None):
        if int(k) < 2:
            raise ValueError(
                "speculative k must be >= 2 (k=1 is plain decode), "
                "got %r" % k)
        self.verify_fn = verify_fn
        self.draft_step_fn = draft_step_fn
        self.draft_make_cache = draft_make_cache
        self.k = int(k)
        self.draft_meta = dict(draft_meta or {})


class SelfDraftConfig(SpeculativeConfig):
    """The draft is the target's own multi-token-prediction module.

    ``verify_fn(cache, tokens [S, K], ts [S]) -> (logits [S, K, V],
    hidden [S, K, D], cache)``: the target's K-wide forward, which also
    yields the last block's output.  ``module_fn(cache, hidden [S, K,
    D], next_tokens [S, K], ts [S]) -> (logits [S, K, V], cache)``: the
    module at positions ``ts .. ts + K - 1`` over K/V leaves that are
    part of the target's ``cache``.  ``k`` is 2: ONE module proposes one
    token a round (a chain of modules would carry more)."""

    kind = "self"

    def __init__(self, verify_fn: Callable, module_fn: Callable, k: int = 2,
                 draft_meta: Optional[Dict[str, object]] = None):
        if int(k) != 2:
            raise ValueError(
                "a self-drafting round verifies k = 2 rows: one module "
                "proposes one token (a chain of modules is not "
                "supported), got k = %r" % k)
        self.verify_fn = verify_fn
        self.module_fn = module_fn
        self.draft_step_fn = None
        self.draft_make_cache = None
        self.k = 2
        self.draft_meta = dict(draft_meta or {})


def make_self_draft(make_cache) -> SelfDraftConfig:
    """The :class:`SelfDraftConfig` of a builder that declares its
    verify and its module (``decoding.CacheSpec.verify_fn`` /
    ``.mtp_fn``: ``decoding.make_mtp_routed_lm_pooled_step_fn``)."""
    from paddle_tpu.decoding import spec_of

    spec = spec_of(make_cache)
    if spec.verify_fn is None or spec.mtp_fn is None:
        raise ValueError(
            "make_cache declares no verify_fn / mtp_fn: this builder has "
            "no multi-token-prediction module to draft with")
    return SelfDraftConfig(spec.verify_fn, spec.mtp_fn,
                           draft_meta={"kind": "self", "k": 2})


def make_lm_speculative(target_state, *, vocab_size: int, d_model: int,
                        n_layer: int, n_head: int, d_inner: int,
                        draft_state, draft_d_model: int,
                        draft_n_layer: int, draft_n_head: int,
                        draft_d_inner: int, k: int = 4,
                        name: str = "lm",
                        draft_name: str = "draft",
                        kv_dtype: str = "fp32") -> SpeculativeConfig:
    """A :class:`SpeculativeConfig` for a transformer-LM target + a
    (smaller) transformer-LM draft sharing the vocabulary — the
    in-tree pair ``save/load_decode_endpoint`` persists.

    ``kv_dtype``: the TARGET's KV-cache storage dtype — must match the
    step fn the pool runs, so the verify call reads/writes the same
    int8-coded cache leaves.  The draft always keeps fp32 KV (it is
    small by construction; quantizing it buys nothing)."""
    from paddle_tpu.decoding import (
        make_transformer_lm_pooled_step_fn,
        make_transformer_lm_pooled_verify_fn,
    )

    verify_fn = make_transformer_lm_pooled_verify_fn(
        target_state, vocab_size, d_model, n_layer, n_head, d_inner,
        name=name, kv_dtype=kv_dtype)
    draft_step_fn, draft_make_cache = make_transformer_lm_pooled_step_fn(
        draft_state, vocab_size, draft_d_model, draft_n_layer,
        draft_n_head, draft_d_inner, name=draft_name)
    return SpeculativeConfig(
        verify_fn, draft_step_fn, draft_make_cache, k=k,
        draft_meta={
            "d_model": int(draft_d_model), "n_layer": int(draft_n_layer),
            "n_head": int(draft_n_head), "d_inner": int(draft_d_inner),
            "name": draft_name, "k": int(k),
        })


def _commit_chain(state, ctoks, g, eos_id: int):
    """The greedy-exact acceptance chain and its commit, shared by both
    kinds of round.  ``ctoks`` ``[S, K]`` the tokens consumed at ``pos ..
    pos + K - 1``, ``g`` ``[S, K]`` the target's argmax after each: a
    stored prompt token is right by construction, a drafted one must
    equal the target's own prediction for its position (and only
    speculative slots draft at all); ``g[:, j]`` is emitted while the
    chain is alive and past the prompt.  Returns ``(tokens, adv,
    n_emit, newly_fin)``; the chain is unrolled over ``j`` (``K`` is a
    compile-time constant)."""
    import jax.numpy as jnp

    tokens, pos = state["tokens"], state["pos"]
    prompt_len, total_len = state["prompt_len"], state["total_len"]
    S, T = tokens.shape
    rows = jnp.arange(S)
    alive = state["active"]
    newly_fin = jnp.zeros((S,), bool)
    n_emit = jnp.zeros((S,), jnp.int32)
    adv = jnp.zeros((S,), jnp.int32)
    for j in range(ctoks.shape[1]):
        qj = pos + j
        if j > 0:
            alive = alive & jnp.where(
                qj < prompt_len, True,
                state["spec"] & (ctoks[:, j] == g[:, j - 1]))
        adv = adv + alive.astype(jnp.int32)
        wr = qj + 1
        emit = alive & (wr >= prompt_len) & (wr < total_len)
        wclamp = jnp.minimum(wr, T - 1)
        tokens = tokens.at[rows, wclamp].set(
            jnp.where(emit, g[:, j], tokens[rows, wclamp]))
        n_emit = n_emit + emit.astype(jnp.int32)
        fin = emit & ((g[:, j] == eos_id) | ((qj + 2) >= total_len))
        newly_fin = newly_fin | fin
        alive = alive & ~fin
    return tokens, adv, n_emit, newly_fin


def make_spec_chunk_fn(verify_fn, draft_step_fn, eos_id: int, k: int):
    """The pure per-round function the pool compiles as ``spec_chunk``
    for each rung pair: draft ``k - 1`` proposals, verify all ``k``
    consumptions in one target call, commit the accepted run.  See the
    module docstring for the algebra; the acceptance chain is unrolled
    statically over ``j`` (k is a compile-time constant)."""
    import jax.numpy as jnp

    K = int(k)

    def spec_chunk(state):
        tokens = state["tokens"]
        pos = state["pos"]
        active = state["active"]
        prompt_len = state["prompt_len"]
        S, T = tokens.shape
        rows = jnp.arange(S)
        # --- draft phase: K sequential small steps.  Consumption c_0 is
        # always the stored buffer token at pos (prompt token, or the
        # previously verified emission); later consumptions teacher-
        # force the prompt while q_j < prompt_len, else take the
        # draft's proposal.  The draft consumes ALL K tokens so its
        # cache rows cover a fully accepted round (write-before-read
        # re-covers rejected rows next round).
        dcache = state["draft_cache"]
        tok = tokens[rows, jnp.minimum(pos, T - 1)]
        consumed = []
        for j in range(K):
            qj = pos + j
            consumed.append(tok)
            dlogits, dcache = draft_step_fn(
                dcache, tok,
                jnp.where(active, jnp.minimum(qj, T - 1), -1))
            if j < K - 1:
                prop = jnp.argmax(dlogits, axis=-1).astype("int32")
                nxt_q = qj + 1
                tok = jnp.where(
                    nxt_q < prompt_len,
                    tokens[rows, jnp.minimum(nxt_q, T - 1)], prop)
        ctoks = jnp.stack(consumed, axis=1)  # [S, K]
        # --- verify: ONE K-wide target forward (prefill-shaped);
        # g[:, j] is the target's verified token for position q_j + 1
        logits, cache = verify_fn(state["cache"], ctoks,
                                  jnp.where(active, pos, -1))
        g = jnp.argmax(logits, axis=-1).astype("int32")  # [S, K]
        new_tokens, adv, n_emit, newly_fin = _commit_chain(
            state, ctoks, g, eos_id)
        out = dict(state)
        out.update(
            cache=cache,
            draft_cache=dcache,
            tokens=new_tokens,
            pos=pos + adv,
            active=active & ~newly_fin,
            finished=state["finished"] | newly_fin,
            n_gen=state["n_gen"] + n_emit)
        return out

    return spec_chunk


def make_self_draft_chunk_fn(verify_fn, module_fn, eos_id: int):
    """The pure per-round function the pool compiles as ``spec_chunk``
    where the draft is the target's own module
    (:class:`SelfDraftConfig`; the module docstring has the algebra).
    State leaves beside :func:`make_spec_chunk_fn`'s: ``draft`` ``[S]``
    int32 (the proposal for the token at ``pos + 1``) and ``proposals``
    ``[S, T]`` int32 (the module's argmax by the position it
    predicts)."""
    import jax.numpy as jnp

    def spec_chunk(state):
        tokens = state["tokens"]
        pos = state["pos"]
        active = state["active"]
        prompt_len = state["prompt_len"]
        S, T = tokens.shape
        rows = jnp.arange(S)

        def at(buf, q):
            return buf[rows, jnp.minimum(q, T - 1)]

        forced = (pos + 1) < prompt_len      # c1 is a stored prompt token
        ctoks = jnp.stack([at(tokens, pos),
                           jnp.where(forced, at(tokens, pos + 1),
                                     state["draft"])], axis=1)
        ts = jnp.where(active, pos, -1)
        logits, hidden, cache = verify_fn(state["cache"], ctoks, ts)
        g = jnp.argmax(logits, axis=-1).astype("int32")          # [S, 2]
        new_tokens, adv, n_emit, newly_fin = _commit_chain(
            state, ctoks, g, eos_id)
        # --- the module, on what the target just verified: row j holds
        # (h_{pos+j}, t_{pos+j+1}) and predicts the token at pos + j + 2
        nxt = jnp.stack([at(new_tokens, pos + 1), at(new_tokens, pos + 2)],
                        axis=1)
        mlogits, cache = module_fn(cache, hidden, nxt, ts)
        prop = jnp.argmax(mlogits, axis=-1).astype("int32")      # [S, 2]
        proposals = state["proposals"]
        for j in range(2):
            wrote = active if j == 0 else active & (adv >= 2)
            q = jnp.minimum(pos + j + 2, T - 1)
            proposals = proposals.at[rows, q].set(
                jnp.where(wrote, prop[:, j], proposals[rows, q]))
        out = dict(state)
        out.update(
            cache=cache,
            tokens=new_tokens,
            pos=pos + adv,
            active=active & ~newly_fin,
            finished=state["finished"] | newly_fin,
            n_gen=state["n_gen"] + n_emit,
            draft=jnp.where(active, jnp.where(adv >= 2, prop[:, 1],
                                              prop[:, 0]), state["draft"]),
            proposals=proposals)
        return out

    return spec_chunk


def dispatch_spec_chunk(pool, state):
    """Run one speculative round on ``state`` through the pool's warmed
    ``spec_chunk`` executable for its current rung pair — mirror of
    ``KVSlotPool.chunk``: the state alone (the scheduler's tick-path
    call, ``KVSlotPool.chunk_view(state, spec=True)``, also hands back
    the round's view)."""
    # hot-path: begin spec_verify (executable lookup + async dispatch of
    # the fused draft+verify round; whoever reads results does so
    # OUTSIDE this region)
    out, _ = pool.chunk_view(state, spec=True)
    # hot-path: end spec_verify
    return out
