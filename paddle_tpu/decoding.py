"""Auto-regressive decoding: greedy + beam search.

Reference: paddle/fluid/operators/beam_search_op.cc +
beam_search_decode_op.cc, driven from Python by a While loop over
LoDTensorArray (layers/control_flow.py + book test
test_machine_translation.py).  The reference's per-step op dispatch with
ragged LoD beams becomes ONE compiled `lax.fori_loop`: beams are a dense
[batch, beam] axis, the whole decode loop (including the model forward)
lives in a single XLA module — no host round-trips between steps.

Two regimes:

* ``beam_search``/``greedy_search`` — generic: the model forward is
  re-run over the full padded prefix each step (any ``logits_fn``,
  O(T^2) forwards).
* ``beam_search_cached``/``greedy_search_cached`` — KV-cached: the
  caller provides ``step_fn(cache, tokens, t) -> (logits, cache)`` that
  consumes ONE token per step and carries per-layer key/value caches in
  the scan state (O(T) per step; the beam reorder gathers cache rows by
  parent).  ``make_transformer_lm_step_fn`` builds such a step from a
  trained ``models.transformer.transformer_lm`` Program's weights —
  exact parity with the full-prefix decode
  (tests/test_seq2seq_decode.py::test_cached_decode_*).

The serving slot pool (``serving.kv_pool`` / ``serving.decode``) runs a
third: ``step_fn(cache, tokens, ts)`` with every row at its OWN position
``ts``.  Its builders — ``make_transformer_lm_pooled_step_fn``, its
K-wide twin ``make_transformer_lm_pooled_verify_fn``,
``make_hybrid_ssm_lm_pooled_step_fn``,
``make_sparse_linear_lm_pooled_step_fn`` (which also builds a chunked
prefill), ``make_routed_conv_lm_pooled_step_fn`` (layers that hold
different leaves, routed experts, counts made on the device) and
``make_delta_hybrid_lm_pooled_step_fn`` (a recurrent state that is read
before it is written), ``make_latent_sparse_lm_pooled_step_fn``
(latent leaves read through a learned per-row selection),
``make_latent_mtp_lm_pooled_step_fn`` (latent leaves read densely, K
rows a slot, under a self-drafting round) and
``make_kda_latent_lm_pooled_step_fn`` (a recurrent delta-rule state
beside latent leaves, the rule's chunkwise form in its prefill) — are
made of the same parts:

* ONE cache format, whatever the storage dtype (fp32, bf16, int8):
  ``paddle_tpu.decode_attention`` says what a K/V leaf is, appends the
  fresh rows and reads them, by one of its two implementations of one
  contract (the Pallas TPU kernel, or the XLA form); a builder allocates
  through ``decode_attention.kv_leaves`` and never spells the layout.
* ONE transformer-LM block, :func:`_lm_forward_one`, around whatever
  ``attend`` the builder hands it: the pooled step, the verify forward
  (the step at K rows, by construction) and the scalar-``t`` builder
  all run it.  The scalar-``t`` builder keeps a cache and an attention
  of its own on purpose — it is the independent reference the tests
  hold the pooled path to.
* every builder DECLARES what its ``make_cache`` builds and what else
  it offers, in ONE :class:`CacheSpec` (:func:`declare`, read back by
  :func:`spec_of`): each leaf's kind (:class:`Leaf`: sequence axis,
  stride, ring window, slot axis — the pool infers nothing from a
  shape), a chunked or a batched prefill, a verify forward and a
  drafting module, what its steps read of a slot's positions
  (:class:`PositionRead`) and the counts it keeps of routed experts.
  What a pool can do follows from the declaration: a request seated and
  prefilled in one dispatch, chunked prefill, prefix snapshots over
  recurrent leaves, self-drafting rounds.
"""
from __future__ import annotations

import functools
from typing import Callable, NamedTuple, Optional, Tuple

import numpy as np

from paddle_tpu import compile_cache
from paddle_tpu.monitor import registry as _registry

__all__ = [
    "beam_search", "greedy_search", "make_program_logits_fn",
    "beam_search_cached", "greedy_search_cached",
    "make_transformer_lm_step_fn",
    "make_transformer_lm_pooled_step_fn", "make_slot_decode_fns",
    "make_transformer_lm_pooled_verify_fn", "make_prefix_admit_fn",
    "make_hybrid_ssm_lm_pooled_step_fn",
    "make_sparse_linear_lm_pooled_step_fn",
    "make_routed_conv_lm_pooled_step_fn",
    "make_windowed_routed_lm_pooled_step_fn",
    "make_mtp_routed_lm_pooled_step_fn",
    "make_delta_hybrid_lm_pooled_step_fn",
    "make_kda_routed_lm_pooled_step_fn",
    "make_latent_sparse_lm_pooled_step_fn",
    "make_latent_mtp_lm_pooled_step_fn",
    "make_kda_latent_lm_pooled_step_fn",
    "Leaf", "PositionRead", "CacheSpec", "READ_KINDS", "declare", "spec_of",
    "normalize_kv_dtype",
    "random_transformer_lm_state",
]

#: KV-cache storage dtypes the pooled builders know (the leaves are
#: ``paddle_tpu.decode_attention``'s, in every one of them).  "int8"
#: stores K/V rows quantized (per-slot-per-position-per-head absmax
#: scales as sibling ``k_scale``/``v_scale`` leaves — see
#: paddle_tpu.quant), quantize-on-write / dequant-at-read inside the
#: jitted step.  "bf16" stores them rounded to bfloat16 as they are
#: appended; only the hybrid SSM builder takes it (the transformer-LM
#: builders take ``_LM_KV_DTYPES``: no cell has priced bf16 KV under
#: fp32 weights yet, ROADMAP Queue 1).
KV_DTYPES = ("fp32", "int8", "bf16")
_LM_KV_DTYPES = ("fp32", "int8")
#: what ``decode_attention.kv_leaves`` stores each of them as
_KV_STORAGE = {"fp32": "float32", "int8": "int8", "bf16": "bfloat16"}

WEIGHT_COPIES = _registry.REGISTRY.counter(
    "decode_weight_copies_total",
    "weight matrices a pooled transformer-LM builder copied to bfloat16 "
    "at build (one per matrix: the dtype the backend takes their products "
    "in, so no step casts them again); 0 where the step multiplies its "
    "weights as stored (the CPU, a raised matmul precision)")


def normalize_kv_dtype(kv_dtype, supported=KV_DTYPES) -> str:
    d = str(kv_dtype or "fp32").lower()
    d = {"float32": "fp32", "fp32": "fp32", "int8": "int8",
         "bf16": "bf16", "bfloat16": "bf16"}.get(d)
    if d not in supported:
        raise ValueError(
            "unsupported kv_dtype %r (supported: %s)"
            % (kv_dtype, list(supported)))
    return d


# ---------------------------------------------------------------------------
# What a builder tells the slot pool and the server: ONE declaration
# ---------------------------------------------------------------------------
class Leaf:
    """What ONE cache leaf is to the slot pool (a plain object: a tree
    of them flattens leaf for leaf like the cache it describes).

    ``seq_axis``: the leaf's sequence axis (K/V rows: covered by the
    write-before-read invariant, sliced by ``extract_kv`` /
    ``admit_prefix``, counted by ``kv_rung_bytes``), or None for a
    RECURRENT leaf (read and re-written whole each step: carried, never
    sliced, started from zero by the step itself at position 0).
    ``stride``: positions a row of the sequence axis stands for (a
    prefix of ``P`` positions is its first ``P // stride`` rows).
    ``window``: a RING leaf — ``min(rung, window)`` rows, position ``p``
    in row ``p mod window`` (``decode_attention.kv_leaves(...,
    window=W)``): carried whole, never sliced by positions nor rolled
    back.  ``slot=False``: axis 0 is NOT the slot (counts kept for the
    whole pool): no slot's row exists to snapshot, no sequence's state."""

    __slots__ = ("seq_axis", "stride", "window", "slot")

    def __init__(self, seq_axis: Optional[int] = None, stride: int = 1,
                 window: Optional[int] = None, slot: bool = True):
        self.seq_axis, self.stride = seq_axis, stride
        self.window, self.slot = window or None, slot

    def __repr__(self):
        return "Leaf(seq_axis=%r, stride=%r, window=%r, slot=%r)" % (
            self.seq_axis, self.stride, self.window, self.slot)


#: the kinds of read a builder may declare a rule for; the server maps
#: each to its two series (``serving.decode.POSITION_SERIES``)
READ_KINDS = ("kv", "sparse", "window", "latent")


class PositionRead(NamedTuple):
    """What a step reads of a slot's positions, for the server's
    counters, one a kind of read the builder has.  ``kind`` ``"kv"``:
    ``rule(ts, seq_len)`` is what a one-row step at ``ts`` reads of the
    slot's sequence leaves on a rung (a kernel's rounding, or the whole
    rung: ``decode_attention.step_positions_read``); a ``K``-row round
    counts it once a slot that advanced, at its last fresh row — unless
    ``rounds`` is False: the builder's ``K``-row verify reads masked
    over the whole pool.  A builder with no ``"kv"`` read is counted the
    whole pool a step.  The other kinds (:data:`READ_KINDS`): ``rule(n)``
    is what a query of context ``n`` reads in each of ``layers`` layers
    that select what they read."""

    kind: str
    rule: Callable
    layers: int = 1
    rounds: bool = True


class CacheSpec:
    """Everything a builder tells ``KVSlotPool`` and ``DecodeServer``
    beside ``make_cache`` itself; :func:`declare` hangs it on
    ``make_cache``, :func:`spec_of` reads it back, and what a pool can
    do follows from it.

    ``leaves``: ONE pytree shaped like the cache, a :class:`Leaf` a
    leaf.  ``prefill_fn(cache, row, tokens [C + lookahead], start, n)``
    (``.chunk_tokens`` = ``C``): a CHUNKED prefill of one slot — the
    pool compiles ``prefill`` and keeps prefixes as whole-row SNAPSHOTS
    taken at its boundaries.  ``prefill_rows_fn(cache, rows [G], tokens
    [G, C])``: a BATCHED prefill from position 0 — a turn's requests
    are seated and prefilled in one dispatch.  ``verify_fn`` /
    ``mtp_fn``: the ``K``-row forward and the module of a self-drafting
    round (``serving.speculative.make_self_draft``).  ``reads``: a
    :class:`PositionRead` a kind.  ``expert_stats(cache)``: the leaf of
    counts a step of ``n_expert`` routed experts keeps on the device."""

    def __init__(self, leaves, *, prefill_fn=None, prefill_rows_fn=None,
                 verify_fn=None, mtp_fn=None, reads=(), expert_stats=None,
                 n_expert: int = 0):
        import jax

        self.leaves = leaves
        #: the leaves' :class:`Leaf` in tree-flatten order: what the host
        #: side (``KVSlotPool``) and the traced side
        #: (:func:`make_prefix_admit_fn`) both read
        self.flat = tuple(jax.tree.leaves(leaves))
        self.prefill_fn, self.prefill_rows_fn = prefill_fn, prefill_rows_fn
        self.verify_fn, self.mtp_fn = verify_fn, mtp_fn
        self.reads = tuple(reads)
        self.expert_stats, self.n_expert = expert_stats, int(n_expert)

    def names(self, which: Callable) -> list:
        """Tree paths of the leaves ``which(leaf)`` holds of."""
        import jax

        return [jax.tree_util.keystr(path) for path, leaf in
                jax.tree_util.tree_flatten_with_path(self.leaves)[0]
                if which(leaf)]


def declare(make_cache, spec: CacheSpec):
    """Hang ``spec`` on ``make_cache`` after holding it to the cache
    ``make_cache`` builds (its shapes only: nothing is allocated);
    returns ``make_cache``.  The one place a declaration is checked."""
    import jax

    flat = spec.flat
    built = jax.eval_shape(lambda: make_cache(1, 128))
    cache = jax.tree.leaves(built)
    if (jax.tree.structure(spec.leaves) != jax.tree.structure(built)
            or not all(isinstance(leaf, Leaf) for leaf in flat)):
        raise ValueError(
            "CacheSpec.leaves declares %d leaves, the cache has %d: it is "
            "a pytree shaped like the cache make_cache builds, a "
            "decoding.Leaf a leaf" % (len(flat), len(cache)))
    if any(leaf.stride < 1 for leaf in flat):
        raise ValueError(
            "CacheSpec.leaves must declare a stride >= 1 for each of the "
            "cache's %d leaves" % len(cache))
    if any((leaf.window or 0) < 0 for leaf in flat):
        raise ValueError(
            "CacheSpec.leaves must declare a window >= 0 (None: no "
            "window) for each of the cache's %d leaves" % len(cache))
    kinds = [read.kind for read in spec.reads]
    if len(set(kinds)) < len(kinds) or not set(kinds) <= set(READ_KINDS):
        raise ValueError(
            "CacheSpec.reads declares the kinds %s: one PositionRead a "
            "kind, of %s" % (kinds, list(READ_KINDS)))
    make_cache.cache_spec = spec
    return make_cache


def spec_of(make_cache) -> CacheSpec:
    """The :class:`CacheSpec` ``make_cache`` was declared with: the ONE
    reader.  A ``make_cache`` that declares nothing is an error."""
    spec = getattr(make_cache, "cache_spec", None)
    if spec is None:
        raise ValueError(
            "make_cache declares nothing: call decoding.declare("
            "make_cache, CacheSpec(leaves)) with a pytree shaped like the "
            "cache it builds, holding each leaf's decoding.Leaf (its "
            "sequence axis, None for a leaf with none); the slot pool "
            "infers nothing from a shape")
    return spec


def random_transformer_lm_state(rng, vocab, d_model, n_layer, n_head,
                                d_inner, max_pos, name="lm"):
    """A randomly initialized transformer-LM weight dict with exactly
    the keys the ``make_transformer_lm_*_step_fn`` builders read —
    the one place the key/shape schema lives for benches and tests."""
    w = {name + "_word_emb": rng.randn(vocab, d_model) * 0.1,
         name + "_pos_emb": rng.randn(max_pos, d_model) * 0.1,
         name + "_head_w": rng.randn(d_model, vocab) * 0.1,
         name + "_head_b": np.zeros(vocab)}
    for i in range(n_layer):
        p = "%s_dec_%d" % (name, i)
        for nm, shp in (("_att_q", (d_model, d_model)),
                        ("_att_k", (d_model, d_model)),
                        ("_att_v", (d_model, d_model)),
                        ("_att_out", (d_model, d_model)),
                        ("_ffn_fc0", (d_model, d_inner)),
                        ("_ffn_fc1", (d_inner, d_model))):
            w[p + nm + "_w"] = rng.randn(*shp) * 0.1
            w[p + nm + "_b"] = np.zeros(shp[1])
        for ln in ("_ln1", "_ln2"):
            w[p + ln + "_scale"] = np.ones(d_model)
            w[p + ln + "_bias"] = np.zeros(d_model)
    return {k: np.asarray(v, "float32") for k, v in w.items()}


def make_program_logits_fn(program, state, feed_names, logits_name):
    """Lower an inference program into ``logits_fn(feeds_dict) -> logits``
    for use inside the decode loop.  ``state``: persistable name->array
    (trained params)."""
    from paddle_tpu.core import lowering

    block = program.global_block()
    fn = lowering.lower_block(block, feed_names, [logits_name], [])

    def logits_fn(feeds):
        fetches, _ = fn(dict(state), feeds)
        return fetches[0]

    return logits_fn


def _beam_core(step, state0, B, K, bos_id, eos_id, max_len, length_penalty):
    """Shared beam bookkeeping for the full-prefix and KV-cached paths.

    ``step(state, tokens_flat [B*K, max_len], t) -> (logits [B*K, V],
    state)`` returns the next-token logits for loop position ``t``
    (i.e. conditioned on the prefix through ``t - 1``); ``state`` is an
    arbitrary pytree (None for stateless full-prefix, per-layer KV
    caches for the cached path) whose leaves carry a leading B*K axis —
    after each selection its rows are gathered by the winning parents.
    """
    import jax
    import jax.numpy as jnp

    NEG = -1e9
    tokens0 = jnp.full((B, K, max_len), eos_id, dtype="int32")
    tokens0 = tokens0.at[:, :, 0].set(bos_id)
    scores0 = jnp.where(jnp.arange(K)[None, :] == 0, 0.0, NEG) * jnp.ones((B, 1))
    finished0 = jnp.zeros((B, K), dtype=bool)

    def body(t, carry):
        tokens, scores, finished, st = carry
        flat = tokens.reshape(B * K, max_len)
        logits, st = step(st, flat, t)
        logp = jax.nn.log_softmax(logits, axis=-1).reshape(B, K, -1)
        V = logp.shape[-1]
        # finished beams may only extend with EOS at zero cost
        eos_only = jnp.full((V,), NEG).at[eos_id].set(0.0)
        logp = jnp.where(finished[..., None], eos_only[None, None, :], logp)
        total = scores[..., None] + logp  # [B, K, V]
        top_scores, top_idx = jax.lax.top_k(total.reshape(B, K * V), K)
        parent = top_idx // V  # [B, K]
        tok = (top_idx % V).astype("int32")
        rows = (jnp.arange(B)[:, None] * K + parent).reshape(-1)
        tokens = jnp.take_along_axis(tokens, parent[..., None], axis=1)
        tokens = tokens.at[:, :, t].set(tok)
        finished = jnp.take_along_axis(finished, parent, axis=1) | (tok == eos_id)
        st = jax.tree.map(lambda c: c[rows], st)
        return tokens, top_scores, finished, st

    tokens, scores, finished, _ = jax.lax.fori_loop(
        1, max_len, body, (tokens0, scores0, finished0, state0)
    )
    if length_penalty > 0.0:
        lengths = jnp.sum((tokens != eos_id).astype("float32"), axis=-1) + 1.0
        scores = scores / (lengths ** length_penalty)
        order = jnp.argsort(-scores, axis=-1)
        tokens = jnp.take_along_axis(tokens, order[..., None], axis=1)
        scores = jnp.take_along_axis(scores, order, axis=1)
    return tokens, scores


def beam_search(
    logits_fn: Callable,
    src: np.ndarray,
    bos_id: int,
    eos_id: int,
    beam_size: int = 4,
    max_len: int = 16,
    src_feed_name: str = "src",
    tgt_feed_name: str = "tgt",
    length_penalty: float = 0.0,
    extra_feeds: Optional[dict] = None,
):
    """Returns (tokens [B, beam, max_len], scores [B, beam]) sorted best
    first.  ``logits_fn`` maps {src, tgt [N, max_len]} -> [N, max_len, V].
    """
    import jax.numpy as jnp

    src = jnp.asarray(src)
    B, K = src.shape[0], beam_size
    src_tiled = jnp.repeat(src, K, axis=0)  # [B*K, S]
    extra_tiled = {
        k: jnp.repeat(jnp.asarray(v), K, axis=0) for k, v in (extra_feeds or {}).items()
    }

    def step(state, flat, t):
        feeds = {src_feed_name: src_tiled, tgt_feed_name: flat}
        feeds.update(extra_tiled)
        logits = logits_fn(feeds)  # [B*K, T, V]
        return logits[:, t - 1, :], state

    return _beam_core(step, None, B, K, bos_id, eos_id, max_len, length_penalty)


def greedy_search(logits_fn, src, bos_id, eos_id, max_len=16, **kwargs):
    """Greedy = beam 1; returns (tokens [B, max_len], scores [B])."""
    tokens, scores = beam_search(
        logits_fn, src, bos_id, eos_id, beam_size=1, max_len=max_len, **kwargs
    )
    return tokens[:, 0], scores[:, 0]


# ---------------------------------------------------------------------------
# KV-cached decoding
# ---------------------------------------------------------------------------
def beam_search_cached(
    step_fn: Callable,
    init_cache,
    batch: int,
    bos_id: int,
    eos_id: int,
    beam_size: int = 4,
    max_len: int = 16,
    length_penalty: float = 0.0,
):
    """Beam search with a KV cache carried through the compiled loop.

    ``step_fn(cache, tokens [N] int32, t) -> (logits [N, V], cache)``:
    consume the token at position ``t`` and return logits for position
    ``t + 1``; cache leaves carry a leading ``N = batch * beam`` axis
    so the beam reorder can gather rows by parent.  ``init_cache``: the
    zeroed cache pytree (leaves ``[N, ...]``).  One lax.fori_loop, no
    host round-trips; each step is O(prefix) instead of the
    full-prefix re-run's O(prefix^2)."""

    def step(cache, flat, t):
        return step_fn(cache, flat[:, t - 1], t - 1)

    return _beam_core(step, init_cache, batch, beam_size, bos_id, eos_id,
                      max_len, length_penalty)


def greedy_search_cached(step_fn, init_cache, batch, bos_id, eos_id,
                         max_len=16, **kwargs):
    """Greedy = beam 1 on the cached path; returns ([B, max_len], [B])."""
    tokens, scores = beam_search_cached(
        step_fn, init_cache, batch, bos_id, eos_id, beam_size=1,
        max_len=max_len, **kwargs
    )
    return tokens[:, 0], scores[:, 0]


def make_transformer_lm_step_fn(
    state,
    vocab_size: int,
    d_model: int,
    n_layer: int,
    n_head: int,
    d_inner: int,
    max_len: int,
    name: str = "lm",
):
    """Build (step_fn, make_cache) for KV-cached decoding from a trained
    ``models.transformer.transformer_lm`` Program's weights.

    ``state``: persistable name -> array (the same dict
    ``make_program_logits_fn`` takes).  Mirrors the Program math exactly
    — post-LN blocks (eps 1e-5), exact (non-tanh) gelu FFN, per-head
    scaled dot product — on an incrementally updated ``[N, H, T, Dh]``
    key/value cache per layer, so cached decode == full-prefix decode
    bit-for-tolerance (parity-tested).

    All rows sit at the same scalar position ``t``.  The cache and its
    attention are this builder's own (one ``dynamic_update_index_in_dim``
    row per lane, a softmax masked over the whole T axis) and share
    nothing with ``paddle_tpu.decode_attention``: the tests hold the
    slot-pool path to this one as its independent reference.  The block
    around the attention is the pooled steps' (:func:`_lm_forward_one`).

    Returns ``(step_fn, make_cache)`` where ``make_cache(n_rows)``
    allocates the zeroed cache for ``n_rows = batch * beam`` lanes.
    """
    import jax
    import jax.numpy as jnp

    d_head = d_model // n_head
    W = {k: jnp.asarray(v) for k, v in state.items()}

    def make_cache(n_rows: int):
        return [
            {
                "k": jnp.zeros((n_rows, n_head, max_len, d_head), "float32"),
                "v": jnp.zeros((n_rows, n_head, max_len, d_head), "float32"),
            }
            for _ in range(n_layer)
        ]

    scale = 1.0 / float(np.sqrt(d_head))

    def step_fn(cache, tokens, t):
        # tokens [N] int32; t: position being consumed
        n = tokens.shape[0]
        pos_ok = (jnp.arange(cache[0]["k"].shape[2]) <= t)[None, None, :]

        def attend(q, k, v, kv):
            q, k, v = (a.reshape(n, n_head, d_head) for a in (q, k, v))
            kc = jax.lax.dynamic_update_index_in_dim(kv["k"], k, t, axis=2)
            vc = jax.lax.dynamic_update_index_in_dim(kv["v"], v, t, axis=2)
            scores = jnp.einsum("nhd,nhtd->nht", q, kc) * scale
            w = jax.nn.softmax(jnp.where(pos_ok, scores, -1e9), axis=-1)
            ctx = jnp.einsum("nht,nhtd->nhd", w, vc).reshape(n, d_model)
            return ctx, {"k": kc, "v": vc}

        x = W[name + "_word_emb"][tokens] + W[name + "_pos_emb"][t]
        return _lm_forward_one(W, name, cache, x, n_layer, attend)

    return step_fn, make_cache


def _lm_forward_one(W, name, cache, x, n_layer, attend):
    """The transformer-LM forward over fresh rows ``x`` [..., d_model]:
    ``n_layer`` post-LN blocks (q/k/v, attention, out-projection, LN,
    exact-GELU FFN, LN) and the head — the ONE definition every step
    builder of this module runs, whatever its cache.

    ``attend(q, k, v, kv) -> (ctx, kv)`` is the builder's: it appends
    the rows' keys and values to layer cache ``kv``, attends each row to
    what it may see and returns the context beside the updated layer.
    Returns ``(logits [..., V], new_cache)``."""
    new_cache = []
    for i in range(n_layer):
        x, kv = _lm_block(W, "%s_dec_%d" % (name, i), cache[i], x, attend)
        new_cache.append(kv)
    return _fc(W, x, name + "_head"), new_cache


#: the parameters of one block, by their names after the layer's prefix
_BLOCK_PARAMS = tuple(
    m + s for m in ("_att_q", "_att_k", "_att_v", "_att_out", "_ffn_fc0",
                    "_ffn_fc1") for s in ("_w", "_b")) + tuple(
    ln + s for ln in ("_ln1", "_ln2") for s in ("_scale", "_bias"))


def _lm_block(W, p, kv, x, attend):
    """One post-LN block of :func:`_lm_forward_one` over the parameters
    ``W[p + ...]`` (:data:`_BLOCK_PARAMS`): returns the rows out and
    whatever ``attend`` returned beside the context."""
    import jax

    ctx, kv = attend(_fc(W, x, p + "_att_q"), _fc(W, x, p + "_att_k"),
                     _fc(W, x, p + "_att_v"), kv)
    x = _ln(W, x + _fc(W, ctx, p + "_att_out"), p + "_ln1")
    h = jax.nn.gelu(_fc(W, x, p + "_ffn_fc0"), approximate=False)
    return _ln(W, x + _fc(W, h, p + "_ffn_fc1"), p + "_ln2"), kv


def _fc(W, x, pname):
    """``x @ w + b`` in the products ``w`` is stored for: a bf16 matrix
    (a copy :func:`_pooled_lm_parts` made) takes ``x`` rounded to bf16
    and accumulates in fp32 — what a TPU makes of the fp32 product at
    its default precision, minus the cast of ``w``; any other matrix is
    multiplied as the backend multiplies its dtype."""
    import jax.numpy as jnp

    w = W[pname + "_w"]
    if w.dtype == jnp.bfloat16:
        return jnp.dot(x.astype(jnp.bfloat16), w,
                       preferred_element_type=jnp.float32) + W[pname + "_b"]
    return x @ w + W[pname + "_b"]


def _ln(W, x, pname):
    import jax.numpy as jnp

    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.var(x, axis=-1, keepdims=True)
    y = (x - mean) / jnp.sqrt(var + 1e-5)
    return y * W[pname + "_scale"] + W[pname + "_bias"]


def _products_are_bf16(backend: str, precision) -> bool:
    """Whether ``backend`` rounds both operands of an fp32 matmul to
    bf16 under the process's ``jax_default_matmul_precision``: a TPU at
    the default does (one MXU pass, fp32 accumulation); the CPU, and a
    TPU asked for anything higher, do not."""
    return backend == "tpu" and precision in (None, "default")


def _multiplied_matrices(name, n_layer):
    """The keys of every matrix :func:`_lm_forward_one` multiplies (its
    :func:`_fc` calls): six a layer and the head.  The embedding tables
    are gathered, never multiplied."""
    return ["%s_dec_%d%s_w" % (name, i, m) for i in range(n_layer)
            for m in ("_att_q", "_att_k", "_att_v", "_att_out",
                      "_ffn_fc0", "_ffn_fc1")] + [name + "_head_w"]


def _stacked_layers(W, name, n_layer):
    """``{param: [n_layer, ...]}``: each of a block's parameters
    (:data:`_BLOCK_PARAMS`) stacked over the layers, as ``W`` holds them
    (a bf16 copy stays bf16) — what a ``lax.scan`` over the blocks
    takes, made by one jitted call.  A second copy of the matrices for
    the builder's life (0.17 GB at ``gpt1_117m`` on a TPU, beside the 12
    GB pool)."""
    import jax
    import jax.numpy as jnp

    return jax.jit(lambda by_param: jax.tree.map(
        lambda *per_layer: jnp.stack(per_layer), *by_param))(
            [{param: W["%s_dec_%d%s" % (name, i, param)]
              for param in _BLOCK_PARAMS} for i in range(n_layer)])


def _ragged_kv_reads(kv: str):
    """The ``"kv"`` read of a builder with no rule of its own (the
    transformer LM; the sparse-linear and latent-sparse builders, whose
    selected reads have kinds of their own), by its leaves' storage
    dtype: float32 as ``decode_attention.ragged_positions_read`` rounds
    whatever the backend, a ``K``-row round masked over the whole pool
    (what the server counted for every fp32 pool without a rule until
    PR 58, kept to the count); any other storage no rule: the whole
    pool a step."""
    from paddle_tpu.decode_attention import ragged_positions_read

    if kv != "float32":
        return ()
    return (PositionRead("kv", ragged_positions_read, rounds=False),)


def _dense_latent_kv_reads(d, kv: str):
    """The ``"kv"`` read of the dense latent builder over leaves of
    storage dtype ``kv`` and ``d``'s widths: over bf16 leaves the read's
    own host mirror, ``decode_attention.dense_latent_positions_read``
    (the kernel's whole key blocks up to a slot's own last fresh row, or
    the XLA form's up to the longest context, whichever lowering is in
    force); float32 leaves stay :func:`_ragged_kv_reads`'s."""
    from paddle_tpu.decode_attention import dense_latent_positions_read

    if kv != "bfloat16":
        return _ragged_kv_reads(kv)
    return (PositionRead("kv", functools.partial(
        dense_latent_positions_read, lanes=d.d_latent, dtype=kv,
        n_head=d.n_head, d_value=d.d_c)),)


def _step_kv_read(d, kv: str) -> PositionRead:
    """The ``"kv"`` read of a builder whose steps attend through
    ``decode_attention.make_decode_attention`` over leaves of storage
    dtype ``kv`` and ``d``'s head grouping: that chooser's host mirror,
    ``decode_attention.step_positions_read``."""
    from paddle_tpu.decode_attention import step_positions_read

    return PositionRead("kv", functools.partial(
        step_positions_read, width=d.d_kv, dtype=kv, n_head=d.n_head,
        n_kv_head=d.n_kv_head))


def _pooled_lm_parts(state, d_model, n_layer, n_head, name, kv_dtype):
    """What the pooled step and the K-wide verify forward of one model
    share: ``forward(cache, x, ts)`` — :func:`_lm_forward_one` over the
    fresh rows ``x`` ([S, d_model] at ``ts``, or [S, K, d_model] at
    ``ts .. ts + K - 1``) with ``decode_attention``'s append and read —
    ``prefill(cache, rows, x, layers)`` — the same block
    (:func:`_lm_block`) over ``x`` [G, C, d_model] at positions ``0 .. C
    - 1`` of the slots ``rows``, which start their sequences there
    (``decode_attention.fresh_prompt_attention``), scanned over
    ``layers`` (the blocks' parameters stacked:
    :func:`_stacked_layers`); returns the cache — the weights, and
    ``make_cache``.

    The weights are held in the dtype their products are taken in.
    Where the backend would round an fp32 matmul operand to bf16 anyway
    (:func:`_products_are_bf16`: a TPU at the default matmul precision,
    read HERE, at build), every fp32 matrix the forward multiplies — q,
    k, v, out and both FFN matrices of each layer, and the head — is
    copied to bf16 ONCE, by one jitted cast where the weights live, and
    the step closes over the copies (:func:`_fc` multiplies a matrix as
    stored).  XLA:TPU otherwise makes the same copies inside every
    ``chunk`` call: at ``gpt1_117m`` 73 matrices, 0.47 GB read and 0.23
    GB written per call, 15% of the chip's busy time at 10 live slots.
    The copies cost 2 bytes a matrix element of HBM for the builder's
    life (0.23 GB there) beside the caller's fp32 ``state``, which this
    function never changes.  Biases, LayerNorm vectors and both
    embedding tables stay as given; so does every matrix that is not
    fp32, and everything on a backend that multiplies fp32 as fp32 — no
    copy, ``decode_weight_copies_total`` unmoved."""
    import jax
    import jax.numpy as jnp

    from paddle_tpu.decode_attention import (KV_SEQ_AXIS,
                                             fresh_prompt_attention,
                                             kv_leaves,
                                             make_decode_attention,
                                             write_prompt_rows)

    kv = _KV_STORAGE[normalize_kv_dtype(kv_dtype, _LM_KV_DTYPES)]
    d_head = d_model // n_head
    W = {k: jnp.asarray(v) for k, v in state.items()}
    if _products_are_bf16(jax.default_backend(),
                          jax.config.jax_default_matmul_precision):
        copied = [k for k in _multiplied_matrices(name, n_layer)
                  if W[k].dtype == jnp.float32]
        # each copy where its original lives, whatever its sharding
        with compile_cache.build("weight_copies", rest="first_run",
                                 matrices=len(copied)):
            W.update(zip(copied, jax.jit(
                lambda ws: [w.astype(jnp.bfloat16) for w in ws],
                out_shardings=[W[k].sharding for k in copied])(
                    [W[k] for k in copied])))
        WEIGHT_COPIES.inc(len(copied))
    scale = 1.0 / float(np.sqrt(d_head))

    def make_cache(n_rows: int, seq_len: int):
        return [kv_leaves(n_rows, seq_len, n_head, d_head, kv)
                for _ in range(n_layer)]

    def forward(cache, x, ts):
        T = cache[0]["k"].shape[KV_SEQ_AXIS]
        attend = make_decode_attention(
            jnp.minimum(ts, T - 1), cache[0], n_head=n_head,
            n_kv_head=n_head, scale=scale)
        return _lm_forward_one(W, name, cache, x, n_layer, attend)

    def prefill(cache, rows, x, layers):
        def block(x, layer):
            return _lm_block(
                layer, "", None, x, lambda q, k, v, _: fresh_prompt_attention(
                    q, k, v, cache[0], n_head=n_head, n_kv_head=n_head,
                    scale=scale))

        # the layers as ONE scanned body (a program of this many
        # unrolled would be as large as the step's own a width, and as
        # slow to build and load); no head: no logits are made
        _, stored = jax.lax.scan(block, x, layers)
        return [write_prompt_rows(
            kv, {leaf: rows_[i] for leaf, rows_ in stored.items()}, rows)
            for i, kv in enumerate(cache)]

    return forward, prefill, W, make_cache


def make_transformer_lm_pooled_step_fn(
    state,
    vocab_size: int,
    d_model: int,
    n_layer: int,
    n_head: int,
    d_inner: int,
    name: str = "lm",
    kv_dtype: str = "fp32",
):
    """The slot-pool variant of :func:`make_transformer_lm_step_fn`.

    Continuous batching decodes a POOL of sequences that are each at a
    DIFFERENT position (a request admitted mid-flight starts its prefill
    while its neighbors are deep into generation), so the step consumes
    per-row positions: ``step_fn(cache, tokens [N] int32, ts [N] int32)
    -> (logits [N, V], cache)`` where row ``i`` consumes ``tokens[i]``
    at position ``ts[i]`` (cache row ``i`` updated at ``ts[i]``; its
    attention reads positions ``<= ts[i]``).  ``ts[i] < 0`` marks an
    IDLE row: nothing of its cache row is read or written and its
    logits are garbage — ``make_slot_decode_fns`` passes ``-1`` for
    every slot that is not active, and ``verify_fn``, the draft step
    and any stand-in step fn follow the same contract.

    The cache is ``paddle_tpu.decode_attention``'s format in
    ``kv_dtype`` — per layer ``k``, ``v`` ``[N, T, d_model]`` leaves
    (``"int8"``: int8 codes beside per-(slot, position, head) fp32
    scales ``k_scale`` / ``v_scale`` ``[N, T, n_head]``, quantize-on-
    write / dequant-at-read, roughly quartering per-slot KV bytes so a
    fixed HBM budget holds ~2x+ the concurrent sequences) — and that
    module appends and reads it: each new K/V row is written in place
    (O(row), the state is donated); on a TPU the fp32 step reads each
    row's live positions only, in blocks of
    ``decode_attention.kv_read_block(T)`` and its last block as far as
    it is live (the Pallas kernel; ``kv_positions_read``), every other
    step reads masked over the whole rung (the XLA form).

    The cache T axis is read from the cache arrays themselves, so one
    step fn serves every length rung of the slot pool's bucket ladder:
    ``make_cache(n_rows, seq_len)`` allocates the zeroed pytree for one
    (slot-rung, length-rung) pair, and its :class:`CacheSpec` declares
    every leaf's sequence axis, so ``extract_kv`` / ``admit_prefix``
    carry both dtypes unchanged and prefix caching and speculative
    decode compose.  What a step reads is declared by
    :func:`_ragged_kv_reads`.  The block around
    the attention is the scalar-t builder's (:func:`_lm_forward_one`) —
    with all rows at the same position the two agree to rounding
    (parity-tested in tests/test_seq2seq_decode.py).

    The pool relies on a write-before-read invariant instead of cache
    zeroing on slot reuse: a sequence at position ``ts`` has itself
    written every cache position ``<= ts`` (prefill consumes each prompt
    token through the same step), and positions ``> ts`` are never read
    — stale rows from a previous occupant are never read.  The invariant
    covers leaves WITH a sequence axis, which is every leaf of this
    builder; recurrent leaves (no sequence axis, read and re-written
    whole each step) are outside it — see
    :func:`make_hybrid_ssm_lm_pooled_step_fn`.

    A prompt need not walk that step.  The spec's ``prefill_rows_fn(
    cache, rows [G] int32, tokens [G, C] int32) -> cache`` is the
    builder's BATCHED PREFILL: ``tokens[g, j]`` is fed at position ``j``
    of slot ``rows[g]`` for every ``j`` in one ``C``-wide forward of the
    same block (every ``rows[g]`` a slot: a caller with fewer seats than
    ``G`` repeats one), the K/V rows it appends are the rows the step would have
    appended (``decode_attention.fresh_prompt_attention``: the slots
    START their sequences, so no row of the leaves is read), the blocks
    run as one scanned body over a stacked copy of their parameters
    made at build, and no logits are made.  ``G`` and ``C`` are whatever the caller traces it
    at; positions past a prompt's end may be fed any token (the
    invariant above covers what lands there).  A pool that finds the
    declaration seats a request and feeds it all of its prompt but the
    last token in ONE dispatch (``KVSlotPool.seat_prefill``), and the
    slot's first step eats that last token.
    """
    import jax
    import jax.numpy as jnp

    from paddle_tpu.decode_attention import KV_SEQ_AXIS

    forward, prefill, W, make_cache = _pooled_lm_parts(
        state, d_model, n_layer, n_head, name, kv_dtype)

    def step_fn(cache, tokens, ts):
        x = (W[name + "_word_emb"][tokens]
             + W[name + "_pos_emb"][jnp.maximum(ts, 0)])
        return forward(cache, x, ts)

    layers = _stacked_layers(W, name, n_layer)

    def prefill_rows_fn(cache, rows, tokens):
        x = (W[name + "_word_emb"][tokens]
             + W[name + "_pos_emb"][jnp.arange(tokens.shape[1])][None])
        return prefill(cache, rows, x, layers)

    declare(make_cache, CacheSpec(
        jax.tree.map(lambda _: Leaf(KV_SEQ_AXIS),
                     jax.eval_shape(lambda: make_cache(1, 1))),
        prefill_rows_fn=prefill_rows_fn, reads=_ragged_kv_reads(
            _KV_STORAGE[normalize_kv_dtype(kv_dtype, _LM_KV_DTYPES)])))
    return step_fn, make_cache


def make_hybrid_ssm_lm_pooled_step_fn(state, cfg, name: str = "lm",
                                      kv_dtype: str = "bf16",
                                      ssm_state_dtype: str = "float32"):
    """The slot-pooled step of a hybrid SSM + attention decoder
    (``model_type: falcon_h1``: in every block a Mamba-2 mixer beside
    grouped-query attention on the same normed input, then SwiGLU; the
    parts and the equations are ``paddle_tpu.hybrid_ssm``).

    Same contract as :func:`make_transformer_lm_pooled_step_fn`:
    ``step_fn(cache, tokens [N] int32, ts [N] int32) -> (logits [N, V]
    fp32, cache)`` with ``ts[i] < 0`` an idle row, and ``make_cache(
    n_rows, seq_len)``.  ``state``: weights under
    ``hybrid_ssm.param_shapes(cfg)``, used in the dtype they are given
    (bf16 as stored: no per-step conversion); ``cfg``: the published
    config keys (``hybrid_ssm.dims``).

    One layer's cache is two kinds of leaf, and the builder DECLARES
    which is which (:class:`CacheSpec`'s ``leaves``: a pytree shaped
    like the cache holding each leaf's :class:`Leaf`), so the slot pool
    never guesses from a shape — a length rung equal to ``d_state`` or
    ``head_dim`` is an ordinary rung:

    * ``k``, ``v`` ``[N, T, n_kv_head * head_dim]`` in ``kv_dtype``
      (``k`` after rotary): appended in place at ``ts``, read
      ``0..ts`` (``decode_attention``'s format, through its
      ``make_decode_attention``: grouped heads, so the XLA form).
      The write-before-read invariant covers these: a reused slot's
      stale positions are never read.
    * ``ssm`` ``[N, heads, d_head, d_state]`` in ``ssm_state_dtype`` and
      ``conv`` ``[N, d_conv - 1, d_xbc]`` fp32: RECURRENT state, read
      and re-written whole every step, NOT covered by that invariant.
      The step reads zeros for a row at ``ts == 0``
      (``hybrid_ssm.starts_fresh``): admit, release + re-admit and a
      deadline abort all restart a slot at position 0, so none of them
      can forget the reset, and it costs a select on a load the update
      makes anyway.  An idle row's state is kept as it was.

    Position 0 is where a sequence starts HERE: this builder has no
    chunked prefill, so nothing can stop at a boundary to copy the
    state a prefix of positions would need (a builder that has one gets
    prefix snapshots: :func:`make_sparse_linear_lm_pooled_step_fn`), and
    no step can roll ``pos`` back out of a state (a rejected speculative
    round); ``KVSlotPool`` refuses ``prefix=True`` and ``speculative=``
    over this builder at construction.
    """
    import jax.numpy as jnp

    from paddle_tpu import hybrid_ssm as hs
    from paddle_tpu.decode_attention import kv_leaves, make_decode_attention

    d = hs.dims(cfg)
    kv = _KV_STORAGE[normalize_kv_dtype(kv_dtype, ("fp32", "bf16"))]
    ssm_dt = jnp.dtype(ssm_state_dtype)
    W = {k: jnp.asarray(v) for k, v in state.items()}
    scale = 1.0 / float(np.sqrt(d.head_dim))

    def make_cache(n_rows: int, seq_len: int):
        return [
            {
                **kv_leaves(n_rows, seq_len, d.n_kv_head, d.head_dim, kv),
                "ssm": jnp.zeros((n_rows, d.ssm_heads, d.ssm_head_dim,
                                  d.d_state), ssm_dt),
                "conv": jnp.zeros((n_rows, d.d_conv - 1, d.d_xbc),
                                  jnp.float32),
            }
            for _ in range(d.n_layer)
        ]

    declare(make_cache, CacheSpec(
        [{"k": Leaf(1), "v": Leaf(1), "ssm": Leaf(), "conv": Leaf()}
         for _ in range(d.n_layer)],
        reads=[_step_kv_read(d, kv)]))

    def step_fn(cache, tokens, ts):
        n = tokens.shape[0]
        T = cache[0]["k"].shape[1]
        ts = jnp.minimum(ts, T - 1)     # idle rows stay < 0
        pos = jnp.maximum(ts, 0)
        attend = make_decode_attention(
            ts, cache[0], n_head=d.n_head, n_kv_head=d.n_kv_head,
            scale=scale)
        h = W[name + "_emb"][tokens].astype(jnp.float32) \
            * d.embedding_multiplier
        new_cache = []
        for i in range(d.n_layer):
            p = "%s_l%d_" % (name, i)
            c = cache[i]
            u = hs.rms_norm(h, W[p + "norm1"], d.eps)
            mix, ssm, conv = hs.mamba2_step(
                d.ssm_in_multiplier * u, W, p, c["ssm"], c["conv"], ts, d)
            xa = d.attention_in_multiplier * u
            q = hs.linear(xa, W[p + "attn_q"]).reshape(
                n, d.n_head, d.head_dim)
            k = (d.key_multiplier * hs.linear(xa, W[p + "attn_k"])).reshape(
                n, d.n_kv_head, d.head_dim)
            ctx, kvs = attend(
                hs.rotary(q, pos, d.rope_theta).reshape(n, -1),
                hs.rotary(k, pos, d.rope_theta).reshape(n, -1),
                hs.linear(xa, W[p + "attn_v"]),
                {"k": c["k"], "v": c["v"]})
            new_cache.append({**kvs, "ssm": ssm, "conv": conv})
            h = (h + d.ssm_out_multiplier * mix
                 + d.attention_out_multiplier * hs.linear(
                     ctx, W[p + "attn_o"]))
            h = h + hs.swiglu(
                hs.rms_norm(h, W[p + "norm2"], d.eps), W[p + "mlp_gate"],
                W[p + "mlp_up"], W[p + "mlp_down"], *d.mlp_multipliers)
        logits = hs.linear(hs.rms_norm(h, W[name + "_final_norm"], d.eps),
                           W[name + "_head"]) * d.lm_head_multiplier
        return logits, new_cache

    return step_fn, make_cache


def make_sparse_linear_lm_pooled_step_fn(state, cfg, name: str = "lm",
                                         kv_dtype: str = "bf16",
                                         state_dtype: str = "float32",
                                         prefill_tokens: int = 512):
    """The slot-pooled step AND the chunked prefill of a decoder that
    mixes lightning linear-attention layers with InfLLM-v2 block-sparse
    attention layers (``model_type: minicpm_sala``; the parts and the
    equations are ``paddle_tpu.sparse_linear_lm``).

    Returns ``(step_fn, make_cache, prefill_fn)``.  ``step_fn`` and
    ``make_cache`` keep the contract of the two builders above
    (``step_fn(cache, tokens [N] int32, ts [N] int32) -> (logits [N, V]
    fp32, cache)``, ``ts[i] < 0`` an idle row).  ``state``: weights
    under ``sparse_linear_lm.param_shapes(cfg)``, used in the dtype
    they are given; ``cfg``: the published config keys plus
    ``sparse_config`` (``sparse_linear_lm.dims``).

    The spec (:class:`CacheSpec`) declares three kinds of leaf:

    * per sparse layer ``k``, ``v`` ``[N, T, n_kv_head * head_dim]`` in
      ``kv_dtype`` (``decode_attention``'s format: appended in place at
      ``ts``, covered by write-before-read) ...
    * ... and ``ck`` ``[N, T // kernel_stride, n_kv_head * head_dim]``,
      the compressed keys the selection scores: a sequence leaf that
      advances ONE ROW PER ``kernel_stride`` POSITIONS (its
      :class:`Leaf`'s ``stride``); row ``j`` is written by the step that
      appends position ``kernel_stride * j + kernel_size - 1``, read only by
      queries past it, so write-before-read covers it too;
    * per lightning layer ``s`` ``[N, heads, d, d]`` in ``state_dtype``:
      RECURRENT (no sequence axis), read as zero for a row at ``ts == 0``
      (``hybrid_ssm.starts_fresh``), kept for an idle row.

    The sparse read is ``decode_attention.grouped_block_decode_attention``
    over the blocks ``sparse_linear_lm.select_blocks`` names: a row reads
    at most ``n_sel * block_size`` positions however long the rung.

    ``prefill_fn(cache, row, tokens [C], start, n_valid) -> cache`` (``C
    = prefill_tokens``, also ``prefill_fn.chunk_tokens``) feeds slot
    ``row`` its prompt tokens at positions ``start .. start + n_valid -
    1`` through every layer in ONE call and no logits: lightning layers
    by ``lightning_chunk`` from the slot's state (zero at ``start ==
    0``), leaving it at ``start + n_valid``; sparse layers append the
    chunk's K/V rows and the ``ck`` rows it completes, and every query
    attends by the step's rule (``chunk_attend``).  ``start`` must be a
    multiple of ``kernel_stride``.  It equals ``n_valid`` steps leaf for
    leaf (tests/test_sparse_linear_lm.py).  The spec's ``prefill_fn``
    declares it to the pool, which compiles it as one more executable a
    rung pair and, because a prefill can stop at a boundary, may keep
    prefix SNAPSHOTS over these recurrent leaves (``KVSlotPool``).  Its
    ``"sparse"`` read is what the rule lets a sparse query of context
    ``n`` read in each sparse layer, for the server's counters.
    """
    import jax
    import jax.numpy as jnp

    from paddle_tpu import sparse_linear_lm as sl
    from paddle_tpu.decode_attention import (append_rows,
                                             grouped_block_decode_attention,
                                             kv_leaves)

    d = sl.dims(cfg)
    kv = _KV_STORAGE[normalize_kv_dtype(kv_dtype, ("fp32", "bf16"))]
    s_dt = jnp.dtype(state_dtype)
    W = {k: jnp.asarray(v) for k, v in state.items()}
    C = int(prefill_tokens)
    if C % d.kernel_stride or C % d.block_size:
        raise ValueError("prefill_tokens must be a multiple of "
                         "kernel_stride and block_size")
    G, R = d.n_kv_head, d.n_head // d.n_kv_head
    scale = 1.0 / float(np.sqrt(d.head_dim))
    sparse_at = [i for i, kind in enumerate(d.kinds) if kind == sl.SPARSE]

    def make_cache(n_rows: int, seq_len: int):
        if seq_len % d.block_size:
            raise ValueError("a length rung must be a multiple of "
                             "block_size %d" % d.block_size)
        out = []
        for kind in d.kinds:
            if kind == sl.LIGHTNING:
                out.append({"s": jnp.zeros(
                    (n_rows, d.l_heads, d.l_head_dim, d.l_head_dim), s_dt)})
            else:
                out.append({
                    **kv_leaves(n_rows, seq_len, G, d.head_dim, kv),
                    "ck": jnp.zeros((n_rows, seq_len // d.kernel_stride,
                                     d.d_kv), kv)})
        return out

    leaves = [
        {"s": Leaf()} if kind == sl.LIGHTNING else
        {"k": Leaf(1), "v": Leaf(1), "ck": Leaf(1, stride=d.kernel_stride)}
        for kind in d.kinds]

    def close_layer(h, u, o, p):
        """The gate, the out-projection and the MLP around a mixer's
        output ``o`` ``[M, width]``."""
        gate = jax.nn.sigmoid(sl.linear(u, W[p + "attn_g"]))
        h = h + d.res_scale * sl.linear(gate * o, W[p + "attn_o"])
        return h + d.res_scale * sl.swiglu(
            sl.rms_norm(h, W[p + "norm2"], d.eps), W[p + "mlp_gate"],
            W[p + "mlp_up"], W[p + "mlp_down"], 1.0, 1.0)

    def step_fn(cache, tokens, ts):
        n = tokens.shape[0]
        if sparse_at:
            ts = jnp.minimum(ts, cache[sparse_at[0]]["k"].shape[1] - 1)
        pos = jnp.maximum(ts, 0)      # idle rows stay < 0 in ``ts``
        h = W[name + "_emb"][tokens].astype(jnp.float32) * d.scale_emb
        new_cache = []
        for i, kind in enumerate(d.kinds):
            p = "%s_l%d_" % (name, i)
            c = cache[i]
            u = sl.rms_norm(h, W[p + "norm1"], d.eps)
            q, k, v = sl.mixer_inputs(u, W, p, kind, pos, d)
            if kind == sl.LIGHTNING:
                o, s = sl.lightning_step(q, k, v, c["s"], ts, d)
                o = sl.rms_norm(o.reshape(n, -1), W[p + "o_norm"], d.eps)
                new_cache.append({"s": s})
            else:
                kvs = append_rows({"k": c["k"], "v": c["v"]},
                                  k.reshape(n, -1), v.reshape(n, -1), ts)
                ck = sl.update_compressed(c["ck"], kvs["k"], ts, d)
                blocks, valid, dense = sl.select_blocks(
                    q.reshape(n, G, R, d.head_dim), ck, pos, d)
                with jax.named_scope(sl.SPARSE_ATTEND_SCOPE):
                    o = grouped_block_decode_attention(
                        q.reshape(n, -1), kvs, ts, blocks, valid, dense,
                        n_head=d.n_head, n_kv_head=G, scale=scale,
                        block=d.block_size, dense_len=d.dense_len,
                        shared_runs=sl.forced_runs(d))
                new_cache.append({**kvs, "ck": ck})
            h = close_layer(h, u, o, p)
        logits = sl.linear(
            sl.rms_norm(h, W[name + "_final_norm"], d.eps) / d.logit_div,
            W[name + "_head"])
        return logits, new_cache

    def prefill_layer(c, kind, h, p, row, start, n_valid, pos, ts_q):
        u = sl.rms_norm(h, W[p + "norm1"], d.eps)
        q, k, v = sl.mixer_inputs(u, W, p, kind, pos, d)
        if kind == sl.LIGHTNING:
            s_in = jnp.where(
                sl.starts_fresh(start), 0.0,
                jax.lax.dynamic_index_in_dim(
                    c["s"], row, 0, keepdims=False).astype(jnp.float32))
            o, s_out = sl.lightning_chunk(q, k, v, s_in, n_valid, d)
            o = sl.rms_norm(o.reshape(C, -1), W[p + "o_norm"], d.eps)
            new = {"s": jax.lax.dynamic_update_index_in_dim(
                c["s"], s_out.astype(c["s"].dtype), row, 0)}
            return close_layer(h, u, o, p), new
        live = (ts_q >= 0)[:, None]
        new = {}
        for leaf, fresh in (("k", k), ("v", v)):
            old = jax.lax.dynamic_slice(
                c[leaf], (row, start, 0), (1, C, d.d_kv))[0]
            rows = jnp.where(live, fresh.reshape(C, -1).astype(
                c[leaf].dtype), old)
            new[leaf] = jax.lax.dynamic_update_slice(
                c[leaf], rows[None], (row, start, 0))
        # the kernels this chunk completes end inside it: they start up
        # to kernel_size - kernel_stride rows before it
        back = d.kernel_size - d.kernel_stride
        lo = jnp.maximum(start - back, 0)
        win = jax.lax.dynamic_slice(
            new["k"], (row, lo, 0), (1, C + back, d.d_kv))[0]
        j0, n_new = lo // d.kernel_stride, C // d.kernel_stride
        done = (((j0 + jnp.arange(n_new)) * d.kernel_stride
                 + d.kernel_size) <= start + n_valid)[:, None]
        old = jax.lax.dynamic_slice(c["ck"], (row, j0, 0),
                                    (1, n_new, d.d_kv))[0]
        new["ck"] = jax.lax.dynamic_update_slice(
            c["ck"], jnp.where(done, sl.compress_keys(win, d).astype(
                c["ck"].dtype), old)[None], (row, j0, 0))
        ck_row = jax.lax.dynamic_index_in_dim(new["ck"], row, 0)
        qh = q.reshape(C, G, R, d.head_dim)
        blocks, valid, dense = sl.select_blocks(qh, ck_row, pos, d)
        o = sl.chunk_attend(qh, new["k"], new["v"], row, ts_q, blocks,
                            valid, dense, start + n_valid, d)
        return close_layer(h, u, o.reshape(C, -1), p), new

    def prefill_fn(cache, row, tokens, start, n_valid):
        with jax.named_scope(sl.PREFILL_CHUNK_SCOPE):
            pos = start + jnp.arange(C)
            ts_q = jnp.where(jnp.arange(C) < n_valid, pos, -1)
            h = W[name + "_emb"][tokens].astype(jnp.float32) * d.scale_emb
            new_cache = []
            for i, kind in enumerate(d.kinds):
                h, new = prefill_layer(cache[i], kind, h,
                                       "%s_l%d_" % (name, i), row, start,
                                       n_valid, pos, ts_q)
                new_cache.append(new)
            return new_cache

    prefill_fn.chunk_tokens = C
    declare(make_cache, CacheSpec(
        leaves, prefill_fn=prefill_fn, reads=_ragged_kv_reads(kv) + (
            PositionRead("sparse", lambda n: sl.selected_positions(n, d),
                         layers=len(sparse_at)),)))
    return step_fn, make_cache, prefill_fn


def make_routed_conv_lm_pooled_step_fn(state, cfg, name: str = "lm",
                                       kv_dtype: str = "bf16", held=None):
    """The slot-pooled step of a decoder whose layers are a gated short
    convolution or grouped-query attention, each followed by a dense
    SwiGLU (the leading ``num_dense_layers``) or a mixture of routed
    experts (``model_type: lfm2_moe``; the parts and the equations are
    ``paddle_tpu.routed_experts``, the experts' grouped product
    ``paddle_tpu.grouped_matmul``).

    Same contract as the builders above: ``step_fn(cache, tokens [N]
    int32, ts [N] int32) -> (logits [N, V] fp32, cache)`` with ``ts[i] <
    0`` an idle row, and ``make_cache(n_rows, seq_len)``.  ``state``:
    weights under ``routed_experts.param_shapes(cfg)``, multiplied in
    the dtype they are given (bf16 as stored: no per-step conversion;
    the router stays float32); ``cfg``: the published config keys
    (``routed_experts.dims``); ``held``: the contiguous range ``(lo,
    hi)`` of experts whose matrices ``state`` holds (default: all) —
    every layer routes over all ``num_experts`` and adds what the held
    ones give.  The output head is the embedding (tied).

    The cache is ``{"layers": [...], "expert_stats": ...}`` and the
    spec's ``leaves`` declare every leaf, layer by layer, because the
    layers hold DIFFERENT leaves:

    * an attention layer ``k``, ``v`` ``[N, T, n_kv_head * head_dim]``
      in ``kv_dtype`` (``k`` after its per-head norm and rotary),
      ``decode_attention``'s format through ``make_decode_attention``
      (grouped heads: on a TPU the kernel that reads what is live, two
      64-lane heads a lane tile, else the XLA form; the spec's ``"kv"``
      read tells the server its rounding), covered by write-before-read;
    * a conv layer ``conv`` ``[N, conv_L_cache - 1, d_model]`` fp32, the
      row's last inputs of the depthwise convolution: RECURRENT, read as
      zero for a row at ``ts == 0`` (``hybrid_ssm.starts_fresh``), kept
      for an idle row;
    * ``expert_stats`` ``[expert layers, 4]`` int32 (``slot=False``: the
      pool carries it and never slices it): what the steps so far counted
      on the DEVICE, per expert layer, in ``routed_experts.STAT_NAMES``'
      order — (row, choice) pairs of live rows, experts that got at
      least one, the largest group, steps with a live row — summed over
      steps (it wraps as a uint32 does).  Which experts a step touches
      is known only there; the spec's ``expert_stats(cache)`` picks the
      leaf, and a server that finds it declared fetches it with the
      scheduler's view, in the same ``device_get``.

    Prompts walk the one-token step (no chunked prefill), so, as over
    :func:`make_hybrid_ssm_lm_pooled_step_fn`, ``KVSlotPool`` refuses
    ``prefix=True`` and ``speculative=`` over this builder.
    """
    import jax
    import jax.numpy as jnp

    from paddle_tpu import routed_experts as rx
    from paddle_tpu.decode_attention import kv_leaves, make_decode_attention

    d = rx.dims(cfg)
    kv = _KV_STORAGE[normalize_kv_dtype(kv_dtype, ("fp32", "bf16"))]
    W = {k: jnp.asarray(v) for k, v in state.items()}
    scale = 1.0 / float(np.sqrt(d.head_dim))
    attn_at = [i for i, kind in enumerate(d.kinds) if kind == rx.ATTENTION]
    n_stats = len(rx.STAT_NAMES)

    def make_cache(n_rows: int, seq_len: int):
        return {
            "layers": [
                kv_leaves(n_rows, seq_len, d.n_kv_head, d.head_dim, kv)
                if kind == rx.ATTENTION else
                {"conv": jnp.zeros((n_rows, d.conv_len - 1, d.d_model),
                                   jnp.float32)}
                for kind in d.kinds],
            "expert_stats": jnp.zeros((len(d.expert_layers), n_stats),
                                      jnp.int32)}

    declare(make_cache, CacheSpec(
        {"layers": [{"k": Leaf(1), "v": Leaf(1)} if kind == rx.ATTENTION
                    else {"conv": Leaf()} for kind in d.kinds],
         "expert_stats": Leaf(slot=False)},
        reads=[_step_kv_read(d, kv)],
        expert_stats=lambda cache: cache["expert_stats"],
        n_expert=d.n_expert))

    def step_fn(cache, tokens, ts):
        n = tokens.shape[0]
        layers = cache["layers"]
        attend = None
        if attn_at:
            ts = jnp.minimum(ts, layers[attn_at[0]]["k"].shape[1] - 1)
            attend = make_decode_attention(
                ts, layers[attn_at[0]], n_head=d.n_head,
                n_kv_head=d.n_kv_head, scale=scale)
        pos = jnp.maximum(ts, 0)      # idle rows stay < 0 in ``ts``
        emb = W[name + "_emb"]
        h = emb[tokens].astype(jnp.float32)
        new_layers, stats = [], []
        for i, kind in enumerate(d.kinds):
            p = "%s_l%d_" % (name, i)
            c = layers[i]
            r = rx.rms_norm(h, W[p + "operator_norm"], d.eps)
            if kind == rx.CONV:
                o, conv = rx.short_conv_step(r, W, p, c["conv"], ts, d)
                new_layers.append({"conv": conv})
            else:
                q = rx.rms_norm(rx.linear(r, W[p + "attn_q"]).reshape(
                    n, d.n_head, d.head_dim), W[p + "q_layernorm"], d.eps)
                k = rx.rms_norm(rx.linear(r, W[p + "attn_k"]).reshape(
                    n, d.n_kv_head, d.head_dim), W[p + "k_layernorm"], d.eps)
                ctx, kvs = attend(
                    rx.rotary(q, pos, d.rope_theta).reshape(n, -1),
                    rx.rotary(k, pos, d.rope_theta).reshape(n, -1),
                    rx.linear(r, W[p + "attn_v"]), c)
                o = rx.linear(ctx, W[p + "attn_o"])
                new_layers.append(kvs)
            h = h + o
            f = rx.rms_norm(h, W[p + "ffn_norm"], d.eps)
            if i < d.n_dense:
                h = h + rx.swiglu(f, W[p + "ffn_gate"], W[p + "ffn_up"],
                                  W[p + "ffn_down"], 1.0, 1.0)
            else:
                y, st = rx.expert_layer(f, W, p, ts, d, held)
                h = h + y
                stats.append(st)
        x = rx.rms_norm(h, W[name + "_embedding_norm"], d.eps)
        logits = jax.lax.dot_general(
            x.astype(emb.dtype), emb, (((1,), (1,)), ((), ())),
            preferred_element_type=jnp.float32)
        counted = cache["expert_stats"]
        if stats:
            counted = counted + jnp.stack(stats)
        return logits, {"layers": new_layers, "expert_stats": counted}

    return step_fn, make_cache


def make_windowed_routed_lm_pooled_step_fn(state, cfg, name: str = "lm",
                                            kv_dtype: str = "bf16",
                                            held=None,
                                            prefill_tokens: int = 512):
    """The slot-pooled step AND the chunked prefill of a decoder whose
    blocks are grouped-query attention over a sliding WINDOW (rotary) or
    over the whole context (no positions), each followed by a mixture of
    routed experts whose router reads the block's input before attention
    (``model_name: smallthinker_*``; the parts and the equations are
    ``paddle_tpu.windowed_routed_lm``, the expert layer
    ``paddle_tpu.routed_experts``).

    Returns ``(step_fn, make_cache, prefill_fn)`` with the contract of
    :func:`make_sparse_linear_lm_pooled_step_fn` (``step_fn(cache, tokens
    [N] int32, ts [N] int32) -> (logits [N, V] fp32, cache)``, ``ts[i] <
    0`` an idle row; ``prefill_fn(cache, row, tokens [C], start, n_valid)
    -> cache``).  ``state``: weights under
    ``windowed_routed_lm.param_shapes(cfg)``, multiplied in the dtype
    they are given (the router stays float32); ``cfg``: the published
    config keys (``windowed_routed_lm.dims``); ``held``: the contiguous
    range of experts whose matrices ``state`` holds, as
    :func:`make_routed_conv_lm_pooled_step_fn`.

    The cache is ``{"layers": [...], "expert_stats": ...}`` and its
    layers' leaves differ in LENGTH (their :class:`Leaf`'s ``window``
    says so):

    * a global layer ``k``, ``v`` ``[N, T, n_kv_head * head_dim]`` in
      ``kv_dtype``: sequence leaves of the length rung (``k`` bare: a
      global layer has no positions);
    * a window layer ``k``, ``v`` ``[N, min(T, window), ...]``: RING
      leaves (``decode_attention.kv_leaves(..., window=)``; ``k`` after
      rotary), position ``p`` in row ``p mod window``;
    * ``expert_stats`` ``[layers, 4]`` int32, as
      :func:`make_routed_conv_lm_pooled_step_fn` but declared
      ``slot=False``: this pool keeps snapshots of a slot's row, and a
      leaf of counts has no slot's row (the prefill's chunks are not
      counted: the counts are of steps).

    The step takes its length rung from a GLOBAL layer's leaf and makes
    two ``attend``s (``decode_attention.make_decode_attention``: over
    the ring, over the whole rung).  ``prefill_fn`` feeds slot ``row``
    ``C = prefill_tokens`` prompt tokens at ``start .. start + n_valid -
    1`` through every layer in one call and no logits: a global layer
    writes the chunk's rows and every query reads keys ``0 .. its own``;
    a window layer's queries read the OLD ring and the chunk's own rows
    (``decode_attention.ring_positions``) before the chunk overwrites
    ``C`` of its rows; both through ``windowed_routed_lm.chunk_attend``
    (key blocks, online softmax: no temporary grows with the rung); the
    chunk's ``C`` rows go through the expert layer as a step's rows do
    (``C * top_k`` pairs).  ``C`` must divide the window and ``start`` be
    a multiple of ``C``, so that a chunk's rows lie in the ring without a
    wrap.  It equals ``n_valid`` steps leaf for leaf, but for the
    summation order (tests/test_windowed_routed_lm.py).
    The spec's ``"window"`` read is what a window layer's query of
    context ``n`` reads, for the server's counters.

    Ring leaves cannot be sliced by positions: ``KVSlotPool`` serves
    ``prefix=True`` over this builder by SNAPSHOTS (it has a prefill)
    and refuses ``speculative=``.
    """
    import jax
    import jax.numpy as jnp

    from paddle_tpu import routed_experts as rx
    from paddle_tpu import windowed_routed_lm as wr
    from paddle_tpu.decode_attention import (kv_leaves, make_decode_attention,
                                             ring_positions)

    d = wr.dims(cfg)
    kv = _KV_STORAGE[normalize_kv_dtype(kv_dtype, ("fp32", "bf16"))]
    W = {k: jnp.asarray(v) for k, v in state.items()}
    C = int(prefill_tokens)
    if d.window % C:
        raise ValueError("prefill_tokens must divide sliding_window_size")
    scale = 1.0 / float(np.sqrt(d.head_dim))
    global_at = [i for i, kind in enumerate(d.kinds) if kind == wr.GLOBAL]
    window_at = [i for i, kind in enumerate(d.kinds) if kind == wr.WINDOW]
    n_stats = len(rx.STAT_NAMES)

    def make_cache(n_rows: int, seq_len: int):
        return {
            "layers": [
                kv_leaves(n_rows, seq_len, d.n_kv_head, d.head_dim, kv,
                          window=d.window if kind == wr.WINDOW else None)
                for kind in d.kinds],
            "expert_stats": jnp.zeros((d.n_layer, n_stats), jnp.int32)}

    ring = Leaf(1, window=d.window)
    leaves = {
        "layers": [{"k": ring, "v": ring} if kind == wr.WINDOW
                   else {"k": Leaf(1), "v": Leaf(1)} for kind in d.kinds],
        "expert_stats": Leaf(slot=False)}  # a snapshot must not carry counts

    def close_layer(h, r, o, p, ts):
        """The out-projection and the expert layer (routed by the
        block's normed input ``r``) around a mixer's output ``o``."""
        h = h + wr.linear(o, W[p + "attn_o"])
        f = wr.rms_norm(h, W[p + "ffn_norm"], d.eps)
        y, st = rx.expert_layer(f, W, p, ts, d, held, router_input=r)
        return h + y, st

    def step_fn(cache, tokens, ts):
        layers = cache["layers"]
        attend = {}
        if global_at:
            ts = jnp.minimum(ts, layers[global_at[0]]["k"].shape[1] - 1)
            attend[wr.GLOBAL] = make_decode_attention(
                ts, layers[global_at[0]], n_head=d.n_head,
                n_kv_head=d.n_kv_head, scale=scale)
        if window_at:
            attend[wr.WINDOW] = make_decode_attention(
                ts, layers[window_at[0]], n_head=d.n_head,
                n_kv_head=d.n_kv_head, scale=scale, window=d.window)
        pos = jnp.maximum(ts, 0)      # idle rows stay < 0 in ``ts``
        n = tokens.shape[0]
        h = W[name + "_emb"][tokens].astype(jnp.float32)
        new_layers, stats = [], []
        for i, kind in enumerate(d.kinds):
            p = "%s_l%d_" % (name, i)
            r = wr.rms_norm(h, W[p + "input_norm"], d.eps)
            q, k, v = wr.attention_inputs(r, W, p, kind, pos, d)
            with jax.named_scope(wr.WINDOW_ATTEND_SCOPE if kind == wr.WINDOW
                                 else wr.GLOBAL_ATTEND_SCOPE):
                ctx, kvs = attend[kind](q.reshape(n, -1), k.reshape(n, -1),
                                        v, layers[i])
            new_layers.append(kvs)
            h, st = close_layer(h, r, ctx, p, ts)
            stats.append(st)
        logits = wr.linear(wr.rms_norm(h, W[name + "_final_norm"], d.eps),
                           W[name + "_head"])
        return logits, {"layers": new_layers,
                        "expert_stats": cache["expert_stats"]
                        + jnp.stack(stats)}

    def prefill_layer(c, kind, h, p, row, start, n_valid, pos, ts_q):
        r = wr.rms_norm(h, W[p + "input_norm"], d.eps)
        q, k, v = wr.attention_inputs(r, W, p, kind, pos, d)
        live = (ts_q >= 0)[:, None]
        rows = c["k"].shape[1]
        # a chunk's rows lie in a leaf without a wrap: C divides the
        # window and the rungs a chunk is fed on are longer than it
        at = start % rows if kind == wr.WINDOW else start
        old = {leaf: jax.lax.dynamic_index_in_dim(c[leaf], row, 0,
                                                  keepdims=False)
               for leaf in ("k", "v")}
        fresh = {"k": k.reshape(C, -1).astype(kv), "v": v.astype(kv)}
        new = {}
        for leaf in ("k", "v"):
            kept = jax.lax.dynamic_slice(old[leaf], (at, 0), (C, d.d_kv))
            new[leaf] = jax.lax.dynamic_update_slice(
                c[leaf], jnp.where(live, fresh[leaf], kept)[None],
                (row, at, 0))
        if kind == wr.WINDOW:
            # the OLD ring's rows at the positions they hold, then the
            # chunk's own
            with jax.named_scope(wr.WINDOW_ATTEND_SCOPE):
                o = wr.chunk_attend(
                    q, jnp.concatenate([old["k"], fresh["k"]]),
                    jnp.concatenate([old["v"], fresh["v"]]), ts_q,
                    jnp.concatenate([ring_positions(start, rows), ts_q]),
                    rows + C, d, window=d.window)
        else:
            with jax.named_scope(wr.GLOBAL_ATTEND_SCOPE):
                o = wr.chunk_attend(
                    q, jax.lax.dynamic_index_in_dim(new["k"], row, 0, False),
                    jax.lax.dynamic_index_in_dim(new["v"], row, 0, False),
                    ts_q, jnp.arange(rows), start + n_valid, d)
        h, _ = close_layer(h, r, o, p, ts_q)
        return h, new

    def prefill_fn(cache, row, tokens, start, n_valid):
        with jax.named_scope(wr.PREFILL_CHUNK_SCOPE):
            pos = start + jnp.arange(C)
            ts_q = jnp.where(jnp.arange(C) < n_valid, pos, -1)
            h = W[name + "_emb"][tokens].astype(jnp.float32)
            new_layers = []
            for i, kind in enumerate(d.kinds):
                h, new = prefill_layer(cache["layers"][i], kind, h,
                                       "%s_l%d_" % (name, i), row, start,
                                       n_valid, pos, ts_q)
                new_layers.append(new)
            return {"layers": new_layers,
                    "expert_stats": cache["expert_stats"]}

    prefill_fn.chunk_tokens = C
    declare(make_cache, CacheSpec(
        leaves, prefill_fn=prefill_fn,
        reads=[_step_kv_read(d, kv), PositionRead(
            "window", lambda n: np.minimum(n, d.window),
            layers=len(window_at))],
        expert_stats=lambda cache: cache["expert_stats"],
        n_expert=d.n_expert))
    return step_fn, make_cache, prefill_fn


def _fresh_rows(ts, shape):
    """``(ts_rows [N], pos_rows [N])`` of a ``shape`` = ``[S]`` or ``[S,
    K]`` of fresh rows: row ``j`` of a live slot sits at ``ts + j``; an
    idle slot's rows are ``< 0`` in ``ts_rows``."""
    import jax.numpy as jnp

    pos = jnp.maximum(ts, 0)
    if len(shape) == 2:
        pos = pos[:, None] + jnp.arange(shape[1])[None, :]
        ts = jnp.where(ts[:, None] >= 0, pos, -1)
    return ts.reshape(-1), pos.reshape(-1)


def _counted_experts(cache, stats=None, module=None, *, n_sparse: int,
                     n_mtp: int):
    """A drafting builder's ``expert_stats`` ``[n_sparse + n_mtp, 4]``
    advanced by the sparse layers' ``stats`` and the module's (None: as
    they were)."""
    import jax.numpy as jnp

    zero = jnp.zeros(cache["expert_stats"].shape[1], jnp.int32)
    add = list(stats) if stats is not None else [zero] * n_sparse
    if n_mtp:
        add.append(zero if module is None else module)
    return (cache["expert_stats"] + jnp.stack(add) if add
            else cache["expert_stats"])


def make_mtp_routed_lm_pooled_step_fn(state, cfg, name: str = "lm",
                                       kv_dtype: str = "bf16", held=None,
                                       prefill_tokens: int = 512):
    """The slot-pooled step, the K-wide verify, the multi-token-
    prediction module's K-wide pass and the chunked prefill of a decoder
    whose blocks are grouped-query attention over a short sliding WINDOW
    (rotary) or the whole context (no positions), each branch closed by
    its norm, then a dense SwiGLU or routed experts BESIDE A SHARED
    EXPERT, with ONE module that drafts for the model from its own last
    hidden state (``model_type: exaone_moe``; the parts and the
    equations are ``paddle_tpu.mtp_routed_lm``, the expert layer
    ``paddle_tpu.routed_experts``).

    Returns ``(step_fn, make_cache, prefill_fn)`` with the contract of
    :func:`make_windowed_routed_lm_pooled_step_fn`.  ``state``: weights
    under ``mtp_routed_lm.param_shapes(cfg, held=held)``, multiplied in
    the dtype they are given (router, bias and norms float32); ``held``:
    the contiguous range of experts whose matrices ``state`` holds —
    every sparse layer routes over all ``num_experts_all`` and adds what
    the held ones give, plus its shared expert.

    What a self-drafting round needs rides the spec (``mtp_fn`` ``None``
    where the configuration has no module):

    * ``verify_fn(cache, tokens [S, K], ts [S]) -> (logits
      [S, K, V], hidden [S, K, d_model], cache)``: IS the step at ``K``
      fresh rows a slot (all ``K`` written before any is read, row ``j``
      at position ``ts + j``), and also yields the last block's output;
    * ``mtp_fn(cache, hidden [S, K, d_model], next_tokens
      [S, K], ts [S]) -> (logits [S, K, V], cache)``: the module at
      positions ``ts .. ts + K - 1``, row ``j`` fed ``hidden[:, j]`` and
      the embedding of ``next_tokens[:, j]`` (the token at ``ts + j +
      1``), through its own block over its own leaves, the model's final
      norm and head: logits for the token at ``ts + j + 2``.

    The cache is ``{"layers": [...], "mtp": {...}, "expert_stats": ...}``:
    a window layer's ``k``, ``v`` are RING leaves of ``sliding_window``
    rows, a global layer's and the module's sequence leaves of the length
    rung; ``expert_stats`` ``[sparse
    layers + 1, 4]`` int32 (``slot=False``), the module's expert layer
    in the last row — a step or a verify counts every row it computed,
    drafted ones included; the plain step leaves the module's leaves and
    its row of counts alone.

    ``prefill_fn(cache, row, tokens [C + 1], start, n_valid) -> cache``
    feeds slot ``row`` ``C = prefill_tokens`` prompt tokens through every
    layer AND the module (``prefill_fn.lookahead`` = 1: the module's row
    at position ``i`` needs the token at ``i + 1``, so the pool hands it
    one token more than it feeds).  A ring row is taken from the chunk
    where the chunk holds the last position of that row and kept
    otherwise, so the window may be SMALLER than the chunk (128 under
    512) or larger; ``start`` is a multiple of ``C``.
    """
    import jax
    import jax.numpy as jnp

    from paddle_tpu import mtp_routed_lm as mr
    from paddle_tpu import routed_experts as rx
    from paddle_tpu.decode_attention import (kv_leaves, make_decode_attention,
                                             ring_positions)

    d = mr.dims(cfg)
    kv = _KV_STORAGE[normalize_kv_dtype(kv_dtype, ("fp32", "bf16"))]
    W = {k: jnp.asarray(v) for k, v in state.items()}
    C = int(prefill_tokens)
    scale = 1.0 / float(np.sqrt(d.head_dim))
    global_at = [i for i, kind in enumerate(d.kinds) if kind == mr.GLOBAL]
    window_at = [i for i, kind in enumerate(d.kinds) if kind == mr.WINDOW]
    if not (global_at or d.n_mtp):
        raise ValueError("no layer holds the whole length rung")
    n_stats = len(rx.STAT_NAMES)
    stat_rows = len(d.expert_layers) + (1 if d.n_mtp else 0)
    p_mtp = mr.layer_prefix(name, mr.MTP_LAYER)
    f32 = jnp.float32

    def make_cache(n_rows: int, seq_len: int):
        cache = {
            "layers": [
                kv_leaves(n_rows, seq_len, d.n_kv_head, d.head_dim, kv,
                          window=d.window if kind == mr.WINDOW else None)
                for kind in d.kinds],
            "expert_stats": jnp.zeros((stat_rows, n_stats), jnp.int32)}
        if d.n_mtp:
            cache["mtp"] = kv_leaves(n_rows, seq_len, d.n_kv_head,
                                     d.head_dim, kv)
        return cache

    ring, whole = Leaf(1, window=d.window), {"k": Leaf(1), "v": Leaf(1)}
    leaves = {
        "layers": [{"k": ring, "v": ring} if kind == mr.WINDOW else whole
                   for kind in d.kinds],
        "expert_stats": Leaf(slot=False)}
    if d.n_mtp:
        leaves["mtp"] = whole

    def rung_of(cache):
        whole = cache["mtp"] if d.n_mtp else cache["layers"][global_at[0]]
        return whole["k"].shape[1]

    def block(h, c, p, kind, dense, attend, shape, ts_rows, pos_rows):
        """One block over the fresh rows ``h`` ``[N, d_model]`` (``N`` =
        the product of ``shape``); returns ``(h, leaves, stats)``."""
        n = h.shape[0]
        q, k, v = mr.attention_inputs(h, W, p, kind, pos_rows, d)
        by = tuple(shape) + (-1,)
        with jax.named_scope(mr.WINDOW_ATTEND_SCOPE if kind == mr.WINDOW
                             else mr.GLOBAL_ATTEND_SCOPE):
            ctx, kvs = attend(q.reshape(by), k.reshape(by), v.reshape(by), c)
        h = h + mr.rms_norm(mr.linear(ctx.reshape(n, -1), W[p + "attn_o"]),
                            W[p + "post_attn_norm"], d.eps)
        return close_ffn(h, p, dense, ts_rows) + (kvs,)

    def close_ffn(h, p, dense, ts_rows):
        if dense:
            y, st = mr.swiglu(h, W[p + "ffn_gate"], W[p + "ffn_up"],
                              W[p + "ffn_down"], 1.0, 1.0), None
        else:
            y, st = rx.expert_layer(h, W, p, ts_rows, d, held)
        return h + mr.rms_norm(y, W[p + "post_ffn_norm"], d.eps), st

    def head(h):
        return mr.linear(mr.rms_norm(h, W[name + "_final_norm"], d.eps),
                         W[name + "_head"])

    counted = functools.partial(_counted_experts,
                                n_sparse=len(d.expert_layers), n_mtp=d.n_mtp)

    def forward(cache, tokens, ts):
        """The layers over ``tokens`` ``[S]`` (one fresh row a slot) or
        ``[S, K]``: the kernels where they exist (the global layers'
        grouped kernel takes either, ``make_decode_attention``)."""
        layers = cache["layers"]
        ts = jnp.minimum(ts, rung_of(cache) - 1)
        attend = {}
        if global_at:
            attend[mr.GLOBAL] = make_decode_attention(
                ts, layers[global_at[0]], n_head=d.n_head,
                n_kv_head=d.n_kv_head, scale=scale)
        if window_at:
            attend[mr.WINDOW] = make_decode_attention(
                ts, layers[window_at[0]], n_head=d.n_head,
                n_kv_head=d.n_kv_head, scale=scale, window=d.window)
        ts_rows, pos_rows = _fresh_rows(ts, tokens.shape)
        h = W[name + "_emb"][tokens.reshape(-1)].astype(f32)
        new_layers, stats = [], []
        for i, kind in enumerate(d.kinds):
            h, st, kvs = block(h, layers[i], mr.layer_prefix(name, i), kind,
                               d.dense[i], attend[kind], tokens.shape,
                               ts_rows, pos_rows)
            new_layers.append(kvs)
            if st is not None:
                stats.append(st)
        out = dict(cache, layers=new_layers,
                   expert_stats=counted(cache, stats))
        by = tuple(tokens.shape) + (-1,)
        return head(h).reshape(by), h.reshape(by), out

    def step_fn(cache, tokens, ts):
        logits, _, cache = forward(cache, tokens, ts)
        return logits, cache

    def verify_fn(cache, tokens, ts):
        with jax.named_scope(mr.SPEC_VERIFY_SCOPE):
            return forward(cache, tokens, ts)

    def mtp_fn(cache, hidden, next_tokens, ts):
        with jax.named_scope(mr.MTP_MODULE_SCOPE):
            ts = jnp.minimum(ts, rung_of(cache) - 1)
            shape = next_tokens.shape
            ts_rows, pos_rows = _fresh_rows(ts, shape)
            u = mr.module_input(
                hidden.reshape(-1, d.d_model),
                W[name + "_emb"][next_tokens.reshape(-1)].astype(f32),
                W, p_mtp, d)
            attend = make_decode_attention(
                ts, cache["mtp"], n_head=d.n_head, n_kv_head=d.n_kv_head,
                scale=scale)
            u, st, kvs = block(u, cache["mtp"], p_mtp, mr.GLOBAL, False,
                               attend, shape, ts_rows, pos_rows)
            out = dict(cache, mtp=kvs,
                       expert_stats=counted(cache, None, st))
            return head(u).reshape(tuple(shape) + (-1,)), out

    def prefill_layer(c, kind, dense, h, p, row, start, n_valid, pos, ts_q):
        q, k, v = mr.attention_inputs(h, W, p, kind, pos, d)
        rows = c["k"].shape[1]
        old = {leaf: jax.lax.dynamic_index_in_dim(c[leaf], row, 0,
                                                  keepdims=False)
               for leaf in ("k", "v")}
        fresh = {"k": k.reshape(C, -1).astype(kv), "v": v.astype(kv)}
        new = {}
        if kind == mr.WINDOW:
            # a ring row takes the chunk's row where the chunk holds the
            # last position that row will hold, and stays otherwise
            holds = ring_positions(jnp.asarray(start + n_valid), rows)
            take = (holds >= start)[:, None]
            src = jnp.clip(holds - start, 0, C - 1)
            for leaf in ("k", "v"):
                new[leaf] = jax.lax.dynamic_update_slice(
                    c[leaf], jnp.where(take, fresh[leaf][src],
                                       old[leaf])[None], (row, 0, 0))
            # the OLD ring's rows at the positions they hold, then the
            # chunk's own
            with jax.named_scope(mr.WINDOW_ATTEND_SCOPE):
                o = mr.chunk_attend(
                    q, jnp.concatenate([old["k"], fresh["k"]]),
                    jnp.concatenate([old["v"], fresh["v"]]), ts_q,
                    jnp.concatenate([ring_positions(jnp.asarray(start),
                                                    rows), ts_q]),
                    rows + C, d, window=d.window)
        else:
            live = (ts_q >= 0)[:, None]
            for leaf in ("k", "v"):
                kept = jax.lax.dynamic_slice(old[leaf], (start, 0),
                                             (C, d.d_kv))
                new[leaf] = jax.lax.dynamic_update_slice(
                    c[leaf], jnp.where(live, fresh[leaf], kept)[None],
                    (row, start, 0))
            with jax.named_scope(mr.GLOBAL_ATTEND_SCOPE):
                o = mr.chunk_attend(
                    q, jax.lax.dynamic_index_in_dim(new["k"], row, 0, False),
                    jax.lax.dynamic_index_in_dim(new["v"], row, 0, False),
                    ts_q, jnp.arange(rows), start + n_valid, d)
        h = h + mr.rms_norm(mr.linear(o, W[p + "attn_o"]),
                            W[p + "post_attn_norm"], d.eps)
        return close_ffn(h, p, dense, ts_q)[0], new

    def prefill_fn(cache, row, tokens, start, n_valid):
        with jax.named_scope(mr.PREFILL_CHUNK_SCOPE):
            pos = start + jnp.arange(C)
            ts_q = jnp.where(jnp.arange(C) < n_valid, pos, -1)
            emb = W[name + "_emb"]
            h = emb[tokens[:C]].astype(f32)
            new_layers = []
            for i, kind in enumerate(d.kinds):
                h, new = prefill_layer(
                    cache["layers"][i], kind, d.dense[i], h,
                    mr.layer_prefix(name, i), row, start, n_valid, pos, ts_q)
                new_layers.append(new)
            out = dict(cache, layers=new_layers)
            if d.n_mtp:
                with jax.named_scope(mr.MTP_MODULE_SCOPE):
                    u = mr.module_input(h, emb[tokens[1:]].astype(f32), W,
                                        p_mtp, d)
                    _, out["mtp"] = prefill_layer(
                        cache["mtp"], mr.GLOBAL, False, u, p_mtp, row,
                        start, n_valid, pos, ts_q)
            return out

    prefill_fn.chunk_tokens = C
    prefill_fn.lookahead = 1 if d.n_mtp else 0
    declare(make_cache, CacheSpec(
        leaves, prefill_fn=prefill_fn, verify_fn=verify_fn,
        mtp_fn=mtp_fn if d.n_mtp else None,
        reads=[_step_kv_read(d, kv), PositionRead(
            "window", lambda n: np.minimum(n, d.window),
            layers=len(window_at))],
        expert_stats=lambda cache: cache["expert_stats"],
        n_expert=(d.n_expert if held is None
                  else int(held[1]) - int(held[0]))))
    return step_fn, make_cache, prefill_fn


def make_delta_hybrid_lm_pooled_step_fn(state, cfg, name: str = "lm",
                                        kv_dtype: str = "bf16"):
    """The slot-pooled step of a decoder whose layers are a GATED
    DELTA-RULE linear attention or full multi-head attention, each
    branch closed by its norm, every layer followed by a SwiGLU
    (``model_type: olmo_hybrid``; the parts and the equations are
    ``paddle_tpu.delta_hybrid_lm``).

    Same contract as the builders above: ``step_fn(cache, tokens [N]
    int32, ts [N] int32) -> (logits [N, V] fp32, cache)`` with ``ts[i] <
    0`` an idle row, and ``make_cache(n_rows, seq_len)``.  ``state``:
    weights under ``delta_hybrid_lm.param_shapes(cfg)``, multiplied in
    the dtype they are given (bf16 as stored: no per-step conversion;
    norms, gates' biases and the conv kernel float32); ``cfg``: the
    published config keys (``delta_hybrid_lm.dims``).

    The cache is a list, a dict a layer, and the spec's ``leaves``
    declare every leaf, because the layers hold DIFFERENT leaves:

    * a full layer ``k``, ``v`` ``[N, T, n_kv_head * head_dim]`` in
      ``kv_dtype`` (``k`` after its norm), ``decode_attention``'s format
      through ``make_decode_attention`` — ONE query head per K/V head in
      ``olmo_hybrid``: over bf16 leaves on a TPU the grouped kernel's
      read of what is live, a head one row of a unit (heads of whole
      lane tiles, a rung its block divides; the server's read counter
      is told that rounding: the spec's ``"kv"`` read), else an
      XLA form that reads the whole rung
      (``decode_attention_ungrouped_lowered_total{path}`` says which
      form a program took); covered by write-before-read;
    * a linear layer ``state`` ``[N, H / g, dk, g * dv]`` float32 (``g``
      heads side by side in the lanes: ``delta_hybrid_lm.heads_per_
      tile``) and ``conv`` ``[N, K - 1, 2 H dk + H dv]`` float32:
      RECURRENT (no sequence axis), read as zero for a row at ``ts == 0``
      (``hybrid_ssm.starts_fresh``), kept for an idle row.

    Prompts walk the one-token step (no chunked prefill: the delta
    rule's chunkwise form is not built), so, as over
    :func:`make_hybrid_ssm_lm_pooled_step_fn`, ``KVSlotPool`` refuses
    ``prefix=True`` and ``speculative=`` over this builder.
    """
    import jax.numpy as jnp

    from paddle_tpu import delta_hybrid_lm as dh
    from paddle_tpu.decode_attention import kv_leaves, make_decode_attention

    d = dh.dims(cfg)
    kv = _KV_STORAGE[normalize_kv_dtype(kv_dtype, ("fp32", "bf16"))]
    W = {k: jnp.asarray(v) for k, v in state.items()}
    scale = 1.0 / float(np.sqrt(d.head_dim))
    full_at = [i for i, kind in enumerate(d.kinds) if kind == dh.FULL]

    def make_cache(n_rows: int, seq_len: int):
        return [
            kv_leaves(n_rows, seq_len, d.n_kv_head, d.head_dim, kv)
            if kind == dh.FULL else
            {"state": jnp.zeros((n_rows,) + d.state_shape, jnp.float32),
             "conv": jnp.zeros((n_rows, d.conv_len - 1, d.d_qkv),
                               jnp.float32)}
            for kind in d.kinds]

    declare(make_cache, CacheSpec(
        [{"k": Leaf(1), "v": Leaf(1)} if kind == dh.FULL
         else {"state": Leaf(), "conv": Leaf()} for kind in d.kinds],
        reads=[_step_kv_read(d, kv)]))

    def step_fn(cache, tokens, ts):
        attend = None
        if full_at:
            ts = jnp.minimum(ts, cache[full_at[0]]["k"].shape[1] - 1)
            attend = make_decode_attention(
                ts, cache[full_at[0]], n_head=d.n_head,
                n_kv_head=d.n_kv_head, scale=scale)
        pos = jnp.maximum(ts, 0)      # idle rows stay < 0 in ``ts``
        h = W[name + "_emb"][tokens].astype(jnp.float32)
        new_cache = []
        for i, kind in enumerate(d.kinds):
            p = "%s_l%d_" % (name, i)
            c = cache[i]
            if kind == dh.LINEAR:
                o, s, conv = dh.delta_layer_step(h, W, p, c["state"],
                                                 c["conv"], ts, d)
                new_cache.append({"state": s, "conv": conv})
            else:
                ctx, kvs = attend(*dh.full_attention_rows(h, W, p, pos, d), c)
                o = dh.linear(ctx, W[p + "attn_o"])
                new_cache.append(kvs)
            h = h + dh.rms_norm(o, W[p + "mixer_norm"], d.eps)
            h = h + dh.rms_norm(
                dh.swiglu(h, W[p + "mlp_gate"], W[p + "mlp_up"],
                          W[p + "mlp_down"], 1.0, 1.0),
                W[p + "mlp_norm"], d.eps)
        logits = dh.linear(dh.rms_norm(h, W[name + "_final_norm"], d.eps),
                           W[name + "_head"])
        return logits, new_cache

    return step_fn, make_cache


def make_kda_routed_lm_pooled_step_fn(state, cfg, name: str = "lm",
                                      kv_dtype: str = "bf16", held=None):
    """The slot-pooled step of a decoder whose layers are KIMI DELTA
    ATTENTION (a gated delta rule whose decay is one factor a key CHANNEL
    of a head) or gated position-free grouped-query attention, pre-norm,
    EVERY layer followed by routed experts beside a shared expert
    (``model_type: solar_open2``; the parts and the equations are
    ``paddle_tpu.delta_hybrid_lm``'s ``kda_*``, the expert layer
    ``paddle_tpu.routed_experts``).

    Same contract as the builders above: ``step_fn(cache, tokens [N]
    int32, ts [N] int32) -> (logits [N, V] fp32, cache)`` with ``ts[i] <
    0`` an idle row, and ``make_cache(n_rows, seq_len)``.  ``state``:
    weights under ``delta_hybrid_lm.kda_param_shapes(cfg, held=held)``,
    multiplied in the dtype they are given (router, bias, norms, the conv
    kernel, ``A_log`` and ``dt_bias`` float32); ``held``: the contiguous
    range of experts whose matrices ``state`` holds — every layer routes
    over all of them and adds what the held ones give, plus its shared
    expert.

    The cache is ``{"layers": [...], "expert_stats": ...}``:

    * a G layer ``k``, ``v`` ``[N, T, n_kv_head * head_dim]`` in
      ``kv_dtype``, ``decode_attention``'s format through
      ``make_decode_attention`` (grouped heads of whole lane tiles over
      bf16 leaves on a TPU: the grouped kernel's read of what is live;
      the spec's ``"kv"`` read tells the server its rounding); the
      sigmoid gate is applied to the read's output;
    * a K layer ``state`` ``[N, H / g, dk, g * dv]`` float32 and ``conv``
      ``[N, K - 1, 2 H dk + H dv]`` float32: RECURRENT (no sequence axis), as
      :func:`make_delta_hybrid_lm_pooled_step_fn`'s;
    * ``expert_stats`` ``[layers, 4]`` int32 (``slot=False``), as
      :func:`make_routed_conv_lm_pooled_step_fn`'s; the spec's ``n_expert``
      counts the experts HELD (what the counts' groups are over).

    Prompts walk the one-token step (the delta rule's chunkwise form,
    ``delta_hybrid_lm.gated_delta_chunk``, is not wired into this
    builder: :func:`make_kda_latent_lm_pooled_step_fn` is the one that
    prefills with it), so ``KVSlotPool`` refuses ``prefix=True`` and
    ``speculative=`` over this builder.
    """
    import jax
    import jax.numpy as jnp

    from paddle_tpu import delta_hybrid_lm as dh
    from paddle_tpu import routed_experts as rx
    from paddle_tpu.decode_attention import kv_leaves, make_decode_attention

    d = dh.kda_dims(cfg)
    if d.n_dense:
        raise ValueError("first_k_dense_replace: this builder follows every "
                         "mixer by routed experts (leading dense layers are "
                         "make_kda_latent_lm_pooled_step_fn's)")
    kv = _KV_STORAGE[normalize_kv_dtype(kv_dtype, ("fp32", "bf16"))]
    W = {k: jnp.asarray(v) for k, v in state.items()}
    scale = 1.0 / float(np.sqrt(d.head_dim))
    full_at = [i for i, kind in enumerate(d.kinds) if kind == dh.FULL]
    n_stats = len(rx.STAT_NAMES)

    def make_cache(n_rows: int, seq_len: int):
        return {
            "layers": [
                kv_leaves(n_rows, seq_len, d.n_kv_head, d.head_dim, kv)
                if kind == dh.FULL else
                {"state": jnp.zeros((n_rows,) + d.state_shape, jnp.float32),
                 "conv": jnp.zeros((n_rows, d.conv_len - 1, d.d_qkv),
                                   jnp.float32)}
                for kind in d.kinds],
            "expert_stats": jnp.zeros((d.n_layer, n_stats), jnp.int32)}

    declare(make_cache, CacheSpec(
        {"layers": [{"k": Leaf(1), "v": Leaf(1)} if kind == dh.FULL
                    else {"state": Leaf(), "conv": Leaf()}
                    for kind in d.kinds],
         "expert_stats": Leaf(slot=False)},
        reads=[_step_kv_read(d, kv)],
        expert_stats=lambda cache: cache["expert_stats"],
        n_expert=(d.n_expert if held is None
                  else int(held[1]) - int(held[0]))))

    def step_fn(cache, tokens, ts):
        layers = cache["layers"]
        attend = None
        if full_at:
            ts = jnp.minimum(ts, layers[full_at[0]]["k"].shape[1] - 1)
            attend = make_decode_attention(
                ts, layers[full_at[0]], n_head=d.n_head,
                n_kv_head=d.n_kv_head, scale=scale)
        pos = jnp.maximum(ts, 0)      # idle rows stay < 0 in ``ts``
        h = W[name + "_emb"][tokens].astype(jnp.float32)
        new_layers, stats = [], []
        for i, kind in enumerate(d.kinds):
            p = "%s_l%d_" % (name, i)
            c = layers[i]
            r = dh.rms_norm(h, W[p + "mixer_norm"], d.eps)
            if kind == dh.LINEAR:
                o, s, conv = dh.kda_layer_step(r, W, p, c["state"], c["conv"],
                                               ts, d)
                new_layers.append({"state": s, "conv": conv})
            else:
                with jax.named_scope(dh.FULL_ATTENTION_SCOPE):
                    q, k, v, gate = dh.gated_attention_rows(r, W, p, pos, d)
                    ctx, kvs = attend(q, k, v, c)
                    if gate is not None:
                        ctx = ctx * gate
                o = dh.linear(ctx, W[p + "attn_o"])
                new_layers.append(kvs)
            h = h + o
            y, st = rx.expert_layer(dh.rms_norm(h, W[p + "ffn_norm"], d.eps),
                                    W, p, ts, d, held)
            h = h + y
            stats.append(st)
        logits = dh.linear(dh.rms_norm(h, W[name + "_final_norm"], d.eps),
                           W[name + "_head"])
        return logits, {"layers": new_layers,
                        "expert_stats": cache["expert_stats"]
                        + jnp.stack(stats)}

    return step_fn, make_cache


def make_latent_sparse_lm_pooled_step_fn(state, cfg, name: str = "lm",
                                          kv_dtype: str = "bf16", held=None,
                                          prefill_tokens: int = 512):
    """The slot-pooled step AND the chunked prefill of a decoder whose
    every block is multi-head LATENT attention read through a learned
    top-k selection (a lightning indexer), then a dense SwiGLU or
    group-limited routed experts beside a shared expert (``model_type:
    deepseek_v32``; the parts and the equations are
    ``paddle_tpu.latent_sparse_lm``, the expert layer
    ``paddle_tpu.routed_experts``, the leaves and the selected read
    ``paddle_tpu.decode_attention``).

    Returns ``(step_fn, make_cache, prefill_fn)`` with the contract of
    :func:`make_windowed_routed_lm_pooled_step_fn`.  ``state``: weights
    under ``latent_sparse_lm.param_shapes(cfg, held=held)``, multiplied in
    the dtype they are given (router, biases and norms float32);
    ``held``: the contiguous range of experts whose matrices ``state``
    holds — every sparse layer routes over all ``n_routed_experts_all``
    (inside the best groups) and adds what the held ones give, plus its
    shared expert.

    The cache is ``{"layers": [...], "expert_stats": ...}``: every layer
    ``decode_attention.latent_leaves`` — ``latent`` ``[N, T, kv_lora_rank
    + rope]`` and ``index_k`` ``[N, T, index_head_dim]`` in ``kv_dtype``,
    each zero-padded to whole 128-lane tiles (576 -> 640), both sequence
    leaves of the length rung; ``expert_stats`` ``[sparse
    layers, 4]`` int32 (``slot=False``), as
    :func:`make_windowed_routed_lm_pooled_step_fn`'s.

    The step is ABSORBED: a layer appends its row ``(c, kR)`` and its
    index key in place, scores the slot's index keys against the fresh
    query's (the whole rung, masked to what is live), takes the
    ``min(index_topk, ts + 1)`` best positions, and reads those rows of
    the latent leaf alone with queries projected into the latent space
    (``decode_attention.selected_latent_attention``): the cache is never
    expanded to heads.  ``prefill_fn`` feeds slot ``row`` ``C =
    prefill_tokens`` prompt tokens at ``start .. start + n_valid - 1``
    through every layer in one call and no logits, EXPANDED: each query
    selects among the keys ``0 .. its own`` (the chunk's rows written
    first) and attends to what it selected, a key block at a time
    (``latent_sparse_lm.chunk_select``, ``chunk_attend_expanded``); the
    chunk's rows go through the expert layer as a step's rows do.  It
    equals ``n_valid`` steps leaf for leaf, but for the summation order
    (tests/test_latent_sparse_lm.py).

    The spec's ``"latent"`` read (what a query of context ``n`` reads
    of a layer: the lesser of ``n`` and ``index_topk``; it SCORES all
    ``n``) is for the server's counters.  All leaves are
    sequence leaves: ``KVSlotPool`` serves ``prefix=True`` over this
    builder by snapshots (it has a prefill).
    """
    import jax
    import jax.numpy as jnp

    from paddle_tpu import latent_sparse_lm as ls
    from paddle_tpu import routed_experts as rx
    from paddle_tpu.decode_attention import (append_latent_rows,
                                             latent_leaves, pad_lanes,
                                             selected_latent_attention)

    d = ls.dims(cfg)
    kv = _KV_STORAGE[normalize_kv_dtype(kv_dtype, ("fp32", "bf16"))]
    W = {k: jnp.asarray(v) for k, v in state.items()}
    C = int(prefill_tokens)
    n_stats = len(rx.STAT_NAMES)
    f32 = jnp.float32

    def make_cache(n_rows: int, seq_len: int):
        return {
            "layers": [latent_leaves(n_rows, seq_len, d.d_latent, d.d_index,
                                     kv) for _ in range(d.n_layer)],
            "expert_stats": jnp.zeros((len(d.expert_layers), n_stats),
                                      jnp.int32)}

    leaves = {
        "layers": [{"latent": Leaf(1), "index_k": Leaf(1)}
                   for _ in range(d.n_layer)],
        "expert_stats": Leaf(slot=False)}  # a snapshot must not carry counts

    def close_layer(h, o, p, dense, ts_rows):
        """The residual around a mixer's output ``o`` and the layer's
        FFN; ``(h, stats or None)``."""
        h = h + o
        f = ls.rms_norm(h, W[p + "ffn_norm"], d.eps)
        if dense:
            return h + ls.swiglu(f, W[p + "ffn_gate"], W[p + "ffn_up"],
                                 W[p + "ffn_down"], 1.0, 1.0), None
        y, st = rx.expert_layer(f, W, p, ts_rows, d, held)
        return h + y, st

    def projections(h, p, pos):
        with jax.named_scope(ls.LATENT_PROJECT_SCOPE):
            x = ls.rms_norm(h, W[p + "input_norm"], d.eps)
            cq, qc, qr, row = ls.latent_inputs(x, W, p, pos, d)
            qi, ki, wi = ls.index_inputs(x, cq, W, p, pos, d)
        return qc, qr, row, qi, ki, wi

    def step_fn(cache, tokens, ts):
        layers = cache["layers"]
        ts = jnp.minimum(ts, layers[0]["latent"].shape[1] - 1)
        pos = jnp.maximum(ts, 0)      # idle rows stay < 0 in ``ts``
        h = W[name + "_emb"][tokens].astype(f32)
        new_layers, stats = [], []
        for i in range(d.n_layer):
            p = "%s_l%d_" % (name, i)
            qc, qr, row, qi, ki, wi = projections(h, p, pos)
            with jax.named_scope(ls.LATENT_PROJECT_SCOPE):
                q = ls.absorb_queries(qc, qr, W, p, d)
                leaves = append_latent_rows(layers[i], row, ki, ts)
            with jax.named_scope(ls.INDEX_SCORE_SCOPE):
                scores = ls.index_scores(qi, wi, leaves["index_k"])
            with jax.named_scope(ls.INDEX_SELECT_SCOPE):
                sel, valid = ls.select_positions(scores, ts, d.index_topk)
            with jax.named_scope(ls.LATENT_ATTEND_SCOPE):
                u = selected_latent_attention(q, leaves, ts, sel, valid,
                                              d_value=d.d_c, scale=d.scale)
                o = ls.attend_out(u, W, p, d)
            new_layers.append(leaves)
            h, st = close_layer(h, o, p, d.dense[i], ts)
            if st is not None:
                stats.append(st)
        logits = ls.linear(ls.rms_norm(h, W[name + "_final_norm"], d.eps),
                           W[name + "_head"])
        counts = cache["expert_stats"]
        return logits, {"layers": new_layers,
                        "expert_stats": counts + jnp.stack(stats)
                        if stats else counts}

    def prefill_layer(c, h, p, dense, row, start, n_valid, pos, ts_q):
        qc, qr, fresh, qi, ki, wi = projections(h, p, pos)
        live = (ts_q >= 0)[:, None]
        new, mine = {}, {}
        for leaf, rows in (("latent", fresh), ("index_k", ki)):
            lanes = c[leaf].shape[2]        # whole tiles: zero-padded
            rows = pad_lanes(rows, lanes)
            old = jax.lax.dynamic_slice(c[leaf], (row, start, 0),
                                        (1, C, lanes))[0]
            new[leaf] = jax.lax.dynamic_update_slice(
                c[leaf], jnp.where(live, rows.astype(kv), old)[None],
                (row, start, 0))
            mine[leaf] = jax.lax.dynamic_index_in_dim(new[leaf], row, 0,
                                                      False)
        n_keys = start + n_valid
        with jax.named_scope(ls.INDEX_SCORE_SCOPE):
            member = ls.chunk_select(qi, wi, mine["index_k"], ts_q, n_keys,
                                     d.index_topk)
        with jax.named_scope(ls.LATENT_ATTEND_SCOPE):
            o = ls.linear(ls.chunk_attend_expanded(
                qc, qr, mine["latent"], member, n_keys, W, p, d),
                W[p + "attn_o"])
        return close_layer(h, o, p, dense, ts_q)[0], new

    def prefill_fn(cache, row, tokens, start, n_valid):
        with jax.named_scope(ls.PREFILL_CHUNK_SCOPE):
            pos = start + jnp.arange(C)
            ts_q = jnp.where(jnp.arange(C) < n_valid, pos, -1)
            h = W[name + "_emb"][tokens].astype(f32)
            new_layers = []
            for i in range(d.n_layer):
                h, new = prefill_layer(cache["layers"][i], h,
                                       "%s_l%d_" % (name, i), d.dense[i],
                                       row, start, n_valid, pos, ts_q)
                new_layers.append(new)
            return {"layers": new_layers,
                    "expert_stats": cache["expert_stats"]}

    prefill_fn.chunk_tokens = C
    declare(make_cache, CacheSpec(
        leaves, prefill_fn=prefill_fn, reads=_ragged_kv_reads(kv) + (
            PositionRead("latent", lambda n: np.minimum(n, d.index_topk),
                         layers=d.n_layer),),
        expert_stats=lambda cache: cache["expert_stats"],
        n_expert=(d.n_expert if held is None
                  else int(held[1]) - int(held[0]))))
    return step_fn, make_cache, prefill_fn


def make_latent_mtp_lm_pooled_step_fn(state, cfg, name: str = "lm",
                                       kv_dtype: str = "bf16", held=None,
                                       prefill_tokens: int = 512):
    """The slot-pooled step, the K-wide verify, the multi-token-
    prediction module's K-wide pass and the chunked prefill of a decoder
    whose every block is multi-head LATENT attention read DENSELY (every
    live position: no indexer), each branch between two norms, then a
    dense SwiGLU or routed experts beside a shared expert under an
    ungrouped, bias-free sigmoid router, with ONE module that drafts for
    the model from its own last hidden state (``model_type:
    pangu_ultra_moe``; the parts and the equations are
    ``paddle_tpu.latent_mtp_lm``, which takes the latent projections
    from ``paddle_tpu.latent_sparse_lm`` and the module's input from
    ``paddle_tpu.mtp_routed_lm``; the expert layer
    ``paddle_tpu.routed_experts``, the leaves and the dense read
    ``paddle_tpu.decode_attention``).

    Returns ``(step_fn, make_cache, prefill_fn)`` with the contract of
    :func:`make_mtp_routed_lm_pooled_step_fn`, ``verify_fn`` and
    ``mtp_fn`` (None without a module) on the spec as there.  ``state``:
    weights under ``latent_mtp_lm.param_shapes(cfg, held=held)``,
    multiplied in the dtype they are given (routers and norms float32);
    ``held``: the contiguous range of experts whose matrices ``state``
    holds.

    The cache is ``{"layers": [...], "mtp": {...}, "expert_stats":
    ...}``: every layer and the module ONE ``latent`` leaf ``[N, T,
    kv_lora_rank + rope]`` in ``kv_dtype``, zero-padded to whole 128-lane
    tiles (576 -> 640), NO ``index_k``; ``expert_stats`` ``[sparse
    layers + 1, 4]`` int32 (``slot=False``), the module's expert layer
    in the last row, counted as
    :func:`make_mtp_routed_lm_pooled_step_fn` counts.

    Step, verify and module are ABSORBED through ONE read: the ``K``
    fresh rows (1 or 2) are appended at ``ts .. ts + K - 1`` and row
    ``j`` reads every position ``<= ts + j`` of the leaf as it lies
    (``decode_attention.dense_latent_attention``): the cache is never
    expanded to heads.  ``prefill_fn(cache, row, tokens [C + 1], start,
    n_valid)`` feeds slot ``row`` ``C = prefill_tokens`` prompt tokens
    through every layer AND the module (``prefill_fn.lookahead`` = 1),
    EXPANDED, a key block at a time, causal membership in the place of a
    selection (``latent_sparse_lm.chunk_attend_expanded``); the chunk's
    rows go through the expert layer as a step's rows do.  It equals
    ``n_valid`` steps leaf for leaf, but for the summation order
    (tests/test_latent_mtp_lm.py).

    The spec's ``"latent"`` read (a query of context ``n`` reads all
    ``n`` positions in each of the layers and the module) and, over bf16
    leaves, its ``"kv"`` read (what the read's lowering TOUCHES of a
    slot's leaf: :func:`_dense_latent_kv_reads`) are for the server's
    counters.  All leaves are sequence leaves: ``KVSlotPool``
    serves ``prefix=True`` over this builder by snapshots, and a
    self-drafting round over them.
    """
    import jax
    import jax.numpy as jnp

    from paddle_tpu import latent_mtp_lm as lm
    from paddle_tpu import routed_experts as rx
    from paddle_tpu.decode_attention import (append_latent_rows,
                                             dense_latent_attention,
                                             latent_leaves, pad_lanes)

    d = lm.dims(cfg)
    kv = _KV_STORAGE[normalize_kv_dtype(kv_dtype, ("fp32", "bf16"))]
    W = {k: jnp.asarray(v) for k, v in state.items()}
    C = int(prefill_tokens)
    n_stats = len(rx.STAT_NAMES)
    stat_rows = len(d.expert_layers) + d.n_mtp
    p_mtp = lm.layer_prefix(name, lm.MTP_LAYER)
    f32 = jnp.float32

    def make_cache(n_rows: int, seq_len: int):
        leaf = lambda: latent_leaves(n_rows, seq_len, d.d_latent, None, kv)
        cache = {"layers": [leaf() for _ in range(d.n_layer)],
                 "expert_stats": jnp.zeros((stat_rows, n_stats), jnp.int32)}
        if d.n_mtp:
            cache["mtp"] = leaf()
        return cache

    leaves = {"layers": [{"latent": Leaf(1)} for _ in range(d.n_layer)],
              "expert_stats": Leaf(slot=False)}
    if d.n_mtp:
        leaves["mtp"] = {"latent": Leaf(1)}

    def block(h, c, p, dense, shape, ts, ts_rows, pos_rows):
        """One block over the fresh rows ``h`` ``[S * K, d_model]``;
        returns ``(h, stats, leaves)``."""
        n = h.shape[0]
        with jax.named_scope(lm.LATENT_PROJECT_SCOPE):
            x = lm.rms_norm(h, W[p + "input_norm"], d.eps)
            _, qc, qr, row = lm.latent_inputs(x, W, p, pos_rows, d)
            q = lm.absorb_queries(qc, qr, W, p, d)
            kvs = append_latent_rows(
                c, row.reshape(tuple(shape) + (-1,)), None, ts)
        with jax.named_scope(lm.LATENT_ATTEND_SCOPE):
            u = dense_latent_attention(
                q.reshape(tuple(shape) + q.shape[1:]), kvs, ts,
                d_value=d.d_c, scale=d.scale)
            o = lm.attend_out(u.reshape(n, d.n_head, d.d_c), W, p, d)
        h = lm.close_attention(h, o, W, p, d)
        return lm.ffn_branch(h, W, p, dense, ts_rows, d, held) + (kvs,)

    def head(h):
        return lm.linear(lm.rms_norm(h, W[name + "_final_norm"], d.eps),
                         W[name + "_head"])

    counted = functools.partial(_counted_experts,
                                n_sparse=len(d.expert_layers), n_mtp=d.n_mtp)

    def clamp(cache, ts):
        return jnp.minimum(ts, cache["layers"][0]["latent"].shape[1] - 1)

    def forward(cache, tokens, ts):
        """The layers over ``tokens`` ``[S, K]``: ``(logits [S, K, V],
        hidden [S, K, d_model], cache)``."""
        ts = clamp(cache, ts)
        shape = tokens.shape
        ts_rows, pos_rows = _fresh_rows(ts, shape)
        h = W[name + "_emb"][tokens.reshape(-1)].astype(f32)
        new_layers, stats = [], []
        for i in range(d.n_layer):
            h, st, kvs = block(h, cache["layers"][i],
                               lm.layer_prefix(name, i), d.dense[i], shape,
                               ts, ts_rows, pos_rows)
            new_layers.append(kvs)
            if st is not None:
                stats.append(st)
        out = dict(cache, layers=new_layers,
                   expert_stats=counted(cache, stats))
        by = tuple(shape) + (-1,)
        return head(h).reshape(by), h.reshape(by), out

    def step_fn(cache, tokens, ts):
        logits, _, cache = forward(cache, tokens[:, None], ts)
        return logits[:, 0], cache

    def verify_fn(cache, tokens, ts):
        with jax.named_scope(lm.SPEC_VERIFY_SCOPE):
            return forward(cache, tokens, ts)

    def mtp_fn(cache, hidden, next_tokens, ts):
        with jax.named_scope(lm.MTP_MODULE_SCOPE):
            ts = clamp(cache, ts)
            shape = next_tokens.shape
            ts_rows, pos_rows = _fresh_rows(ts, shape)
            u = lm.module_input(
                hidden.reshape(-1, d.d_model),
                W[name + "_emb"][next_tokens.reshape(-1)].astype(f32),
                W, p_mtp, d)
            u, st, kvs = block(u, cache["mtp"], p_mtp, False, shape, ts,
                               ts_rows, pos_rows)
            out = dict(cache, mtp=kvs,
                       expert_stats=counted(cache, None, st))
            return head(u).reshape(tuple(shape) + (-1,)), out

    def prefill_layer(c, h, p, dense, row, start, n_valid, pos, ts_q):
        with jax.named_scope(lm.LATENT_PROJECT_SCOPE):
            x = lm.rms_norm(h, W[p + "input_norm"], d.eps)
            _, qc, qr, fresh = lm.latent_inputs(x, W, p, pos, d)
        leaf = c["latent"]
        lanes = leaf.shape[2]               # whole tiles: zero-padded
        old = jax.lax.dynamic_slice(leaf, (row, start, 0), (1, C, lanes))[0]
        new = jax.lax.dynamic_update_slice(
            leaf, jnp.where((ts_q >= 0)[:, None],
                            pad_lanes(fresh, lanes).astype(kv), old)[None],
            (row, start, 0))
        mine = jax.lax.dynamic_index_in_dim(new, row, 0, False)
        # every query reads the positions 0 .. its own: causal membership
        # where the indexed form hands a selection
        member = jnp.arange(leaf.shape[1])[None, :] <= ts_q[:, None]
        with jax.named_scope(lm.LATENT_ATTEND_SCOPE):
            o = lm.linear(lm.chunk_attend_expanded(
                qc, qr, mine, member, start + n_valid, W, p, d),
                W[p + "attn_o"])
        h = lm.close_attention(h, o, W, p, d)
        return lm.ffn_branch(h, W, p, dense, ts_q, d, held)[0], {
            "latent": new}

    def prefill_fn(cache, row, tokens, start, n_valid):
        with jax.named_scope(lm.PREFILL_CHUNK_SCOPE):
            pos = start + jnp.arange(C)
            ts_q = jnp.where(jnp.arange(C) < n_valid, pos, -1)
            emb = W[name + "_emb"]
            h = emb[tokens[:C]].astype(f32)
            new_layers = []
            for i in range(d.n_layer):
                h, new = prefill_layer(cache["layers"][i], h,
                                       lm.layer_prefix(name, i), d.dense[i],
                                       row, start, n_valid, pos, ts_q)
                new_layers.append(new)
            out = dict(cache, layers=new_layers)
            if d.n_mtp:
                with jax.named_scope(lm.MTP_MODULE_SCOPE):
                    u = lm.module_input(h, emb[tokens[1:]].astype(f32), W,
                                        p_mtp, d)
                    _, out["mtp"] = prefill_layer(
                        cache["mtp"], u, p_mtp, False, row, start, n_valid,
                        pos, ts_q)
            return out

    prefill_fn.chunk_tokens = C
    prefill_fn.lookahead = 1 if d.n_mtp else 0
    declare(make_cache, CacheSpec(
        leaves, prefill_fn=prefill_fn, verify_fn=verify_fn,
        mtp_fn=mtp_fn if d.n_mtp else None,
        reads=_dense_latent_kv_reads(d, kv) + (
            PositionRead("latent", lambda n: n, layers=d.n_layer + d.n_mtp),),
        expert_stats=lambda cache: cache["expert_stats"],
        n_expert=(d.n_expert if held is None
                  else int(held[1]) - int(held[0]))))
    return step_fn, make_cache, prefill_fn


def make_kda_latent_lm_pooled_step_fn(state, cfg, name: str = "lm",
                                      kv_dtype: str = "bf16", held=None,
                                      prefill_tokens: int = 512):
    """The slot-pooled step AND the chunked prefill of a decoder whose
    layers are KIMI DELTA ATTENTION (a recurrent state and a conv window
    a slot) or multi-head LATENT attention read densely and position-free
    (one compressed row a position), pre-norm, the leading layers' FFN a
    dense SwiGLU and every other layer's routed experts beside a shared
    expert (``model_type: kimi_linear``; the sizes and the schema are
    ``paddle_tpu.kda_latent_lm``, the K layer's parts
    ``paddle_tpu.delta_hybrid_lm``'s ``kda_*``, the M layer's
    ``paddle_tpu.latent_sparse_lm``'s, the expert layer
    ``paddle_tpu.routed_experts``, the latent leaf and its dense read
    ``paddle_tpu.decode_attention``).

    Returns ``(step_fn, make_cache, prefill_fn)`` with the contract of
    :func:`make_sparse_linear_lm_pooled_step_fn`.  ``state``: weights
    under ``kda_latent_lm.param_shapes(cfg, held=held)``, multiplied in
    the dtype they are given (router, bias, norms, the conv kernel,
    ``A_log`` and ``dt_bias`` float32); ``held``: the contiguous range of
    experts whose matrices ``state`` holds.

    The cache is ``{"layers": [...], "expert_stats": ...}``: a K layer
    ``state`` ``[N, H / g, dk, g * dv]`` and ``conv`` ``[N, K - 1, 2 H dk
    + H dv]`` float32, RECURRENT; an M layer ONE ``latent`` leaf ``[N, T,
    kv_lora_rank + shared]`` in ``kv_dtype``, zero-padded to whole
    128-lane tiles (576 -> 640), a sequence leaf; ``expert_stats``
    ``[sparse layers, 4]`` int32 (``slot=False``).

    The step is one token a row: a K layer by ``kda_layer_step`` (the
    rule's kernel on a TPU), an M layer ABSORBED through
    ``decode_attention.dense_latent_attention`` at ``K`` = 1 (every live
    position of the leaf as it lies).  ``prefill_fn(cache, row, tokens
    [C], start, n_valid)`` feeds slot ``row`` ``C = prefill_tokens``
    prompt tokens at ``start .. start + n_valid - 1`` through every layer
    in ONE call and no logits: a K layer by the rule's CHUNKWISE form from
    the slot's state and conv window (zero at ``start == 0``), leaving
    both at ``start + n_valid``; an M layer EXPANDED, a key block at a
    time under causal membership (``chunk_attend_expanded``); the chunk's
    rows go through the expert layer as a step's rows do.  It equals
    ``n_valid`` steps leaf for leaf, but for the summation order
    (tests/test_kda_latent_lm.py).  Because a prefill can stop at a
    boundary, ``KVSlotPool`` serves ``prefix=True`` over this builder by
    whole-row SNAPSHOTS that carry the delta state, the conv window and
    the latent rows together; it refuses ``speculative=`` (recurrent
    leaves cannot be rolled back).

    The spec's ``"latent"`` read (a query of context ``n`` reads all
    ``n`` positions in each M layer) and, over bf16 leaves, its ``"kv"``
    read (what the dense read's lowering TOUCHES of a slot's leaf) are
    for the server's counters.
    """
    import jax
    import jax.numpy as jnp

    from paddle_tpu import delta_hybrid_lm as dh
    from paddle_tpu import kda_latent_lm as kl
    from paddle_tpu import latent_sparse_lm as ls
    from paddle_tpu import routed_experts as rx
    from paddle_tpu.decode_attention import (append_latent_rows,
                                             dense_latent_attention,
                                             latent_leaves, pad_lanes)

    d = kl.dims(cfg)
    kv = _KV_STORAGE[normalize_kv_dtype(kv_dtype, ("fp32", "bf16"))]
    W = {k: jnp.asarray(v) for k, v in state.items()}
    C = int(prefill_tokens)
    n_stats = len(rx.STAT_NAMES)
    latent_at = [i for i, kind in enumerate(d.kinds) if kind == kl.LATENT]
    f32 = jnp.float32

    def make_cache(n_rows: int, seq_len: int):
        return {
            "layers": [
                latent_leaves(n_rows, seq_len, d.d_latent, None, kv)
                if kind == kl.LATENT else
                {"state": jnp.zeros((n_rows,) + d.state_shape, f32),
                 "conv": jnp.zeros((n_rows, d.conv_len - 1, d.d_qkv), f32)}
                for kind in d.kinds],
            "expert_stats": jnp.zeros((len(d.expert_layers), n_stats),
                                      jnp.int32)}

    leaves = {
        "layers": [{"latent": Leaf(1)} if kind == kl.LATENT
                   else {"state": Leaf(), "conv": Leaf()}
                   for kind in d.kinds],
        "expert_stats": Leaf(slot=False)}  # a snapshot must not carry counts

    def close_layer(h, o, p, dense, ts_rows):
        """The residual around a mixer's output ``o`` and the layer's
        FFN; ``(h, stats or None)``."""
        h = h + o
        f = dh.rms_norm(h, W[p + "ffn_norm"], d.eps)
        if dense:
            return h + dh.swiglu(f, W[p + "ffn_gate"], W[p + "ffn_up"],
                                 W[p + "ffn_down"], 1.0, 1.0), None
        y, st = rx.expert_layer(f, W, p, ts_rows, d, held)
        return h + y, st

    def step_fn(cache, tokens, ts):
        layers = cache["layers"]
        if latent_at:
            ts = jnp.minimum(ts, layers[latent_at[0]]["latent"].shape[1] - 1)
        pos = jnp.maximum(ts, 0)      # idle rows stay < 0 in ``ts``
        n = tokens.shape[0]
        h = W[name + "_emb"][tokens].astype(f32)
        new_layers, stats = [], []
        for i, kind in enumerate(d.kinds):
            p = "%s_l%d_" % (name, i)
            c = layers[i]
            x = dh.rms_norm(h, W[p + "mixer_norm"], d.eps)
            if kind == kl.KDA:
                o, s, conv = dh.kda_layer_step(x, W, p, c["state"],
                                               c["conv"], ts, d)
                new_layers.append({"state": s, "conv": conv})
            else:
                with jax.named_scope(ls.LATENT_PROJECT_SCOPE):
                    _, qc, qr, row = ls.latent_inputs(x, W, p, pos, d)
                    q = ls.absorb_queries(qc, qr, W, p, d)
                    kvs = append_latent_rows(c, row[:, None], None, ts)
                with jax.named_scope(ls.LATENT_ATTEND_SCOPE):
                    u = dense_latent_attention(
                        q[:, None], kvs, ts, d_value=d.d_c, scale=d.scale)
                    o = ls.attend_out(u.reshape(n, d.n_head, d.d_c), W, p, d)
                new_layers.append(kvs)
            h, st = close_layer(h, o, p, d.dense[i], ts)
            if st is not None:
                stats.append(st)
        logits = dh.linear(dh.rms_norm(h, W[name + "_final_norm"], d.eps),
                           W[name + "_head"])
        counts = cache["expert_stats"]
        return logits, {"layers": new_layers,
                        "expert_stats": counts + jnp.stack(stats)
                        if stats else counts}

    def prefill_layer(c, kind, h, p, dense, row, start, n_valid, pos, ts_q):
        x = dh.rms_norm(h, W[p + "mixer_norm"], d.eps)
        if kind == kl.KDA:
            mine = {leaf: jax.lax.dynamic_index_in_dim(c[leaf], row, 0, False)
                    for leaf in ("state", "conv")}
            o, s, conv = dh.kda_layer_chunk(x, W, p, mine["state"],
                                            mine["conv"], start, n_valid, d)
            new = {"state": jax.lax.dynamic_update_index_in_dim(
                       c["state"], s, row, 0),
                   "conv": jax.lax.dynamic_update_index_in_dim(
                       c["conv"], conv, row, 0)}
            return close_layer(h, o, p, dense, ts_q)[0], new
        with jax.named_scope(ls.LATENT_PROJECT_SCOPE):
            _, qc, qr, fresh = ls.latent_inputs(x, W, p, pos, d)
        leaf = c["latent"]
        lanes = leaf.shape[2]               # whole tiles: zero-padded
        old = jax.lax.dynamic_slice(leaf, (row, start, 0), (1, C, lanes))[0]
        new = jax.lax.dynamic_update_slice(
            leaf, jnp.where((ts_q >= 0)[:, None],
                            pad_lanes(fresh, lanes).astype(kv), old)[None],
            (row, start, 0))
        mine = jax.lax.dynamic_index_in_dim(new, row, 0, False)
        # every query reads the positions 0 .. its own
        member = jnp.arange(leaf.shape[1])[None, :] <= ts_q[:, None]
        with jax.named_scope(ls.LATENT_ATTEND_SCOPE):
            o = ls.linear(ls.chunk_attend_expanded(
                qc, qr, mine, member, start + n_valid, W, p, d),
                W[p + "attn_o"])
        return close_layer(h, o, p, dense, ts_q)[0], {"latent": new}

    def prefill_fn(cache, row, tokens, start, n_valid):
        with jax.named_scope(ls.PREFILL_CHUNK_SCOPE):
            pos = start + jnp.arange(C)
            ts_q = jnp.where(jnp.arange(C) < n_valid, pos, -1)
            h = W[name + "_emb"][tokens].astype(f32)
            new_layers = []
            for i, kind in enumerate(d.kinds):
                h, new = prefill_layer(cache["layers"][i], kind, h,
                                       "%s_l%d_" % (name, i), d.dense[i],
                                       row, start, n_valid, pos, ts_q)
                new_layers.append(new)
            return {"layers": new_layers,
                    "expert_stats": cache["expert_stats"]}

    prefill_fn.chunk_tokens = C
    declare(make_cache, CacheSpec(
        leaves, prefill_fn=prefill_fn,
        reads=_dense_latent_kv_reads(d, kv) + (
            PositionRead("latent", lambda n: n, layers=len(latent_at)),),
        expert_stats=lambda cache: cache["expert_stats"],
        n_expert=(d.n_expert if held is None
                  else int(held[1]) - int(held[0]))))
    return step_fn, make_cache, prefill_fn


def make_transformer_lm_pooled_verify_fn(
    state,
    vocab_size: int,
    d_model: int,
    n_layer: int,
    n_head: int,
    d_inner: int,
    name: str = "lm",
    kv_dtype: str = "fp32",
):
    """The K-wide teacher-forced forward for speculative verification.

    ``verify_fn(cache, tokens [S, K] int32, ts [S] int32) -> (logits
    [S, K, V], cache)``: row ``i`` consumes ``tokens[i, j]`` at position
    ``ts[i] + j`` for every ``j`` in ONE call.  It IS the pooled step
    (:func:`make_transformer_lm_pooled_step_fn`, same ``kv_dtype``) at
    ``K`` fresh rows per slot: the same block and the same append and
    read (``decode_attention``'s contract at ``K`` rows: all ``K`` rows
    are written before any is read, row ``j`` attends to positions
    ``<= ts + j``, an int8 row is quantized as the sequential step
    quantizes it), so ``argmax(logits[i, j])`` is the token the
    sequential path produces after consuming ``tokens[i, :j + 1]`` —
    what makes greedy-exact speculative acceptance output-identical
    (tests/test_prefix_cache.py).

    A row past the cache T axis is not written, and its logits are
    garbage: such lanes are beyond their sequence's length cap and are
    never committed.  ``ts[i] < 0`` marks an idle row, as in the pooled
    step: none of its cache row is written.
    """
    import jax.numpy as jnp

    forward, _, W, _ = _pooled_lm_parts(
        state, d_model, n_layer, n_head, name, kv_dtype)

    def verify_fn(cache, tokens, ts):
        T = cache[0]["k"].shape[1]
        p = jnp.minimum(jnp.maximum(ts, 0)[:, None]
                        + jnp.arange(tokens.shape[1])[None, :], T - 1)
        x = W[name + "_word_emb"][tokens] + W[name + "_pos_emb"][p]
        return forward(cache, x, ts)

    return verify_fn


# ---------------------------------------------------------------------------
# Slot-pool decode: the fused multi-token chunk + admit executables
# ---------------------------------------------------------------------------
def make_slot_decode_fns(step_fn, eos_id: int, steps: int,
                         draft_step_fn=None):
    """Build the three pure functions the serving slot pool compiles per
    (slot-rung, length-rung) pair: ``chunk(state) -> state`` advancing
    every active slot by up to ``steps`` tokens in ONE device dispatch
    (a ``fori_loop`` — multi-step dispatch amortizes host overhead
    between scheduler interventions), ``admit(state, slot_mask,
    prompts, prompt_len, total_len) -> state`` seating a SET of requests
    into free slots at once — everything a scheduler turn admits rides
    one dispatch — and ``release(state, slot_mask) -> state``
    deactivating slots mid-flight (deadline abort) so their lanes stop
    advancing.

    The pool state is a dict pytree (every leaf's axis 0 is the slot):

    * ``cache``    — the step fn's cache pytree (T axis read by the
      step): leaves with a sequence axis (K/V), which the
      write-before-read invariant covers, and possibly recurrent leaves
      with none, which the STEP starts from zero for a row at position
      0 (``make_hybrid_ssm_lm_pooled_step_fn``) — ``admit`` seats every
      request at ``pos = 0``, so neither ``admit`` nor ``release``
      touches the cache (a request seated over a prefix SNAPSHOT starts
      at ``pos = prefix_len`` with its recurrent leaves installed:
      :func:`make_prefix_admit_fn`)
    * ``tokens``   — [S, T] int32, position-indexed token buffer
    * ``pos``      — [S] int32, tokens consumed so far (the step eats
      index ``pos`` and produces the token for ``pos + 1``)
    * ``prompt_len``/``total_len`` — [S] int32 per-slot prompt size and
      overall length cap (prompt + generated <= total_len <= T)
    * ``active``/``finished`` — [S] bool scheduler flags
    * ``n_gen``    — [S] int32 generated-token count (prefill/decode
      ratio accounting reads the deltas host-side)

    In THESE three functions prefill and decode are the same step:
    while ``pos + 1 < prompt_len`` the produced token is discarded in
    favor of the stored prompt token (teacher forcing), so a prompt
    seated at ``pos = 0`` fills its cache inside the running batch — no
    separate prefill executable, no second compiled shape.  That is the
    whole truth for a builder that declares no prefill.  For one that
    declares a BATCHED prefill (``CacheSpec.prefill_rows_fn``,
    :func:`make_transformer_lm_pooled_step_fn`) the pool compiles one
    more function, which takes ``admit``'s place at a turn's admission:
    it seats the turn's requests AND feeds each all of its prompt but
    the last token in one dispatch, so a slot enters ``chunk`` at ``pos
    = prompt_len - 1`` and its first step here produces its first token
    (``KVSlotPool.seat_prefill``).  For one that declares a CHUNKED
    prefill (``CacheSpec.prefill_fn``,
    :func:`make_sparse_linear_lm_pooled_step_fn`) the pool compiles one
    more function beside these three, which feeds ``C`` prompt tokens
    of ONE slot a dispatch while the slot is held inactive (so ``chunk``
    leaves it alone: an inactive slot is fully masked), and only the
    remainder shorter than ``C`` is teacher-forced here.  A slot
    finishes when it emits ``eos_id``
    or reaches ``total_len``; inactive slots are fully masked (their
    ``pos`` does not advance), reach the step as ``ts = -1`` so their
    cache rows are neither read nor written, and cost only the wasted
    lane math the bucket ladder already prices in.

    Extra state leaves pass through untouched (dict-copy semantics), so
    the speculative pool's ``spec`` flag and ``draft_cache`` ride the
    same executables.  With ``draft_step_fn`` the plain chunk also
    teacher-forces each consumed token through the draft model, keeping
    ``state["draft_cache"]`` position-synced with the target — a slot
    that alternates plain and speculative rounds never sees a stale
    draft cache (write-before-read covers the rest).  ``admit`` grows an
    optional trailing ``spec_flag`` marking which of the seated slots are
    speculative.

    ``admit``'s arguments are indexed BY SLOT: ``slot_mask`` [S] bool
    (the slots being seated), ``prompts`` [S, T] int32 (row ``i`` is slot
    ``i``'s padded prompt; rows outside the mask are ignored),
    ``prompt_len`` / ``total_len`` / ``spec_flag`` [S].  Slot-indexed
    rows keep the argument shapes a function of the rung pair alone, so
    a batch of one and a batch of S are the SAME executable and no count
    of admissions is ever a compiled shape.  Each argument also
    broadcasts from one request's form (``prompts`` [T], scalar
    lengths), which is how :func:`make_prefix_admit_fn` seats its single
    request through the same function.
    """
    import jax
    import jax.numpy as jnp

    def _body(_, state):
        tokens = state["tokens"]
        pos = state["pos"]
        active = state["active"]
        S, T = tokens.shape
        rows = jnp.arange(S)
        tok_in = tokens[rows, jnp.minimum(pos, T - 1)]
        # an idle slot keeps its stale ``pos``: tell the step (ts < 0)
        # so it neither reads nor writes that slot's cache row
        ts = jnp.where(active, pos, -1)
        logits, cache = step_fn(state["cache"], tok_in, ts)
        nxt = jnp.argmax(logits, axis=-1).astype("int32")
        write_idx = jnp.minimum(pos + 1, T - 1)
        in_prefill = (pos + 1) < state["prompt_len"]
        do_write = active & ~in_prefill
        cur = tokens[rows, write_idx]
        tokens = tokens.at[rows, write_idx].set(
            jnp.where(do_write, nxt, cur))
        newly_fin = do_write & (
            (nxt == eos_id) | ((pos + 2) >= state["total_len"]))
        out = dict(state)
        out.update(
            cache=cache,
            tokens=tokens,
            pos=jnp.where(active, pos + 1, pos),
            active=active & ~newly_fin,
            finished=state["finished"] | newly_fin,
            n_gen=state["n_gen"] + do_write.astype("int32"))
        if draft_step_fn is not None:
            _, out["draft_cache"] = draft_step_fn(
                state["draft_cache"], tok_in, ts)
        return out

    def chunk(state):
        return jax.lax.fori_loop(0, steps, _body, state)

    def admit(state, slot_mask, prompts, prompt_len, total_len,
              spec_flag=None):
        # slot_mask [S] bool (every slot seated by this call), prompts
        # [S, T] int32 (padded host-side, indexed by slot), prompt_len /
        # total_len / spec_flag [S]; one request's [T] and scalars
        # broadcast.  The cache passes through UNTOUCHED: the
        # write-before-read invariant (see
        # make_transformer_lm_pooled_step_fn) makes zeroing a reused
        # slot's K/V rows unnecessary, and a step with recurrent leaves
        # reads them as zero at the ``pos = 0`` set here
        # (make_hybrid_ssm_lm_pooled_step_fn).
        mask = slot_mask
        out = dict(state)
        out.update(
            tokens=jnp.where(mask[:, None], prompts, state["tokens"]),
            pos=jnp.where(mask, 0, state["pos"]),
            prompt_len=jnp.where(mask, prompt_len, state["prompt_len"]),
            total_len=jnp.where(mask, total_len, state["total_len"]),
            active=state["active"] | mask,
            finished=state["finished"] & ~mask,
            n_gen=jnp.where(mask, 0, state["n_gen"]))
        if spec_flag is not None:
            out["spec"] = jnp.where(mask, spec_flag, state["spec"])
        return out

    def release(state, slot_mask):
        # deactivate without finishing: the slot becomes seatable again
        # (its request was aborted host-side); tokens/cache stay — the
        # write-before-read invariant protects the next occupant's K/V
        # rows, and its recurrent state starts from zero at its pos 0
        out = dict(state)
        out.update(
            active=state["active"] & ~slot_mask,
            finished=state["finished"] & ~slot_mask)
        return out

    return chunk, admit, release


# ---------------------------------------------------------------------------
# Prefix KV installation (serving.prefix_cache's device half)
# ---------------------------------------------------------------------------
def make_prefix_admit_fn(admit_fn, kinds, whole_rows: bool = False):
    """Wrap a :func:`make_slot_decode_fns` ``admit`` with shared-prefix
    installation: ``admit_prefix(state, slot_mask, prompt,
    prompt_len, total_len, kv_leaves, prefix_len[, spec_flag])`` seats
    the request as usual, then overwrites the slot's first
    ``prefix_len`` cache positions with the retained rows and
    starts ``pos`` at ``prefix_len`` — prefill resumes at the unmatched
    suffix.

    ``kv_leaves`` is the flattened leaf list of the state's cache
    subtrees (``cache`` plus ``draft_cache`` when present, in
    tree-flatten order), each leaf shaped like one slot's row of the
    state's leaf (sequence leaves padded to the length rung); a leaf
    that is not installed carries a ``(1,)`` dummy.  What is installed
    is STATIC (``kinds``: the :class:`Leaf` of every one of those
    leaves, as the pool's builders declared them — and the leaf
    shapes), so one compiled
    executable per rung pair serves every cached prefix length —
    ``prefix_len`` stays a dynamic scalar:

    * a leaf WITH a sequence axis is installed under the position mask:
      its rows below ``prefix_len // stride``;
    * a RECURRENT leaf (no sequence axis) given whole is installed
      whole: the retained entry is then a SNAPSHOT, the state as it
      stood when exactly ``prefix_len`` positions had been consumed, and
      the slot does not start a sequence at position 0 — it resumes one
      at ``prefix_len``, where no step reads the state as zero.  Only a
      builder with a chunked prefill can stop at a boundary to take
      such a snapshot (``KVSlotPool``); given a dummy, the leaf is left
      alone, as before.

    ``whole_rows`` (a pool whose entries are snapshots: every leaf a
    slot's whole row): the one seated slot's row of every leaf is
    REPLACED by the snapshot's (a ``dynamic_update_slice`` of that row:
    82 MB at the ``minicpm_sala`` rung) instead of selected under a mask
    over the whole pool (5.2 GB read and written there: 16 ms an
    admission on the chip, PR 31).  The rows past ``prefix_len`` it
    brings along are whatever the snapshot's slot held; like a reused
    slot's own stale rows they are never read before they are written.

    Positional embeddings are absolute, so retained rows are
    position-correct for any matching prompt.
    """
    import jax
    import jax.numpy as jnp

    def admit_prefix(state, slot_mask, prompt, prompt_len, total_len,
                     kv_leaves, prefix_len, spec_flag=None):
        if spec_flag is None:
            out = admit_fn(state, slot_mask, prompt, prompt_len,
                           total_len)
        else:
            out = admit_fn(state, slot_mask, prompt, prompt_len,
                           total_len, spec_flag)
        S = state["tokens"].shape[0]
        sub = {"cache": out["cache"]}
        if "draft_cache" in out:
            sub["draft_cache"] = out["draft_cache"]
        leaves, treedef = jax.tree_util.tree_flatten(sub)
        new_leaves = []
        for cur, pre, kind in zip(leaves, kv_leaves, kinds):
            ax, stride = kind.seq_axis, kind.stride
            if tuple(pre.shape) != tuple(cur.shape[1:]):
                new_leaves.append(cur)
                continue
            if whole_rows:
                new_leaves.append(jax.lax.dynamic_update_index_in_dim(
                    cur, pre.astype(cur.dtype), jnp.argmax(slot_mask), 0))
                continue
            sel = slot_mask.reshape((S,) + (1,) * (cur.ndim - 1))
            if ax is not None:
                kshape = [1] * cur.ndim
                kshape[ax] = cur.shape[ax]
                rows = (prefix_len if stride == 1
                        else prefix_len // stride)
                sel = sel & (jnp.arange(cur.shape[ax]) < rows).reshape(
                    kshape)
            new_leaves.append(
                jnp.where(sel, pre[None].astype(cur.dtype), cur))
        sub = jax.tree_util.tree_unflatten(treedef, new_leaves)
        out["cache"] = sub["cache"]
        if "draft_cache" in sub:
            out["draft_cache"] = sub["draft_cache"]
        out["pos"] = jnp.where(slot_mask, prefix_len, out["pos"])
        return out

    return admit_prefix
