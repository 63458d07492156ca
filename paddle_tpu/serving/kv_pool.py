"""Bucketed KV-cache slot pool for continuous-batching decode.

The serving batcher's zero-recompile story (``BucketPolicy``: pad every
batch onto a closed ladder of sizes, ``warmup()`` pre-compiles each
rung) extends here to AUTOREGRESSIVE state: a decode step's executable
is shaped by (slot count, cache length), so the pool quantizes both
onto ladders — ``slot_ladder`` rungs over batch slots x ``len_ladder``
rungs over sequence length — and AOT-compiles the two pure functions
the scheduler dispatches (``decoding.make_slot_decode_fns``: the
multi-step ``chunk``, the ``admit`` that seats everything a scheduler
turn admits in one dispatch, and ``release``) for every rung pair at
:meth:`warmup`.  After warmup, a mixed prompt/decode storm runs
entirely on warmed executables — the pool's
:meth:`jit_cache_stats` is the recompile ground truth the serving
``/statusz`` reports, exactly like ``AnalysisPredictor`` on the
request-batching path.

The pool state is one dict pytree (slot axis 0 on every leaf; the KV
cache's T axis read by the step fn).  What each cache leaf is — a
sequence leaf, a RING leaf, a RECURRENT leaf, a leaf of pool-wide counts
— the builder says in its ONE declaration (``decoding.CacheSpec``, a
``decoding.Leaf`` a leaf, read through ``decoding.spec_of``: the kinds
are explained there, once); the pool infers nothing from a shape and
refuses a ``make_cache`` that declares nothing.  Sequence leaves are
what ``extract_kv`` / ``admit_prefix`` slice and ``kv_rung_bytes``
counts; recurrent ones ``recurrent_rung_bytes`` counts; a ring leaf is
allocated, resized (cut or padded to ``min(new rung, W)`` rows), counted
and carried whole by ``snapshot`` / ``admit_prefix`` like any other.
What would slice a ring by positions or roll a state back
(``extract_kv``, ``prefix=True`` without a chunked prefill,
``speculative=`` over recurrent leaves or with ``k > 2`` over ring
leaves) is refused at construction, each with its reason.

What else a pool compiles follows from what the builder declares, and
the two prefills are separate paths below that one chooser (their needs
conflict: one-pass seating here, snapshot boundaries and held slots
there).  A builder with a BATCHED prefill (``CacheSpec.prefill_rows_fn``:
several slots' prompts from position 0 in one ``C``-wide forward) gets
one more executable a rung pair, ``seat_prefill``: a scheduler turn's
seats travel as compact rows, are seated by scatter and fed all of
their prompts but the last token in the same dispatch, in three widths
(:meth:`KVSlotPool.prefill_classes`), each a loop with as many forwards
as the turn's seats of that width need — so a prompt never walks the
one-token step, and the slot's first step produces its first token.
Not with a draft model attached (nothing would feed the draft's cache).
A builder with a chunked prefill (``CacheSpec.prefill_fn``) gets one more
executable a rung pair, ``prefill``: ``C`` prompt tokens of ONE slot in
one dispatch, for a slot the scheduler holds out of the decode chunk
until its last whole chunk is in.  Because such a prefill can stop at a
boundary, ``prefix=True`` over recurrent leaves is then served by
SNAPSHOTS: the slot's whole cache row — sequence leaves and recurrent
leaves alike — copied on the device where a prefilled prompt's last
whole chunk ends (``snapshot``), and installed whole by ``admit_prefix``
for a later prompt that starts with the same tokens.  A pool over
recurrent leaves whose builder has NO prefill still refuses
``prefix=True`` (a prefix of positions cannot rebuild a state, and
nothing can stop at a boundary to copy one), and every pool over
recurrent leaves refuses ``speculative=`` (a rejected round cannot be
rolled back out of a state).  ``chunk`` and ``spec_chunk`` return the
scheduler's view of the state beside it (:func:`with_view`: the few
arrays a turn fetches, packed into one vector of its own), so a scheduler may dispatch
the next chunk — which donates the state — before it has read this
one's view.  Buffer donation applies to the
state argument on every executable that returns a state — the multi-MB KV cache updates in
place in device memory instead of being copied per tick — with the same
CPU carve-out as the executor (``executor._donate_kwargs``: donation +
the persistent compile cache corrupts fetches on the CPU backend).

Rung transitions (a storm outgrowing its slot rung, a long prompt
outgrowing the length rung) are CONTROL-PLANE operations: the state is
materialized host-side, zero-padded into the next rung's shapes with
plain numpy, and handed back to the (already warmed) larger
executables.  No XLA compile, no new shape — a transition costs one
d2h/h2d round trip, amortized over the thousands of decode steps that
follow.
"""
from __future__ import annotations

import threading
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

from paddle_tpu import compile_cache
from paddle_tpu.serving.bucketing import BucketPolicy

__all__ = ["KVSlotPool", "default_len_ladder", "VIEW", "with_view",
           "unpack_view"]

#: the scheduler's view of a pool state: what a turn fetches of it
VIEW = ("tokens", "pos", "active", "finished", "n_gen")


def with_view(fn, expert_stats=None):
    """``fn(state) -> state`` (a ``chunk``, a speculative round) as
    ``state -> (state, view)``: the scheduler's view of the state it
    returns — :data:`VIEW` and, where the builder declares one, its
    ``expert_stats`` leaf — PACKED into ONE int32 vector
    (:func:`unpack_view` is its reader), an output of its OWN that the
    compiled program writes beside the state it updates in place.  The
    state argument is donated whole, so every leaf of a returned state
    dies with the next dispatch; the view does not, which is what lets a
    scheduler queue the next chunk before it has read this one's
    (``DecodeServer._tick``).  ONE array because every output that
    aliases no argument costs a dispatch ~0.05 ms on a TPU (five of
    them and the counts added 0.34 ms to every ``chunk`` call, 6% of a
    chat tick: v5e chip runs, PR 62), and one transfer to the host
    where five went.  Writing ``tokens`` ``[S, T]`` once more is 2-8 MB
    on the device, microseconds."""
    import jax.numpy as jnp

    def viewed(state):
        out = fn(state)
        parts = [out[k].astype(jnp.int32).ravel() for k in VIEW]
        if expert_stats is not None:
            parts.append(expert_stats(out["cache"]).astype(jnp.int32).ravel())
        return out, jnp.concatenate(parts)

    return viewed


def unpack_view(packed, s: int, t: int) -> Dict[str, np.ndarray]:
    """The host's reading of :func:`with_view`'s vector for rung pair
    ``(s, t)``: ``{name: array}`` over :data:`VIEW` (no copy: slices of
    ``packed``; the two flags as bool), and under ``"expert_stats"``
    whatever follows them, ``[-1, 4]`` (``routed_experts.STAT_NAMES``'
    four sums a layer), where anything does."""
    packed = np.asarray(packed)
    view = {"tokens": packed[:s * t].reshape(s, t)}
    at = s * t
    for k in VIEW[1:]:
        view[k] = packed[at:at + s]
        at += s
    for k in ("active", "finished"):
        view[k] = view[k] != 0
    if at < packed.size:
        view["expert_stats"] = packed[at:].reshape(-1, 4)
    return view


def _bytes_beside_the_state(exe) -> Optional[int]:
    """What one execution of the compiled ``exe`` allocates beside its
    arguments: its temporaries and the outputs that alias no argument
    (the view; on the CPU, where nothing is donated, the whole state).
    None where the backend's executable cannot say."""
    try:
        m = exe.memory_analysis()
        return int(m.temp_size_in_bytes + m.output_size_in_bytes
                   - m.alias_size_in_bytes)
    except Exception:  # noqa: BLE001 — no analysis: nothing to hold to
        return None


def default_len_ladder(max_seq_len: int, start: int = 8) -> List[int]:
    """Powers of two from ``start`` up to ``max_seq_len`` (appended when
    not itself a power of two) — the length-axis analog of the batch
    bucket ladder."""
    if max_seq_len < 1:
        raise ValueError("max_seq_len must be >= 1, got %r" % max_seq_len)
    ladder = []
    b = min(start, max_seq_len)
    while b < max_seq_len:
        ladder.append(b)
        b *= 2
    ladder.append(max_seq_len)
    return sorted(set(ladder))


class KVSlotPool:
    """Warmed executables + state plumbing for one decode endpoint.

    ``step_fn``/``make_cache``: the slot-pooled step builder's outputs
    (``decoding.make_transformer_lm_pooled_step_fn`` — per-row positions,
    cache T axis read from the cache itself, so ONE step fn serves every
    rung pair).  ``steps``: tokens advanced per ``chunk`` dispatch (the
    ``fori_loop`` multi-step amortization between scheduler
    interventions).

    ``on_recompile``: called (once per compile) when an executable is
    built AFTER :meth:`warmup` — the serving layer counts it as a
    recompile, the guarantee violation.
    """

    def __init__(self, step_fn: Callable, make_cache: Callable, *,
                 eos_id: int, max_slots: int, max_seq_len: int,
                 slot_ladder: Optional[Sequence[int]] = None,
                 len_ladder: Optional[Sequence[int]] = None,
                 steps: int = 4,
                 on_recompile: Optional[Callable[[], None]] = None,
                 prefix: bool = False,
                 speculative=None,
                 kv_dtype: str = "fp32",
                 len_multiple: int = 1):
        from paddle_tpu.decoding import (make_prefix_admit_fn,
                                         make_slot_decode_fns,
                                         normalize_kv_dtype, spec_of)

        self._make_cache = make_cache
        spec = spec_of(make_cache)
        #: the builder's leaf of device-made counts (None: it has none),
        #: part of the view a ``chunk`` / ``spec_chunk`` returns
        self._expert_stats = spec.expert_stats
        #: tree paths of the cache leaves declared recurrent (no
        #: sequence axis); empty for a K/V-only cache
        self.recurrent_leaves = spec.names(lambda leaf: leaf.seq_axis is None)
        #: the builder's chunked prefill (None: prompts ride the step)
        self._prefill = spec.prefill_fn
        #: the builder's batched prefill (None: as above).  Not taken
        #: with a draft attached: the plain chunk keeps ``draft_cache``
        #: position-synced by feeding it every consumed token, and
        #: nothing here feeds the draft a prompt
        self._prefill_rows = (spec.prefill_rows_fn
                              if speculative is None else None)
        # a leaf of counts with no slot axis is no sequence's state: a
        # rejected round has nothing of it to roll back
        for what, on, leaves in (
                ("prefix=True", prefix and self._prefill is None,
                 self.recurrent_leaves),
                ("speculative=", speculative is not None,
                 spec.names(lambda leaf: leaf.seq_axis is None
                            and leaf.slot))):
            if on and leaves:
                raise ValueError(
                    "KVSlotPool(%s) over a cache with recurrent leaves "
                    "(%s ... %d in all): a recurrent state has no "
                    "positions to copy a prefix into or to roll a "
                    "rejected round back from, and would serve wrong "
                    "tokens; a prefix over such leaves needs a state "
                    "SNAPSHOT taken at the boundary, which only a "
                    "builder with a chunked prefill "
                    "(CacheSpec.prefill_fn) can stop at"
                    % (what, leaves[0], len(leaves)))
        #: tree paths of the cache leaves declared RING leaves; empty
        #: for most builders
        self.ring_leaves = spec.names(lambda leaf: leaf.window is not None)
        if prefix and self._prefill is None and self.ring_leaves:
            raise ValueError(
                "KVSlotPool(prefix=True) over a cache with ring leaves (%s "
                "... %d in all): position p of a ring leaf lives in row p "
                "mod its window, so a wrapped row cannot be sliced as a "
                "prefix of positions; a prefix over such leaves needs a "
                "whole-row SNAPSHOT taken at a boundary, which only a "
                "builder with a chunked prefill (CacheSpec.prefill_fn) "
                "can stop at" % (self.ring_leaves[0], len(self.ring_leaves)))
        if (speculative is not None and speculative.k > 2
                and self.ring_leaves):
            raise ValueError(
                "KVSlotPool(speculative= with k = %d) over a cache with "
                "ring leaves (%s ... %d in all): a round writes ring rows "
                "pos .. pos + k - 1 over positions pos - W ..; after a "
                "rejection the next query, at pos + 1, reads pos + 2 - W "
                "..., which a round of k > 2 rows has overwritten and "
                "cannot be rolled back: a ring of W rows carries k <= 2, "
                "more needs k - 2 spare rows"
                % (speculative.k, self.ring_leaves[0],
                   len(self.ring_leaves)))
        # the cache storage dtype ``make_cache`` allocates (advertised
        # on /healthz; the pool itself is dtype-agnostic — shapes and
        # dtypes all flow from the state spec, so the int8 rung variant
        # with its sibling scale leaves rides resize/extract/admit
        # unchanged)
        self.kv_dtype = normalize_kv_dtype(kv_dtype)
        self.eos_id = int(eos_id)
        self.steps = max(1, int(steps))
        self.slot_policy = BucketPolicy(max_slots, slot_ladder)
        # ``len_multiple`` (sequence-parallel serving): every length
        # rung — and the cap itself — rounds UP to the next multiple,
        # so a pool feeding an sp-sharded model only ever compiles
        # sp-divisible sequence lengths (the ring layout's divisibility
        # rule holds on every rung, not just the top)
        self.len_multiple = max(1, int(len_multiple))
        ladder = list(len_ladder or default_len_ladder(max_seq_len))
        if self.len_multiple > 1:
            lm = self.len_multiple
            max_seq_len = -(-int(max_seq_len) // lm) * lm
            ladder = sorted({-(-int(t) // lm) * lm for t in ladder}
                            | {max_seq_len})
        self.len_policy = BucketPolicy(max_seq_len, ladder)
        # decode tier 2 (both default-off so the base pool's compiled
        # set — and its warmup count — are exactly the PR-9 three):
        # ``prefix`` adds the admit_prefix executable (shared-prefix KV
        # installation); ``speculative`` (a SpeculativeConfig) threads
        # the draft cache + spec flag through the state and adds the
        # fused draft+verify spec_chunk executable.
        self.prefix = bool(prefix)
        self.speculative = speculative
        self._fns = make_slot_decode_fns(
            step_fn, self.eos_id, self.steps,
            draft_step_fn=(speculative.draft_step_fn
                           if speculative is not None else None))
        self._chunk_fn, self._seat_fn, self._release_fn = self._fns
        #: the draft is the target's own module: its proposal and the
        #: proposals' record ride the state, no ``draft_cache`` does
        self._self_draft = (speculative is not None
                            and speculative.kind == "self")
        #: the declared ``decoding.Leaf`` of each of
        #: :meth:`_kv_subtree_leaves`: the target's leaves, then a draft
        #: model's — known before anything compiles, and a ``make_cache``
        #: (the draft's too) that declares nothing is refused here
        self._kv_decl = spec.flat + (
            () if speculative is None or self._self_draft
            else spec_of(speculative.draft_make_cache).flat)
        #: prefix entries are device snapshots of a slot's whole cache
        #: row (recurrent leaves included), taken at a prefill boundary
        self.snapshots = self.prefix and self._prefill is not None
        #: positions at a snapshot's END that are not a prefix's to
        #: install: a prefill that feeds a drafting module wrote the
        #: module's row at the boundary's last position from the token
        #: AFTER the boundary (``prefill_fn.lookahead``), which the next
        #: request need not share — it is seated that many positions
        #: short of the boundary and writes those rows again, every
        #: layer's (write-before-read: the same rows where nothing
        #: looked ahead)
        self.snapshot_lookahead = (
            int(getattr(self._prefill, "lookahead", 0))
            if self.snapshots else 0)
        self._admit_prefix_fn = (
            make_prefix_admit_fn(self._seat_fn, self._kv_decl,
                                 whole_rows=self.snapshots)
            if self.prefix else None)
        if self._self_draft:
            from paddle_tpu.serving.speculative import (
                make_self_draft_chunk_fn)

            self._spec_chunk_fn = make_self_draft_chunk_fn(
                speculative.verify_fn, speculative.module_fn, self.eos_id)
        elif speculative is not None:
            from paddle_tpu.serving.speculative import make_spec_chunk_fn

            self._spec_chunk_fn = make_spec_chunk_fn(
                speculative.verify_fn, speculative.draft_step_fn,
                self.eos_id, speculative.k)
        else:
            self._spec_chunk_fn = None
        self._specs: Dict[Tuple[int, int], dict] = {}
        # a leaf declared a ring leaf that is none is refused here,
        # before anything compiles
        self._state_spec(*self.rung_pairs()[0])
        self._exe: Dict[Tuple[str, int, int], object] = {}
        # per ``chunk`` / ``spec_chunk`` built: the device bytes one more
        # execution of it takes beside the state (:meth:`queued_bytes`)
        self._queued: Dict[Tuple[str, int, int], Optional[int]] = {}
        # host-born constants :meth:`_lower` hoisted: the pool's one
        # device copy of each distinct one, how many copies it made, and
        # per executable kind what it found (count, bytes)
        self._placed: Dict[tuple, object] = {}
        #: device copies made of host-born hoisted constants
        self.constants_placed = 0
        self._host_born: Dict[str, Tuple[int, int]] = {}
        self._lock = threading.Lock()
        self._hits = 0
        self._misses = 0
        self.warmed = False
        self._on_recompile = on_recompile

    # ------------------------------------------------------------------
    @property
    def max_slots(self) -> int:
        return self.slot_policy.max_batch_size

    @property
    def max_seq_len(self) -> int:
        return self.len_policy.max_batch_size

    def rung_pairs(self) -> List[Tuple[int, int]]:
        return [(s, t) for s in self.slot_policy.ladder
                for t in self.len_policy.ladder]

    # ------------------------------------------------------------------
    @property
    def prefill_tokens(self) -> int:
        """Prompt tokens one ``prefill`` dispatch feeds a slot (0: the
        builder has no chunked prefill)."""
        return int(self._prefill.chunk_tokens) if self._prefill else 0

    def _kinds(self) -> List[str]:
        """Every executable kind this pool compiles per rung pair."""
        kinds = ["chunk", "admit", "release"]
        if self._prefill is not None:
            kinds.append("prefill")
        if self._prefill_rows is not None:
            kinds.append("seat_prefill")
        if self.prefix:
            kinds.append("admit_prefix")
        if self.snapshots:
            kinds.append("snapshot")
        if self.speculative is not None:
            kinds.append("spec_chunk")
        return kinds

    def _state_spec(self, s: int, t: int):
        """Abstract (ShapeDtypeStruct) pool state for rung pair
        ``(s, t)`` — shapes without allocating a byte (``jax.eval_shape``
        traces ``make_cache`` instead of running it)."""
        import jax

        if (s, t) in self._specs:   # admit_prefix asks on every call
            return self._specs[s, t]
        cache = jax.eval_shape(lambda: self._make_cache(s, t))
        i32 = np.dtype(np.int32)
        spec = {
            "cache": cache,
            "tokens": jax.ShapeDtypeStruct((s, t), i32),
            "pos": jax.ShapeDtypeStruct((s,), i32),
            "prompt_len": jax.ShapeDtypeStruct((s,), i32),
            "total_len": jax.ShapeDtypeStruct((s,), i32),
            "active": jax.ShapeDtypeStruct((s,), np.dtype(bool)),
            "finished": jax.ShapeDtypeStruct((s,), np.dtype(bool)),
            "n_gen": jax.ShapeDtypeStruct((s,), i32),
        }
        if self.speculative is not None:
            spec["spec"] = jax.ShapeDtypeStruct((s,), np.dtype(bool))
        if self._self_draft:
            spec["draft"] = jax.ShapeDtypeStruct((s,), i32)
            spec["proposals"] = jax.ShapeDtypeStruct((s, t), i32)
        elif self.speculative is not None:
            spec["draft_cache"] = jax.eval_shape(
                lambda: self.speculative.draft_make_cache(s, t))
        for leaf, decl in zip(self._kv_subtree_leaves(spec), self._kv_decl):
            # nothing is inferred: a declared ring leaf must BE one
            if decl.window is not None and (
                    decl.seq_axis is None
                    or leaf.shape[decl.seq_axis] != min(t, decl.window)):
                raise ValueError(
                    "CacheSpec.leaves declares a window of %d "
                    "for a leaf shaped %s at length rung %d: a ring leaf "
                    "has min(rung, window) rows on its sequence axis"
                    % (decl.window, leaf.shape, t))
        self._specs[s, t] = spec
        return spec

    def _kv_subtree_leaves(self, state_or_spec):
        """Flattened leaves of the state's KV subtrees (``cache`` plus
        ``draft_cache`` when speculative) — the fixed order the prefix
        cache stores and ``admit_prefix`` consumes."""
        import jax

        sub = {"cache": state_or_spec["cache"]}
        if "draft_cache" in state_or_spec:
            sub["draft_cache"] = state_or_spec["draft_cache"]
        leaves, _ = jax.tree_util.tree_flatten(sub)
        return leaves

    def alloc(self, s: int, t: int) -> Dict[str, object]:
        """A fresh zeroed pool state for rung pair ``(s, t)``, HOST-side
        (plain numpy): device memory is first touched by the executable
        that consumes it, and an idle pool that dropped its state holds
        no HBM at all."""
        import jax

        return jax.tree.map(
            lambda sd: np.zeros(sd.shape, sd.dtype), self._state_spec(s, t))

    def resize(self, state, new_s: int, new_t: int) -> Dict[str, object]:
        """Re-shape ``state`` into rung pair ``(new_s, new_t)``
        host-side: every leaf is materialized (d2h), copied into a
        zero-padded (or sliced) buffer of the target rung's shape, and
        returned as numpy for the next executable call (h2d).  A pure
        control-plane move — no XLA compile is ever involved, so the
        zero-recompile guarantee survives rung transitions.  Shrinking
        assumes the caller vacated the dropped tail slots.  No axis is
        looked for: every leaf is cut or padded to the target SPEC's
        shape, so a recurrent leaf (whose shape follows the slot rung
        alone) keeps every value whatever the length rungs are, and a
        ring leaf goes to ``min(new_t, window)`` rows (below its window
        position ``p`` is row ``p``, so growing pads; at or past it
        nothing moves)."""
        import jax

        spec = self._state_spec(new_s, new_t)

        def one(arr, sd):
            src = np.asarray(arr)
            if src.shape == sd.shape:
                return src
            out = np.zeros(sd.shape, sd.dtype)
            sl = tuple(slice(0, min(a, b))
                       for a, b in zip(src.shape, sd.shape))
            out[sl] = src[sl]
            return out

        return jax.tree.map(one, state, spec)

    @staticmethod
    def state_rungs(state) -> Tuple[int, int]:
        """The (slot, length) rung pair a state currently occupies."""
        s, t = state["tokens"].shape
        return int(s), int(t)

    def _rung_bytes(self, s: int, t: int) -> Tuple[int, int, int]:
        """(bytes in the leaves with a sequence axis, bytes in those
        declared to have none, what the first would be were every ring
        leaf as long as the rung) of the cache subtrees at rung pair
        ``(s, t)``, from the state SPEC's stored dtypes — no
        allocation."""
        spec = self._state_spec(s, t)
        seq = rec = whole = 0
        for leaf, decl in zip(self._kv_subtree_leaves(spec), self._kv_decl):
            n = int(np.prod(leaf.shape)) * np.dtype(leaf.dtype).itemsize
            if decl.seq_axis is None:
                rec += n
                continue
            seq += n
            whole += (n if decl.window is None else
                      n // leaf.shape[decl.seq_axis] * (t // decl.stride))
        return seq, rec, whole

    def kv_rung_bytes(self, s: int, t: int) -> int:
        """KV bytes one state of rung pair ``(s, t)`` holds (cache +
        sibling scale leaves + draft cache; NOT the leaves declared
        recurrent — :meth:`recurrent_rung_bytes`).  This is the pool-
        accounting ground truth the ``serving_kv_cache_bytes`` gauge
        and the int8-KV capacity bench read: an int8 pool's rung holds
        ~4x less than fp32's, so a fixed HBM budget seats ~2x+ the
        concurrent sequences at the next slot rung up."""
        return self._rung_bytes(s, t)[0]

    def kv_rung_bytes_one_length(self, s: int, t: int) -> int:
        """What :meth:`kv_rung_bytes` would count if every sequence leaf
        held the whole length rung: the same where no leaf is a ring
        leaf.  The ``serving_decode_kv_bytes_one_length`` gauge reads
        this; over it, :meth:`kv_rung_bytes` is what the windows save."""
        return self._rung_bytes(s, t)[2]

    def recurrent_rung_bytes(self, s: int, t: int) -> int:
        """Bytes of the leaves declared recurrent (no sequence axis) at
        rung pair ``(s, t)``: they scale with the slot rung alone.  The
        ``serving_recurrent_state_bytes`` gauge reads this."""
        return self._rung_bytes(s, t)[1]

    def kv_state_bytes(self, state) -> int:
        """:meth:`kv_rung_bytes` for ``state``'s current rung pair."""
        s, t = self.state_rungs(state)
        return self.kv_rung_bytes(s, t)

    # ------------------------------------------------------------------
    def _get_exe(self, kind: str, s: int, t: int):
        key = (kind, s, t)
        with self._lock:
            exe = self._exe.get(key)
            if exe is not None:
                self._hits += 1
                return exe
        exe = self._compile(kind, s, t)
        with self._lock:
            self._exe[key] = exe
            self._misses += 1
            if self.warmed and self._on_recompile is not None:
                self._on_recompile()
        return exe

    def _compile(self, kind: str, s: int, t: int):
        import jax

        spec = self._state_spec(s, t)
        if kind in ("chunk", "spec_chunk"):
            return self._lower(kind, spec)
        i32 = np.dtype(np.int32)
        mask = jax.ShapeDtypeStruct((s,), np.dtype(bool))
        scalar = jax.ShapeDtypeStruct((), i32)
        if kind == "release":
            return self._lower(kind, spec, mask)
        if kind == "prefill":
            return self._lower(kind, spec, scalar, scalar,
                               jax.ShapeDtypeStruct((), np.dtype(bool)))
        if kind == "snapshot":  # reads the state: nothing to donate
            return self._lower(kind, spec, scalar, donate=False)
        if kind == "admit":
            return self._lower(kind, spec, jax.ShapeDtypeStruct(
                (s, t + self._seat_columns()), i32))
        if kind == "seat_prefill":
            return self._lower(kind, spec, jax.ShapeDtypeStruct(
                (self._packed_seats_size(s, t),), i32))
        prompt = jax.ShapeDtypeStruct((t,), i32)
        args = [spec, mask, prompt, scalar, scalar]
        if kind == "admit_prefix":
            kv = []
            for leaf, decl in zip(self._kv_subtree_leaves(spec),
                                  self._kv_decl):
                # a snapshot carries every leaf of the slot's row; else
                # only the leaves with positions, recurrent ones a dummy
                whole = decl.seq_axis is not None or (
                    self.snapshots and decl.slot)
                kv.append(jax.ShapeDtypeStruct(
                    leaf.shape[1:] if whole else (1,),
                    leaf.dtype if whole else np.dtype(np.float32)))
            args.append(kv)
            args.append(scalar)  # prefix_len
        if self.speculative is not None:
            args.append(jax.ShapeDtypeStruct((), np.dtype(bool)))
        return self._lower(kind, *args)

    def _lower(self, kind: str, *arg_specs, donate: bool = True):
        """AOT-compile ``kind`` for ``arg_specs`` with every array the
        function closes over — the model weights — HOISTED to an
        executable argument.  A closed-over array is otherwise baked
        into the HLO as a constant, once per executable: at BERT-base
        width (0.53 GB of fp32 weights) the one chunk executable of a
        one-rung ladder measured a 0.99 GB persistent-cache entry, 28 s
        of warmup compile cold and 11 s to load it back warm; hoisted,
        0.9 MB, 2.9 s and 1.0 s, same tokens (v5e chip runs, PR 21).
        Hoisted, every rung pair's executable takes the SAME device
        arrays: one copy of the weights however long the ladders.

        What the trace hoists is not only weights.  A numpy array the
        step closes over (the two 0/1 indicator matrices a layer that
        ``decode_attention.ragged_decode_attention`` hands its kernel)
        comes out HOST-BORN, and an executable bound to it copies it to
        the device again on every call, one transfer each, before the
        program can start: ``gpt1_117m``'s ``chunk`` paid 24 of them,
        2.6-2.8 ms of a 3.4-3.6 ms call with the chip idle (v5e chip
        runs, PR 30).  So every hoisted constant that is not a
        ``jax.Array`` already is placed ONCE, here, where the compiled
        executable expects that argument (:meth:`_place`), and the
        executable is bound to the device arrays; a ``jax.Array`` (the
        weights, whatever their sharding) is bound as it is.  The
        program is lowered from the constants as the trace gave them,
        so the HLO and its compile-cache key are what they were.

        The state argument is DONATED so the KV cache updates in place —
        except on CPU, where donation + the persistent compile cache is
        known-unsafe (executor._donate_kwargs pins the policy).  The
        constants are never donated: they outlive every state.

        The four stages in a row — the trace (where every Pallas body
        is walked), the lowering, the compile or cache load, the
        placing — are each a ``compile_cache.build_stage`` of one
        ``compile_cache.build``: their seconds are in
        ``program_build_seconds_total{program=kind}`` whether or not
        anything records."""
        import functools

        import jax

        from paddle_tpu.executor import _donate_kwargs

        spec = arg_specs[0]  # the state's: its rung pair names the build
        fn = getattr(self, "_%s_fn" % kind)
        viewed = kind in ("chunk", "spec_chunk")
        if viewed:
            fn = with_view(fn, self._expert_stats)
        with compile_cache.build(
                kind, rungs=list(spec["tokens"].shape)) as built:
            with compile_cache.build_stage(kind, "trace"):
                closed, out_shape = jax.make_jaxpr(
                    fn, return_shape=True)(*arg_specs)
            out_tree = jax.tree.structure(out_shape)

            def hoisted(consts, *args):
                return jax.tree.unflatten(out_tree, jax.core.eval_jaxpr(
                    closed.jaxpr, consts, *jax.tree.leaves(args)))

            hoisted.__name__ = kind  # names the XLA module and cache entry
            donate = ({"donate_argnums": (1,)}  # the state, after consts
                      if donate and _donate_kwargs(jax.devices()[0]) else {})
            with compile_cache.build_stage(kind, "lower"):
                lowered = jax.jit(hoisted, **donate).lower(
                    closed.consts, *arg_specs)
            with compile_cache.build_stage(kind, "compile"):
                exe = lowered.compile()
            if viewed:
                with self._lock:
                    self._queued[(kind,) + tuple(spec["tokens"].shape)] = (
                        _bytes_beside_the_state(exe))
            with compile_cache.build_stage(kind, "place"):
                wanted = exe.input_shardings[0][0]  # of ``consts``, one each
                host_born = [np.asarray(c).nbytes for c in closed.consts
                             if not isinstance(c, jax.Array)]
                with self._lock:
                    self._host_born[kind] = max(
                        self._host_born.get(kind, (0, 0)),
                        (len(host_born), sum(host_born)))
                consts = [
                    c if isinstance(c, jax.Array)
                    else self._place(c, sharding)
                    for c, sharding in zip(closed.consts, wanted)]
            if built.traced:
                built.args.update(
                    equations=compile_cache.equations(closed.jaxpr),
                    constants=len(consts),
                    constant_bytes=int(sum(c.nbytes for c in consts)),
                    host_born=len(host_born))
        return functools.partial(exe, consts)

    def _place(self, const, sharding):
        """The pool's ONE device copy of the host-born constant
        ``const`` under ``sharding``: equal constants (shape, dtype and
        bytes) of every layer, executable kind and rung pair share it —
        a long ladder would otherwise hold 24 copies of two 196 KB
        matrices per kind and rung pair."""
        import jax

        host = np.asarray(const)
        key = (host.shape, host.dtype.name, host.tobytes(), sharding)
        with self._lock:
            placed = self._placed.get(key)
            if placed is None:
                placed = self._placed[key] = jax.device_put(host, sharding)
                self.constants_placed += 1
        return placed

    def host_born_constants(self) -> Dict[str, Tuple[int, int]]:
        """``{kind: (count, bytes)}`` of the hoisted constants that were
        NOT on a device when ``kind`` was lowered (the most over the
        rung pairs built so far): what every call of that executable
        would send the chip again had :meth:`_lower` not placed them."""
        with self._lock:
            return dict(self._host_born)

    # ------------------------------------------------------------------
    def warmup(self) -> int:
        """AOT-compile chunk + admit + release (and ``seat_prefill``,
        ``prefill``, ``admit_prefix``, ``snapshot``, ``spec_chunk`` where
        the pool has them: :meth:`_kinds`) for EVERY rung pair;
        returns the number of compiles performed (0 on a re-warm).
        After this, a storm that stays inside the ladders never builds
        an executable again — :meth:`jit_cache_stats` ``misses`` is the
        proof the serving layer asserts on."""
        compiles = 0
        for s, t in self.rung_pairs():
            for kind in self._kinds():
                if kind == "prefill" and t <= self.prefill_tokens:
                    continue  # no prompt on this rung holds a chunk
                key = (kind, s, t)
                with self._lock:
                    have = key in self._exe
                if have:
                    continue
                exe = self._compile(kind, s, t)
                with self._lock:
                    self._exe[key] = exe
                compiles += 1
        self.warmed = True
        return compiles

    def jit_cache_stats(self) -> Dict[str, int]:
        """The recompile ground truth (same contract as
        ``AnalysisPredictor.jit_cache_stats``): ``misses`` counts built
        executables, ``hits`` runs served by an existing one."""
        with self._lock:
            return {"entries": len(self._exe), "hits": self._hits,
                    "misses": self._misses}

    # ------------------------------------------------------------------
    # dispatch (the scheduler's hot path: one dict lookup + one call)
    # ------------------------------------------------------------------
    def chunk(self, state) -> Dict[str, object]:
        """Advance every active slot by up to ``steps`` tokens in ONE
        device dispatch (a prompt that was not prefilled at its
        admission steps through its tokens inside, beside the rows that
        decode); the state alone (:meth:`chunk_view` also hands back the
        scheduler's view)."""
        return self.chunk_view(state)[0]

    def chunk_view(self, state, spec: bool = False):
        """The scheduler's dispatch: one ``chunk`` — or, ``spec``, one
        speculative round (``spec_chunk``) — over ``state``; returns
        ``(state, view)``, the view (:func:`with_view`) ONE device
        vector of its own that outlives the returned state's donation to
        the next dispatch (:func:`unpack_view` reads it on the host)."""
        s, t = self.state_rungs(state)
        # hot-path: begin kv_chunk (executable lookup + async dispatch;
        # the scheduler materializes results OUTSIDE this region)
        exe = self._get_exe("spec_chunk" if spec else "chunk", s, t)
        out = exe(state)
        # hot-path: end kv_chunk
        return out

    def queued_bytes(self, state, spec: bool = False) -> Optional[int]:
        """Device bytes one more ``chunk`` (``spec``: round) over
        ``state``'s rung pair takes beside the state while it is queued
        or runs — its temporaries and its view, by the compiled
        executable's ``memory_analysis()``, read once where it was
        built.  None where that is not known."""
        with self._lock:
            return self._queued.get(
                ("spec_chunk" if spec else "chunk",) + self.state_rungs(state))

    def admit(self, state, slot, prompt, prompt_len, total_len,
              spec=False) -> Dict[str, object]:
        """Seat requests into free slots in ONE device dispatch: one
        request (``slot`` an int, ``prompt`` its tokens, two ints and a
        bool) or a scheduler turn's whole batch (``slot`` a sequence of
        distinct slots and every other argument a sequence beside it;
        ``spec`` may stay one bool for all).  Either way it is the same
        warmed executable: the seats travel as ONE int32 array indexed by
        slot (:meth:`_pack_seats`: one h2d transfer whatever the batch),
        whose shape follows the rung pair and not the number seated.

        Each prompt is padded host-side to the state's length rung and
        the seated slots' flags and cursors are reset; the cache passes
        through untouched — write-before-read makes zeroing a reused
        slot's K/V rows unnecessary, and the step starts a recurrent leaf
        from zero at the position 0 every admit seats.  ``spec`` marks a
        slot for speculative rounds (ignored unless the pool was built
        with a SpeculativeConfig)."""
        if np.ndim(slot) == 0:
            slot, prompt = [slot], [prompt]
            prompt_len, total_len = [prompt_len], [total_len]
        if np.ndim(spec) == 0:
            spec = [spec] * len(slot)
        s, t = self.state_rungs(state)
        seats = self._pack_seats(s, t, slot, prompt, prompt_len,
                                 total_len, spec)
        # hot-path: begin kv_admit (executable lookup + async dispatch)
        exe = self._get_exe("admit", s, t)
        out = exe(state, seats)
        # hot-path: end kv_admit
        return out

    # the columns of a seats array after a row's T prompt tokens
    _SEATED, _PROMPT_LEN, _TOTAL_LEN, _SPEC = range(4)

    def _seat_columns(self) -> int:
        return 4 if self.speculative is not None else 3

    def _pack_seats(self, s: int, t: int, slots, prompts, prompt_lens,
                    total_lens, specs) -> np.ndarray:
        """The host half of :meth:`admit`: every argument of the pure
        ``admit`` in one int32 ``[s, t + columns]`` array — row ``i`` is
        slot ``i``'s padded prompt, then whether it is seated, its two
        lengths and (speculative pools) its flag; rows of slots not
        seated stay zero."""
        seats = np.zeros((s, t + self._seat_columns()), np.int32)
        for slot, prompt, p_len, tot, spec in zip(
                slots, prompts, prompt_lens, total_lens, specs):
            n = min(len(prompt), t)
            row = seats[slot]
            row[:n] = prompt[:n]
            row[t + self._SEATED] = 1
            row[t + self._PROMPT_LEN] = p_len
            row[t + self._TOTAL_LEN] = tot
            if self.speculative is not None:
                row[t + self._SPEC] = bool(spec)
        return seats

    def _admit_fn(self, state, seats):
        """The traced half: unpack :meth:`_pack_seats`' array into the
        pure ``admit``'s slot-indexed arguments."""
        t = state["tokens"].shape[1]
        args = [seats[:, t + self._SEATED] != 0, seats[:, :t],
                seats[:, t + self._PROMPT_LEN],
                seats[:, t + self._TOTAL_LEN]]
        if self.speculative is not None:
            args.append(seats[:, t + self._SPEC] != 0)
        return self._seat_fn(state, *args)

    def admit_prefix(self, state, slot: int, prompt: np.ndarray,
                     prompt_len: int, total_len: int,
                     kv_leaves, prefix_len: int,
                     spec: bool = False) -> Dict[str, object]:
        """Seat a request whose first ``prefix_len`` positions are
        served from a retained entry (``kv_leaves``: the prefix cache's
        stored leaf list, per :meth:`extract_kv` order — host rows,
        padded here to the current length rung — or, in a pool that
        keeps :meth:`snapshot`s, the snapshot's device arrays as they
        are, recurrent leaves included), installed by the warmed
        ``admit_prefix`` executable; the slot starts at ``pos =
        prefix_len`` — prefill resumes at the unmatched suffix.
        Requires ``prefix=True`` at construction."""
        if self._admit_prefix_fn is None:
            raise RuntimeError(
                "pool was built without prefix=True — admit_prefix has "
                "no warmed executable")
        s, t = self.state_rungs(state)
        mask = np.zeros((s,), bool)
        mask[slot] = True
        buf = np.zeros((t,), np.int32)
        n = min(len(prompt), t)
        buf[:n] = prompt[:n]
        shapes = self._state_spec(s, t)  # not ``spec``: that is the flag
        kv = []
        for sd, ent, decl in zip(self._kv_subtree_leaves(shapes), kv_leaves,
                                 self._kv_decl):
            ax = decl.seq_axis
            if self.snapshots:
                # a snapshot's leaves are device arrays of this rung's
                # row shapes already (:meth:`snapshot`): no host copy
                if decl.slot and tuple(ent.shape) != tuple(sd.shape[1:]):
                    raise ValueError(
                        "snapshot leaf %s does not fit rung pair %s"
                        % (ent.shape, (s, t)))
                kv.append(ent)
                continue
            if ax is None or ent is None:
                kv.append(np.zeros((1,), np.float32))
                continue
            tgt = np.zeros(sd.shape[1:], sd.dtype)
            sl = [slice(0, min(a, b))
                  for a, b in zip(ent.shape, tgt.shape)]
            tgt[tuple(sl)] = ent[tuple(sl)]
            kv.append(tgt)
        # hot-path: begin kv_admit_prefix (executable lookup + async
        # dispatch; the leaf re-pad above is host numpy on stored
        # host arrays — no device sync)
        exe = self._get_exe("admit_prefix", s, t)
        args = [state, mask, buf,
                np.asarray(prompt_len, np.int32),  # hot-ok: host scalar
                np.asarray(total_len, np.int32),  # hot-ok: host scalar
                kv,
                np.asarray(prefix_len, np.int32)]  # hot-ok: host scalar
        if self.speculative is not None:
            args.append(np.asarray(bool(spec)))  # hot-ok: host scalar
        out = exe(*args)
        # hot-path: end kv_admit_prefix
        return out

    # ------------------------------------------------------------------
    # seat and prefill in one dispatch (builders that declare a
    # prefill_rows_fn)
    # ------------------------------------------------------------------
    #: most rows (seats and the repeats that fill a group) one
    #: ``seat_prefill`` dispatch carries; more seats take more passes
    _SEAT_ROWS = 16

    @property
    def seats_prefilled(self) -> bool:
        """Whether :meth:`seat_prefill` is how this pool seats a request
        that starts at position 0: the builder declares a batched
        prefill (``CacheSpec.prefill_rows_fn``) and no draft model
        rides along."""
        return self._prefill_rows is not None

    @staticmethod
    def prefill_classes(t: int) -> List[Tuple[int, int]]:
        """The ``(C, G)`` shapes a ``seat_prefill`` program of length
        rung ``t`` feeds prompts in, narrowest first: ``G`` seats of at
        most ``C`` fed positions each a forward.  Widths a quarter, a
        half and the whole of the rung, so a 96-token prompt does not
        pay for 384; ``G * C`` about half a rung of tokens, enough that
        a forward is not bound by reading the weights (two seats a
        forward at the narrowest width)."""
        widths = sorted({max(1, t // 4), max(1, t // 2), t})
        return [(c, max(1, t // 2 // c)) for c in widths]

    def _seat_rows(self, s: int, t: int) -> int:
        return max(min(self._SEAT_ROWS, s),
                   max(g for _, g in self.prefill_classes(t)))

    # a compact seat's columns are :meth:`_pack_seats`' (no draft: three),
    # but for the first: the row's slot
    _SLOT = _SEATED

    def _packed_seats_size(self, s: int, t: int) -> int:
        return (self._seat_rows(s, t) * (t + self._seat_columns())
                + 2 * len(self.prefill_classes(t)))

    def _pack_seat_passes(self, s: int, t: int, slots, prompts, total_lens):
        """The host half of :meth:`seat_prefill`: the seats of one turn
        as one int32 array a pass — ``_seat_rows`` COMPACT rows (a
        row's padded prompt, then its slot and its two lengths: 33 KB at
        16 rows of a 512 rung where :meth:`_pack_seats`' slot-indexed
        array is 659 KB at 320 slots), then per class of
        :meth:`prefill_classes` the first row of its seats and how many
        forwards they take.  Seats are ordered by class (the narrowest
        width that holds all but the prompt's last token) and laid out
        in the class's groups of ``G`` rows; what does not fit
        ``_seat_rows`` rows goes to the next pass.  EVERY row names a
        seat: a row no seat took (the rest of a group that is not full,
        the rows past the last group) REPEATS the pass's first seat, so
        the traced half scatters in bounds and twice the same where it
        scatters twice — no row is "idle", and nothing leans on an
        out-of-range index being dropped (on a TPU a ``[4, T]`` token
        buffer lost slot 0's row that way: v5e chip run, PR 45)."""
        classes = self.prefill_classes(t)
        rows, width = self._seat_rows(s, t), t + self._seat_columns()
        todo = [[] for _ in classes]
        for i, prompt in enumerate(prompts):
            todo[next((k for k, (c, _) in enumerate(classes)
                       if len(prompt) - 1 <= c), -1)].append(i)
        passes = []
        while any(todo):
            packed = np.zeros(self._packed_seats_size(s, t), np.int32)
            seats = packed[:rows * width].reshape(rows, width)
            plan = packed[rows * width:].reshape(len(classes), 2)
            seated = np.zeros(rows, bool)
            row = 0
            for k, (_, g) in enumerate(classes):
                groups = min(-(-len(todo[k]) // g), (rows - row) // g)
                plan[k] = row, groups
                take, todo[k] = todo[k][:groups * g], todo[k][groups * g:]
                for at, i in enumerate(take, row):
                    n = min(len(prompts[i]), t)
                    seats[at, :n] = prompts[i][:n]
                    seats[at, t + self._SLOT] = slots[i]
                    seats[at, t + self._PROMPT_LEN] = len(prompts[i])
                    seats[at, t + self._TOTAL_LEN] = total_lens[i]
                seated[row:row + len(take)] = True
                row += groups * g
            seats[~seated] = seats[np.argmax(seated)]
            passes.append(packed)
        return passes

    def _seat_prefill_fn(self, state, packed):
        """The traced ``seat_prefill``: seat every row of one of
        :meth:`_pack_seat_passes`' arrays (what ``admit`` does, by
        scatter from compact rows) at ``pos = prompt_len - 1`` and feed
        each its prompt from position 0 through the builder's batched
        prefill — per class a loop of as many ``[G, C]`` forwards as the
        plan says, none for a class no seat fell in — so the slot's
        first step eats the prompt's last token and produces the first
        generated one."""
        import jax
        import jax.numpy as jnp

        s, t = state["tokens"].shape
        classes = self.prefill_classes(t)
        rows, width = self._seat_rows(s, t), t + self._seat_columns()
        seats = packed[:rows * width].reshape(rows, width)
        plan = packed[rows * width:].reshape(len(classes), 2)
        at = seats[:, t + self._SLOT]   # every row a seat: in bounds

        def seated(name, value):
            return state[name].at[at].set(value)

        out = dict(state)
        out.update(
            tokens=seated("tokens", seats[:, :t]),
            pos=seated("pos",
                       jnp.maximum(seats[:, t + self._PROMPT_LEN] - 1, 0)),
            prompt_len=seated("prompt_len", seats[:, t + self._PROMPT_LEN]),
            total_len=seated("total_len", seats[:, t + self._TOTAL_LEN]),
            active=seated("active", True),
            finished=seated("finished", False),
            n_gen=seated("n_gen", 0))
        cache = state["cache"]
        for k, (c, g) in enumerate(classes):
            def forward(i, cache, k=k, c=c, g=g):
                group = jax.lax.dynamic_slice(
                    seats, (plan[k, 0] + i * g, 0), (g, width))
                return self._prefill_rows(
                    cache, group[:, t + self._SLOT], group[:, :c])

            cache = jax.lax.fori_loop(0, plan[k, 1], forward, cache)
        out["cache"] = cache
        return out

    def seat_prefill(self, state, slots, prompts, total_lens):
        """Seat requests that start at position 0 AND feed each all of
        its prompt but the last token, in one ``seat_prefill`` dispatch
        for up to ``_seat_rows`` seats (a pass; a turn that seats more,
        or whose groups leave too many rows unused, takes more passes —
        degraded, never wrong).  Returns ``(state, passes)``.  Every
        seated slot is active at ``pos = len(prompt) - 1`` afterwards."""
        s, t = self.state_rungs(state)
        passes = self._pack_seat_passes(s, t, slots, prompts, total_lens)
        # hot-path: begin kv_seat_prefill (executable lookup + async
        # dispatch a pass)
        exe = self._get_exe("seat_prefill", s, t)
        for packed in passes:
            state = exe(state, packed)
        # hot-path: end kv_seat_prefill
        return state, len(passes)

    # ------------------------------------------------------------------
    # chunked prefill and snapshots (builders that declare a prefill_fn)
    # ------------------------------------------------------------------
    def _prefill_fn(self, state, row, start, activate):
        """The traced ``prefill``: slot ``row``'s prompt tokens ``start
        .. start + C - 1``, read from the state's own token buffer (the
        admit put them there), through the builder's ``prefill_fn``;
        ``pos`` moves to ``start + C``.  The slot is HELD (inactive, so
        the decode chunk leaves it alone) while whole chunks remain;
        ``activate`` on its last one hands it to the step.  Nothing is
        generated: ``n_gen`` is untouched."""
        import jax
        import jax.numpy as jnp

        c = self.prefill_tokens
        # a builder that feeds a drafting module too asks for the token
        # after the chunk (``lookahead``): a whole chunk is fed only
        # while a prompt token follows it
        toks = jax.lax.dynamic_slice(
            state["tokens"], (row, start),
            (1, c + getattr(self._prefill, "lookahead", 0)))[0]
        out = dict(state)
        out.update(
            cache=self._prefill(state["cache"], row, toks, start,
                                jnp.int32(c)),
            pos=state["pos"].at[row].set(start + c),
            active=state["active"].at[row].set(
                state["active"][row] | activate))
        return out

    def _snapshot_fn(self, state, slot):
        """The traced ``snapshot``: slot ``slot``'s row of every cache
        leaf (:meth:`_kv_subtree_leaves` order), copied; a ``(1,)``
        dummy for a leaf declared to have no slot axis."""
        import jax
        import jax.numpy as jnp

        return [jax.lax.dynamic_index_in_dim(leaf, slot, 0, keepdims=False)
                if decl.slot else jnp.zeros((1,), jnp.float32)
                for leaf, decl in zip(self._kv_subtree_leaves(state),
                                      self._kv_decl)]

    def can_prefill(self, state, pos: int, prompt_len: int) -> bool:
        """Whether a slot at ``pos`` of a ``prompt_len``-token prompt
        takes a ``prefill`` chunk next: the builder has one, the rung is
        longer than a chunk, and a whole chunk of prompt remains with at
        least one token after it (the step that eats the LAST prompt
        token produces the first generated one: it must run)."""
        c = self.prefill_tokens
        return (c > 0 and self.state_rungs(state)[1] > c
                and int(prompt_len) - int(pos) > c)

    def prefill(self, state, slot: int, start: int,
                activate: bool) -> Dict[str, object]:
        """Feed slot ``slot`` its next ``prefill_tokens`` prompt tokens
        (positions ``start ..``) in ONE dispatch; ``activate`` on the
        slot's last whole chunk."""
        s, t = self.state_rungs(state)
        # hot-path: begin kv_prefill (executable lookup + async dispatch)
        exe = self._get_exe("prefill", s, t)
        out = exe(state, np.int32(slot), np.int32(start), np.bool_(activate))
        # hot-path: end kv_prefill
        return out

    def snapshot(self, state, slot: int):
        """Slot ``slot``'s whole cache row as DEVICE arrays (one per
        cache leaf, recurrent leaves included), copied by one warmed
        dispatch: a prefix snapshot's payload.  Valid as a prefix of
        ``P`` positions when taken with the slot at ``pos == P``; rows
        past ``P`` are whatever the slot held and are masked off at
        installation.  Requires ``prefix=True`` over a builder with a
        prefill."""
        if not self.snapshots:
            raise RuntimeError(
                "pool keeps no snapshots (prefix=True over a builder "
                "that declares CacheSpec.prefill_fn)")
        s, t = self.state_rungs(state)
        return self._get_exe("snapshot", s, t)(state, np.int32(slot))

    def extract_kv(self, state, slot: int, m: int):
        """Materialize slot ``slot``'s first ``m`` KV positions as host
        arrays (the prefix cache's retained-entry payload): one list
        entry per KV subtree leaf (tree-flatten order), ``None`` for
        leaves carrying no per-slot sequence state (recurrent leaves
        among them: they are never sliced).  A control-plane d2h —
        called when a slot is FREED, off the tick's dispatch path.
        Refused over ring leaves: a prefix of positions is not a prefix
        of a wrapped leaf's rows."""
        if self.ring_leaves:
            raise ValueError(
                "extract_kv over a cache with ring leaves (%s ... %d in "
                "all): position p of a ring leaf lives in row p mod its "
                "window, so its first m positions are not its first m "
                "rows; copy the slot's whole row at a boundary instead "
                "(snapshot)" % (self.ring_leaves[0], len(self.ring_leaves)))
        out = []
        for leaf, decl in zip(self._kv_subtree_leaves(state), self._kv_decl):
            if decl.seq_axis is None:
                out.append(None)
                continue
            sl = [slice(None)] * (leaf.ndim - 1)
            sl[decl.seq_axis - 1] = slice(0, int(m) // decl.stride)
            out.append(np.asarray(leaf[slot][tuple(sl)]))
        return out

    def release(self, state, slots: Sequence[int]) -> Dict[str, object]:
        """Deactivate ``slots`` mid-flight (expired deadline, abort):
        their lanes stop advancing and become seatable again."""
        s, t = self.state_rungs(state)
        mask = np.zeros((s,), bool)
        for i in slots:
            mask[i] = True
        exe = self._get_exe("release", s, t)
        return exe(state, mask)
