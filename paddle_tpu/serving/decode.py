"""Token-level decode scheduling: continuous batching + streaming.

The request-batching server (``serving.server``) holds every sequence
in a batch until the slowest finishes — fatal for autoregressive
endpoints, where sequence lengths are wildly mixed and a late arrival
would wait out a whole decode.  :class:`DecodeServer` replaces the
request-at-a-time regime with vLLM-style CONTINUOUS batching:

* one fused decode step per tick over a persistent **slot pool**
  (``serving.kv_pool.KVSlotPool``): finished sequences (EOS or length
  cap) free their slots mid-flight, and queued prompts join the running
  batch at the next tick, all of a turn's in ONE admit dispatch;
* **prefill follows from what the step's builder declares.**  Nothing:
  prefill and decode are the same step — a freshly admitted prompt
  teacher-forces its stored tokens through the shared step fn, filling
  its KV cache inside the running batch (no separate prefill
  executable, no second compiled shape).  A BATCHED PREFILL
  (``decoding.CacheSpec.prefill_rows_fn``: the transformer LM): the turn's ONE
  admission dispatch is ``KVSlotPool.seat_prefill``, which seats every
  request the turn popped AND feeds each all of its prompt but the last
  token, before the turn's decode chunk is dispatched; the slot joins
  that chunk at ``pos = prompt_len - 1`` and its first step produces
  its first token (a request seated over a retained prefix steps
  through its suffix; a server with a draft model attached does not
  engage).  A CHUNKED PREFILL (``CacheSpec.prefill_fn``): a turn
  runs at most one ``prefill`` dispatch (``C`` prompt tokens of the
  oldest seated request that still has a whole chunk to go, which is
  held out of the decode chunk until its last whole chunk is in)
  before its decode chunk, and only the remainder shorter than ``C``
  rides the step;
* the pool's bucket ladders (slot rungs x length rungs) keep the
  compiled-shape set CLOSED: :meth:`warmup` pre-compiles every rung
  pair, after which a mixed prompt/decode storm performs **zero XLA
  compiles** (``jit_cache_stats`` is the ground truth, same contract as
  the request-batching path);
* between scheduler interventions the step loop dispatches
  **multi-step chunks** (``steps`` tokens per device call, a
  ``fori_loop`` inside the executable) to amortize host overhead, with
  buffer donation so the KV cache updates in place;
* **run-ahead of depth one**: where the host could decide nothing from
  the running chunk's view — the pool is full and its slot ladder
  cannot grow, no seated slot is held for a chunked prefill, no live
  request can reach its length inside the chunk by the tokens the host
  has seen, and the device's free bytes hold a second set of the
  executable's temporaries — the NEXT chunk is dispatched before the
  turn waits for this one, so the device goes from one to the other
  with no gap and the host's whole turn runs under the second
  (:meth:`DecodeServer._tick`, :meth:`DecodeServer._why_serial`; the
  chunk's view is an output of its own beside the donated state,
  ``kv_pool.with_view``).  Nothing selects it: a lightly loaded or an
  emptying server fails the first condition and runs the serial turn;
* requests flow through the SAME admission front door as the batching
  server (``DynamicBatcher``/``AdmissionQueue``: EDF ordering, priority
  classes with weighted fair sharing, AIMD admit limit, retry hints).

Streaming: :meth:`submit` returns a :class:`DecodeRequest` whose
``stream()`` yields token chunks as the scheduler produces them —
``Client.infer_stream`` / ``RemoteClient.infer_stream`` /
``FleetBalancer.infer_stream`` ride it, over the wire via chunked codec
frames (``serving.wire``).

Observability: ``serving_decode_*`` metrics (generated/prefill token
counters, ``serving_decode_prefill_chunk_tokens_total`` for the prompt
tokens a prefill dispatch fed and not the step, tick counter, TTFT
histogram, slot-occupancy gauge; the pool's
bytes as two gauges, ``serving_kv_cache_bytes`` for leaves with a
sequence axis and ``serving_recurrent_state_bytes`` for those without,
``serving_decode_state_resets_total``, one per admission into a pool
that has recurrent leaves, and ``serving_decode_admitted_total`` over
``serving_decode_admit_dispatches_total``, the requests one admit
dispatch carried: a turn seats everything it pops in one, and
``serving_pool_constants_placed_total``, the host-born constants the
pool's lowering put on the device once so that no dispatch sends them
again, and ``serving_decode_idle_drops_total`` beside the
``serving/pool_dropped`` event, one per drop of an idle server's pool
state, and ``serving_decode_chunks_ahead_total`` with
``serving_decode_sync_turns_total{reason}``, which add up to the tick
counter: the chunks dispatched before the host waited for the one
running, and the turns that waited with nothing queued, by the first
reason that held, and its twin ``serving/pool_placed`` beside
``serving_pool_state_seconds_total``, one per fresh state brought to
the device) on top of the standard ``ServingMetrics`` series; the
``decode.step`` fault point injects failures into the tick dispatch for
chaos coverage.  While a span sink is live every scheduler turn is ONE
``serving/decode_tick`` span (it carries the active requests' trace
ids) tiled by leaf spans, one a phase and in this order:
``serving/decode/admit_plan``, ``/admit_dispatch``, ``/prefill``,
``/dispatch`` (``ahead``: whether a chunk was queued behind the one the
turn waits for; ``chunks``: executables it launched), ``/wait`` (on the
OLDER chunk where one is queued behind it), ``/copy``, ``/deliver``; an
empty server's wait is ``serving/decode/idle_wait``, a turn of its own.  The leaves
are on the device trace's clock too (``monitor.spans.open_span``); with
no sink live a turn reads the sink's flag once and nothing else is
different (:class:`_Turn`).

Decode tier 2 (both independently toggleable, see README):

* ``prefix_cache=`` attaches a :class:`serving.prefix_cache.
  PrefixKVCache` — freed slots' prompt-prefix KV blocks are retained
  and matching admissions skip the shared prefill (the
  ``decode.prefix_admit`` fault point guards the degraded-not-wrong
  fallback); over a builder with a chunked prefill the entries are
  device SNAPSHOTS of the slot's whole cache row, recurrent state
  included, taken where a prompt's last whole chunk ends, and a request
  seated over one starts at ``pos = prefix_len``;
* ``speculative=`` attaches a :class:`serving.speculative.
  SpeculativeConfig` — requests submitted with ``speculative=True``
  run draft-then-verify rounds, greedy-exact (output-identical) with
  acceptance telemetry.  TWO kinds of draft: a separate small model
  with a cache of its own, or (:class:`serving.speculative.
  SelfDraftConfig`) the target's own multi-token-prediction module,
  whose proposals a request may ask to keep (``keep_drafts=True``:
  ``DecodeRequest.draft_tokens`` at completion, one more fetch for
  that request alone).
"""
from __future__ import annotations

import contextlib
import json
import os
import queue
import threading
import time
from typing import Dict, List, Optional, Sequence

import numpy as np

from paddle_tpu import compile_cache
from paddle_tpu import faults as _faults
from paddle_tpu import monitor
from paddle_tpu.decode_attention import last_fresh_row
from paddle_tpu.decoding import spec_of
from paddle_tpu.monitor import events as _events
from paddle_tpu.monitor import spans as _mon_spans
from paddle_tpu.serving.admission import PRIORITY_NORMAL
from paddle_tpu.serving.batching import DynamicBatcher, ServingRequest
from paddle_tpu.serving.errors import (
    DeadlineExceeded,
    ServerClosed,
    ServerOverloaded,
    ServingError,
)
from paddle_tpu.serving.kv_pool import VIEW, KVSlotPool, unpack_view
from paddle_tpu.serving.metrics import ServingMetrics
from paddle_tpu.serving.prefix_cache import PrefixKVCache
from paddle_tpu.serving.speculative import (
    SPEC_ACCEPTED,
    SPEC_PROPOSED,
    SPEC_ROUNDS,
    SPEC_ROW_ROUNDS,
)

__all__ = ["DecodeServer", "DecodeRequest", "save_decode_endpoint",
           "load_decode_endpoint"]

_LABELS = ("server", "instance")
DECODE_TOKENS = monitor.counter(
    "serving_decode_tokens_total",
    "tokens generated by the continuous-batching decode scheduler",
    _LABELS)
DECODE_PREFILL_TOKENS = monitor.counter(
    "serving_decode_prefill_tokens_total",
    "prompt tokens consumed (prefill) by the decode scheduler — the "
    "prefill/decode traffic ratio is this over the generated counter",
    _LABELS)
DECODE_TICKS = monitor.counter(
    "serving_decode_ticks_total",
    "decode scheduler ticks (one multi-step chunk dispatch each)",
    _LABELS)
DECODE_CHUNKS_AHEAD = monitor.counter(
    "serving_decode_chunks_ahead_total",
    "chunks (speculative rounds) the scheduler dispatched BEFORE it "
    "waited for the one running, so that the device went from one to "
    "the next with no gap and the host's whole turn ran under the "
    "second: a full pool whose last view could hold nothing to decide "
    "(DecodeServer._why_serial); with serving_decode_sync_turns_total "
    "it adds up to serving_decode_ticks_total", _LABELS)
DECODE_SYNC_TURNS = monitor.counter(
    "serving_decode_sync_turns_total",
    "turns that waited for their chunk with nothing queued behind it, "
    "by the first reason that held: free_seat (a seat is free or the "
    "slot ladder can grow: an arrival is seated before the next chunk), "
    "held (a chunked prefill decides slot by slot), length_finish (a "
    "request can reach its length inside the chunk: its seat is handed "
    "on before the next), memory (a second set of the executable's "
    "temporaries may not fit beside the running chunk's)",
    _LABELS + ("reason",))
#: why a turn waits for its chunk with nothing queued behind it, in the
#: order the rule asks (``DecodeServer._why_serial``)
SYNC_REASONS = ("free_seat", "held", "length_finish", "memory")
DECODE_KV_READ = monitor.counter(
    "serving_decode_kv_positions_read_total",
    "KV-cache positions the decode steps read: per step and active "
    "slot its live positions rounded up as the kernel that serves the "
    "step rounds them, by the rule the builder declares (its 'kv' "
    "decoding.PositionRead: decode_attention.kv_positions_read with the "
    "slot's last block in classes of KV_TAIL rows for the ragged "
    "kernel, of the grouped kernel's tail for that one; a speculative "
    "round counts the rule ONCE a slot that advanced, at its last fresh "
    "row); the whole rung for a step an XLA form serves (grouped heads "
    "off the TPU), and the whole pool where the builder declares no "
    "rule (an int8 pool) or none for a round (a separate draft's "
    "verify), whose reads are masked, not ragged",
    _LABELS)
DECODE_KV_LIVE = monitor.counter(
    "serving_decode_kv_positions_live_total",
    "KV-cache positions that were live in the steps run (ts + 1 per "
    "step and active slot) — read / live is what the read's rounding "
    "costs", _LABELS)
DECODE_KV_POOL = monitor.counter(
    "serving_decode_kv_positions_pool_total",
    "KV-cache positions the pool held while those steps ran (slots x "
    "length rung x steps) — read / pool is the share of the pool a step "
    "touched", _LABELS)
DECODE_TTFT = monitor.histogram(
    "serving_decode_ttft_seconds",
    "submit-to-first-token latency per decode request", _LABELS)
DECODE_OCCUPANCY = monitor.gauge(
    "serving_decode_slot_occupancy",
    "active decode slots / current slot rung (continuous-batching "
    "lane utilization)", _LABELS)
DECODE_KV_BYTES = monitor.gauge(
    "serving_kv_cache_bytes",
    "KV-cache bytes held by the decode slot pool at its CURRENT "
    "(slot, length) rung pair, from the stored dtype (int8 KV halves "
    "this vs fp32 at the same rungs); 0 while the pool is idle",
    _LABELS)

DECODE_STATE_RESETS = monitor.counter(
    "serving_decode_state_resets_total",
    "slots whose recurrent state (cache leaves with no sequence axis: "
    "SSM and conv state) was started from zero — one per admission into "
    "a pool that has such leaves, none otherwise", _LABELS)
DECODE_ADMIT_DISPATCHES = monitor.counter(
    "serving_decode_admit_dispatches_total",
    "device dispatches that seated requests into pool slots: one per "
    "scheduler turn that admits, plus one per request seated over a "
    "retained prefix (admit_prefix)", _LABELS)
DECODE_ADMITTED = monitor.counter(
    "serving_decode_admitted_total",
    "requests seated into pool slots; over "
    "serving_decode_admit_dispatches_total it is the requests one "
    "admit dispatch carried", _LABELS)
POOL_CONSTANTS_PLACED = monitor.counter(
    "serving_pool_constants_placed_total",
    "host-born constants (numpy arrays a step closes over, hoisted to "
    "executable arguments) the slot pool copied to the device ONCE at "
    "lowering, one per distinct constant, so that no dispatch sends "
    "them again; 0 for a step that closes over device arrays only",
    _LABELS)
DECODE_IDLE_DROPS = monitor.counter(
    "serving_decode_idle_drops_total",
    "times a server with nothing seated and no arrival for a whole idle "
    "wait dropped its pool state (the device memory goes; whoever "
    "arrives next pays for a fresh one); each also leaves a "
    "serving/pool_dropped event", _LABELS)
# the pool state's birth and the warm-up's wall are a process's history,
# read after a server stopped (the benchmark's set-up readers): by
# server alone, and not retired with the instance's series at stop()
POOL_STATE_SECONDS = monitor.counter(
    "serving_pool_state_seconds_total",
    "seconds a fresh pool state cost, at start-up and after every idle "
    "drop: stage alloc is KVSlotPool.alloc (zeros on the host), stage "
    "place runs from its end until the first turn over that state has "
    "delivered (the executable that first takes the zeros carries them "
    "to the device), less what that stretch spent building executables",
    ("server", "stage"))
POOL_STATE_BYTES_PLACED = monitor.counter(
    "serving_pool_state_bytes_placed_total",
    "bytes of the fresh pool states those seconds brought to the device "
    "(every leaf of the state); each also leaves a serving/pool_placed "
    "event", ("server",))
WARMUP_SECONDS = monitor.gauge(
    "serving_warmup_seconds",
    "wall seconds of the server's last DecodeServer.warmup that built "
    "anything (a re-warm that builds nothing leaves it)", ("server",))
DECODE_RECURRENT_BYTES = monitor.gauge(
    "serving_recurrent_state_bytes",
    "bytes of the pool's recurrent cache leaves (no sequence axis) at "
    "its CURRENT slot rung; serving_kv_cache_bytes counts the leaves "
    "that have one; 0 while the pool is idle", _LABELS)

DECODE_PREFILL_CHUNKS = monitor.counter(
    "serving_decode_prefill_chunks_total",
    "prefill dispatches: one slot's next prefill_tokens prompt tokens "
    "through a builder's chunked prefill (at most one a scheduler "
    "turn), or one seat-and-prefill pass of a builder with a batched "
    "prefill (every seat of a turn, 16 a pass); 0 for a builder with "
    "neither", _LABELS)
DECODE_PREFILL_CHUNK_TOKENS = monitor.counter(
    "serving_decode_prefill_chunk_tokens_total",
    "prompt tokens a prefill dispatch fed (a chunked builder's whole "
    "chunks; all of a prompt but its last token where a request is "
    "seated and prefilled in one dispatch) — over "
    "serving_decode_prefill_tokens_total it is the share of prompt "
    "tokens that did NOT ride the one-token step", _LABELS)
DECODE_SPARSE_READ = monitor.counter(
    "serving_decode_sparse_positions_read_total",
    "K/V positions the block-sparse layers' decode reads were told to "
    "read: per step, active slot and sparse layer the lesser of its "
    "live positions and what the selection rule names (everything up to "
    "dense_len; past it the initial, the window and the top-k blocks)",
    _LABELS)
DECODE_SPARSE_LIVE = monitor.counter(
    "serving_decode_sparse_positions_live_total",
    "K/V positions live for those reads (what dense attention would "
    "have read) — read / live is how sparse the traffic makes the "
    "layer", _LABELS)

DECODE_WINDOW_LIVE = monitor.counter(
    "serving_decode_window_positions_live_total",
    "K/V positions live for the window layers' decode reads: per step, "
    "active slot and window layer (the builder's 'window' read) the slot's "
    "context, from the pos the tick already fetched; 0 for a builder "
    "without window layers", _LABELS)
DECODE_WINDOW_READ = monitor.counter(
    "serving_decode_window_positions_read_total",
    "K/V positions those reads may read: the lesser of the context and "
    "the window (that read's rule) — read / live is how "
    "much of a context the window layers leave unread (and unheld)",
    _LABELS)
DECODE_INDEX_SCORED = monitor.counter(
    "serving_decode_index_positions_scored_total",
    "index keys the latent layers' learned selection scored: per step, "
    "active slot and latent layer (the builder's 'latent' read) the slot's "
    "context — every live position is scored before any is read; 0 for "
    "a builder without latent layers", _LABELS)
DECODE_LATENT_SELECTED = monitor.counter(
    "serving_decode_latent_positions_selected_total",
    "latent rows those steps' reads were told to read: the lesser of "
    "the context and the selection's top-k "
    "(that read's rule) — selected / scored is how "
    "sparse the traffic makes the read", _LABELS)
DECODE_KV_BYTES_HELD = monitor.gauge(
    "serving_decode_kv_bytes_held",
    "bytes of the pool's sequence leaves at its CURRENT rung pair, ring "
    "leaves at their own length (what KVSlotPool.kv_rung_bytes counts); "
    "0 while the pool is idle", _LABELS)
DECODE_KV_BYTES_ONE_LENGTH = monitor.gauge(
    "serving_decode_kv_bytes_one_length",
    "what those leaves would hold if every one were as long as the "
    "length rung (no window): held / one_length is what two cache "
    "lengths in one pool save; equal where no leaf is a ring leaf",
    _LABELS)

#: THE table from a kind of read a builder declares (a
#: ``decoding.PositionRead`` of ``decoding.READ_KINDS``) to what counts
#: it: (series of positions read, series of positions live, the
#: ``deliver`` span's field for the first or None).  A new kind is a row
#: here and a pair of series above: the scheduler names no kind but
#: ``"kv"`` (its rule takes the rung; counted a slot, not a layer).
#: ``metrics()`` keys: a series' name less ``serving_decode_``, ``_total``
POSITION_SERIES = {
    "kv": (DECODE_KV_READ, DECODE_KV_LIVE, None),
    "sparse": (DECODE_SPARSE_READ, DECODE_SPARSE_LIVE, None),
    "window": (DECODE_WINDOW_READ, DECODE_WINDOW_LIVE, "window_rows"),
    "latent": (DECODE_LATENT_SELECTED, DECODE_INDEX_SCORED, None),
}

_EXPERT_STATS_HELP = (
    " — counted on the device by a step whose builder declares "
    "CacheSpec.expert_stats, summed over its expert layers, fetched "
    "with the scheduler's view once a tick; 0 for a builder without "
    "routed experts")
DECODE_EXPERT_COUNTERS = tuple(
    monitor.counter("serving_decode_%s_total" % name, text
                    + _EXPERT_STATS_HELP, _LABELS)
    for name, text in (
        ("expert_assignments",
         "(row, choice) pairs of live rows routed to an expert this pool "
         "holds"),
        ("experts_touched",
         "experts that got at least one row, per step and expert layer: "
         "over expert_layer_steps it is the matrices a layer streams a "
         "step"),
        ("expert_peak_load",
         "rows of the largest group, per step and expert layer: times "
         "the expert count over expert_assignments it is the peak over "
         "the mean group (1.0 = even routing)"),
        ("expert_layer_steps",
         "steps with a live row times expert layers")))

# safety-net bound while parked on the empty-queue condition (real
# wakeups are offer()/wake() notifies); a server with nothing seated
# that sees no arrival for this long is idle and drops its pool state
_IDLE_WAIT_S = 0.5

_END = ("end", None)
_ERR = ("err", None)


class DecodeRequest(ServingRequest):
    """One decode request: a token prompt plus a streaming future.

    ``result()`` blocks for the FULL generated sequence (a one-element
    list ``[tokens]``, matching the infer outputs contract; the EOS
    token, when emitted, is included).  ``stream()`` iterates token
    chunks as the scheduler produces them — at least one chunk arrives
    before the sequence completes whenever more than ``steps`` tokens
    are generated, which is what makes ``infer_stream`` real."""

    def __init__(self, prompt: np.ndarray, total_len: int,
                 deadline: Optional[float] = None,
                 trace_id: Optional[str] = None,
                 parent_span: Optional[str] = None,
                 priority: int = PRIORITY_NORMAL,
                 speculative: bool = False,
                 keep_drafts: bool = False):
        prompt = np.asarray(prompt, np.int32).reshape(-1)
        super().__init__({"tokens": prompt[None, :]}, 1, deadline,
                         trace_id=trace_id, parent_span=parent_span,
                         priority=priority)
        self.prompt = prompt
        self.total_len = int(total_len)
        self.speculative = bool(speculative)
        #: a self-drafting server's proposals for this request's
        #: generated positions, on request (None otherwise)
        self.keep_drafts = bool(keep_drafts)
        self.draft_tokens: Optional[np.ndarray] = None
        self.first_token_t: Optional[float] = None  # perf_counter stamp
        self._chunks: "queue.Queue" = queue.Queue()

    # --- scheduler side -------------------------------------------------
    def push_tokens(self, tokens: np.ndarray, now: float) -> None:
        if self.first_token_t is None:
            self.first_token_t = now
        self._chunks.put(("tokens", tokens))

    def complete(self, value) -> None:
        if self._done.is_set():
            return
        super().complete(value)
        self._chunks.put(_END)

    def fail(self, exc: BaseException) -> None:
        if self._done.is_set():
            return
        super().fail(exc)
        self._chunks.put(_ERR)

    # --- submitter side -------------------------------------------------
    def stream(self):
        """Yield generated-token chunks (1-D int32 arrays) until the
        sequence completes; terminal errors re-raise typed, and the
        request deadline bounds every wait (a stream can hang no longer
        than ``result()`` would)."""
        while True:
            timeout = None
            if self.deadline is not None:
                timeout = max(0.0, self.deadline - time.monotonic()) + 0.05
            try:
                kind, val = self._chunks.get(timeout=timeout)
            except queue.Empty:
                raise DeadlineExceeded(
                    "deadline passed mid-stream") from None
            if kind == "tokens":
                yield val
            elif kind == "end":
                return
            else:
                assert self._exc is not None
                raise self._exc


class _Slot:
    """Host-side record of one occupied pool slot."""

    __slots__ = ("req", "prompt_len", "seen", "spec", "pos", "held", "seq")

    def __init__(self, req: DecodeRequest, pos: int = 0,
                 held: bool = False, seq: int = 0):
        self.req = req
        self.prompt_len = len(req.prompt)
        self.seen = 0  # generated tokens already streamed to the client
        self.pos = pos  # positions consumed as of the last tick's view
        self.spec = bool(getattr(req, "speculative", False))
        # held out of the decode chunk (inactive on the device) while
        # whole prefill chunks of its prompt remain
        self.held = held
        self.seq = seq  # admission order: the oldest held slot goes first


class _Flight:
    """One dispatch whose view the host has not read yet: the view on
    the device (a chunk's: ONE packed vector, an output of its own,
    alive after the state it was copied from is donated on; a turn that
    stepped nothing: the state's own leaves), the slot records the chunk was
    dispatched with — a row of the view is a slot's only while the slot
    still holds that record — and which executable ran (``"none"``: a
    turn that stepped nothing)."""

    __slots__ = ("view", "recs", "kind", "rungs")

    def __init__(self, view, recs, kind: str, rungs):
        self.view, self.recs, self.kind = view, recs, kind
        self.rungs = rungs      # of the state the chunk ran over

    @property
    def spec(self) -> bool:
        return self.kind == "spec_chunk"


class _Turn:
    """The spans of ONE traced scheduler turn: the ``serving/decode_tick``
    parent and the phase that is open.  Made only while a span sink is
    live; an untraced turn has ``None`` in its place and pays an
    ``is not None`` a phase, nothing else (no clock read, no
    ``thread_time``, no ``block_until_ready``, no annotation)."""

    __slots__ = ("tick", "leaf", "edge", "leaves", "active", "tids")

    def __init__(self):
        # not annotated: a span around the whole turn would give every
        # device gap its name (monitor.spans, "Two clocks")
        self.tick = _mon_spans.open_span(
            "serving/decode_tick", cat="serving", annotate=False)
        self.leaf = None
        # the leaves tile the tick: each starts where the last ended
        self.edge = self.tick.t0
        self.leaves = 0      # leaves recorded under this tick
        self.active = 0
        self.tids = ()

    def enter(self, phase: str, cpu: bool = False) -> None:
        self.leaf = _mon_spans.open_span(
            "serving/decode/" + phase, cat="serving", cpu=cpu, t0=self.edge)

    def leave(self, error: bool = False, **args) -> None:
        self.edge = self.leaf.close(error=error, **args)
        self.leaf = None
        self.leaves += 1

    def skip(self) -> None:
        """Leave a phase that turned out to have nothing in it."""
        self.leaf.cancel()
        self.leaf = None

    def close(self, server: "DecodeServer") -> None:
        """End of the turn: record the parent, unless nothing ran under
        it (an empty server's turn is its ``idle_wait``)."""
        if not self.leaves:
            self.tick.cancel()
            return
        with _mon_spans.trace_context(self.tids):
            self.tick.close(end=self.edge, server=server.name,
                            active=self.active, steps=server._pool.steps)


def _device_free_bytes(array) -> Optional[int]:
    """The least ``bytes_limit - bytes_in_use`` over the devices that
    hold ``array``, as their allocators report it now; None where the
    backend reports none (the CPU's)."""
    free = None
    for device in array.devices():
        stats = device.memory_stats()
        if stats and "bytes_limit" in stats:
            left = stats["bytes_limit"] - stats["bytes_in_use"]
            free = left if free is None else min(free, left)
    return free


class _PoolPredictorView:
    """The predictor-shaped facade the admin/wire surfaces read
    (``statusz``/``healthz`` expect a ``_predictor`` with names +
    jit-cache stats; here the POOL is the compile-cache owner)."""

    def __init__(self, server: "DecodeServer"):
        self._server = server

    def get_input_names(self) -> List[str]:
        return ["tokens"]

    def get_output_names(self) -> List[str]:
        return ["tokens"]

    def jit_cache_stats(self) -> Dict[str, int]:
        return self._server._pool.jit_cache_stats()


class DecodeServer:
    """Continuous-batching decode endpoint over a slot-pooled step fn.

    ``step_fn``/``make_cache``: a slot-pooled incremental model step
    (``decoding.make_transformer_lm_pooled_step_fn``).  ``max_slots`` is
    the slot ladder's top rung (the widest concurrent batch);
    ``max_seq_len`` caps prompt + generated tokens per sequence.
    ``steps_per_tick`` is the multi-step chunk size — the scheduler
    intervenes (admits, streams, frees) every ``steps_per_tick`` tokens.
    ``len_multiple`` rounds every KV length rung (and ``max_seq_len``)
    up to a multiple — set to ``n_sp`` when the step fn runs over an
    sp-sharded model so every compiled length divides the ring.

    Lifecycle mirrors ``InferenceServer``: construct (the tick thread
    starts parked) -> ``warmup()`` -> ``submit()``/client traffic ->
    ``stop(drain=True)``.

    A turn keeps at most ONE chunk queued behind the one that runs, and
    only where all four hold (:meth:`_why_serial`, asked once a turn
    from what the host can observe — no argument, flag or key selects
    it): (i) no seat is free and the slot ladder cannot grow, (ii) no
    seated slot is held for a chunked prefill, (iii) no live request can
    reach its ``total_len`` inside the running chunk by the tokens the
    host has seen, (iv) the device's free bytes hold a second set of the
    executable's temporaries.  Depth one because one queued chunk
    already hides the host's whole turn, and a second would be
    dispatched from a view two chunks old (:meth:`_tick`).
    ``stop(drain=True)`` reads a queued chunk's view before its loop
    returns; an abort, a failed dispatch and a failed read drop it with
    the pool.
    """

    #: Client.infer_stream duck-types on this (an InferenceServer lacks it)
    supports_streaming = True

    def __init__(self, step_fn, make_cache, *, eos_id: int,
                 max_seq_len: int, max_slots: int = 8,
                 slot_ladder: Optional[Sequence[int]] = None,
                 len_ladder: Optional[Sequence[int]] = None,
                 steps_per_tick: int = 4,
                 queue_capacity: int = 256,
                 max_new_tokens: Optional[int] = None,
                 name: str = "decode",
                 target_queue_wait_ms: float = 50.0,
                 class_weights="default",
                 prefix_cache=None,
                 speculative=None,
                 kv_dtype: str = "fp32",
                 len_multiple: int = 1):
        self.name = name
        self._metrics = ServingMetrics(name)
        lbl = {"server": name, "instance": self._metrics.instance}
        self._tokens_c = DECODE_TOKENS.labels(**lbl)
        self._prefill_c = DECODE_PREFILL_TOKENS.labels(**lbl)
        self._ticks_c = DECODE_TICKS.labels(**lbl)
        self._ahead_c = DECODE_CHUNKS_AHEAD.labels(**lbl)
        self._sync_cs = {reason: DECODE_SYNC_TURNS.labels(reason=reason, **lbl)
                         for reason in SYNC_REASONS}
        self._kv_pool_c = DECODE_KV_POOL.labels(**lbl)
        self._ttft_h = DECODE_TTFT.labels(**lbl)
        self._occupancy_g = DECODE_OCCUPANCY.labels(**lbl)
        self._kv_bytes_g = DECODE_KV_BYTES.labels(**lbl)
        self._state_resets_c = DECODE_STATE_RESETS.labels(**lbl)
        self._recurrent_bytes_g = DECODE_RECURRENT_BYTES.labels(**lbl)
        self._admit_dispatches_c = DECODE_ADMIT_DISPATCHES.labels(**lbl)
        self._admitted_c = DECODE_ADMITTED.labels(**lbl)
        self._idle_drops_c = DECODE_IDLE_DROPS.labels(**lbl)
        self._constants_placed_c = POOL_CONSTANTS_PLACED.labels(**lbl)
        self._constants_placed_seen = 0
        self._prefill_chunks_c = DECODE_PREFILL_CHUNKS.labels(**lbl)
        self._prefill_chunk_tokens_c = DECODE_PREFILL_CHUNK_TOKENS.labels(
            **lbl)
        # every series of POSITION_SERIES (metrics() shows them all),
        # then what the builder declares, bound once: the "kv" rule
        # (None: the whole pool a step) and for each other read (rule,
        # layers, read series, live series, the deliver span's field)
        spec = spec_of(make_cache)
        self._position_cs = {
            kind: (read.labels(**lbl), live.labels(**lbl))
            for kind, (read, live, _) in POSITION_SERIES.items()}
        self._kv_read_c, self._kv_live_c = self._position_cs["kv"]
        self._kv_rule, self._kv_rounds, self._layer_reads = None, False, []
        for read in spec.reads:
            if read.kind not in POSITION_SERIES:
                raise ValueError(
                    "make_cache declares a %r read: DecodeServer counts "
                    "the kinds %s (serving.decode.POSITION_SERIES)"
                    % (read.kind, sorted(POSITION_SERIES)))
            if read.kind == "kv":
                self._kv_rule, self._kv_rounds = read.rule, read.rounds
            elif read.layers:
                read_c, live_c = self._position_cs[read.kind]
                self._layer_reads.append(
                    (read.rule, read.layers, read_c, live_c,
                     POSITION_SERIES[read.kind][2]))
        self._kv_held_g = DECODE_KV_BYTES_HELD.labels(**lbl)
        self._kv_one_length_g = DECODE_KV_BYTES_ONE_LENGTH.labels(**lbl)
        # what the builder's steps count on the device (routed experts):
        # the leaf rides the tick's one device_get, the deltas go to the
        # four counters, in routed_experts.STAT_NAMES' order
        self._expert_stats_of = spec.expert_stats
        self._n_expert = spec.n_expert
        self._expert_cs = [c.labels(**lbl) for c in DECODE_EXPERT_COUNTERS]
        self._expert_seen = None     # the leaf as last fetched
        self._admit_seq = 0
        # decode tier 2, each independently toggleable: ``prefix_cache``
        # (a PrefixKVCache, or a byte budget to own one) retains freed
        # slots' prefix KV for shared-prefix admission; ``speculative``
        # (a SpeculativeConfig) arms draft-then-verify rounds for
        # requests submitted with speculative=True.
        self._prefix_owned = False
        if isinstance(prefix_cache, (int, np.integer)):
            prefix_cache = PrefixKVCache(
                capacity_bytes=int(prefix_cache), name=name)
            self._prefix_owned = True
        self._prefix = prefix_cache
        self._speculative = speculative
        self._spec_proposed_c = self._spec_accepted_c = None
        self._accept_len_hist: Dict[int, int] = {}
        if speculative is not None:
            self._spec_proposed_c = SPEC_PROPOSED.labels(**lbl)
            self._spec_accepted_c = SPEC_ACCEPTED.labels(**lbl)
            self._spec_rounds_c = SPEC_ROUNDS.labels(**lbl)
            self._spec_row_rounds_c = SPEC_ROW_ROUNDS.labels(**lbl)
        self._pool = KVSlotPool(
            step_fn, make_cache, eos_id=eos_id, max_slots=max_slots,
            max_seq_len=max_seq_len, slot_ladder=slot_ladder,
            len_ladder=len_ladder, steps=steps_per_tick,
            prefix=self._prefix is not None, speculative=speculative,
            kv_dtype=kv_dtype, len_multiple=len_multiple,
            on_recompile=self._on_recompile)
        # a pool without a chunked prefill never holds a slot: its
        # turns run what they always ran
        self._chunked = self._pool.prefill_tokens > 0
        self._default_max_new = (
            int(max_new_tokens) if max_new_tokens is not None else None)
        # the SAME admission front door as the batching server: EDF +
        # priority classes (weighted fair sharing) + AIMD admit limit.
        # batch_timeout is irrelevant here — the tick IS the coalescing
        # window — so the batcher runs eager.
        self._batcher = DynamicBatcher(
            max_slots, 0.0, queue_capacity, name=name,
            target_wait_ms=target_queue_wait_ms,
            class_weights=class_weights)
        self._batcher.on_shed = self._on_queue_shed
        self._batcher.on_expired = self._on_expired
        self._feed_names = ["tokens"]
        self._predictor = _PoolPredictorView(self)
        # observed total sequence lengths (prompt + generation budget)
        # per admitted request — the input to the offline KV length-
        # ladder proposal (serving.autotune.propose_len_ladder)
        self._seq_len_hist: Dict[int, int] = {}
        self._seq_len_lock = threading.Lock()
        self._closed = False
        self._abort = False
        self._stop = threading.Event()
        self._warmed = False
        self._state = None               # tick-thread owned pool state
        # the chunk dispatched AHEAD of the one the last turn waited
        # for: on the device (running or done), its view unread
        self._flight: Optional[_Flight] = None
        # a fresh state not yet delivered over: (perf_counter at the end
        # of its alloc, the alloc's seconds, its bytes, the tick
        # thread's build seconds then); None once its birth is booked
        self._born = None
        self._slots: List[Optional[_Slot]] = []
        self._worker = threading.Thread(
            target=self._loop, name="serving-decode-%s" % name, daemon=True)
        self._worker.start()

    # ------------------------------------------------------------------
    @property
    def num_replicas(self) -> int:
        return 0 if self._closed else 1

    @property
    def max_batch_size(self) -> int:
        return self._pool.max_slots

    @property
    def max_seq_len(self) -> int:
        return self._pool.max_seq_len

    @property
    def prefix_cache(self):
        """The attached :class:`PrefixKVCache` (None when disabled) —
        the wire surfaces advertise its stats via /healthz."""
        return self._prefix

    @property
    def speculative_k(self) -> Optional[int]:
        """Verify width of the attached draft (None when speculative
        decoding is disabled)."""
        return self._speculative.k if self._speculative is not None else None

    @property
    def kv_dtype(self) -> str:
        """The pool's KV-cache storage dtype (``"fp32"`` or ``"int8"``)
        — the wire surfaces advertise it via /healthz."""
        return self._pool.kv_dtype

    def load(self) -> Dict[str, object]:
        return {
            "queue_depth": self._batcher.qsize(),
            "admit_limit": self._batcher.queue.limit,
            "brownout_level": 0,
        }

    def metrics(self) -> Dict[str, object]:
        snap = self._metrics.snapshot()
        snap["queue_depth"] = self._batcher.qsize()
        snap["admit_limit"] = self._batcher.queue.limit
        snap["brownout_level"] = 0
        snap["warmed_up"] = self._warmed
        snap["decode"] = {
            "generated_tokens": int(self._tokens_c.value),
            "prefill_tokens": int(self._prefill_c.value),
            "ticks": int(self._ticks_c.value),
            # of those ticks: chunks dispatched before the host waited
            # for the one running / turns that waited with nothing
            # queued, by reason (they add up to ``ticks``)
            "chunks_ahead": int(self._ahead_c.value),
            "ahead_share": (self._ahead_c.value / self._ticks_c.value
                            if self._ticks_c.value else 0.0),
            "sync_turns": {reason: int(c.value)
                           for reason, c in self._sync_cs.items()},
            # kv_positions_read / _live, and each layer kind's pair
            **{series.name[len("serving_decode_"):-len("_total")]:
               int(c.value)
               for kind, cs in self._position_cs.items()
               for series, c in zip(POSITION_SERIES[kind], cs)},
            "kv_positions_pool": int(self._kv_pool_c.value),
            "slot_occupancy": float(self._occupancy_g.value),
            "steps_per_tick": self._pool.steps,
            "slot_ladder": list(self._pool.slot_policy.ladder),
            "len_ladder": list(self._pool.len_policy.ladder),
            "max_seq_len": self._pool.max_seq_len,
            "kv_dtype": self._pool.kv_dtype,
            "kv_cache_bytes": int(self._kv_bytes_g.value),
            "recurrent_state_bytes": int(self._recurrent_bytes_g.value),
            "state_resets": int(self._state_resets_c.value),
            "admit_dispatches": int(self._admit_dispatches_c.value),
            "admitted": int(self._admitted_c.value),
            "idle_drops": int(self._idle_drops_c.value),
            "prefill_chunks": int(self._prefill_chunks_c.value),
            "prefill_chunk_tokens": self._pool.prefill_tokens,
            "prefill_chunk_tokens_total": int(
                self._prefill_chunk_tokens_c.value),
            "kv_bytes_held": int(self._kv_held_g.value),
            "kv_bytes_one_length": int(self._kv_one_length_g.value),
            "expert_assignments": int(self._expert_cs[0].value),
            "experts_touched": int(self._expert_cs[1].value),
            "expert_peak_load": int(self._expert_cs[2].value),
            "expert_layer_steps": int(self._expert_cs[3].value),
            "seq_len_histogram": {
                str(k): v
                for k, v in sorted(self.seq_len_histogram().items())},
            "kv_ladder_plan": self._kv_ladder_plan(),
        }
        if self._prefix is not None:
            snap["decode"]["prefix_cache"] = self._prefix.stats()
        if self._speculative is not None:
            with self._seq_len_lock:
                hist = dict(self._accept_len_hist)
            snap["decode"]["speculative"] = {
                "k": self._speculative.k,
                "kind": self._speculative.kind,
                "proposed_tokens": int(self._spec_proposed_c.value),
                "accepted_tokens": int(self._spec_accepted_c.value),
                "rounds": int(self._spec_rounds_c.value),
                "row_rounds": int(self._spec_row_rounds_c.value),
                "accepted_len_histogram": {
                    str(a): n for a, n in sorted(hist.items())},
            }
        return snap

    def _kv_ladder_plan(self) -> Optional[Dict[str, object]]:
        """The offline KV length-ladder proposal for the observed
        sequence lengths (``serving.autotune.plan_kv_ladder``), embedded
        in every metrics/statusz snapshot so a recorded snapshot is a
        COMPLETE kv-ladder input for ``tools/autotune_ladder.py``.
        Proposal only: applying a new ladder re-warms every rung pair,
        so it is a restart-time decision — pass the proposed ladder to
        ``DecodeServer(len_ladder=...)`` on the next deploy."""
        hist = self.seq_len_histogram()
        if not hist:
            return None
        from paddle_tpu.serving.autotune import plan_kv_ladder

        try:
            return plan_kv_ladder(
                hist, self._pool.max_seq_len,
                current_ladder=list(self._pool.len_policy.ladder))
        except Exception:
            return None

    def seq_len_histogram(self) -> Dict[int, int]:
        """{total sequence length: admitted-request count} — the
        observed input to ``serving.autotune.propose_len_ladder`` /
        ``plan_kv_ladder`` (offline KV length-ladder proposal)."""
        with self._seq_len_lock:
            return dict(self._seq_len_hist)

    def tracez(self) -> Dict[str, object]:
        from paddle_tpu.monitor import flight as _flight

        rec = _flight.get()
        if rec is None:
            return {"recorder": False, "retained": 0, "requests": []}
        doc = rec.statusz()
        doc["recorder"] = True
        return doc

    def statusz(self) -> Dict[str, object]:
        return {
            "server": self.name,
            "metrics": self.metrics(),
            "jit_cache": self._pool.jit_cache_stats(),
            "registry": monitor.snapshot(),
        }

    def replica_stats(self) -> Dict[str, Dict[str, object]]:
        return {"decode": {"alive": not self._closed,
                           "slots": len(self._slots)}}

    # ------------------------------------------------------------------
    def _count_constants_placed(self) -> None:
        """Bring ``serving_pool_constants_placed_total`` up to what the
        pool has placed (it places at lowering: warmup, or a recompile)."""
        placed = self._pool.constants_placed
        self._constants_placed_c.inc(placed - self._constants_placed_seen)
        self._constants_placed_seen = placed

    def _on_recompile(self) -> None:
        self._metrics.count("recompiles")
        self._count_constants_placed()

    def warmup(self, configure_cache: bool = True) -> int:
        """Pre-compile chunk/admit/release for every (slot, length) rung
        pair; arms the recompile counter (any executable built after
        this increments ``metrics()['recompiles']``)."""
        t0 = time.perf_counter()
        with _mon_spans.parent_scope() as span_id:
            if configure_cache:
                compile_cache.configure()
            compiles = self._pool.warmup()
        wall = time.perf_counter() - t0
        if compiles:
            WARMUP_SECONDS.labels(server=self.name).set(wall)
        _mon_spans.record_span(
            "serving/warmup", t0, wall, cat="serving", span_id=span_id,
            server=self.name, compiles=compiles,
            rung_pairs=len(self._pool.rung_pairs()))
        self._count_constants_placed()
        self._metrics.count("warmup_compiles", compiles)
        self._warmed = True
        return compiles

    # ------------------------------------------------------------------
    def submit(self, feed, timeout_ms: Optional[float] = None,
               trace_id: Optional[str] = None,
               parent_span: Optional[str] = None,
               priority: int = PRIORITY_NORMAL,
               max_new_tokens: Optional[int] = None,
               speculative: bool = False,
               keep_drafts: bool = False) -> DecodeRequest:
        """Enqueue one prompt; returns its streaming future.

        ``feed``: ``{"tokens": [L] or [1, L] int}`` (or the positional
        one-array form) — ONE sequence per request; continuous batching
        makes the server-side batch, so there is nothing to gain from
        client-side batching.  ``max_new_tokens`` caps generation
        (default: the server's cap, else to ``max_seq_len``).
        ``speculative=True`` opts the request into draft-then-verify
        rounds (requires a server-side ``SpeculativeConfig``; the
        output is bit-identical either way — speculation only changes
        speed).  ``keep_drafts=True`` (a self-drafting server): the
        module's proposal for each generated position is fetched when
        the request completes (``DecodeRequest.draft_tokens``; no other
        request's tick pays for it).  Deadline, priority, shedding, and
        trace-id semantics match ``InferenceServer.submit``."""
        if self._closed:
            raise ServerClosed("server %r is stopped" % self.name)
        if speculative and self._speculative is None:
            raise ValueError(
                "server %r has no draft model: build the DecodeServer "
                "with speculative=SpeculativeConfig(...) to accept "
                "speculative submissions" % self.name)
        if timeout_ms is not None and float(timeout_ms) <= 0:
            self._metrics.count("expired")
            raise DeadlineExceeded(
                "deadline exhausted before admission (%.1f ms)"
                % float(timeout_ms))
        prompt = self._normalize_prompt(feed)
        if max_new_tokens is not None and int(max_new_tokens) < 1:
            raise ValueError(
                "max_new_tokens must be >= 1, got %d (the decode server "
                "always generates; use infer() shapes for prefill-only "
                "probes)" % int(max_new_tokens))
        cap = max_new_tokens if max_new_tokens is not None else self._default_max_new
        total = len(prompt) + int(cap) if cap is not None else self._pool.max_seq_len
        total = max(len(prompt) + 1, min(int(total), self._pool.max_seq_len))
        deadline = (
            time.monotonic() + float(timeout_ms) / 1e3
            if timeout_ms is not None else None)
        req = DecodeRequest(prompt, total, deadline, trace_id=trace_id,
                            parent_span=parent_span, priority=priority,
                            speculative=bool(speculative),
                            keep_drafts=bool(keep_drafts and speculative))
        try:
            self._batcher.offer(req)
        except Exception:
            self._metrics.count("shed")
            raise
        # record AFTER admission: a shed request was never decoded, so
        # counting it would skew the ladder toward traffic never served
        with self._seq_len_lock:
            self._seq_len_hist[total] = (
                self._seq_len_hist.get(total, 0) + 1)
        self._metrics.count("requests")
        if not self._worker.is_alive():
            # the submit-vs-stop race — or a crashed tick thread:
            # nothing will ever serve this queue, so never park a caller
            self._fail_stragglers()
            if req.done():
                raise ServerClosed("server %r is stopped" % self.name)
        return req

    def _normalize_prompt(self, feed) -> np.ndarray:
        if isinstance(feed, dict):
            if set(feed) != {"tokens"}:
                raise ValueError(
                    "decode feed must be {'tokens': ...}, got %s"
                    % sorted(feed))
            arr = feed["tokens"]
        elif isinstance(feed, (list, tuple)) and len(feed) == 1:
            arr = feed[0]
        else:
            arr = feed
        arr = np.asarray(arr)
        if arr.ndim == 2:
            if arr.shape[0] != 1:
                raise ValueError(
                    "one sequence per decode request (got %d rows); the "
                    "scheduler batches server-side" % arr.shape[0])
            arr = arr[0]
        if arr.ndim != 1 or arr.size < 1:
            raise ValueError(
                "prompt must be a non-empty 1-D token array, got shape %s"
                % (arr.shape,))
        if arr.size >= self._pool.max_seq_len:
            raise ValueError(
                "prompt of %d tokens leaves no room to generate "
                "(max_seq_len=%d)" % (arr.size, self._pool.max_seq_len))
        return arr.astype(np.int32, copy=False)

    # ------------------------------------------------------------------
    def _on_queue_shed(self, req, retry_after_ms: float) -> None:
        self._metrics.count("shed")
        req.fail(ServerOverloaded(
            "evicted by a higher-priority request",
            retry_after_ms=retry_after_ms))

    def _on_expired(self, req) -> None:
        self._metrics.count("expired")
        req.fail(DeadlineExceeded("deadline passed while queued"))

    def _fail_stragglers(self) -> None:
        for req in self._batcher.drain_pending():
            req.fail(ServerClosed("server %r stopped" % self.name))

    # ------------------------------------------------------------------
    # the tick loop: admit -> chunk dispatch -> materialize/stream/free
    # ------------------------------------------------------------------
    def _active_count(self) -> int:
        return sum(1 for s in self._slots if s is not None)

    def _loop(self) -> None:
        _mon_spans.set_thread_lane("serving/%s/decode" % self.name)
        try:
            while True:
                # the turn's ONE read of the span sink: every phase
                # below asks ``turn is not None``
                turn = _Turn() if _mon_spans.recording() else None
                self._admit_pending(turn)
                # a chunk still queued is delivered before the server
                # is idle (every slot it stepped freed a turn ago)
                if self._active_count() == 0 and self._flight is None:
                    if turn is not None:
                        turn.close(self)
                    if self._stop.is_set() and (
                            self._abort or self._batcher.qsize() == 0):
                        return
                    self._occupancy_g.set(0.0)
                    idle = None if turn is None else _mon_spans.open_span(
                        "serving/decode/idle_wait", cat="serving")
                    loaded = self._state is not None
                    if loaded:
                        t_idle = time.perf_counter()
                    cv = self._batcher.queue.cv
                    with cv:
                        arrived = (self._batcher.queue.qsize() > 0
                                   or self._stop.is_set()
                                   or cv.wait(timeout=_IDLE_WAIT_S))
                    if loaded and not arrived:
                        self._drop_idle_pool(time.perf_counter() - t_idle)
                    if idle is not None:
                        idle.close(server=self.name,
                                   dropped=loaded and not arrived)
                    continue
                if self._abort:
                    self._fail_in_flight(
                        ServerClosed("server %r is stopped" % self.name))
                    return
                self._tick(turn)
                if turn is not None:
                    turn.close(self)
        finally:
            # a crash escaping the loop must not leave a zombie front
            # door: close the server so later submits fail typed instead
            # of queueing work nothing will ever drain
            self._closed = True
            self._stop.set()
            self._fail_in_flight(
                ServerClosed("server %r stopped" % self.name))
            self._fail_stragglers()

    def _drop_idle_pool(self, idle_s: float) -> None:
        """Idle: drop the pool state (frees the KV memory; the next
        admit re-allocs at the smallest rungs).  Only after a whole
        wait with no arrival: a gap between two requests is not
        idleness, and the state is re-made on the host and carried to
        the device again — 8 s for gpt1_117m's 12 GB, paid by whoever
        arrives next (v5e chip run, PR 32).  Counted, and left in the
        event ring with the seconds since the last turn ended: a wait
        that outlasted ``_IDLE_WAIT_S`` by much is a process that
        stood still."""
        rungs = self._pool.state_rungs(self._state)
        freed = (self._pool.kv_rung_bytes(*rungs)
                 + self._pool.recurrent_rung_bytes(*rungs))
        self._state = None
        self._born = None
        self._slots = []
        self._set_pool_bytes(None)
        self._idle_drops_c.inc()
        _events.emit("serving/pool_dropped", cat="serving",
                     server=self.name, bytes=int(freed),
                     idle_s=float(idle_s))

    def _alloc_state(self, s: int, t: int):
        """A fresh pool state, its birth timed: ``alloc``'s zeros on the
        host here; what carrying them to the device costs is known only
        when the first turn over them has delivered
        (:meth:`_pool_placed`)."""
        import jax

        t0 = time.perf_counter()
        state = self._pool.alloc(s, t)
        t1 = time.perf_counter()
        POOL_STATE_SECONDS.labels(server=self.name, stage="alloc").inc(
            t1 - t0)
        self._born = (t1, t1 - t0,
                      sum(leaf.nbytes for leaf in jax.tree.leaves(state)),
                      compile_cache.thread_build_seconds())
        return state

    def _pool_placed(self, now: float) -> None:
        """The first turn over a fresh state has delivered (``now`` is
        the stamp of its tokens, after the tick's fetch): the state is
        on the device.  The twin of :meth:`_drop_idle_pool`'s event — a
        far-off run can be asked what bringing 12 GB back cost."""
        t_alloc_end, alloc_s, nbytes, built0 = self._born
        self._born = None
        # an unwarmed server builds its executables inside this stretch:
        # those seconds are the build record's
        place_s = max(0.0, now - t_alloc_end - (
            compile_cache.thread_build_seconds() - built0))
        POOL_STATE_SECONDS.labels(server=self.name, stage="place").inc(
            place_s)
        POOL_STATE_BYTES_PLACED.labels(server=self.name).inc(nbytes)
        _events.emit("serving/pool_placed", cat="serving",
                     server=self.name, bytes=int(nbytes),
                     alloc_s=float(alloc_s), place_s=float(place_s))

    def _admit_pending(self, turn: Optional[_Turn]) -> None:
        """Seat queued prompts into free slots with ONE device dispatch
        for the whole turn.

        The turn is planned on the host first: requests are popped while
        a slot is free (or the slot ladder can still grow) and the queue
        has work — FIFO, each to the lowest free slot, an expired one
        failed at the pop — and the rung pair the whole batch needs is
        worked out as the pops go.  Then the pool is resized ONCE (the
        bucket ladders bound the shapes, so growth lands on a warmed
        executable — never a compile) and ONE ``pool.admit`` seats every
        popped request, so the chip waits through one dispatch between
        two chunks however many slots the last chunk freed.  A request
        with a prefix-cache hit keeps an ``admit_prefix`` call of its
        own (it installs KV, or a whole snapshot); one whose
        installation fails joins the plain batch (degraded, never
        wrong).  Over a builder with a chunked prefill, the seated
        requests that still have a whole chunk of prompt to go are then
        HELD (one ``release`` for all of them: inactive, so the decode
        chunk leaves them alone) until :meth:`_prefill_turn` has fed
        them."""
        pool = self._pool
        slots = list(self._slots)    # the turn's plan of self._slots
        cur = (None if self._state is None
               else pool.state_rungs(self._state))
        new_s, new_t = cur or (0, 0)
        popped: List[DecodeRequest] = []
        seats = []                   # (slot, req, pre_len, pre_kv)
        probes = []                  # one per prefix lookup: did it hit
        if turn is not None:
            expired0 = self._metrics.counted("expired")
            turn.enter("admit_plan", cpu=True)
        try:
            while None in slots or len(slots) < pool.max_slots:
                with self._batcher.queue.cv:
                    req, expired = self._batcher.queue.pop_locked()
                for r in expired:
                    self._on_expired(r)
                if req is None:
                    break
                if req.expired():
                    self._on_expired(req)
                    continue
                popped.append(req)
                pre_len, pre_kv = 0, None
                if self._prefix is not None:
                    pre_len, pre_kv = self._prefix.lookup(req.prompt)
                    probes.append(pre_len > 0)
                    # a snapshot's last rows may have looked ahead at
                    # another request's tokens: seated short of them
                    pre_len = max(pre_len - pool.snapshot_lookahead, 0)
                new_t = max(new_t, pool.len_policy.bucket_for(req.total_len))
                if None not in slots:
                    new_s = pool.slot_policy.bucket_for(new_s + 1)
                    slots.extend([None] * (new_s - len(slots)))
                slot = slots.index(None)
                slots[slot] = req
                seats.append((slot, req, pre_len, pre_kv))
            if turn is not None:
                if popped or self._metrics.counted("expired") > expired0:
                    turn.leave(popped=len(popped), lookups=len(probes))
                else:
                    turn.skip()
            if not popped:
                return
            if turn is not None:
                dispatches0 = self._admit_dispatches_c.value
                turn.enter("admit_dispatch")
            if (new_s, new_t) != cur:
                if cur is None:
                    self._expert_seen = None   # a fresh state counts from 0
                    self._state = self._alloc_state(new_s, new_t)
                else:
                    self._state = pool.resize(self._state, new_s, new_t)
                self._slots.extend([None] * (new_s - len(self._slots)))
                self._set_pool_bytes((new_s, new_t))
            seats = [self._admit_with_prefix(*seat) if seat[2] > 0 else seat
                     for seat in seats]
            plain = [(slot, req) for slot, req, pre_len, _ in seats
                     if pre_len == 0]
            fed = {}     # slot: prompt tokens a prefill pass fed it
            if plain:
                at, reqs = zip(*plain)
                prompts = [r.prompt for r in reqs]
                totals = [r.total_len for r in reqs]
            if plain and pool.seats_prefilled:
                # seated AND fed all but the prompt's last token, in the
                # same dispatch (a pass; 16 seats each)
                self._state, passes = pool.seat_prefill(
                    self._state, at, prompts, totals)
                self._admit_dispatches_c.inc(passes)
                self._prefill_chunks_c.inc(passes)
                fed = {slot: len(p) - 1 for slot, p in zip(at, prompts)}
                self._prefill_chunk_tokens_c.inc(sum(fed.values()))
            elif plain:
                self._state = pool.admit(
                    self._state, at, prompts, [len(p) for p in prompts],
                    totals, spec=[r.speculative for r in reqs])
                self._admit_dispatches_c.inc()
            held = [slot for slot, req, pre_len, _ in seats
                    if pool.can_prefill(self._state, pre_len,
                                        len(req.prompt))
                    ] if self._chunked else ()
            if held:
                self._state = pool.release(self._state, held)
        except BaseException as exc:  # noqa: BLE001 — fail typed,
            # keep serving (the _tick discipline): the popped requests
            # are in neither the queue nor a slot, so an escaping
            # exception would strand their callers forever, and a
            # failure mid-resize may have corrupted the pool
            for req in popped:
                req.fail(exc)
            self._metrics.count("failed", len(popped))
            self._fail_and_drop_pool(exc)
            for hit in probes:
                self._prefix.count_probe(hit)
            if turn is not None and turn.leaf is not None:
                turn.leave(error=True)
            return
        for slot, req, pre_len, _ in seats:
            self._admit_seq += 1
            self._slots[slot] = _Slot(req, pre_len + fed.get(slot, 0),
                                      slot in held, self._admit_seq)
            # the shared-prefix win, measured where it happens: only the
            # unmatched suffix re-enters prefill (in chunks or by steps)
            self._prefill_c.inc(len(req.prompt) - pre_len)
        # the turn's lookups are counted HERE, beside its admissions and
        # not at the pop: a turn of five prefix admissions is ~12 ms of
        # hashing and dispatches, and a thread that read both counters
        # inside it saw five hits that no admission matched yet
        # (minicpm_sala's window check failed on that: v5e chip run,
        # PR 35)
        for hit in probes:
            self._prefix.count_probe(hit)
        self._admitted_c.inc(len(seats))
        if pool.recurrent_leaves:
            # a slot seated over a snapshot resumes a state: no reset
            self._state_resets_c.inc(
                sum(1 for seat in seats if seat[2] == 0))
        if turn is not None:
            # the turn's bookkeeping above rides this leaf: the phases
            # tile the turn
            turn.leave(seated=len(seats), dispatches=int(
                self._admit_dispatches_c.value - dispatches0),
                rows=len(fed), tokens=sum(fed.values()))

    def _admit_with_prefix(self, slot, req, pre_len, pre_kv):
        """Seat ``req`` over its retained prefix KV, in a dispatch of its
        own; returns its seat, with ``pre_len`` 0 if the installation
        failed and the request is to take the plain ``admit``."""
        try:
            # a corrupted or evicted-mid-admit prefix entry must DEGRADE
            # (full prefill), never emit wrong tokens: any failure here
            # falls back to the plain warmed admit, typed and counted
            if _faults.active is not None:  # one is-None gate
                _faults.active.faultpoint(
                    "decode.prefix_admit", server=self.name,
                    prefix_len=pre_len)
            self._state = self._pool.admit_prefix(
                self._state, slot, req.prompt, len(req.prompt),
                req.total_len, pre_kv, pre_len, spec=req.speculative)
            self._admit_dispatches_c.inc()
        except Exception:
            self._metrics.count("prefix_fallback")
            self._prefix.count_fallback()
            # degraded-not-wrong is exactly what the event ring exists
            # to surface (+ span instant)
            _events.emit(
                "serving/prefix_fallback", severity="warning",
                cat="serving", server=self.name, prefix_len=pre_len,
                trace_id=req.trace_id or None)
            pre_len = 0
        return slot, req, pre_len, None

    def _set_pool_bytes(self, rungs) -> None:
        """The two byte gauges for the state's rung pair (None: the pool
        holds nothing)."""
        pool = self._pool
        self._kv_bytes_g.set(
            0.0 if rungs is None else float(pool.kv_rung_bytes(*rungs)))
        self._recurrent_bytes_g.set(
            0.0 if rungs is None
            else float(pool.recurrent_rung_bytes(*rungs)))
        self._kv_held_g.set(self._kv_bytes_g.value)
        self._kv_one_length_g.set(
            0.0 if rungs is None
            else float(pool.kv_rung_bytes_one_length(*rungs)))

    def _tick(self, turn: Optional[_Turn]) -> None:
        """One scheduler turn: see that a multi-step chunk is on the
        device, dispatch the NEXT one behind it where nothing could be
        decided in between, then materialize the older chunk's (small)
        scheduler view and stream/complete.

        **Run-ahead of depth one.**  Serial, a turn is dispatch chunk N
        -> ``wait`` -> ``copy`` -> ``deliver`` -> ``admit`` -> dispatch
        N + 1, and the device has nothing queued from the instant N ends
        until the next launch reaches it: the host's whole turn, 3 ms of
        a 21 ms self-drafting round (v5e chip runs, PR 59 / PR 61).  So
        before it waits for N the turn dispatches N + 1 wherever N's
        view can hold nothing the host would act on
        (:meth:`_why_serial`: a full pool, no held slot, no request
        within reach of its length, room for a second set of
        temporaries); the device goes from N to N + 1 with no gap and
        everything from ``wait``'s wake-up to the next turn's launch
        runs under N + 1.  Otherwise the turn is the serial one — the
        serial turn IS the run-ahead turn with nothing queued.  The
        depth is ONE: a second chunk queued would be dispatched from a
        view two chunks old (a request within ``2 x steps`` tokens of
        its length would keep the turn serial, and a seat would wait two
        chunks), and one queued chunk already covers the whole turn.

        What run-ahead costs: an EOS, an expired deadline or an
        abandoned stream inside N is found one chunk late.  The slot is
        inert on the device meanwhile (``active & ~newly_fin``; its
        ``ts`` is -1), so it costs one slot-chunk, never a token.  Each
        chunk in flight carries the slot records of its dispatch
        (:class:`_Flight`), and a view's row is applied to a slot only
        while the slot still holds that record: a request seated into a
        slot freed while N + 1 was queued never reads N + 1's row.

        A turn is still ONE ``serving/decode_tick``: ONE ``dispatch``
        leaf (``ahead`` = whether a chunk was queued behind the one the
        turn waits for, ``chunks`` = executables launched under it: 2
        where a run of turns ahead begins, 0 where it ends), then
        ``wait`` / ``copy`` / ``deliver`` of the OLDER chunk; every
        counter moves once a view, when it is delivered."""
        import jax

        flight, self._flight = self._flight, None
        recs = [(i, s) for i, s in enumerate(self._slots) if s is not None]
        tids = ()
        if turn is not None:
            tids = tuple(s.req.trace_id for _, s in recs if s.req.trace_id)
            turn.active, turn.tids = len(recs), tids
        if (flight is None and self._chunked
                and any(s.held for _, s in recs)
                and not self._prefill_turn(recs, turn)):
            return
        if turn is not None:
            turn.enter("dispatch")
        launched = 0
        try:
            with contextlib.ExitStack() as stack:
                if tids:
                    stack.enter_context(_mon_spans.trace_context(tids))
                if flight is None:
                    flight = self._dispatch(recs)
                    launched += flight.kind != "none"
                serial = self._why_serial(flight)
                if serial is None:
                    self._flight = self._dispatch(recs)
                    launched += 1
        except BaseException as exc:  # noqa: BLE001 — fail typed, keep serving
            if turn is not None:
                turn.leave(error=True, chunks=launched, kind=(
                    "error" if flight is None else flight.kind))
            self._fail_and_drop_pool(exc)
            return
        kind, use_spec = flight.kind, flight.spec
        stepped = kind != "none"
        try:
            if turn is not None:
                turn.leave(kind=kind, ahead=int(serial is None),
                           chunks=launched)
                # tell the chunk's rest from the copies without delaying
                # either: queue the five copies behind the chunk NOW, as
                # the device_get below does in an untraced turn (waiting
                # first and asking for them afterwards cost a traced
                # chat tick 0.5 ms: v5e chip run, PR 36), then wait on
                # the smallest of the five — outputs of one execution,
                # ready together
                turn.enter("wait")
                for v in jax.tree.leaves(flight.view):
                    v.copy_to_host_async()
                jax.block_until_ready(jax.tree.leaves(flight.view)[-1])
                turn.leave()
                turn.enter("copy")
            # the scheduler intervention: one d2h of the control-plane
            # arrays (tokens/pos/flags — KBs, not the KV cache), which
            # a chunk hands over packed into ONE vector
            view = jax.device_get(flight.view)
            copied = sum(v.nbytes for v in jax.tree.leaves(view))
            if stepped:
                view = unpack_view(view, *flight.rungs)
        except BaseException as exc:  # noqa: BLE001 — an error of the
            # chunk (or of the one queued behind it) surfaces where its
            # outputs are first read: as a dispatch error does
            if turn is not None and turn.leaf is not None:
                turn.leave(error=True)
            self._fail_and_drop_pool(exc)
            return
        if turn is not None:
            turn.leave(bytes=copied)
            turn.enter("deliver", cpu=True)
            tokens0 = self._tokens_c.value
        # a row is its slot's only while the slot holds the record the
        # chunk was dispatched with (a serial turn: every one of them)
        recs = [(i, rec) for i, rec in flight.recs if self._slots[i] is rec]
        fields = {}     # of the deliver span: what the chunk counted
        self_draft = use_spec and self._speculative.kind == "self"
        if use_spec:
            proposed0 = self._spec_proposed_c.value
            accepted0 = self._spec_accepted_c.value
            # before the position counters move ``rec.pos``
            self._count_spec_round(recs, view, self_draft)
        if stepped:
            if "expert_stats" in view:
                fields = self._count_experts(view["expert_stats"])
            fields.update(self._count_kv_positions(recs, view, use_spec))
            (self._ahead_c if serial is None else self._sync_cs[serial]).inc()
            # after the position counters: whoever sees the tick counted
            # sees its positions counted too
            self._ticks_c.inc()
        now = time.perf_counter()
        if self._born is not None:
            self._pool_placed(now)
        released: List[int] = []
        for i, rec in recs:
            n_gen = int(view["n_gen"][i])
            fresh = n_gen - rec.seen
            if fresh > 0:
                lo = rec.prompt_len + rec.seen
                chunk = view["tokens"][i, lo:lo + fresh].copy()
                if rec.req.first_token_t is None:
                    self._ttft_h.observe(
                        now - rec.req.submit_t,
                        exemplar=({"trace_id": rec.req.trace_id}
                                  if rec.req.trace_id else None))
                rec.req.push_tokens(chunk, now)
                if use_spec and not self_draft and rec.spec and rec.seen > 0:
                    # a pure decode-phase round (the slot had emitted
                    # before, so no prefill teacher-forcing inflated
                    # ``fresh``): the round's first token is the
                    # target's own step, the rest are accepted
                    # proposals out of the k - 1 drafted
                    k1 = self._speculative.k - 1
                    accepted = min(max(fresh - 1, 0), k1)
                    self._spec_proposed_c.inc(k1)
                    self._spec_accepted_c.inc(accepted)
                    with self._seq_len_lock:
                        self._accept_len_hist[accepted] = (
                            self._accept_len_hist.get(accepted, 0) + 1)
                rec.seen = n_gen
                self._tokens_c.inc(fresh)
            if bool(view["finished"][i]):
                out = view["tokens"][
                    i, rec.prompt_len:rec.prompt_len + n_gen].copy()
                self._offer_prefix(i, rec, int(view["pos"][i]))
                if rec.req.keep_drafts and "proposals" in self._state:
                    # this request alone pays the fetch, at its end
                    # (``self._state`` may be a chunk on: the rows of a
                    # slot that finished are what they were)
                    rec.req.draft_tokens = np.asarray(jax.device_get(
                        self._state["proposals"]))[
                            i, rec.prompt_len:rec.prompt_len + n_gen].copy()
                rec.req.complete([out])
                self._metrics.observe_request(
                    now - rec.req.submit_t, trace_id=rec.req.trace_id)
                self._slots[i] = None
            elif rec.req.expired():
                # deadline passed mid-decode: abort the lane so the slot
                # frees for queued work.  fail() is a no-op when the
                # client already failed the request (its typed error
                # wins), but the miss still counts as expired
                rec.req.fail(DeadlineExceeded(
                    "deadline passed mid-decode"))
                self._metrics.count("expired")
                self._offer_prefix(i, rec, int(view["pos"][i]))
                released.append(i)
                self._slots[i] = None
            elif rec.req.done():
                # the client gave up (an abandoned stream already failed
                # the request) with its deadline intact: free the lane
                # silently — this is not a deadline miss
                self._offer_prefix(i, rec, int(view["pos"][i]))
                released.append(i)
                self._slots[i] = None
        if released:
            self._state = self._pool.release(self._state, released)
        if self._slots:
            self._occupancy_g.set(
                self._active_count() / float(len(self._slots)))
        if turn is not None:
            if use_spec:
                fields["proposed"] = int(
                    self._spec_proposed_c.value - proposed0)
                fields["accepted"] = int(
                    self._spec_accepted_c.value - accepted0)
            turn.leave(
                fresh_tokens=int(self._tokens_c.value - tokens0),
                finished=sum(1 for i, _ in recs if self._slots[i] is None),
                **fields)

    def _takes_a_round(self, slots) -> bool:
        """Whether a dispatch over ``slots`` is a speculative round: only
        when an opted-in slot is live."""
        return self._speculative is not None and any(s.spec for s in slots)

    def _dispatch(self, recs) -> _Flight:
        """Hand the device ONE chunk over the seated slots ``recs`` — a
        speculative round where an opted-in slot is live (a pool with a
        draft attached but no speculative traffic ticks the plain chunk:
        one executable kind a dispatch, both warmed), nothing where
        every seated slot is held (the turn was its prefill chunk) — and
        return what is now in flight.  The ``decode.step`` fault point
        fires once a dispatch."""
        use_spec = self._takes_a_round(s for _, s in recs)
        # hot-path: begin decode_tick (fault gate + the chunk dispatch;
        # materialization happens OUTSIDE, after the async dispatch
        # returns)
        if _faults.active is not None:  # disarmed: one is-None gate
            _faults.active.faultpoint(
                "decode.step", server=self.name, active=len(recs))
        if self._chunked and all(s.held for _, s in recs):
            # nothing for a chunk to advance: the view is the state's
            # own leaves, read before anything donates them
            view = {k: self._state[k] for k in VIEW}
            if self._expert_stats_of is not None:
                view["expert_stats"] = self._expert_stats_of(
                    self._state["cache"])
            kind = "none"
        else:
            self._state, view = self._pool.chunk_view(
                self._state, spec=use_spec)
            kind = "spec_chunk" if use_spec else "chunk"
        # hot-path: end decode_tick
        return _Flight(view, recs, kind,
                       self._pool.state_rungs(self._state))

    def _why_serial(self, flight: _Flight) -> Optional[str]:
        """THE rule of run-ahead, from what the host can observe: None
        where the next chunk is dispatched BEFORE the turn waits for
        ``flight`` (on the device, its view unread), else the first
        reason (:data:`SYNC_REASONS`) the turn stays serial:

        * ``free_seat`` — a seat is free or the slot ladder can grow: an
          arrival is seated before the next chunk, not after it (what
          keeps a lightly loaded and an emptying server on the serial
          turn);
        * ``held`` — a seated slot is held for a chunked prefill, whose
          turns decide slot by slot (a turn that stepped nothing, too);
        * ``length_finish`` — a live request can reach its ``total_len``
          inside ``flight`` by the tokens the host has seen of it
          (``steps`` more a plain chunk, ``k`` a round): its view may
          finish a request, whose seat is handed on before the next
          chunk;
        * ``memory`` — the device's free bytes, read now with ``flight``
          on it, do not hold TWO sets of the executable's temporaries
          and view: the allocator's count shows neither the running
          chunk's temporaries nor a queued one's (``bytes_in_use`` moved
          by the view's 2.6 MB alone with a chunk of 147 MB of
          temporaries running and a second queued: v5e chip run, PR 62,
          ``tools/time_run_ahead.py``), so room is held for both.  A
          backend that reports no limit: nothing to hold to."""
        slots = self._slots
        if None in slots or len(slots) < self._pool.max_slots:
            return "free_seat"
        if flight.kind == "none" or (
                self._chunked and any(s.held for s in slots)):
            return "held"
        reach = self._speculative.k if flight.spec else self._pool.steps
        if any(s.req.total_len - s.prompt_len - s.seen <= reach
               for s in slots):
            return "length_finish"
        need = self._pool.queued_bytes(
            self._state, spec=self._takes_a_round(slots))
        free = _device_free_bytes(flight.view) if need else None
        if free is not None and free < 2 * need:
            return "memory"
        return None

    def _prefill_turn(self, recs, turn: Optional[_Turn]) -> bool:
        """The turn's ONE prefill dispatch, before its decode chunk: the
        oldest held slot's next ``prefill_tokens`` prompt tokens.  On
        the slot's last whole chunk the dispatch also hands it to the
        step (the remainder shorter than a chunk rides the one-token
        step by teacher forcing), and — where the pool keeps snapshots —
        the slot's cache row is copied as it stands at that boundary and
        retained as a prefix entry: K/V and compressed-key rows below
        it, recurrent state AT it.  Returns False when the dispatch
        failed (every in-flight request failed typed, the pool
        dropped)."""
        pool = self._pool
        slot, rec = min(((i, s) for i, s in recs if s.held),
                        key=lambda x: x[1].seq)
        end = rec.pos + pool.prefill_tokens
        last = not pool.can_prefill(self._state, end, rec.prompt_len)
        if turn is not None:
            turn.enter("prefill")
        try:
            if _faults.active is not None:  # disarmed: one is-None gate
                _faults.active.faultpoint(
                    "decode.step", server=self.name, active=len(recs))
            self._state = pool.prefill(self._state, slot, rec.pos, last)
        except BaseException as exc:  # noqa: BLE001 — fail typed, keep serving
            if turn is not None:
                turn.leave(error=True, slot=slot, last=last)
            self._fail_and_drop_pool(exc)
            return False
        self._prefill_chunks_c.inc()
        self._prefill_chunk_tokens_c.inc(pool.prefill_tokens)
        rec.pos, rec.held = end, not last
        if last and self._prefix is not None and pool.snapshots:
            try:
                head = rec.req.prompt[:end]
                if not self._prefix.holds(head):
                    self._prefix.put(head, pool.snapshot(self._state, slot))
            except Exception:
                self._metrics.count("prefix_store_failed")
        if turn is not None:
            turn.leave(slot=slot, last=last)
        return True

    def _count_spec_round(self, recs, view, self_draft: bool) -> None:
        """One speculative round's counters, from the ``pos`` the tick
        already fetched (before the position counters move ``rec.pos``):
        the round, the slots it advanced and, where the draft is the
        target's own module, its proposals and acceptances — a
        speculative slot whose round began at ``p0`` with the prompt's
        last token behind or under it (``p0 + 1 >= prompt_len``) consumed
        ONE drafted token, accepted iff the slot advanced by two; a round
        that teacher-forced a prompt token as its second row proposed
        nothing.  (A separate draft model's are counted at delivery, by
        the tokens a slot emitted.)"""
        advanced = proposed = accepted = 0
        for i, rec in recs:
            gone = int(view["pos"][i]) - rec.pos
            if gone <= 0:
                continue
            advanced += 1
            if self_draft and rec.spec and rec.pos + 1 >= rec.prompt_len:
                proposed += 1
                accepted += gone - 1
        self._spec_rounds_c.inc()
        self._spec_row_rounds_c.inc(advanced)
        if proposed:
            self._spec_proposed_c.inc(proposed)
            self._spec_accepted_c.inc(accepted)
            with self._seq_len_lock:
                for took, n in ((1, accepted), (0, proposed - accepted)):
                    if n:
                        self._accept_len_hist[took] = (
                            self._accept_len_hist.get(took, 0) + n)

    def _count_experts(self, stats) -> Dict[str, float]:
        """Advance the four expert counters by what the chunk just run
        added to the builder's ``expert_stats`` leaf (``[expert layers,
        4]`` int32 sums that wrap as uint32 does), and return the
        ``deliver`` span's fields: experts a layer touched a step, and
        the largest group over the mean group."""
        now = np.asarray(stats).astype(np.uint32)
        seen = np.zeros_like(now) if self._expert_seen is None \
            else self._expert_seen
        self._expert_seen = now
        delta = (now - seen).astype(np.int64).sum(axis=0)   # modulo 2**32
        for c, n in zip(self._expert_cs, delta.tolist()):
            c.inc(n)
        pairs, touched, peak, layer_steps = delta.tolist()
        return {
            "experts_touched": touched / layer_steps if layer_steps else 0.0,
            "peak_over_mean": (self._n_expert * peak / pairs
                               if pairs else 0.0)}

    def _count_kv_positions(self, recs, view, use_spec: bool) -> dict:
        """Advance the KV read / live / pool position counters for the
        chunk just run, from the ``pos`` the tick already fetched: a slot
        that went from ``p0`` to ``p1`` ran steps at ``ts = p0..p1 - 1``,
        each with ``ts + 1`` live positions, of which the ragged kernel
        reads what the builder's ``"kv"`` rule says; and for every other
        read the builder declares (:data:`POSITION_SERIES`) its pair,
        per step, active slot and layer.  Returns the ``deliver`` span's
        fields: what was read of each kind that has one there."""
        s, t = view["tokens"].shape
        idx = np.fromiter((i for i, _ in recs), np.intp, len(recs))
        p1 = view["pos"][idx].astype(np.int64)
        p0 = np.fromiter((r.pos for _, r in recs), np.int64, len(recs))
        steps = 1 if use_spec else self._pool.steps
        pool = s * t * steps
        # the steps this chunk ran, row by row: ts = p0 .. p1 - 1; a
        # speculative round COMPUTED all its k rows for every slot that
        # advanced, whatever it kept (the layer reads' counters
        # count reads, not commits)
        rows = self._speculative.k if use_spec else steps
        ts = p0[:, None] + np.arange(rows)[None, :]
        ran = (p1 > p0)[:, None] & (ts < t) if use_spec else ts < p1[:, None]
        if self._kv_rule is None or (use_spec and not self._kv_rounds):
            read = pool  # masked reads over the whole rung
        elif use_spec:
            # ONE read a round, made for its last row (the earlier rows
            # see less of the same blocks): what the rule rounds that to
            last = last_fresh_row(p0, rows, t)
            read = int((self._kv_rule(last, t) * (p1 > p0)).sum())
        else:
            read = int((self._kv_rule(ts, t) * ran).sum())
        fields = {}
        if self._layer_reads:
            n = ts + 1                              # contexts p0 + 1 .. p1
            live = int((n * ran).sum())
            for rule, layers, read_c, live_c, field in self._layer_reads:
                rows_read = int((rule(n) * ran).sum()) * layers
                live_c.inc(live * layers)
                read_c.inc(rows_read)
                if field is not None:
                    fields[field] = rows_read
        self._kv_live_c.inc(int((p1 * (p1 + 1) - p0 * (p0 + 1)).sum()) // 2)
        for (_, rec), p in zip(recs, p1.tolist()):
            rec.pos = p
        self._kv_pool_c.inc(pool)
        self._kv_read_c.inc(read)
        return fields

    def _offer_prefix(self, slot: int, rec: _Slot, consumed: int) -> None:
        """Retain a freed slot's prefix KV in the cache (a control-plane
        d2h per NEW entry, on the scheduler thread after the tick's
        materialization — never inside the dispatch hot path).  Failures
        degrade silently: retention is an optimization, losing one entry
        must not take down the loop."""
        if self._prefix is None or self._pool.snapshots:
            # a snapshot pool's entries are taken at prefill boundaries
            # (:meth:`_prefill_turn`): a freed slot's recurrent state
            # is past every boundary
            return
        try:
            self._prefix.offer(
                rec.req.prompt, consumed,
                lambda m, s=slot: self._pool.extract_kv(
                    self._state, s, m))
        except Exception:
            self._metrics.count("prefix_store_failed")

    def _fail_and_drop_pool(self, exc: BaseException) -> None:
        """A dispatch failed (or a resize may have corrupted the pool):
        every seated request fails typed and the pool state goes, so the
        next admission starts from a fresh one — keep serving."""
        self._fail_in_flight(exc)
        self._state = None
        self._born = None   # never delivered over: no birth to book
        self._slots = []
        self._set_pool_bytes(None)

    def _fail_in_flight(self, exc: BaseException) -> None:
        self._flight = None     # a queued chunk's view is nobody's now
        n = 0
        for i, rec in enumerate(self._slots):
            if rec is not None:
                rec.req.fail(exc)
                self._slots[i] = None
                n += 1
        if n and not isinstance(exc, ServerClosed):
            self._metrics.count("failed", n)

    # ------------------------------------------------------------------
    def stop(self, drain: bool = True, timeout: Optional[float] = None) -> None:
        """``drain=True``: stop admitting, finish every queued and
        in-flight sequence, then join the tick thread.  ``drain=False``:
        queued and in-flight requests fail with ``ServerClosed``."""
        self._closed = True
        if not drain:
            self._abort = True
            self._fail_stragglers()
        self._stop.set()
        self._batcher.wake()
        self._worker.join(timeout)
        if not self._worker.is_alive():
            self._fail_stragglers()
            # a stopped server holds no device memory, as an idle one
            # (the loop's exit dropped a queued view: _fail_in_flight)
            self._state = None
            self._slots = []
        self._metrics.close()
        self._batcher.close()
        lbl = {"server": self.name, "instance": self._metrics.instance}
        for metric in (DECODE_TOKENS, DECODE_PREFILL_TOKENS, DECODE_TICKS,
                       DECODE_KV_READ, DECODE_KV_LIVE, DECODE_KV_POOL,
                       DECODE_TTFT, DECODE_OCCUPANCY, DECODE_KV_BYTES,
                       DECODE_STATE_RESETS, DECODE_RECURRENT_BYTES,
                       DECODE_ADMIT_DISPATCHES, DECODE_ADMITTED,
                       DECODE_IDLE_DROPS, POOL_CONSTANTS_PLACED,
                       DECODE_PREFILL_CHUNKS, DECODE_PREFILL_CHUNK_TOKENS,
                       DECODE_SPARSE_READ, DECODE_SPARSE_LIVE,
                       DECODE_CHUNKS_AHEAD):
            metric.remove_labels(**lbl)
        for reason in SYNC_REASONS:
            DECODE_SYNC_TURNS.remove_labels(reason=reason, **lbl)
        if self._speculative is not None:
            for metric in (SPEC_PROPOSED, SPEC_ACCEPTED, SPEC_ROUNDS,
                           SPEC_ROW_ROUNDS):
                metric.remove_labels(**lbl)
        if self._prefix is not None and self._prefix_owned:
            self._prefix.close()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop(drain=exc == (None, None, None))
        return False


# ---------------------------------------------------------------------------
# saved decode endpoints (the launch_server / fleet integration)
# ---------------------------------------------------------------------------
_DECODE_CONFIG = "decode.json"
_DECODE_WEIGHTS = "weights.npz"
_DRAFT_WEIGHTS = "draft_weights.npz"


def save_decode_endpoint(dirname: str, state: Dict[str, np.ndarray], *,
                         vocab_size: int, d_model: int, n_layer: int,
                         n_head: int, d_inner: int, eos_id: int,
                         max_seq_len: int, max_slots: int = 8,
                         steps_per_tick: int = 4, name: str = "lm",
                         draft: Optional[Dict[str, object]] = None,
                         prefix_cache_bytes: Optional[int] = None,
                         kv_dtype: str = "fp32") -> str:
    """Persist a transformer-LM decode endpoint (weights + config) so a
    serving child (``wire.launch_server``) can host it: the launcher
    detects ``decode.json`` in the model dir and builds a
    :class:`DecodeServer` instead of an ``InferenceServer``.

    ``draft``: an optional speculative-decoding manifest ``{"state":
    weight dict, "d_model": ..., "n_layer": ..., "n_head": ...,
    "d_inner": ..., "name": ..., "k": ...}`` — the draft LM shares the
    target's vocabulary and rides the endpoint dir as
    ``draft_weights.npz`` + a ``draft`` config block, so every fleet
    child hosts the same pair.  ``prefix_cache_bytes``: when set, loads
    build the server with a prefix KV cache of that byte budget.
    ``kv_dtype``: the KV-cache storage dtype (``"fp32"`` or ``"int8"``)
    every load reconstructs — int8 halves per-slot KV bytes with
    greedy-exact output parity (same tokens, per-head absmax scales)."""
    from paddle_tpu.decoding import normalize_kv_dtype

    kv_dtype = normalize_kv_dtype(kv_dtype)
    _check_pos_emb(state, name, max_seq_len)
    os.makedirs(dirname, exist_ok=True)
    np.savez(os.path.join(dirname, _DECODE_WEIGHTS),
             **{k: np.asarray(v) for k, v in state.items()})
    cfg = {
        "kind": "transformer_lm", "name": name,
        "vocab_size": int(vocab_size), "d_model": int(d_model),
        "n_layer": int(n_layer), "n_head": int(n_head),
        "d_inner": int(d_inner), "eos_id": int(eos_id),
        "max_seq_len": int(max_seq_len), "max_slots": int(max_slots),
        "steps_per_tick": int(steps_per_tick),
        "kv_dtype": kv_dtype,
    }
    if draft is not None:
        draft_name = str(draft.get("name", "draft"))
        draft_state = draft["state"]
        _check_pos_emb(draft_state, draft_name, max_seq_len)
        np.savez(os.path.join(dirname, _DRAFT_WEIGHTS),
                 **{k: np.asarray(v) for k, v in draft_state.items()})
        cfg["draft"] = {
            "d_model": int(draft["d_model"]),
            "n_layer": int(draft["n_layer"]),
            "n_head": int(draft["n_head"]),
            "d_inner": int(draft["d_inner"]),
            "name": draft_name,
            "k": int(draft.get("k", 4)),
        }
    if prefix_cache_bytes is not None:
        cfg["prefix_cache_bytes"] = int(prefix_cache_bytes)
    with open(os.path.join(dirname, _DECODE_CONFIG), "w") as f:
        json.dump(cfg, f)
    return dirname


def _check_pos_emb(state, name: str, max_seq_len: int) -> None:
    """``max_seq_len`` beyond the positional table would not crash —
    JAX's clamping gather silently reuses the last row — so refuse it
    loudly instead of generating degraded sequences."""
    pe = state.get(name + "_pos_emb")
    if pe is not None and int(np.shape(pe)[0]) < int(max_seq_len):
        raise ValueError(
            "pos_emb covers %d positions but max_seq_len=%d — positions "
            "past the table would silently clamp to its last row"
            % (int(np.shape(pe)[0]), int(max_seq_len)))


def is_decode_endpoint(dirname: str) -> bool:
    return os.path.exists(os.path.join(dirname, _DECODE_CONFIG))


def load_decode_endpoint(dirname: str, **overrides) -> DecodeServer:
    """Build a :class:`DecodeServer` from a saved decode endpoint dir.
    ``overrides`` (``max_slots``, ``queue_capacity``, ``name``,
    ``steps_per_tick``, ...) win over the saved config."""
    from paddle_tpu.decoding import (
        make_transformer_lm_pooled_step_fn,
        normalize_kv_dtype,
    )

    with open(os.path.join(dirname, _DECODE_CONFIG)) as f:
        cfg = json.load(f)
    if cfg.get("kind") != "transformer_lm":
        raise ValueError(
            "unsupported decode endpoint kind %r" % cfg.get("kind"))
    weights = dict(np.load(os.path.join(dirname, _DECODE_WEIGHTS)))
    # kv_dtype shapes the COMPILED step/verify fns, not just server
    # config, so an override must land before they are built
    kv_dtype = normalize_kv_dtype(
        overrides.pop("kv_dtype", cfg.get("kv_dtype", "fp32")))
    step_fn, make_cache = make_transformer_lm_pooled_step_fn(
        weights, cfg["vocab_size"], cfg["d_model"], cfg["n_layer"],
        cfg["n_head"], cfg["d_inner"], name=cfg.get("name", "lm"),
        kv_dtype=kv_dtype)
    kw = {
        "eos_id": cfg["eos_id"],
        "max_seq_len": cfg["max_seq_len"],
        "max_slots": cfg.get("max_slots", 8),
        "steps_per_tick": cfg.get("steps_per_tick", 4),
        "kv_dtype": kv_dtype,
    }
    if cfg.get("draft"):
        from paddle_tpu.serving.speculative import make_lm_speculative

        d = cfg["draft"]
        draft_weights = dict(
            np.load(os.path.join(dirname, _DRAFT_WEIGHTS)))
        kw["speculative"] = make_lm_speculative(
            weights, vocab_size=cfg["vocab_size"],
            d_model=cfg["d_model"], n_layer=cfg["n_layer"],
            n_head=cfg["n_head"], d_inner=cfg["d_inner"],
            draft_state=draft_weights, draft_d_model=d["d_model"],
            draft_n_layer=d["n_layer"], draft_n_head=d["n_head"],
            draft_d_inner=d["d_inner"], k=d.get("k", 4),
            name=cfg.get("name", "lm"),
            draft_name=d.get("name", "draft"),
            kv_dtype=kv_dtype)
    if cfg.get("prefix_cache_bytes"):
        kw["prefix_cache"] = int(cfg["prefix_cache_bytes"])
    kw.update(overrides)
    # after overrides: a hand-edited config (or max_seq_len override)
    # must not outrun the positional table
    _check_pos_emb(weights, cfg.get("name", "lm"), kw["max_seq_len"])
    return DecodeServer(step_fn, make_cache, **kw)
