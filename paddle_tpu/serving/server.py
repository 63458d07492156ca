"""InferenceServer: a dynamic-batching front end over AnalysisPredictor.

The reference stack ships models to an external serving system
(Paddle Serving); this repo's TPU-native answer is in-process: N
replica worker threads (one per predictor — typically one per device)
sit behind ONE bounded queue + DynamicBatcher, a dispatcher routes each
coalesced batch to the least-loaded live replica (per-replica in-flight
accounting), and a BucketPolicy pads every batch onto a fixed size
ladder so each replica's jit cache sees a CLOSED shape set — after
``warmup()`` pre-compiles each rung on EVERY replica, steady-state
serving performs zero XLA compiles fleet-wide (asserted through
Executor.jit_cache_stats, not inferred from timing).

Replica fleet semantics: a batch whose replica fails is re-routed to a
live replica (accepted requests never drop with a survivor available);
a replica that fails repeatedly is retired from routing, and
``remove_replica()`` drains one gracefully at runtime.

Lifecycle: construct (workers start) -> warmup() -> submit()/Client
traffic -> stop(drain=True) for a graceful drain.

Observability: metrics live in the process-global registry
(``paddle_tpu.monitor``); ``start_admin()`` binds a localhost HTTP
surface exposing ``/metrics`` (Prometheus text exposition of the whole
registry — or OpenMetrics 1.0 with exemplars when the scraper sends
``Accept: application/openmetrics-text``), ``/statusz`` (JSON snapshot:
this server's metrics incl. bucket-ladder occupancy, per-replica
health, and recompile counts, the predictors' jit-cache stats, and the
full registry), and ``/tracez`` (the flight recorder's tail-sampled
slow/errored request traces).

Request-scoped tracing: each request carries a trace id (minted by the
Client or passed to ``submit(trace_id=...)``); while a batch executes,
the replica worker installs a ``monitor.trace_context`` so every span
in the chain — queue wait, merge/pad/dispatch, executor h2d /
device_execute / d2h, materialize — is attributable to the requests in
the batch, and replica workers register named thread lanes so the
fleet renders as parallel tracks in the merged Chrome trace.  With a
``monitor.flight_recorder()`` installed, batches additionally run under
a span capture and slow/errored/deadline-missed requests retain their
full span trees.
"""
from __future__ import annotations

import contextlib
import json
import queue
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from paddle_tpu import compile_cache
from paddle_tpu import faults as _faults
from paddle_tpu import monitor, profiler
from paddle_tpu.faults.metrics import BACKEND_HALFOPEN_PROBES
from paddle_tpu.monitor import flight as _flight
from paddle_tpu.monitor import spans as _mon_spans
from paddle_tpu.serving.admission import (
    ADMISSION_EXPIRED,
    PRIORITY_LOW,
    PRIORITY_NORMAL,
    BrownoutController,
)
from paddle_tpu.serving.batching import DynamicBatcher, ServingRequest
from paddle_tpu.serving.bucketing import BucketPolicy
from paddle_tpu.serving.errors import (
    DeadlineExceeded,
    ServerClosed,
    ServerOverloaded,
    ServingError,
)
from paddle_tpu.serving.metrics import ServingMetrics

__all__ = ["InferenceServer"]

# dispatched-but-not-finalized batches a replica may hold: one executing
# (async dispatch, d2h pending) + one queued behind it — the same
# double-buffer depth the single-worker server ran, now per replica.
# The batcher queue (NOT replica queues) stays the admission buffer, so
# shedding and drain semantics are unchanged.
_MAX_IN_FLIGHT = 2

# consecutive batch failures before a replica is retired from routing
_REPLICA_FAIL_LIMIT = 3

# request-facing dtype aliases: the same shared map AnalysisPredictor
# dispatches by, so submit() can never admit a spelling the predictor
# would then reject (one dict lookup, no contrib import)
from paddle_tpu.core.types import PRECISION_ALIASES as _PRECISION_ALIASES

# safety-net bound for the routing capacity wait (real wakeups are
# notifies from _release/_retire/stop)
_ROUTE_WAIT_S = 0.5


class _Replica:
    """One predictor behind the shared batcher: its own worker thread,
    bounded in-flight accounting, and health state."""

    __slots__ = ("idx", "name", "predictor", "nonblocking", "precision",
                 "lock", "q", "thread", "alive", "in_flight", "executed",
                 "failed", "consec_failures", "retired_at", "removed")

    def __init__(self, idx: int, predictor):
        self.idx = idx
        self.name = "r%d" % idx
        self.predictor = predictor
        # non-blocking fetch (AnalysisPredictor return_numpy=False) lets
        # the replica overlap batch N's d2h with batch N+1's dispatch; a
        # duck-typed predictor without the kwarg runs synchronously.
        # precision-variant dispatch (run_padded precision=) is detected
        # the same way so duck-typed test predictors keep working.
        import inspect

        try:
            params = inspect.signature(predictor.run_padded).parameters
            self.nonblocking = "return_numpy" in params
            self.precision = "precision" in params
        except (TypeError, ValueError):
            self.nonblocking = False
            self.precision = False
        self.lock = threading.Lock()  # warmup vs worker predictor use
        self.q: "queue.Queue" = queue.Queue()  # (batch, retries) | None
        self.thread: Optional[threading.Thread] = None
        self.alive = True
        self.in_flight = 0  # guarded by the server's _route_cv
        self.executed = 0
        self.failed = 0
        self.consec_failures = 0
        self.retired_at = None  # monotonic stamp of failure retirement
        self.removed = False    # remove_replica(): never re-admit


class InferenceServer:
    """Wraps one or more predictors exposing ``run_padded`` /
    ``jit_cache_stats`` / ``get_input_names`` (AnalysisPredictor) behind
    a batched, bucketed, deadline-aware submit() API.

    ``predictor``: a single predictor, or a SEQUENCE of predictors —
    one replica each (e.g. one AnalysisPredictor per device) — behind
    the same queue with least-loaded routing.

    ``input_specs`` (``{name: (per_row_shape, dtype)}``) defaults to the
    first predictor's program-derived specs; pass it explicitly when a
    feed var has dynamic non-batch dims.
    """

    def __init__(
        self,
        predictor,
        max_batch_size: int = 32,
        batch_timeout_ms: float = 5.0,
        queue_capacity: int = 256,
        bucket_ladder: Optional[Sequence[int]] = None,
        input_specs: Optional[Dict[str, Tuple[tuple, Any]]] = None,
        name: str = "server",
        readmit_cooldown_s: Optional[float] = None,
        target_queue_wait_ms: float = 50.0,
        brownout_hold_s: float = 0.25,
        class_weights="default",
        embedding_cache=None,
    ):
        self.name = name
        # circuit-breaker re-admission for failure-retired replicas: a
        # retired replica goes half-open after this cooldown and takes
        # ONE probe batch (it rejoins routing with a single remaining
        # strike — the probe's success resets the streak, a failure
        # re-retires immediately).  None (default) keeps retirement
        # terminal, the pre-existing behavior.
        self._readmit_cooldown = (
            float(readmit_cooldown_s) if readmit_cooldown_s is not None
            else None)
        predictors = (
            list(predictor) if isinstance(predictor, (list, tuple))
            else [predictor])
        if not predictors:
            raise ValueError("InferenceServer needs at least one predictor")
        self._replicas = [_Replica(i, p) for i, p in enumerate(predictors)]
        self._predictor = predictors[0]  # single-replica compat surface
        self._nonblocking = self._replicas[0].nonblocking
        self._policy = BucketPolicy(max_batch_size, bucket_ladder)
        self._batcher = DynamicBatcher(
            max_batch_size, batch_timeout_ms, queue_capacity, name=name,
            target_wait_ms=target_queue_wait_ms,
            class_weights=class_weights)
        self._metrics = ServingMetrics(name)
        # queue-level drops (priority eviction / offer-time sweep) route
        # through the server's accounting, not the batcher's defaults
        self._batcher.on_shed = self._on_queue_shed
        self._batcher.on_expired = self._on_expired
        # hot-id embedding cache (serving/embedding_cache.py): bound to
        # every replica's program so sparse lookups read through it, and
        # to the brownout ladder — a 4th rung serves CACHE-ONLY under
        # sustained saturation (misses get the fallback row instead of
        # queuing on PS pulls), so Zipf-skewed traffic degrades
        # gracefully through a PS outage
        self._embedding_cache = embedding_cache
        if embedding_cache is not None:
            for p in predictors:
                embedding_cache.bind(p)
        # deterministic degradation ladder, driven by queue pressure
        # from the dispatcher loop (L1 drops flight capture, L2 forces
        # eager batching, L3 sheds the lowest priority class, and — on
        # embedding-cache endpoints — L4 serves lookups cache-only)
        thresholds = (
            BrownoutController.THRESHOLDS
            + (BrownoutController.CACHE_ONLY_THRESHOLD,)
            if embedding_cache is not None else None)
        self._brownout = BrownoutController(
            name, hold_s=brownout_hold_s, thresholds=thresholds)
        self._admission_expired = ADMISSION_EXPIRED.labels(server=name)
        self._specs = (
            dict(input_specs) if input_specs else predictors[0].input_specs())
        self._feed_names = list(predictors[0].get_input_names())
        # mixed-precision endpoints: the serving dtypes, default first
        # (AnalysisPredictor.precision_dtypes); warmup compiles every
        # bucket rung for EVERY entry so the per-request choice (policy
        # default vs fp32 opt-out) never compiles
        dts = getattr(predictors[0], "precision_dtypes", None)
        if callable(dts) and self._replicas[0].precision:
            self._precision_dtypes = [str(d) for d in dts()]
        else:
            self._precision_dtypes = ["fp32"]
        self._default_dtype = self._precision_dtypes[0]
        # rungs already compiled on every replica (warmup + replan
        # barriers); replan_ladder only warms the DELTA
        self._warmed_rungs: set = set()
        self._autotune_thread: Optional[threading.Thread] = None
        self._autotune_stop: Optional[threading.Event] = None
        self._replan_lock = threading.Lock()
        self._stop = threading.Event()
        self._closed = False           # admission gate (set before _stop on shutdown)
        self._abort = False            # stop(drain=False): fail instead of route
        self._admin = None             # optional HTTP surface (start_admin)
        self._admin_lock = threading.Lock()
        self._warmed = False
        self._route_cv = threading.Condition()  # replica in_flight/alive state
        for rep in self._replicas:
            rep.thread = threading.Thread(
                target=self._replica_loop, args=(rep,),
                name="serving-%s-%s" % (name, rep.name), daemon=True)
            rep.thread.start()
        self._worker = threading.Thread(
            target=self._dispatch_loop, name="serving-%s" % name, daemon=True)
        self._worker.start()

    # ------------------------------------------------------------------
    @property
    def bucket_ladder(self) -> List[int]:
        return list(self._policy.ladder)

    @property
    def max_batch_size(self) -> int:
        return self._policy.max_batch_size

    @property
    def num_replicas(self) -> int:
        """Live (routable) replica count."""
        with self._route_cv:
            return sum(1 for r in self._replicas if r.alive)

    def replica_stats(self) -> Dict[str, Dict[str, object]]:
        """Per-replica health/throughput snapshot (the in-flight
        accounting behind least-loaded routing)."""
        with self._route_cv:
            return {
                r.name: {
                    "alive": r.alive,
                    "in_flight": r.in_flight,
                    "executed": r.executed,
                    "failed": r.failed,
                    "nonblocking": r.nonblocking,
                }
                for r in self._replicas
            }

    def metrics(self) -> Dict[str, object]:
        snap = self._metrics.snapshot()
        snap["queue_depth"] = self._batcher.qsize()
        snap["admit_limit"] = self._batcher.queue.limit
        snap["brownout_level"] = self._brownout.level
        snap["bucket_ladder"] = self.bucket_ladder
        snap["batch_timeout_ms"] = self._batcher.batch_timeout_s * 1e3
        # exported so a recorded /statusz snapshot is a complete input
        # for tools/autotune_ladder.py (ladder + histogram + wait EWMA)
        snap["queue_wait_ewma_ms"] = round(
            self._batcher.queue.wait_ewma_ms, 3)
        snap["precision_dtypes"] = list(self._precision_dtypes)
        snap["warmed_up"] = self._warmed
        snap["replicas"] = self.replica_stats()
        if self._embedding_cache is not None:
            snap["embedding_cache"] = self._embedding_cache.stats()
        return snap

    def load(self) -> Dict[str, object]:
        """The overload-control load report: queue depth, the adaptive
        admit limit, and the brownout level.  Rides in every wire
        response meta so the fleet balancer folds REPORTED load (the
        server's actual backlog) into least-loaded routing, not just its
        own in-flight counts."""
        return {
            "queue_depth": self._batcher.qsize(),
            "admit_limit": self._batcher.queue.limit,
            "brownout_level": self._brownout.level,
        }

    def metrics_text(self) -> str:
        """Prometheus text exposition of the WHOLE process registry
        (this server's series are labeled ``server=<name>``)."""
        return monitor.render_text()

    def tracez(self) -> Dict[str, object]:
        """The ``/tracez`` document: the process flight recorder's
        tail-sampled slow/errored/deadline-missed request traces (empty
        shell when no recorder is installed)."""
        rec = _flight.get()
        if rec is None:
            return {"recorder": False, "retained": 0, "requests": []}
        doc = rec.statusz()
        doc["recorder"] = True
        return doc

    def statusz(self) -> Dict[str, object]:
        """JSON-serializable status snapshot: this server's metrics
        (incl. bucket-ladder occupancy histogram, per-replica health,
        and recompile counter), the predictors' jit-cache stats, and the
        process registry."""
        doc = {
            "server": self.name,
            "metrics": self.metrics(),
            "jit_cache": self._predictor.jit_cache_stats(),
            "replica_jit_cache": {
                r.name: r.predictor.jit_cache_stats() for r in self._replicas
            },
            "registry": monitor.snapshot(),
        }
        sharding = {}
        for r in self._replicas:
            stats_fn = getattr(r.predictor, "sharding_stats", None)
            if callable(stats_fn) and getattr(r.predictor, "sharded", False):
                sharding[r.name] = stats_fn()
        if sharding:
            # each replica here is a model-parallel GROUP of devices;
            # the capacity math ("does the model fit one chip's
            # share?") reads hbm_bytes_per_device vs replicated_bytes
            doc["sharding"] = sharding
        pipeline = {}
        for r in self._replicas:
            pstats_fn = getattr(r.predictor, "pipeline_stats", None)
            if callable(pstats_fn):
                pipeline[r.name] = pstats_fn()
        if pipeline:
            # a pipelined replica is a pp-GROUP of devices behind one
            # name; the schedule math ("is the bubble amortized?") reads
            # bubble_ratio vs microbatches_last
            doc["pipeline"] = pipeline
        return doc

    # ------------------------------------------------------------------
    def start_admin(self, host: str = "127.0.0.1", port: int = 0) -> Tuple[str, int]:
        """Serve ``/metrics`` (Prometheus text exposition; OpenMetrics
        1.0 with exemplars when the scraper sends ``Accept:
        application/openmetrics-text``), ``/statusz`` (JSON), and
        ``/tracez`` (flight-recorder tail-sampled request traces) over
        HTTP on ``host:port`` (port 0 = ephemeral); returns the bound
        ``(host, port)``.  Stopped by ``stop()``."""
        from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

        server = self

        class _AdminHandler(BaseHTTPRequestHandler):
            def do_GET(self):
                path = self.path.split("?", 1)[0]
                if path == "/metrics":
                    om = "application/openmetrics-text" in (
                        self.headers.get("Accept") or "")
                    text, ctype = monitor.expose(openmetrics=om)
                    body = text.encode("utf-8")
                elif path == "/statusz":
                    body = json.dumps(
                        server.statusz(), sort_keys=True, default=str
                    ).encode("utf-8")
                    ctype = "application/json"
                elif path == "/tracez":
                    body = json.dumps(
                        server.tracez(), sort_keys=True, default=str
                    ).encode("utf-8")
                    ctype = "application/json"
                else:
                    self.send_error(
                        404,
                        "unknown path (try /metrics, /statusz or /tracez)")
                    return
                self.send_response(200)
                self.send_header("Content-Type", ctype)
                self.send_header("Content-Length", str(len(body)))
                self.end_headers()
                self.wfile.write(body)

            def log_message(self, *args):  # keep scrapes out of stderr
                pass

        with self._admin_lock:
            if self._admin is not None:  # concurrent/repeat start: reuse
                return self._admin.server_address
            self._admin = ThreadingHTTPServer((host, port), _AdminHandler)
            self._admin_thread = threading.Thread(
                target=self._admin.serve_forever,
                name="serving-admin-%s" % self.name, daemon=True)
            self._admin_thread.start()
            return self._admin.server_address

    @property
    def admin_address(self) -> Optional[Tuple[str, int]]:
        return self._admin.server_address if self._admin is not None else None

    # ------------------------------------------------------------------
    def warmup(self, configure_cache: bool = True) -> int:
        """Pre-compile every bucket rung on EVERY replica (the
        zero-recompile guarantee must hold fleet-wide — a cold replica
        would compile on its first routed batch); returns the total
        number of XLA compiles the warmup performed.  Routes through
        jax's persistent compilation cache
        (``paddle_tpu.compile_cache.configure``) — replica 2..N of an
        identical model typically loads replica 1's compiles from the
        disk cache; synthetic rows are zeros (always in-range for int
        id feeds).  After warmup the recompile counter arms: any
        further jit-cache miss on any replica increments
        ``metrics()['recompiles']``.

        NOTE ``configure_cache=True`` mutates PROCESS-GLOBAL state when
        ``JAX_COMPILATION_CACHE_DIR`` is unset (it exports the variable
        and sets ``jax.config`` to ``<checkout>/.jax_cache``); pass
        ``configure_cache=False`` when the embedding application owns
        its own jax cache configuration.
        """
        if configure_cache:
            compile_cache.configure()
        compiles = self._warm_rungs(self._policy.ladder)
        for rep in self._replicas:
            # a mesh-spanning (sharded) replica publishes its per-device
            # HBM footprint now that warmup placed every param per its
            # rule (sharding_group_hbm_bytes gauge, one series per
            # model-parallel group)
            stats_fn = getattr(rep.predictor, "sharding_stats", None)
            if callable(stats_fn) and getattr(rep.predictor, "sharded",
                                              False):
                stats_fn(group="%s/%s" % (self.name, rep.name))
            # a pipelined replica publishes its schedule shape (bubble
            # ratio + per-stage occupancy gauges) once warmup compiled
            # every rung's GPipe executable
            pstats_fn = getattr(rep.predictor, "pipeline_stats", None)
            if callable(pstats_fn):
                self._metrics.set_pipeline(pstats_fn())
        self._metrics.count("warmup_compiles", compiles)
        self._warmed = True
        return compiles

    def _warm_rungs(self, rungs) -> int:
        """Compile ``rungs`` on every replica, for EVERY precision
        dtype the endpoint serves, skipping rungs already warmed —
        shared by ``warmup()`` and the autotuner's re-plan barrier
        (a new ladder compiles HERE, while the old ladder still serves
        traffic, so a ladder change never serves a cold cache).
        Returns the number of XLA compiles performed."""
        compiles = 0
        todo = [b for b in rungs if b not in self._warmed_rungs]
        if not todo:
            return 0
        for rep in self._replicas:
            misses0 = rep.predictor.jit_cache_stats()["misses"]
            for bucket in todo:
                feed = {
                    name: np.zeros((bucket,) + tuple(shape), dtype)
                    for name, (shape, dtype) in self._specs.items()
                }
                for pdtype in (self._precision_dtypes if rep.precision
                               else (None,)):
                    kw = {"precision": pdtype} if pdtype is not None else {}
                    with rep.lock:
                        with profiler.RecordEvent(
                                "serving/%s/warmup" % self.name):
                            rep.predictor.run_padded(
                                feed, n_valid=bucket, **kw)
            compiles += rep.predictor.jit_cache_stats()["misses"] - misses0
        self._warmed_rungs.update(todo)
        return compiles

    # ------------------------------------------------------------------
    def replan_ladder(self, ladder: Optional[Sequence[int]] = None,
                      batch_timeout_ms: Optional[float] = None,
                      max_rungs: int = 8) -> Dict[str, object]:
        """Re-plan the bucket ladder behind a warmup barrier.

        With ``ladder=None`` the new ladder (and, unless overridden,
        the batch window) comes from ``serving.autotune.plan`` over
        this server's observed arrival-size histogram and queue-wait
        EWMA.  Any NEW rungs are compiled on every replica (every
        precision dtype) BEFORE the policy reference is swapped, so a
        re-plan never causes a recompiled request — the old ladder
        keeps serving until the new one is hot.  Returns the applied
        plan; increments ``serving_ladder_replans_total`` only when the
        ladder actually changed."""
        from paddle_tpu.serving import autotune

        with self._replan_lock:
            proposal = None
            if ladder is None:
                proposal = autotune.plan(
                    self._metrics.arrival_histogram(),
                    self.max_batch_size, self._policy.ladder,
                    queue_wait_ewma_ms=self._batcher.queue.wait_ewma_ms,
                    current_timeout_ms=self._batcher.batch_timeout_s * 1e3,
                    max_rungs=max_rungs)
                ladder = proposal["ladder"]
                if batch_timeout_ms is None:
                    batch_timeout_ms = proposal["batch_timeout_ms"]
            new_policy = BucketPolicy(self.max_batch_size, ladder)
            changed = new_policy.ladder != self._policy.ladder
            compiles = 0
            if changed:
                compiles = self._warm_rungs(new_policy.ladder)  # barrier
                self._policy = new_policy  # atomic reference swap
                self._metrics.count_replan()
                monitor.record_instant(
                    "serving/ladder_replan", cat="serving",
                    server=self.name, ladder=str(new_policy.ladder))
            if batch_timeout_ms is not None:
                self._batcher.batch_timeout_s = float(batch_timeout_ms) / 1e3
            return {
                "ladder": list(new_policy.ladder),
                "changed": changed,
                "barrier_compiles": compiles,
                "batch_timeout_ms": (
                    float(batch_timeout_ms) if batch_timeout_ms is not None
                    else self._batcher.batch_timeout_s * 1e3),
                **({"proposal": proposal} if proposal else {}),
            }

    def start_autotuner(self, interval_s: float = 10.0,
                        max_rungs: int = 8) -> None:
        """Periodic online re-plan: every ``interval_s`` the autotuner
        thread re-derives the ladder + batch window from the live
        arrival histogram and applies any change behind the warmup
        barrier.  Idempotent; stopped by ``stop()``."""
        if self._autotune_thread is not None:
            return
        self._autotune_stop = threading.Event()

        def _loop():
            while not self._autotune_stop.wait(interval_s):
                try:
                    self.replan_ladder(max_rungs=max_rungs)
                except Exception as e:  # noqa: BLE001 — keep re-planning
                    # a failed re-plan must never kill the tuner loop
                    # (the server keeps serving on the current ladder);
                    # leave a timeline breadcrumb instead of stderr
                    monitor.record_instant(
                        "serving/ladder_replan_error", cat="serving",
                        server=self.name, error=repr(e))

        self._autotune_thread = threading.Thread(
            target=_loop, name="serving-%s-autotune" % self.name,
            daemon=True)
        self._autotune_thread.start()

    # ------------------------------------------------------------------
    def submit(self, feed, timeout_ms: Optional[float] = None,
               trace_id: Optional[str] = None,
               parent_span: Optional[str] = None,
               priority: int = PRIORITY_NORMAL,
               precision: Optional[str] = None) -> ServingRequest:
        """Enqueue one request; returns its future (ServingRequest).

        ``precision``: compiled-variant choice on a mixed-precision
        endpoint — None serves the policy default, ``"fp32"`` is the
        per-request opt-out; both are pre-compiled by warmup, so the
        choice never costs an XLA compile.  An unknown dtype fails
        typed here, before anything enqueues.

        ``feed``: dict (or positional sequence) of arrays whose shared
        leading dim is the request's row count (1..max_batch_size).
        ``priority`` is the admission class (lower = more important,
        ``serving.admission.PRIORITY_*``): a full queue sheds
        strictly-lower-priority entries first, and brownout level 3
        sheds the lowest class outright.  ``trace_id`` joins the request
        to a caller-owned trace (the Client mints one per call); spans
        recorded while its batch executes carry it.  ``parent_span`` is
        the submitter-side span id this request's spans parent under
        (client infer span, or the wire server's request span on a
        transport hop).  Raises ServerOverloaded (with a computed
        ``retry_after_ms`` hint) when shed, ServerClosed after stop();
        a ``timeout_ms`` that is already <= 0 — expired work arriving
        over the wire — fails fast typed at admission
        (``admission_expired_total``) instead of dispatching stale work.
        """
        if self._closed:
            raise ServerClosed("server %r is stopped" % self.name)
        if timeout_ms is not None and float(timeout_ms) <= 0:
            # deadline propagation fail-fast: the remaining deadline the
            # wire hop carried is already gone — shed at admission, never
            # burn a batch slot dispatching work nobody is waiting for
            self._admission_expired.inc()
            self._metrics.count("expired")
            raise DeadlineExceeded(
                "deadline exhausted before admission (%.1f ms)"
                % float(timeout_ms))
        if _faults.active is not None:  # disarmed: one is-None gate
            _faults.active.faultpoint(
                "server.admit", server=self.name, priority=int(priority))
        # sample the ladder HERE too: at L3 the door sheds low priority
        # before anything enqueues, so low-priority-only traffic would
        # otherwise never wake the parked dispatcher and the level
        # could latch at 3 on an idle server forever
        self._apply_brownout(
            self._brownout.update(self._batcher.depth_ratio()))
        if (self._brownout.level >= 3
                and int(priority) >= PRIORITY_LOW):
            # brownout L3: the lowest priority class sheds at the door
            self._metrics.count("shed")
            raise ServerOverloaded(
                "brownout level %d sheds priority %d"
                % (self._brownout.level, int(priority)),
                retry_after_ms=self._batcher.queue.retry_after_ms())
        if precision is not None:
            precision = _PRECISION_ALIASES.get(
                str(precision).lower(), str(precision))
            if precision not in self._precision_dtypes:
                raise ValueError(
                    "unknown precision %r for endpoint %r (serves %s)"
                    % (precision, self.name, self._precision_dtypes))
            if precision == self._default_dtype:
                precision = None  # one batch group for the default
        feed, n_rows = self._normalize_feed(feed)
        self._metrics.observe_arrival(n_rows)
        deadline = (
            time.monotonic() + float(timeout_ms) / 1e3
            if timeout_ms is not None else None)
        req = ServingRequest(feed, n_rows, deadline, trace_id=trace_id,
                             parent_span=parent_span, priority=priority,
                             precision=precision)
        try:
            self._batcher.offer(req)
        except Exception:
            self._metrics.count("shed")
            raise
        self._metrics.count("requests")
        # close the submit-vs-stop race: if stop() won between the
        # admission check above and the offer, the dispatcher may already
        # be gone — nothing would ever serve this queue, so fail the
        # stragglers (first completion wins, so a request the dispatcher
        # DID pick up keeps its real result)
        if self._stop.is_set() and not self._worker.is_alive():
            self._fail_stragglers()
            if req.done():
                raise ServerClosed("server %r is stopped" % self.name)
        return req

    def _normalize_feed(self, feed) -> Tuple[Dict[str, np.ndarray], int]:
        if not isinstance(feed, dict):
            feed = dict(zip(self._feed_names, feed))
        if set(feed) != set(self._feed_names):
            raise ValueError(
                "feed names %s != endpoint inputs %s"
                % (sorted(feed), sorted(self._feed_names)))
        out, n_rows = {}, None
        for name, val in feed.items():
            shape, dtype = self._specs[name]
            # coerce to the spec dtype so every request produces the
            # SAME compiled signature the warmup buckets did — a stray
            # float64 feed must not become a novel compile
            arr = np.asarray(val, dtype=dtype)
            if arr.shape[1:] != tuple(shape):
                raise ValueError(
                    "feed %r rows have shape %s, endpoint expects %s"
                    % (name, arr.shape[1:], tuple(shape)))
            if n_rows is None:
                n_rows = arr.shape[0]
            elif arr.shape[0] != n_rows:
                raise ValueError(
                    "inconsistent request row counts: %r has %d rows, "
                    "expected %d" % (name, arr.shape[0], n_rows))
            out[name] = arr
        if not n_rows:
            raise ValueError("empty request (0 rows)")
        if n_rows > self._policy.max_batch_size:
            raise ValueError(
                "request of %d rows exceeds max_batch_size=%d — split it"
                % (n_rows, self._policy.max_batch_size))
        return out, n_rows

    # ------------------------------------------------------------------
    def _apply_brownout(self, level: int) -> None:
        """Side effects of a (possibly new) brownout level that live
        outside the controller: the embedding cache's cache-only rung
        engages at the ladder's 4th threshold and releases — with the
        controller's 4x-slower descent hysteresis — when the ladder
        steps back down."""
        if self._embedding_cache is not None:
            self._embedding_cache.set_cache_only(level >= 4)

    def _fail_stragglers(self) -> None:
        """Fail every request still queued once no worker will ever
        serve it — stuck requests must surface as typed errors, never
        hangs (the subsystem's core contract)."""
        for req in self._batcher.drain_pending():
            req.fail(ServerClosed("server %r stopped" % self.name))

    def _on_queue_shed(self, req: ServingRequest,
                       retry_after_ms: float) -> None:
        """A queued request evicted by priority shedding: counted as a
        shed (it never ran) and failed typed with the retry hint."""
        self._metrics.count("shed")
        req.fail(ServerOverloaded(
            "evicted by a higher-priority request",
            retry_after_ms=retry_after_ms))

    def _on_expired(self, req: ServingRequest) -> None:
        self._metrics.count("expired")
        fr = _flight.get()
        if fr is not None:
            # deadline-missed requests are always tail-sampled; the
            # client's span attaches to this record when its future
            # raises (flight merges by trace id)
            fr.consider(
                req.trace_id, time.perf_counter() - req.submit_t,
                "deadline", (), server=self.name)
        req.fail(DeadlineExceeded("deadline passed while queued"))

    # ------------------------------------------------------------------
    # Dispatcher: one thread owns the batcher (single-consumer
    # coalescing) and routes each batch to the least-loaded live replica
    # ------------------------------------------------------------------
    def _dispatch_loop(self) -> None:
        _mon_spans.set_thread_lane("serving/%s/dispatcher" % self.name)
        try:
            while True:
                # one pressure sample per dispatch turn drives the
                # brownout ladder; eager batching (L2+) collapses the
                # coalescing window so a saturated server ships what it
                # has instead of waiting for more
                level = self._brownout.update(self._batcher.depth_ratio())
                self._batcher.eager = level >= 2
                self._apply_brownout(level)
                batch = self._batcher.next_batch(
                    self._stop, self._on_expired, block=True)
                if batch is None:
                    return  # stopped and drained
                self._maybe_readmit()
                self._route(batch, retries=max(1, len(self._replicas)))
        finally:
            for rep in self._replicas:
                rep.q.put(None)  # drain sentinel (idempotent)

    def _maybe_readmit(self) -> None:
        """Half-open re-admission pass (readmit_cooldown_s set): a
        failure-retired replica whose cooldown elapsed rejoins routing
        with one remaining strike — the next routed batch IS the probe
        (success resets the streak in _finalize, failure re-retires in
        _replica_failure)."""
        if self._readmit_cooldown is None:
            return
        now = time.monotonic()
        with self._route_cv:
            for rep in self._replicas:
                if (rep.alive or rep.removed or rep.retired_at is None
                        or now - rep.retired_at < self._readmit_cooldown):
                    continue
                rep.alive = True
                rep.retired_at = None
                rep.consec_failures = _REPLICA_FAIL_LIMIT - 1
                BACKEND_HALFOPEN_PROBES.labels(
                    pool="server/%s" % self.name).inc()
                monitor.record_instant(
                    "serving/replica_readmit", cat="serving",
                    server=self.name, replica=rep.name)
                self._route_cv.notify_all()

    def _pick_replica(self, exclude: Optional[_Replica]):
        """Least-loaded live replica with capacity, or None.  Caller
        holds ``_route_cv``."""
        live = [r for r in self._replicas
                if r.alive and r is not exclude
                and r.in_flight < _MAX_IN_FLIGHT]
        if not live:
            return None
        return min(live, key=lambda r: r.in_flight)

    def _route(self, batch: List[ServingRequest], retries: int,
               exclude: Optional[_Replica] = None) -> None:
        """Hand a coalesced batch to a replica (least loaded wins);
        blocks while every live replica is at its in-flight bound —
        the batcher queue, not replica queues, is the admission buffer.
        With no live replica (or an aborting stop) the batch fails
        typed, never hangs.

        The enqueue happens INSIDE the routing lock: a replica thread
        marks itself dead under the same lock before its final queue
        drain, so every put either targets a replica that will still
        drain it or never picks the dead one — a batch can never strand
        in a queue nobody serves."""
        rep = None
        with self._route_cv:
            while True:
                if self._abort:
                    break
                rep = self._pick_replica(exclude)
                if rep is None and exclude is not None:
                    # the excluded (failing) replica is the only one
                    # left: routing back would loop, so give up
                    if not any(r.alive and r is not exclude
                               for r in self._replicas):
                        break
                if rep is not None:
                    rep.in_flight += 1
                    rep.q.put((batch, retries))
                    return
                if not any(r.alive for r in self._replicas):
                    break
                self._route_cv.wait(timeout=_ROUTE_WAIT_S)
        exc: Exception
        if self._abort or self._closed:
            exc = ServerClosed("server %r is stopped" % self.name)
        else:
            exc = ServingError(
                "no live replicas on server %r" % self.name)
        self._metrics.count("failed", len(batch))
        for r in batch:
            r.fail(exc)

    def _release(self, rep: _Replica) -> None:
        with self._route_cv:
            rep.in_flight -= 1
            self._route_cv.notify_all()

    def _retire_replica(self, rep: _Replica) -> None:
        with self._route_cv:
            rep.alive = False
            rep.retired_at = time.monotonic()  # re-admission cooldown
            self._route_cv.notify_all()

    def _count_requeue(self, rep: _Replica) -> None:
        """One re-routed batch: the ``serving_requeued_total`` counter
        and the timeline marker move together (tests assert they agree),
        tagged with the replica the batch bounced off."""
        self._metrics.count("requeued")
        monitor.record_instant(
            "serving/batch_requeue", cat="serving",
            server=self.name, replica=rep.name)

    def _requeue(self, rep: _Replica, batch: List[ServingRequest],
                 retries: int) -> None:
        """Re-route a batch off ``rep`` — failing already-expired
        requests fast with DeadlineExceeded BEFORE they burn a
        retry/replica slot (an expired request re-routed to a survivor
        would occupy real capacity just to be shed there)."""
        live = []
        for r in batch:
            if r.expired():
                self._on_expired(r)
            else:
                live.append(r)
        if not live:
            return
        self._count_requeue(rep)
        self._route(live, retries, exclude=rep)

    def _replica_exit(self, rep: _Replica) -> None:
        """Terminal bookkeeping for a replica thread: mark dead under
        the routing lock (so no further _route can pick it — the put is
        inside the same lock), then drain anything that landed before
        the mark.  Without this a late failure re-route could strand a
        batch in an exited replica's queue forever."""
        self._retire_replica(rep)
        self._drain_replica_queue(rep)

    # ------------------------------------------------------------------
    def remove_replica(self, replica, timeout: float = 30.0) -> None:
        """Gracefully remove one replica at runtime: stop routing to it,
        wait for its in-flight work to finish (re-routing anything still
        queued).  ``replica``: index or ``r<idx>`` name.  Refuses to
        remove the last live replica (stop() the server instead).

        The replica's thread parks as a cheap re-route forwarder until
        the server stops — it must outlive the removal so a batch routed
        concurrently with it cannot strand in a dead queue."""
        if isinstance(replica, int):
            rep = self._replicas[replica]
        else:
            matches = [r for r in self._replicas if r.name == str(replica)]
            if not matches:
                raise ValueError("unknown replica %r" % (replica,))
            rep = matches[0]
        with self._route_cv:
            if not rep.alive:
                return  # already retired/removed
            if sum(1 for r in self._replicas if r.alive) <= 1:
                raise ValueError(
                    "cannot remove the last live replica of server %r"
                    % self.name)
            monitor.record_instant(
                "serving/replica_drain", cat="serving",
                server=self.name, replica=rep.name)
            rep.alive = False
            rep.removed = True  # deliberate: re-admission never undoes it
            self._route_cv.notify_all()
            deadline = time.monotonic() + timeout
            while rep.in_flight > 0 and time.monotonic() < deadline:
                self._route_cv.wait(timeout=0.1)

    # ------------------------------------------------------------------
    # Replica worker: per-replica double buffer — dispatch batch N+1
    # (async jit call, return_numpy=False) BEFORE materializing batch
    # N's outputs, so N's device compute + d2h overlap N+1's host-side
    # merge/pad/dispatch.
    # ------------------------------------------------------------------
    def _replica_loop(self, rep: _Replica) -> None:
        # stable named lane per replica worker: the merged Chrome trace
        # renders the fleet as parallel tracks
        _mon_spans.set_thread_lane(
            "serving/%s/%s worker" % (self.name, rep.name))
        pending = None
        _unset = object()
        while True:
            item = _unset
            if not rep.alive:
                # retired (failure) or removed (remove_replica): finish
                # the in-flight batch, re-route the rest, then PARK as a
                # forwarder until the server-wide stop sentinel — a
                # batch routed concurrently with the retirement can
                # still land in this queue, and exiting early would
                # strand it (the request would hang to its deadline)
                if pending is not None:
                    self._finalize(rep, *pending)
                    pending = None
                self._drain_replica_queue(rep)
                item = rep.q.get()
                if item is not None and not rep.alive:
                    batch, retries = item
                    self._release(rep)
                    self._requeue(rep, batch, retries)
                    continue
                # item is the stop sentinel (exit below), or the replica
                # was RE-ADMITTED while parked (half-open probe): the
                # batch that just arrived is the probe — serve it via
                # the normal path
            if item is _unset:
                if pending is None:
                    item = rep.q.get()
                else:
                    try:
                        item = rep.q.get_nowait()
                    except queue.Empty:
                        self._finalize(rep, *pending)
                        pending = None
                        continue  # re-enter blocking wait
            if item is None:
                if pending is not None:
                    self._finalize(rep, *pending)
                    pending = None
                self._replica_exit(rep)
                return  # server drained
            batch, retries = item
            live = []
            for r in batch:
                # deadlines are re-checked at the replica: a batch can
                # sit behind a slow predecessor after routing
                if r.expired():
                    self._on_expired(r)
                else:
                    live.append(r)
            if not live:
                self._release(rep)
                continue
            nxt = self._execute(rep, live, retries)
            if pending is not None:
                self._finalize(rep, *pending)
                pending = None
            if nxt is not None and not rep.nonblocking:
                # synchronous predictor: outs are already materialized —
                # deferring would just delay completions by one batch
                self._finalize(rep, *nxt)
                nxt = None
            pending = nxt

    def _drain_replica_queue(self, rep: _Replica) -> None:
        """Re-route (never drop) batches queued on a dead replica.  A
        stop sentinel encountered mid-drain is RE-QUEUED, not swallowed
        — it is the one-per-replica shutdown signal, and consuming it
        here would park the forwarder loop's next ``rep.q.get()``
        forever (stop() would hang on the join)."""
        saw_sentinel = False
        while True:
            try:
                item = rep.q.get_nowait()
            except queue.Empty:
                break
            if item is None:
                saw_sentinel = True
                continue
            batch, retries = item
            self._release(rep)  # give up this replica's slot...
            self._requeue(rep, batch, retries)  # ...take one elsewhere
        if saw_sentinel:
            rep.q.put(None)

    # hot-path: begin serve_execute (merge/pad/dispatch; the d2h sync lives
    # in _finalize, one batch behind)
    def _execute(self, rep: _Replica, batch: List[ServingRequest],
                 retries: int):
        """Merge + pad + DISPATCH one batch on ``rep`` (non-blocking
        fetch); returns the pending tuple for _finalize, or None on
        failure (the failure path re-routes or fails the requests).

        Tracing: with a session or flight recorder live, the whole
        merge/pad/dispatch runs under the batch's trace context (so the
        executor's h2d/execute spans carry the requests' ids) and —
        recorder only — under a span capture whose buffer rides the
        pending tuple into _finalize; otherwise the only rent is two
        gate checks."""
        valid = sum(r.n_rows for r in batch)
        # brownout L1+: flight-recorder capture is the first rent shed
        # under sustained saturation (tracing is a luxury; goodput isn't)
        fr = _flight.get() if self._brownout.level < 1 else None
        cap = [] if fr is not None else None
        tids = ()
        if cap is not None or _mon_spans.recording():
            tids = tuple(r.trace_id for r in batch if r.trace_id)
        try:
            with contextlib.ExitStack() as stack:
                if cap is not None:
                    stack.enter_context(_mon_spans.capture(cap))
                if tids or cap is not None:
                    now = time.perf_counter()
                    for r in batch:
                        # per-request queue wait: submit -> picked up
                        # here, each span owning its single trace id and
                        # parenting under its submitter's span (client
                        # infer span / wire server request span)
                        with _mon_spans.trace_context(
                                (r.trace_id,) if r.trace_id else ()):
                            _mon_spans.record_span(
                                "serving/queue_wait", r.submit_t,
                                now - r.submit_t, cat="serving",
                                parent=r.parent_span,
                                server=self.name, replica=rep.name,
                                n_rows=r.n_rows)
                    stack.enter_context(_mon_spans.trace_context(tids))
                    if len(batch) == 1 and batch[0].parent_span:
                        # an unshared batch can keep a fully connected
                        # tree: the batch/predictor/executor spans graft
                        # under the request's submitter span (a shared
                        # batch has no single parent — its subtree roots
                        # at the RecordEvent batch span instead)
                        stack.enter_context(
                            _mon_spans.parent_scope(batch[0].parent_span))
                if _faults.active is not None:  # disarmed: one is-None gate
                    _faults.active.faultpoint(
                        "replica.dispatch", server=self.name,
                        replica=rep.name)
                merged = {
                    name: (
                        np.concatenate([r.feed[name] for r in batch], axis=0)
                        if len(batch) > 1 else batch[0].feed[name])
                    for name in self._feed_names
                }
                bucket = self._policy.bucket_for(valid)
                padded = self._policy.pad_feed(merged, bucket)
                misses0 = rep.predictor.jit_cache_stats()["misses"]
                t0 = time.perf_counter()
                kw = {"return_numpy": False} if rep.nonblocking else {}
                # one batch = one precision variant (the batcher never
                # mixes); the select itself is a dict lookup downstream
                prec = getattr(batch[0], "precision", None)
                if prec is not None and rep.precision:
                    kw["precision"] = prec
                with rep.lock:
                    with profiler.RecordEvent("serving/%s/batch" % self.name):
                        outs = rep.predictor.run_padded(
                            padded, n_valid=valid, **kw)
                recompiled = (
                    rep.predictor.jit_cache_stats()["misses"] > misses0)
        except BaseException as exc:  # noqa: BLE001 — reroute/fail, keep serving
            self._replica_failure(rep, batch, retries, exc, cap=cap)
            return None
        return (batch, outs, valid, bucket, t0, recompiled, retries, cap)
    # hot-path: end serve_execute

    def _replica_failure(self, rep: _Replica, batch: List[ServingRequest],
                         retries: int, exc: BaseException,
                         cap: Optional[list] = None) -> None:
        """A batch failed on ``rep``: retire the replica when it fails
        repeatedly, and re-route the batch to a surviving replica so
        accepted requests don't drop — only with no survivor (or no
        retry budget) do the requests fail.  Terminally-failed requests
        are always tail-sampled (with whatever spans the batch captured
        before dying); a re-routed batch is not recorded here — it may
        still complete cleanly on the survivor."""
        rep.failed += 1
        rep.consec_failures += 1
        if rep.consec_failures >= _REPLICA_FAIL_LIMIT and rep.alive:
            # an incident marker ONLY for failure retirement (the clean
            # shutdown path also retires replicas — that is not an
            # incident); near-zero cost, gated on recording
            monitor.record_instant(
                "serving/replica_retired", cat="serving",
                server=self.name, replica=rep.name)
            self._retire_replica(rep)
        self._release(rep)
        with self._route_cv:
            survivors = any(
                r.alive and r is not rep for r in self._replicas)
        if retries > 0 and survivors:
            self._requeue(rep, batch, retries - 1)
            return
        self._metrics.count("failed", len(batch))
        fr = _flight.get()
        if fr is not None:
            now = time.perf_counter()
            for r in batch:
                fr.consider(
                    r.trace_id, now - r.submit_t, "error", cap or (),
                    server=self.name, replica=rep.name,
                    error=repr(exc))
        for r in batch:
            r.fail(exc)

    def _finalize(self, rep: _Replica, batch: List[ServingRequest], outs,
                  valid: int, bucket: int, t0: float, recompiled: bool,
                  retries: int, cap: Optional[list] = None) -> None:
        """Materialize a dispatched batch (the d2h sync) and complete its
        requests.  Deferred XLA runtime errors surface here — same
        reroute-or-fail handling as a dispatch failure.  The batch is
        observed HERE so ``run_s`` spans dispatch -> outputs materialized
        (the real batch duration; timing only the async dispatch call
        would report ~0).  ``cap``: the span buffer _execute captured
        for this batch (flight recorder live) — the materialize span
        joins it, then each request is tail-sampled."""
        tids = ()
        rec = cap is not None or _mon_spans.recording()
        if rec:
            tids = tuple(r.trace_id for r in batch if r.trace_id)
        try:
            with contextlib.ExitStack() as stack:
                if cap is not None:
                    stack.enter_context(_mon_spans.capture(cap))
                if tids:
                    stack.enter_context(_mon_spans.trace_context(tids))
                if rec and len(batch) == 1 and batch[0].parent_span:
                    # unshared batch: the d2h span keeps the connected
                    # tree (same graft rule as _execute)
                    stack.enter_context(
                        _mon_spans.parent_scope(batch[0].parent_span))
                if rec:
                    m0 = time.perf_counter()
                outs = [np.asarray(o) for o in outs]
                if rec:
                    _mon_spans.record_span(
                        "serving/materialize", m0,
                        time.perf_counter() - m0, cat="serving",
                        server=self.name, replica=rep.name)
        except BaseException as exc:  # noqa: BLE001
            self._replica_failure(rep, batch, retries, exc, cap=cap)
            return
        rep.executed += 1
        rep.consec_failures = 0
        self._metrics.observe_batch(
            valid, bucket, time.perf_counter() - t0,
            recompiled=recompiled and self._warmed, replica=rep.name)
        self._metrics.count_precision(
            getattr(batch[0], "precision", None) or self._default_dtype,
            len(batch))
        off = 0
        now = time.perf_counter()
        for r in batch:
            per_req = [
                o[off:off + r.n_rows]
                if o.ndim >= 1 and o.shape[0] == valid else o
                for o in outs
            ]
            off += r.n_rows
            r.complete(per_req)
            self._metrics.observe_request(now - r.submit_t,
                                          trace_id=r.trace_id)
        fr = _flight.get() if cap is not None else None
        if fr is not None:
            # tail-sampling decision per request: slow ones keep the
            # batch's full span tree (shared spans, per-request record)
            for r in batch:
                fr.consider(
                    r.trace_id, now - r.submit_t, "ok", cap,
                    server=self.name, replica=rep.name,
                    bucket=int(bucket), n_rows=int(r.n_rows))
        self._release(rep)

    # ------------------------------------------------------------------
    def stop(self, drain: bool = True, timeout: Optional[float] = None) -> None:
        """Shut down.  ``drain=True`` (graceful): stop admitting, finish
        every queued request, then join the dispatcher and replicas.
        ``drain=False``: queued-but-unstarted requests fail with
        ServerClosed (batches already routed to a replica still
        complete)."""
        self._closed = True
        if self._autotune_stop is not None:
            self._autotune_stop.set()
            if self._autotune_thread is not None:
                self._autotune_thread.join(timeout=5.0)
                self._autotune_thread = None
        with self._admin_lock:
            admin, self._admin = self._admin, None
        if admin is not None:
            admin.shutdown()
            admin.server_close()
        if drain:
            monitor.record_instant(
                "serving/server_drain", cat="serving", server=self.name)
        else:
            # empty the queue before releasing the dispatcher so it
            # cannot route work we are abandoning
            self._abort = True
            self._fail_stragglers()
        self._stop.set()
        self._batcher.wake()
        with self._route_cv:
            self._route_cv.notify_all()
        # one shared deadline across every join — N wedged threads must
        # not stretch the caller's bound to (1+N) x timeout
        deadline = (time.monotonic() + timeout) if timeout is not None else None

        def _remaining():
            return (None if deadline is None
                    else max(0.0, deadline - time.monotonic()))

        self._worker.join(_remaining())
        for rep in self._replicas:
            rep.thread.join(_remaining())
        # a submit() that raced past the admission check may have
        # enqueued AFTER the dispatcher drained and exited — fail it
        # (and anything else left) rather than leaving its future pending
        if not self._worker.is_alive():
            self._fail_stragglers()
        # retire this instance's series from the registry exposition;
        # metrics()/statusz() keep working off the detached children
        self._metrics.close()
        self._batcher.close()
        self._brownout.close()
        ADMISSION_EXPIRED.remove_labels(server=self.name)
        if any(getattr(r.predictor, "sharded", False)
               for r in self._replicas):
            from paddle_tpu.sharding.metrics import GROUP_HBM_BYTES

            for rep in self._replicas:
                GROUP_HBM_BYTES.remove_labels(
                    group="%s/%s" % (self.name, rep.name))

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.stop(drain=exc == (None, None, None))
        return False
