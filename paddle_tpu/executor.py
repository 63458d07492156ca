"""Executor: compiles a Program block into one jitted XLA module and runs it.

Reference: paddle/fluid/framework/executor.cc:175 (interpret ops one by one)
and python/paddle/fluid/executor.py:295.  The TPU-native design instead:

* the whole block (forward + backward + optimizer ops) lowers to a single
  XLA computation (core/lowering.py) — the reference's per-op dispatch,
  garbage collector (garbage_collector.h), and memory-reuse passes are
  subsumed by XLA buffer assignment;
* persistable vars are functional state, donated so parameter updates are
  in-place in HBM;
* compiled executables are cached by (program uid+version+op count, feed
  signature, fetch list, steps) — the per-shape compile cache that stands
  in for the reference's ExecutorPrepareContext caching (executor.cc:351);
  the per-run block analysis itself is cached too (_RunPlan), so a
  steady-state run() is plan lookup -> feed coercion -> jitted call.

Data-parallel/sharded execution: pass a CompiledProgram (see
paddle_tpu/parallel/compiled_program.py); the executor consults it for a
device mesh and sharding specs and jits with those in/out shardings —
XLA GSPMD then inserts the all-reduces that the reference built manually
via ParallelExecutor + NCCL op-handles (parallel_executor.cc:356).
"""
from __future__ import annotations

import os
import time
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

from paddle_tpu import compile_cache
from paddle_tpu import framework
from paddle_tpu import faults as _faults
from paddle_tpu.core import lowering
from paddle_tpu.core import types as core_types
from paddle_tpu.monitor import events as _mon_events
from paddle_tpu.monitor import registry as _mon_registry
from paddle_tpu.monitor import spans as _mon_spans
from paddle_tpu.monitor import train as _mon_train
from paddle_tpu.scope import Scope, global_scope

__all__ = ["Executor", "AsyncExecutor"]

# run-phase observability (paddle_tpu/monitor).  The jit hit/miss/run
# counters are COLLECT-ON-READ: every Executor's ``_cache_stats`` dict
# registers here at construction and the registry sums them when a
# consumer scrapes, so the run() hot path pays nothing beyond the dict
# increments it already did (a locked registry counter costs ~1.5us per
# inc — real money against a ~200us cached dispatch).  The per-phase
# spans gate on _mon_spans.recording(), one flag check each when no
# trace session is active.
import collections as _collections
import threading as _threading
import weakref as _weakref

_exec_stats_lock = _threading.Lock()
_exec_stats: List[Dict[str, int]] = []  # one _cache_stats dict per LIVE Executor
_exec_retired = {
    "hits": 0, "misses": 0, "runs": 0,
    "plan_hits": 0, "plan_misses": 0, "dispatch_overhead_s": 0.0,
    "plan_evictions": 0, "jit_evictions": 0,
    "ps_pull_overlap_s": 0.0, "ps_pull_wait_s": 0.0,
}  # folded-in dead executors


#: stats dicts of executors that were finalized and not folded in yet
_exec_dead: "_collections.deque" = _collections.deque()


def _retire_exec_stats(stats: Dict[str, int]) -> None:
    # weakref.finalize callback.  A collection starts wherever an
    # allocation tips it, ALSO on a thread that holds _exec_stats_lock
    # (the generator in _sum_exec_stats): taking the lock here stopped
    # that thread for good, and every later scrape and Executor() with
    # it (tier-1, PR 57).  So: no lock; the next holder folds it in.
    _exec_dead.append(stats)


def _fold_dead_exec_stats() -> None:
    # under the lock: fold dead executors' totals into the retired base
    # so the counters stay monotonic without pinning every stats dict
    while _exec_dead:
        stats = _exec_dead.popleft()
        for i, live in enumerate(_exec_stats):
            if live is stats:       # by identity: equal counts are common
                del _exec_stats[i]
                for k in _exec_retired:
                    _exec_retired[k] += stats.get(k, 0)
                break


def _sum_exec_stats(key: str) -> int:
    with _exec_stats_lock:
        _fold_dead_exec_stats()
        return _exec_retired[key] + sum(d.get(key, 0) for d in _exec_stats)


_mon_registry.REGISTRY.counter_callback(
    "executor_runs_total", "Executor.run invocations (all executors)",
    fn=lambda: _sum_exec_stats("runs"))
_mon_registry.REGISTRY.counter_callback(
    "executor_jit_cache_hits_total",
    "runs served by an existing compiled entry",
    fn=lambda: _sum_exec_stats("hits"))
_mon_registry.REGISTRY.counter_callback(
    "executor_jit_cache_misses_total",
    "newly built jitted entries (an XLA compile on first dispatch)",
    fn=lambda: _sum_exec_stats("misses"))
_mon_registry.REGISTRY.counter_callback(
    "executor_plan_cache_hits_total",
    "runs served by a cached run plan (no per-run block re-analysis)",
    fn=lambda: _sum_exec_stats("plan_hits"))
_mon_registry.REGISTRY.counter_callback(
    "executor_plan_cache_misses_total",
    "run-plan builds (an O(n_ops) dataflow analysis each)",
    fn=lambda: _sum_exec_stats("plan_misses"))
_mon_registry.REGISTRY.counter_callback(
    "executor_dispatch_overhead_seconds_total",
    "host-side run() seconds spent before the jitted dispatch",
    fn=lambda: _sum_exec_stats("dispatch_overhead_s"))
_mon_registry.REGISTRY.counter_callback(
    "executor_plan_cache_evictions_total",
    "run plans evicted by the LRU capacity bound",
    fn=lambda: _sum_exec_stats("plan_evictions"))
_mon_registry.REGISTRY.counter_callback(
    "executor_jit_cache_evictions_total",
    "compiled jit entries evicted by the LRU capacity bound",
    fn=lambda: _sum_exec_stats("jit_evictions"))
_mon_registry.REGISTRY.counter_callback(
    "executor_ps_pull_overlap_seconds_total",
    "dense-PS pull seconds hidden behind device compute (overlapped "
    "pull thread; train_from_dataset async mode)",
    fn=lambda: _sum_exec_stats("ps_pull_overlap_s"))
_mon_registry.REGISTRY.counter_callback(
    "executor_ps_pull_wait_seconds_total",
    "seconds run() blocked joining the overlapped dense-PS pull (the "
    "NOT-hidden remainder of the pull latency)",
    fn=lambda: _sum_exec_stats("ps_pull_wait_s"))
# per-run distribution, observed only while a trace session is active —
# a histogram observe is a lock + bucket scan (~2us), real money on a
# hot path whose whole budget is "almost nothing"; the always-on totals
# live in the callback counters above
_MON_DISPATCH_HIST = _mon_registry.REGISTRY.histogram(
    "executor_dispatch_overhead_seconds",
    "per-run host dispatch overhead (recorded under trace sessions)")
# per-step train-loop distribution — always on (a train step is ms-scale
# against a ~2us observe) with the epoch's trace id pinned as an
# OpenMetrics exemplar, the same linkage mechanism as
# serving_request_latency_seconds: a slow step surfaced in /trainz
# points straight at its flight-recorded span tree
_MON_TRAIN_STEP_HIST = _mon_registry.REGISTRY.histogram(
    "executor_train_step_seconds",
    "per-step train_from_dataset wall time (exemplar: epoch trace id)")


def _as_fetch_name(f) -> str:
    return f.name if isinstance(f, framework.Variable) else str(f)


def pow2_id_bucket(n_unique: int) -> int:
    """The default sparse-prefetch unique-id bucket: the next power of
    two >= ``n_unique``, floored at 8.  THE one definition — the
    prefetch (``_sparse_expand_ids``), the id-ladder autotune's
    comparison baseline (``autotune._pow2_id_ladder``), and the bench's
    warmup-bucket computation all call it, so the bucketing can never
    drift between the runtime and the tools sized against it."""
    return max(8, 1 << max(0, int(n_unique) - 1).bit_length())


def _donate_kwargs(device) -> Dict[str, Any]:
    """Buffer-donation jit kwargs for ``device``.

    Donating the mutable state makes param updates in-place in HBM — the
    point of the design on TPU.  On the CPU backend it buys nothing AND
    is unsafe with jax's persistent compilation cache: an executable
    compiled with input-output aliasing and then RELOADED from the disk
    cache returns fetches that observe the in-place-mutated params
    (reproduced: a DynamicRNN+Adam module fetches its rnn output
    computed with POST-update weights on every warm-cache process;
    cold compiles are always correct).  So: donate everywhere except
    CPU — tests/test_dispatch_fastpath.py pins the kwargs policy and
    tests/test_donation_cache.py pins the HAZARD itself with a
    two-process shared-cache drill (re-enabling donation here makes
    the warm-cache process disagree with the cold one)."""
    if getattr(device, "platform", None) == "cpu":
        return {}
    return {"donate_argnums": (0,)}


class _RunPlan:
    """Hoisted per-(program, feed/fetch signature) block analysis.

    Everything ``run()`` used to recompute per call that only depends on
    the program STRUCTURE plus the feed/fetch name sets lives here: the
    persistable scan over ``program.list_vars()``, the read/written
    dataflow sets, the ``state_mut/ro/out`` tuples, the resolved fetch
    list (including the hidden PS/dense-grad fetch tails), and the
    per-feed dtype coercion table.  A steady-state run is then: plan
    lookup -> coerce feeds -> jitted call.  Keyed (see ``run``) by
    (program uid, version, op count, feed names, fetch names, steps,
    per_step_feed, backend, compiled uid); the op count guards against
    ops appended after a run without a version bump.
    """

    __slots__ = (
        "feed_names", "fetch_names", "n_dense_fetch",
        "state_mut", "state_ro", "state_out",
        "feed_np_dtypes", "feed_jax_dtypes",
    )

    def __init__(self, feed_names, fetch_names, n_dense_fetch,
                 state_mut, state_ro, state_out, feed_np_dtypes,
                 feed_jax_dtypes):
        self.feed_names = feed_names
        self.fetch_names = fetch_names
        self.n_dense_fetch = n_dense_fetch
        self.state_mut = state_mut
        self.state_ro = state_ro
        self.state_out = state_out
        self.feed_np_dtypes = feed_np_dtypes
        self.feed_jax_dtypes = feed_jax_dtypes


class _LRUCache:
    """Bounded mapping with least-recently-used eviction.

    Long-lived multi-program processes (the serving server, a notebook
    driving many programs through one executor) must not grow the plan
    and jit caches without bound: a jit entry pins a compiled XLA
    executable plus its HBM constants.  Capacity defaults are generous
    (steady-state workloads never evict); ``on_evict`` feeds the
    ``executor_*_cache_evictions_total`` counters so an eviction storm
    — a capacity set too small for the program population — is visible
    on /metrics rather than silently recompiling every run."""

    __slots__ = ("_data", "capacity", "_on_evict")

    def __init__(self, capacity: int, on_evict=None):
        from collections import OrderedDict

        self._data: "OrderedDict" = OrderedDict()
        self.capacity = max(1, int(capacity))
        self._on_evict = on_evict

    def get(self, key, default=None):
        try:
            self._data.move_to_end(key)
        except KeyError:
            return default
        return self._data[key]

    def __setitem__(self, key, value):
        data = self._data
        data[key] = value
        data.move_to_end(key)
        while len(data) > self.capacity:
            data.popitem(last=False)
            if self._on_evict is not None:
                self._on_evict()

    def __contains__(self, key):
        return key in self._data

    def __len__(self):
        return len(self._data)

    def clear(self):
        self._data.clear()


# default cache bounds (env-overridable; constructor kwargs win).  Sized
# so ordinary workloads — even a serving process hosting dozens of
# endpoints x bucket rungs — never evict; the bound exists for the
# pathological long-lived case (programs built in a loop forever).
_PLAN_CACHE_CAPACITY = int(os.environ.get(
    "PADDLE_TPU_PLAN_CACHE_CAPACITY", "1024"))
_JIT_CACHE_CAPACITY = int(os.environ.get(
    "PADDLE_TPU_JIT_CACHE_CAPACITY", "512"))


class Executor:
    # train_from_dataset resume bookkeeping — class-level defaults so a
    # fresh executor answers reads before any epoch ran (each call
    # resets them as instance attributes)
    last_resume_step = None
    last_restore_path = None
    last_restore_fallbacks = 0
    last_restore_stats = None
    # training control tower (monitor/train.py): ``_train_ledger`` arms
    # run()'s phase charges for the duration of one train_from_dataset
    # epoch (one is-None gate on the disarmed path); the ``last_*``
    # handles keep /trainz answering after the epoch ends
    _train_ledger = None
    _train_admin = None
    _train_admin_thread = None
    last_train_ledger = None
    last_train_watchdog = None
    last_train_log = None

    def __init__(self, place=None, plan_cache_capacity: Optional[int] = None,
                 jit_cache_capacity: Optional[int] = None,
                 reshard_on_gather: Optional[bool] = None):
        # place=None means "process default device" (jax.devices()[0]) —
        # an explicit TPUPlace/CPUPlace is honored strictly (_device).
        self.place = place if place is not None else framework._DefaultPlace()
        # uncompiled-after-compiled interop: scope state a compiled run
        # committed to a MESH cannot feed a single-device jit.  Default
        # is a loud typed diagnostic (MeshCommittedStateError naming the
        # variable and its mesh); opting in here (or via
        # PADDLE_TPU_RESHARD_ON_GATHER=1) gathers the state back to
        # host ONCE at the offending run instead.
        self._reshard_on_gather = (
            bool(reshard_on_gather) if reshard_on_gather is not None
            else os.environ.get("PADDLE_TPU_RESHARD_ON_GATHER", "0") == "1")
        self._cache = _LRUCache(
            jit_cache_capacity if jit_cache_capacity is not None
            else _JIT_CACHE_CAPACITY,
            on_evict=lambda: self._bump("jit_evictions"))
        self._plans = _LRUCache(
            plan_cache_capacity if plan_cache_capacity is not None
            else _PLAN_CACHE_CAPACITY,
            on_evict=lambda: self._bump("plan_evictions"))
        self._dev = None  # resolved jax device (place is immutable)
        # jit-cache accounting (serving reads this): a miss means a NEW
        # jax.jit entry was built for a novel (program, feed-signature,
        # ...) key — i.e. an XLA compile on first dispatch.  This is the
        # ground truth behind serving's recompile counter, not an
        # inference from timing.  The dict also feeds the registry's
        # executor_* callback counters (summed across live executors at
        # scrape time; a finalizer folds this executor's totals into the
        # retired base on GC so the counters stay monotonic).
        self._cache_stats = {
            "hits": 0, "misses": 0, "runs": 0,
            "plan_hits": 0, "plan_misses": 0, "dispatch_overhead_s": 0.0,
            "plan_evictions": 0, "jit_evictions": 0,
            "ps_pull_overlap_s": 0.0, "ps_pull_wait_s": 0.0,
        }
        with _exec_stats_lock:
            _exec_stats.append(self._cache_stats)
        _weakref.finalize(self, _retire_exec_stats, self._cache_stats)

    def _bump(self, key: str, n: int = 1) -> None:
        self._cache_stats[key] += n

    # ------------------------------------------------------------------
    def _device(self):
        import jax

        backend = getattr(self.place, "backend", None)
        if backend:
            try:
                devs = jax.devices(backend)
                idx = getattr(self.place, "device_id", 0)
                return devs[idx % len(devs)]
            except RuntimeError as e:
                # Place mismatch is an error, like the reference's hard
                # failure on an unavailable Place (platform/place.h) —
                # unless the user opts into fallback explicitly.
                if os.environ.get("FLAGS_allow_place_fallback", "0") == "1":
                    import warnings

                    warnings.warn(
                        "place %r unavailable (%s); falling back to %s"
                        % (self.place, e, jax.devices()[0].platform)
                    )
                else:
                    raise RuntimeError(
                        "place %r requests backend %r which is unavailable: %s. "
                        "Set FLAGS_allow_place_fallback=1 to run on %s instead."
                        % (self.place, backend, e, jax.devices()[0].platform)
                    ) from e
        return jax.devices()[0]

    def _device_cached(self):
        # the place never changes after construction, so resolving the
        # jax device once keeps jax.devices() off the per-run hot path
        dev = self._dev
        if dev is None:
            dev = self._dev = self._device()
        return dev

    # ------------------------------------------------------------------
    def run(
        self,
        program=None,
        feed: Optional[Dict[str, Any]] = None,
        fetch_list: Optional[Sequence] = None,
        scope: Optional[Scope] = None,
        return_numpy: bool = True,
        use_program_cache: bool = True,
        steps: int = 1,
        per_step_feed: bool = False,
    ):
        """``steps`` (TPU-native extension): run N optimizer steps inside ONE
        jitted call (a ``lax.fori_loop`` over the compiled step), returning
        the last step's fetches.  Amortizes the per-dispatch host->device
        overhead — the analog of the reference's multi-iteration DeviceWorker
        loop (device_worker.h TrainFiles runs many batches per Run call).

        By default every iteration re-consumes the same feed (a pure
        compute benchmark regime).  With ``per_step_feed=True`` each feed
        value carries an extra leading ``steps`` axis (shape
        ``(steps,) + per_batch_shape``) and iteration ``i`` consumes slice
        ``i`` via ``lax.dynamic_index_in_dim`` — N *distinct* batches per
        jitted call, the compiled analog of the reference's buffered reader
        feeding the train loop (operators/reader/buffered_reader.cc)."""
        import jax

        stats = self._cache_stats
        stats["runs"] += 1
        if _faults.active is not None:  # disarmed: one is-None gate
            _faults.active.faultpoint("executor.run")
        _rec = _mon_spans.recording()
        # step-phase ledger (training control tower): disarmed runs pay
        # this one is-None gate; armed runs open a window-exclusive
        # device_execute window whose explicit h2d/ps_wait charges below
        # subtract out, so no wall-clock second is attributed twice
        _led = self._train_ledger
        _led_tok = _led.window_begin() if _led is not None else None
        _t_run0 = time.perf_counter()
        compiled = None
        if program is not None and getattr(program, "_is_compiled_program", False):
            compiled = program
            program = compiled._program
        if program is None:
            program = framework.default_main_program()
        scope = scope or global_scope()
        feed = dict(feed or {})

        if getattr(program, "_pserver_ctx", None):
            return self._run_pserver(program)

        if getattr(program, "_pipeline_plan", None):
            if steps != 1:
                raise ValueError("steps>1 is not supported for pipeline programs")
            return self._run_pipeline(
                program, feed,
                [_as_fetch_name(f) for f in (fetch_list or [])],
                scope, return_numpy,
            )

        dense_ps = getattr(program, "_dense_ps_ctx", None)
        if dense_ps is not None:
            if steps != 1:
                raise ValueError(
                    "steps>1 is incompatible with dense PS mode (the grad "
                    "send / param recv is host-side per batch)"
                )
            self._dense_ps_init(dense_ps, scope)
            # overlapped mode: install the params the background thread
            # pulled while the PREVIOUS step's device compute ran (must
            # land before this run's state gather)
            self._dense_ps_join_pending(dense_ps, scope)

        if getattr(program, "_pruned_params", None):
            # a writer appended after prune() would resurrect pruned
            # weights (ADVICE r2); re-validate when the op count moved
            n_ops = sum(len(b.ops) for b in program.blocks)
            if n_ops != getattr(program, "_pruned_checked_ops", None):
                from paddle_tpu.contrib.slim.prune import _check_no_late_writers

                _check_no_late_writers(program)
                program._pruned_checked_ops = n_ops

        # distributed lookup tables: pull rows before the step, push the
        # sparse grads after (reference: parameter_prefetch.cc + the
        # trainer-side send of SelectedRows grads).  Host-side per batch
        # (or a device-side mesh gather — sharding/sparse.py).  NOTE the
        # plan key uses the PRE-expansion feed names: the rows/local
        # names the prefetch adds are a deterministic function of them,
        # so the expanded plan is safe to reuse — and they are EXCLUDED
        # from the key even when already present (the overlapped
        # prefetch installs them ahead of run()), so the inline and
        # overlapped paths share one plan and one jit entry.  A
        # caller-managed manual prefetch (rows fed with NO side-channel
        # ids — grads are not pushed) is keyed separately.
        dist_tables = getattr(program, "_distributed_tables", None)
        feed_key_names = tuple(sorted(feed))
        manual_prefetch = ()
        if dist_tables:
            side = getattr(program, "_sparse_prefetched_ids", None) or {}
            internal = set()
            manual = []
            for meta in dist_tables.values():
                internal.add(meta["rows_name"])
                internal.add(meta["local_name"])
                if meta["rows_name"] in feed and meta["rows_name"] not in side:
                    manual.append(meta["rows_name"])
            feed_key_names = tuple(
                sorted(n for n in feed if n not in internal))
            manual_prefetch = tuple(sorted(manual))
        plan_key = (
            framework._program_uid(program),
            program.version,
            sum(len(b.ops) for b in program.blocks),
            feed_key_names,
            tuple(_as_fetch_name(f) for f in (fetch_list or [])),
            steps,
            per_step_feed,
            getattr(self.place, "backend", None),
            framework._program_uid(compiled) if compiled is not None else None,
            manual_prefetch,
        )
        ps_push = ()
        if dist_tables:
            if _led is None:
                ps_push = self._prefetch_distributed_tables(
                    program, program.global_block(), feed, compiled=compiled)
            else:
                # inline (non-overlapped) sparse pulls block right here —
                # the ledger files them under ps_wait, not device_execute
                _t_ps = time.perf_counter()
                ps_push = self._prefetch_distributed_tables(
                    program, program.global_block(), feed, compiled=compiled)
                _led.charge("ps_wait", time.perf_counter() - _t_ps)

        plan = self._plans.get(plan_key) if use_program_cache else None
        if plan is not None:
            stats["plan_hits"] += 1
        else:
            stats["plan_misses"] += 1
            plan = self._analyze(program, feed, fetch_list, ps_push, dense_ps)
            if use_program_cache:
                self._plans[plan_key] = plan

        if steps != 1 and (ps_push or steps < 1):
            raise ValueError(
                "steps=%d: multi-step run() needs steps>=1 and is "
                "incompatible with distributed lookup tables (the PS "
                "pull/push is host-side per batch)" % steps
            )
        if per_step_feed:
            bad = {
                n: np.shape(v)
                for n, v in feed.items()
                if np.shape(v)[:1] != (steps,)
            }
            if bad:
                raise ValueError(
                    "per_step_feed=True: every feed needs a leading "
                    "steps=%d axis; got %s" % (steps, bad)
                )

        feed_names = plan.feed_names
        fetch_names = plan.fetch_names
        state_mut, state_ro = plan.state_mut, plan.state_ro
        n_dense_fetch = plan.n_dense_fetch

        # hot-path: begin dispatch (plan hit -> feed coercion -> jitted call;
        # no blocking device sync may appear in this region — enforced by
        # tools/check_hot_path.py)
        # materialize feed on the target device; values that are already
        # jax Arrays (e.g. a device-resident input pipeline, reader.py)
        # pass through untouched — no host round-trip.  Dtype coercion
        # tables were resolved once at plan build.
        device = self._device_cached()
        if _rec or _led is not None:
            _t0 = time.perf_counter()
        feed_arrays = {}
        np_dts, jax_dts = plan.feed_np_dtypes, plan.feed_jax_dtypes
        for name, val in feed.items():
            if isinstance(val, jax.Array):
                # coerce device-resident feeds too (cheap on-device cast,
                # stays in HBM) so the compiled signature matches the
                # program var — same contract as numpy feeds
                want = jax_dts.get(name)
                if want is not None and val.dtype != want:
                    val = val.astype(want)
                feed_arrays[name] = val
                continue
            arr = np.asarray(val, dtype=np_dts.get(name))  # hot-ok: host ndarray feed, not a device array
            feed_arrays[name] = jax.device_put(arr, device)
        if _led is not None:
            _led.charge("h2d", time.perf_counter() - _t0)
        if _rec:
            _mon_spans.record_span(
                "executor/h2d_feed", _t0, time.perf_counter() - _t0,
                cat="transfer", n_feeds=len(feed_arrays))

        # gather state from scope (one pass doubles as the init check;
        # the committed-state probe is two getattrs per var, and only
        # for UNcompiled runs — compiled runs re-place via the mesh)
        mut_state, ro_state, missing, committed = {}, {}, None, None
        for names, out in ((state_mut, mut_state), (state_ro, ro_state)):
            for n in names:
                v = scope.get(n)
                if v is None:
                    missing = (missing or []) + [n]
                elif compiled is None:
                    sh = getattr(v, "sharding", None)
                    if sh is not None and len(
                            getattr(sh, "device_set", ())) > 1:
                        committed = (committed or []) + [(n, out, sh)]
                out[n] = v
        if missing:
            raise RuntimeError(
                "Variables %s are not initialized in scope — run the startup "
                "program first (reference: executor.py run startup)" % missing
            )
        if committed:
            # interop gap (ROADMAP): a program run UNCOMPILED after a
            # compiled run sees mesh-committed (sharded or mesh-
            # replicated) state; feeding it to a single-device jit
            # fails deep inside jax with a device mismatch.  Either
            # gather the state back to host once (opt-in) or name the
            # problem loudly here.
            if self._reshard_on_gather:
                for n, out, _sh in committed:
                    host = jax.device_get(out[n])  # hot-ok: cold interop path — committed state detected, gather once
                    out[n] = host
                    scope.set(n, host)  # later runs gather clean
            else:
                from paddle_tpu.sharding.rules import MeshCommittedStateError

                descs = []
                for n, _out, sh in committed[:4]:
                    mesh = getattr(sh, "mesh", None)
                    where = (
                        dict(zip(mesh.axis_names, mesh.devices.shape))
                        if mesh is not None else
                        "%d devices" % len(sh.device_set))
                    descs.append("%r on %s" % (n, where))
                more = len(committed) - len(descs)
                raise MeshCommittedStateError(
                    "running this program UNCOMPILED, but its scope state "
                    "is committed to a device mesh by a previous compiled "
                    "run: %s%s. Run it through the same CompiledProgram, "
                    "or opt into a one-time host gather with "
                    "Executor(reshard_on_gather=True) / "
                    "PADDLE_TPU_RESHARD_ON_GATHER=1."
                    % ("; ".join(descs),
                       " (+%d more)" % more if more > 0 else ""))

        feed_sig = tuple(
            (n, feed_arrays[n].shape, feed_arrays[n].dtype)
            for n in feed_names
        )
        # plan_key already pins program identity/version/op-count, fetch
        # list, steps/per_step_feed, backend, and compiled identity; the
        # state tuples are a pure function of those, so the jit key only
        # needs the per-run shape/dtype signature on top
        key = (plan_key, feed_sig)

        entry = self._cache.get(key) if use_program_cache else None
        first_dispatch = entry is None
        if entry is not None:
            stats["hits"] += 1
        else:
            stats["misses"] += 1
            block = program.global_block()
            state_out = plan.state_out
            fn = lowering.lower_block(block, feed_names, fetch_names, state_out)
            if compiled is not None:
                # a compiled program's block traces under a marker that
                # says GSPMD will partition it (ops with a single-device
                # kernel read it), and, for sequence-parallel serving,
                # under its activation constrainer, so matched
                # intermediates get with_sharding_constraint applied
                # in-trace (trace time = first dispatch of this key —
                # steady-state dispatches never re-enter fn)
                _base_fn = fn

                def fn(state, feed, _base=_base_fn,
                       _c=compiled.activation_constrainer()):
                    from paddle_tpu.sharding import activations as _sh_act

                    if _c is not None:
                        _c.begin_trace()
                    with _sh_act.tracing(_c):
                        out = _base(state, feed)
                    if _c is not None:
                        _c.end_trace()
                    return out

            if steps == 1:
                def stepfn(mut_state, ro_state, feed_dict):
                    state = dict(mut_state)
                    state.update(ro_state)
                    if per_step_feed:
                        feed_dict = {n: v[0] for n, v in feed_dict.items()}
                    return fn(state, feed_dict)
            else:
                def stepfn(mut_state, ro_state, feed_dict):
                    # carry (mut, fetches, extras) with extras = written-but-
                    # not-carried state, so no array appears twice in the
                    # loop carry (a duplicated param forces a copy per
                    # iteration)
                    def step_feed(i):
                        if not per_step_feed:
                            return feed_dict
                        return {
                            n: jax.lax.dynamic_index_in_dim(
                                v, i, axis=0, keepdims=False
                            )
                            for n, v in feed_dict.items()
                        }

                    def one(i, mut):
                        state = dict(mut)
                        state.update(ro_state)
                        fetches, new_state = fn(state, step_feed(i))
                        nxt = {n: new_state.get(n, mut[n]) for n in mut}
                        extras = {
                            n: v for n, v in new_state.items() if n not in mut
                        }
                        return nxt, fetches, extras

                    carry = one(0, mut_state)
                    mut, fetches, extras = jax.lax.fori_loop(
                        1, steps, lambda i, c: one(i, c[0]), carry
                    )
                    return fetches, {**mut, **extras}

            jit_kwargs = dict(_donate_kwargs(device))
            if compiled is not None:
                jit_kwargs.update(
                    compiled._jit_kwargs(
                        block, feed_names, fetch_names, state_mut, state_ro,
                        state_out, per_step_feed=per_step_feed,
                    )
                )
            entry = jax.jit(stepfn, **jit_kwargs)
            if use_program_cache:
                self._cache[key] = entry

        if compiled is not None:
            # the steady token is scoped to THIS executor (uid, not
            # id() — CPython reuses ids after GC): two executors sharing
            # a CompiledProgram have independent scopes, so one reaching
            # steady state must not let the other skip placement
            feed_arrays, mut_state, ro_state, restaged = compiled._shard_inputs(
                feed_arrays, mut_state, ro_state, per_step_feed=per_step_feed,
                steady_token=(framework._program_uid(self), key),
            )
            for n, v in restaged.items():
                # keep the resharded copy: a read-only param must be
                # replicated onto the mesh ONCE, not per step (state_mut
                # self-heals via out_shardings-pinned outputs, but ro
                # state is never written back by the jitted call)
                scope.set(n, v)
        # everything above is the host's per-dispatch rent; on a plan +
        # jit cache hit it must stay "almost nothing"
        # (tests/test_dispatch_fastpath.py reads this accounting)
        _overhead = time.perf_counter() - _t_run0
        stats["dispatch_overhead_s"] += _overhead
        if _rec:
            # a serving replica runs this under the batch's trace
            # context — pin one of its trace ids to the bucket so the
            # OpenMetrics exposition links overhead tails to requests
            _ids = _mon_spans.current_trace_ids()
            _MON_DISPATCH_HIST.observe(
                _overhead, exemplar={"trace_id": _ids[0]} if _ids else None)
            _t0 = time.perf_counter()
        if first_dispatch:
            # jax.jit is lazy: a novel cache key's first dispatch is
            # where the block traces (lowering/trace_block), XLA builds
            # or loads the module (the build record's listener) and the
            # program runs once; the rest of its wall is first_run
            with compile_cache.build("executor_step", rest="first_run",
                                     steps=steps):
                fetches, new_state = entry(mut_state, ro_state, feed_arrays)
        else:
            fetches, new_state = entry(mut_state, ro_state, feed_arrays)
        # hot-path: end dispatch (the jitted call is async; everything
        # below is allowed to sync)
        if _rec:
            # the first dispatch of a novel cache key is where XLA
            # compiles (jax.jit is lazy) — label it as the compile phase;
            # steady-state dispatches are device execution
            _mon_spans.record_span(
                "executor/jit_compile" if first_dispatch
                else "executor/device_execute",
                _t0, time.perf_counter() - _t0,
                cat="compile" if first_dispatch else "execute",
                steps=steps)
        for n, v in new_state.items():
            scope.set(n, v)
        if n_dense_fetch:
            # dense PS round (reference: send_barrier -> send grads ->
            # recv params, distribute_transpiler.py:320): push EVERY grad
            # before pulling ANY param — in sync mode the pull blocks on
            # the server applying all trainers' grads, so interleaving
            # would deadlock this trainer against itself
            client = self._dense_ps_client(dense_ps)
            names = list(dense_ps["params"])
            # overlapped pull (async mode, train_from_dataset): kick the
            # NEXT step's param pull off on a background thread NOW,
            # while this step's device compute is still in flight (the
            # np.asarray(grad) below is the d2h sync point) — the pull
            # latency hides behind the chip instead of serializing after
            # it.  Hogwild semantics: the pulled copy misses this step's
            # own push (bounded staleness 1), which async mode already
            # tolerates by construction.  Sync mode keeps the strict
            # push-all-then-pull-at-version ordering below.
            overlap = bool(dense_ps.get("overlap_pull")) and not dense_ps["sync"]
            if overlap:
                self._dense_ps_spawn_pull(dense_ps, names)
            grads = fetches[len(fetches) - n_dense_fetch:]
            fetches = fetches[: len(fetches) - n_dense_fetch]
            for name, grad in zip(names, grads):
                lr_var = dense_ps["params"][name]["lr_var"]
                lr_val = scope.get(lr_var)
                lr = float(np.asarray(lr_val)) if lr_val is not None else 0.1
                client.push_dense(name, np.asarray(grad), lr)
            dense_ps["step"] += 1
            if not overlap:
                min_v = dense_ps["step"] if dense_ps["sync"] else 0
                _t_pd = time.perf_counter() if _led is not None else 0.0
                for name in names:
                    scope.set(name, client.pull_dense(name, min_version=min_v))
                if _led is not None:
                    # the blocking (non-overlapped) dense pull is PS wire
                    # wait, not device time
                    _led.charge("ps_wait", time.perf_counter() - _t_pd)
        if ps_push:
            # mesh-resident tables: shard-wise device update, grad never
            # leaves HBM.  PS tables: async mode enqueues on the
            # Communicator (merge-before-send background thread), sync
            # mode pushes blocking — and a bound embedding cache
            # invalidates the pushed rows AFTER the server-side write
            # lands (invalidating before it would let a concurrent
            # read-through re-cache the pre-update row permanently; the
            # async path invalidates from the Communicator's send
            # thread, after each applied merge).
            comm = getattr(program, "_ps_communicator", None)
            client = getattr(program, "_ps_client", None)
            mesh_rt = getattr(program, "_mesh_tables", None)
            cache = getattr(program, "_embedding_cache", None)
            if comm is not None and cache is not None:
                comm.on_pushed = cache.invalidate_ids
            # fetch_names still carries the dense-grad tail even though
            # those entries were sliced off `fetches` above — subtract
            # both hidden tails or the sparse-grad zip walks user fetches
            n_user = len(fetch_names) - len(ps_push) - n_dense_fetch
            for (table, uniq, _), grad in zip(ps_push, fetches[n_user:]):
                if mesh_rt is not None and table in mesh_rt:
                    mesh_rt.push(table, uniq, grad)
                    continue
                if comm is not None:
                    comm.push(table, uniq, np.asarray(grad))
                else:
                    client.push_sparse(table, uniq, np.asarray(grad))
                    if cache is not None:
                        cache.invalidate_ids(table, uniq)
            fetches = fetches[:n_user]
        if os.environ.get("FLAGS_check_nan_inf", "0") == "1":
            # module-boundary nan/inf check (reference checks per-op after
            # each kernel, operator.cc:954; one compiled module => one
            # boundary). Costs a d2h sync — debug only.
            bad = [
                name
                for name, val in list(zip(fetch_names, fetches)) + list(new_state.items())
                if np.issubdtype(np.asarray(val).dtype, np.floating)
                and not np.all(np.isfinite(np.asarray(val)))
            ]
            if bad:
                raise RuntimeError(
                    "nan/inf detected in %s (FLAGS_check_nan_inf=1)" % bad
                )
        if return_numpy:
            if _rec:
                _t0 = time.perf_counter()
            fetches = [np.asarray(f) for f in fetches]
            if _rec:
                _mon_spans.record_span(
                    "executor/d2h_fetch", _t0, time.perf_counter() - _t0,
                    cat="transfer", n_fetch=len(fetches))
        if _led is not None:
            # remainder of the run window = dispatch + jitted call + the
            # d2h sync that realizes the device step (run() is async
            # after dispatch; the np.asarray above is where device time
            # becomes observable on this thread)
            _led.window_end(_led_tok, "device_execute")
        return fetches

    # ------------------------------------------------------------------
    def _analyze(self, program, feed, fetch_list, ps_push, dense_ps) -> _RunPlan:
        """The O(n_ops) block analysis ``run()`` used to repeat per call,
        done once per plan-cache key.  ``feed`` must already carry any
        distributed-table expansion (rows/local names) for this feed-name
        set."""
        import jax

        block = program.global_block()
        fetch_names = [_as_fetch_name(f) for f in (fetch_list or [])]

        persistable = {
            v.name for v in program.list_vars() if v.persistable
        }

        # true dataflow reads: a name counts as read-from-outside only
        # when some op reads it BEFORE any op writes it (a load/fill op
        # producing a persistable must not demand scope pre-init)
        read, written = set(), set()
        for op in block.ops:
            for n in op.input_arg_names:
                if n not in written:
                    read.add(n)
            for n in op.output_arg_names:
                written.add(n)
        for fname in fetch_names:
            if fname in persistable and fname not in written:
                read.add(fname)

        if ps_push:
            # fetch each prefetched-rows grad so it can be pushed; hidden
            # from the caller's fetch list (appended, sliced off by run)
            for _, _, gname in ps_push:
                fetch_names.append(gname)
        n_dense_fetch = 0
        if dense_ps is not None:
            # fetch each param's dense grad for the send (hidden like
            # ps_push; sliced off before returning to the caller)
            for desc in dense_ps["params"].values():
                fetch_names.append(desc["grad"])
                n_dense_fetch += 1

        feed_names = tuple(sorted(feed.keys()))
        state_mut = tuple(sorted(read & written & persistable))
        state_ro = tuple(
            sorted((read & persistable) - set(state_mut) - set(feed_names))
        )
        state_out = tuple(sorted(written & persistable))

        # dtype coercion tables: program-var dtype per feed, both as the
        # numpy target (host feeds) and the canonicalized jax target
        # (device-resident feeds) — resolved here so the hot path never
        # walks the var table or calls canonicalize_dtype
        np_dts, jax_dts = {}, {}
        for name in feed_names:
            var = block._find_var_recursive(name)
            if var is not None:
                dt = core_types.np_dtype(var.dtype)
                np_dts[name] = dt
                jax_dts[name] = jax.dtypes.canonicalize_dtype(dt)

        return _RunPlan(
            feed_names, fetch_names, n_dense_fetch,
            state_mut, state_ro, state_out, np_dts, jax_dts,
        )

    # ------------------------------------------------------------------
    # Dense legacy PS (reference: distribute_transpiler.py trainer side +
    # listen_and_serv_op.cc server loop)
    # ------------------------------------------------------------------
    def _dense_ps_client(self, ctx):
        client = ctx.get("_client")
        if client is None:
            from paddle_tpu.distributed.ps import PSClient

            client = ctx["_client"] = PSClient(ctx["endpoints"])
        return client

    def _dense_ps_pull_client(self, ctx):
        # the overlapped pull runs on its own thread CONCURRENTLY with
        # the main thread's push — PSClient sockets are not thread-safe
        # (interleaved frames corrupt the wire), so the pull thread gets
        # a dedicated client over the same endpoints
        client = ctx.get("_pull_client")
        if client is None:
            from paddle_tpu.distributed.ps import PSClient

            client = ctx["_pull_client"] = PSClient(ctx["endpoints"])
        return client

    # transient PS pull failures the background thread may retry: the
    # connection classes only — a PS in-band application error
    # (RuntimeError from PSClient._call) is deterministic and must
    # surface, not be retried
    _PS_PULL_RETRYABLE = (ConnectionError, OSError, TimeoutError)
    _PS_PULL_RETRY = None  # lazily built shared RetryPolicy

    @classmethod
    def _ps_pull_policy(cls):
        if cls._PS_PULL_RETRY is None:
            from paddle_tpu.faults.retry import RetryPolicy

            cls._PS_PULL_RETRY = RetryPolicy(
                max_attempts=4, base_delay_s=0.05, multiplier=2.0,
                max_delay_s=1.0)
        return cls._PS_PULL_RETRY

    def _dense_ps_spawn_pull(self, ctx, names) -> None:
        """Start the next step's param pull on a background thread (one
        in flight at a time — run() joins the previous before spawning).
        A transient PS failure (connection refused/reset — a flapping
        server) closes the dead client's sockets, redials on a fresh
        dedicated client, and retries under a RetryPolicy budget; on
        EVERY failure the erroring client's sockets are closed before
        the error propagates (no socket leak per failed pull thread)."""
        import threading

        from paddle_tpu.distributed.ps import PSClient

        client = self._dense_ps_pull_client(ctx)
        result: Dict[str, Any] = {}
        budget = self._ps_pull_policy().budget(op="ps.pull")

        def _pull():
            nonlocal client
            t0 = time.perf_counter()
            try:
                while True:
                    try:
                        result["vals"] = {
                            n: client.pull_dense(n, min_version=0)
                            for n in names
                        }
                        return
                    except self._PS_PULL_RETRYABLE:
                        # try/finally contract: the dedicated client's
                        # sockets close on this exit path no matter what
                        try:
                            client.close()
                        finally:
                            ctx.pop("_pull_client", None)
                        if not budget.backoff():
                            raise
                        client = ctx["_pull_client"] = PSClient(
                            ctx["endpoints"])
            except BaseException as e:  # noqa: BLE001 — re-raised at join
                result["exc"] = e
            finally:
                result["dur"] = time.perf_counter() - t0

        th = threading.Thread(target=_pull, name="ptpu-ps-pull", daemon=True)
        ctx["_pull_pending"] = (th, result)
        th.start()

    def _dense_ps_join_pending(self, ctx, scope) -> None:
        """Join the in-flight overlapped pull (if any) and install the
        pulled params.  ``ps_pull_overlap_s`` accumulates the pull
        seconds that hid behind device compute; ``ps_pull_wait_s`` the
        remainder this join actually blocked for."""
        pending = ctx.pop("_pull_pending", None)
        if pending is None:
            return
        th, result = pending
        t0 = time.perf_counter()
        th.join()
        wait = time.perf_counter() - t0
        stats = self._cache_stats
        stats["ps_pull_wait_s"] += wait
        stats["ps_pull_overlap_s"] += max(0.0, result.get("dur", 0.0) - wait)
        led = self._train_ledger
        if led is not None:
            led.charge("ps_wait", wait)
        exc = result.get("exc")
        if exc is not None:
            raise exc
        for n, v in result["vals"].items():
            scope.set(n, v)

    # ------------------------------------------------------------------
    # Overlapped SPARSE prefetch (train_from_dataset async mode): batch
    # N+1's per-table PS pulls run on a background thread while batch
    # N's device compute is in flight — the sparse analog of the
    # overlapped dense pulls above, with the same dedicated-client and
    # overlap/wait accounting contracts.  Async (Communicator) mode
    # only: the prefetched rows miss the current step's own push
    # (bounded staleness 1), which async mode already tolerates by
    # construction; sync mode keeps the strict pull-push ordering.
    # ------------------------------------------------------------------
    def _sparse_overlap_clients(self, ctx, endpoints, n: int):
        """The overlap thread's own clients (one per table) — never the
        caller's, and never the inline pool's (those serve the caller
        thread's concurrent pulls)."""
        from paddle_tpu.distributed.ps import PSClient

        pool = ctx.setdefault("clients", [])
        while len(pool) < n:
            pool.append(PSClient(list(endpoints)))
        return pool[:n]

    def _sparse_overlap_close(self, ctx) -> None:
        for cl in ctx.pop("clients", []):
            try:
                cl.close()
            except Exception:  # noqa: BLE001 — teardown best-effort
                pass

    def _sparse_spawn_prefetch(self, program, feed) -> None:
        """Start the NEXT batch's table pulls on a background thread
        (one in flight at a time — the overlap iterator joins before
        spawning).  Per-table pulls inside the thread run concurrently
        on dedicated clients; a transient failure closes the thread's
        clients, redials, and retries under the shared RetryPolicy
        budget — on exhaustion the error surfaces typed at join."""
        import threading

        dist_tables = program._distributed_tables
        mesh_rt = getattr(program, "_mesh_tables", None)
        cache = getattr(program, "_embedding_cache", None)
        ladder = getattr(program, "_sparse_id_ladder", None)
        endpoints = getattr(
            getattr(program, "_ps_client", None), "endpoints", None)
        jobs = []
        for meta in dist_tables.values():
            if meta["rows_name"] in feed or meta["ids_name"] not in feed:
                continue
            if mesh_rt is not None and meta["table"] in mesh_rt:
                continue  # device-side gather: nothing to hide
            uniq_p, n, counts, local = self._sparse_expand_ids(
                meta, feed[meta["ids_name"]], ladder)
            self._record_uniq_count(program, n)
            jobs.append((meta, uniq_p, n, counts, local))
        if not jobs or not endpoints:
            return
        ctx = program.__dict__.setdefault("_sparse_overlap_ctx", {})
        result: Dict[str, Any] = {}
        budget = self._ps_pull_policy().budget(op="ps.pull")

        def _pull():
            t0 = time.perf_counter()
            try:
                while True:
                    try:
                        clients = self._sparse_overlap_clients(
                            ctx, endpoints, len(jobs))
                        vals, errs = self._fanout_table_pulls(
                            jobs, clients, cache)
                        if errs:
                            raise errs[0][0]
                        result["vals"] = vals
                        return
                    except self._PS_PULL_RETRYABLE:
                        # close + redial on a fresh set, like the dense
                        # pull thread (no socket leak per failed pull)
                        self._sparse_overlap_close(ctx)
                        if not budget.backoff():
                            raise
            except BaseException as e:  # noqa: BLE001 — re-raised at join
                result["exc"] = e
            finally:
                result["dur"] = time.perf_counter() - t0

        th = threading.Thread(target=_pull, name="ptpu-sparse-prefetch",
                              daemon=True)
        ctx["pending"] = (th, result, jobs)
        th.start()

    def _sparse_join_prefetch(self, program, feed) -> None:
        """Join the in-flight sparse prefetch and install the pulled
        rows + local maps into ``feed``; the unique ids ride the
        ``_sparse_prefetched_ids`` side-channel so the next run() still
        pushes this batch's sparse grads.  Accounting mirrors the dense
        path: ``ps_pull_overlap_s`` is the pull time that hid behind
        device compute, ``ps_pull_wait_s`` what this join blocked for."""
        ctx = program.__dict__.get("_sparse_overlap_ctx")
        pending = ctx.pop("pending", None) if ctx else None
        if pending is None:
            return
        th, result, jobs = pending
        t0 = time.perf_counter()
        th.join()
        wait = time.perf_counter() - t0
        stats = self._cache_stats
        stats["ps_pull_wait_s"] += wait
        stats["ps_pull_overlap_s"] += max(0.0, result.get("dur", 0.0) - wait)
        led = self._train_ledger
        if led is not None:
            # the join runs inside the data_wait window (next(batches));
            # window-exclusive accounting moves it into ps_wait
            led.charge("ps_wait", wait)
        exc = result.get("exc")
        if exc is not None:
            raise exc
        side = program.__dict__.setdefault("_sparse_prefetched_ids", {})
        for meta, uniq_p, _n, _counts, local in jobs:
            feed[meta["rows_name"]] = result["vals"][meta["rows_name"]]
            feed[meta["local_name"]] = local
            side[meta["rows_name"]] = uniq_p

    def _sparse_overlap_iter(self, program, batches):
        """One-step-lookahead wrapper: spawn batch N+1's pulls BEFORE
        yielding batch N (so they run while N computes), join + install
        when the consumer asks for N+1.  Every exit path joins the
        pending thread and closes the overlap clients."""
        ctx = program.__dict__.setdefault("_sparse_overlap_ctx", {})
        it = iter(batches)

        def pull_next():
            # work on a COPY: the join installs rows/local into the
            # feed, and mutating the CALLER's dict would make a second
            # epoch over the same feed list look manually-prefetched
            # (silently dropping its grad pushes)
            nxt = next(it, None)
            return dict(nxt) if isinstance(nxt, dict) else nxt

        try:
            cur = pull_next()
            if cur is None:
                return
            while True:
                nxt = pull_next()
                if nxt is not None:
                    self._sparse_spawn_prefetch(program, nxt)
                yield cur
                if nxt is None:
                    return
                self._sparse_join_prefetch(program, nxt)
                cur = nxt
        finally:
            pending = ctx.pop("pending", None)
            if pending is not None:
                # abandoned mid-epoch (consumer error/break): drain the
                # thread so it can't race teardown; its error is moot
                pending[0].join()
            self._sparse_overlap_close(ctx)
            program.__dict__.pop("_sparse_prefetched_ids", None)
            closer = getattr(it, "close", None)
            if closer is not None:
                closer()

    def _dense_ps_init(self, ctx, scope):
        """First-run handshake: create the server-side entries, trainer 0
        seeds its initial param values (deterministic broadcast), everyone
        pulls the seeded copy — the reference pserver startup + initial
        recv (distribute_transpiler.py get_startup_program)."""
        if ctx["initialized"]:
            return
        client = self._dense_ps_client(ctx)
        for name, desc in ctx["params"].items():
            val = scope.get(name)
            if val is None:
                raise RuntimeError(
                    "dense PS param %r not in scope — run the startup "
                    "program first" % name
                )
            client.create_dense(
                name, np.shape(val), optimizer=desc["optimizer"],
                attrs=desc["attrs"], n_trainers=ctx["n_trainers"],
                sync=ctx["sync"],
            )
            if ctx["trainer_id"] == 0:
                client.seed_dense(name, np.asarray(val))
            scope.set(name, client.pull_dense(name, min_version=0))
        ctx["initialized"] = True

    def _run_pserver(self, program):
        """Serve the dense params hashed to this endpoint and BLOCK, like
        the reference's listen_and_serv op.  The live server object is
        exposed as ``program._pserver`` so a host test/driver can stop it."""
        from paddle_tpu.distributed.ps import ParameterServer, PSClient

        ctx = program._pserver_ctx
        server = ParameterServer(ctx["endpoint"])
        # register this shard's dense params directly (no wire round-trip;
        # shard placement must match the trainer-side PSClient.shard_for)
        placer = PSClient(ctx["endpoints"])
        my_idx = ctx["endpoints"].index(ctx["endpoint"])
        from paddle_tpu.distributed.ps import _DenseParam

        for name, desc in ctx["params"].items():
            if placer.shard_for(name) != my_idx:
                continue
            server._dense[name] = _DenseParam(
                desc["shape"], optimizer=desc["optimizer"], attrs=desc["attrs"],
                n_trainers=ctx["n_trainers"], sync=ctx["sync"],
            )
        program._pserver = server
        server.start()
        try:
            server._thread.join()
        except KeyboardInterrupt:
            server.stop()
        return []

    # ------------------------------------------------------------------
    def _run_pipeline(self, program, feed, fetch_names, scope, return_numpy):
        """Run one compiled-GPipe step (PipelineOptimizer with cut_list;
        reference: PipelineTrainer/SectionWorker, section_worker.cc:141).
        Fetches are limited to the loss (the schedule's only global
        scalar)."""
        import jax

        from paddle_tpu.parallel import mesh as mesh_lib, pipeline_program

        plan = program._pipeline_plan
        loss_name = plan["loss_name"]
        K = len(plan["cut_vars"]) + 1
        feed_sig = tuple(
            (n, tuple(np.shape(v)),
             str(v.dtype if hasattr(v, "dtype") else np.asarray(v).dtype))
            for n, v in sorted(feed.items())
        )
        key = ("pipeline", framework._program_uid(program), program.version,
               feed_sig)
        entry = self._cache.get(key)
        if entry is None:
            # honor the executor's place like the main path (_device)
            mesh = mesh_lib.make_mesh(
                {"pp": K}, backend=getattr(self.place, "backend", None)
            )
            run_plan = dict(plan)
            run_plan["feed_names"] = sorted(feed.keys())
            step, state_names = pipeline_program.build_pipeline_step(
                program, loss_name, run_plan, mesh
            )
            # donate state like the main path: param/velocity updates are
            # in-place in HBM (skipped on CPU — see _donate_kwargs)
            entry = (
                jax.jit(step, **_donate_kwargs(mesh.devices.flat[0])),
                state_names,
            )
            self._cache[key] = entry
        step, state_names = entry

        # fetches: the loss plus any state var (params and optimizer
        # accumulators are the schedule's persistables)
        for f in fetch_names:
            if f != loss_name and f not in state_names:
                raise ValueError(
                    "pipeline programs can fetch the loss %r or a "
                    "persistable state var %s (got %r)"
                    % (loss_name, state_names, f)
                )
        state = {}
        for n in state_names:
            v = scope.get(n)
            if v is None:
                raise RuntimeError(
                    "var %r not initialized — run the startup program" % n
                )
            state[n] = v
        feed_arrays = {
            n: v if isinstance(v, jax.Array) else np.asarray(v)
            for n, v in feed.items()
        }
        loss, new_state = step(state, feed_arrays)
        for n, v in new_state.items():
            scope.set(n, v)
        out = [loss if f == loss_name else new_state[f] for f in fetch_names]
        if return_numpy:
            out = [np.asarray(o) for o in out]
        return out

    # ------------------------------------------------------------------
    # Distributed lookup tables: the sparse prefetch/push runtime.
    # Three backends behind one feed contract: mesh-resident tables
    # (sharding/sparse.py device gather), PS pulls (optionally through a
    # hot-id cache), and the overlapped background prefetch that
    # pipelines batch N+1's pulls behind batch N's device compute.
    # ------------------------------------------------------------------
    @staticmethod
    def _sparse_expand_ids(meta, ids_val, ladder=None):
        """Unique + bucket one table's batch ids.  Returns
        ``(uniq_padded, n_uniq, counts, local)``: the bucketed unique
        ids (padded by repeating ids[0], which receives zero gradient —
        no local index maps to it, so the push is a no-op for it), the
        real unique count, per-unique occurrence counts (the cache's
        served-rows accounting), and the ids->row map shaped like the
        feed.  ``ladder``: an explicit unique-count bucket ladder (the
        autotuned ``propose_id_bucket_ladder`` output); sizes above its
        top rung — or no ladder — fall back to power-of-two buckets."""
        ids_val = np.asarray(ids_val)
        flat = ids_val.reshape(-1).astype(np.int64)
        uniq, inv, counts = np.unique(
            flat, return_inverse=True, return_counts=True)
        n = len(uniq)
        bucket = None
        if ladder:
            for r in ladder:
                if int(r) >= n:
                    bucket = int(r)
                    break
        if bucket is None:
            bucket = pow2_id_bucket(n)
        fill = uniq[0] if n else 0
        uniq_p = np.concatenate(
            [uniq, np.full(bucket - n, fill, np.int64)])
        local = inv.astype(np.int32)
        if meta["squeeze_last"] and ids_val.ndim >= 2 and ids_val.shape[-1] == 1:
            local = local.reshape(ids_val.shape[:-1])
        else:
            local = local.reshape(ids_val.shape)
        return uniq_p, n, counts, local

    @staticmethod
    def _record_uniq_count(program, n: int) -> None:
        """Per-batch unique-id-count histogram (the offline id-ladder
        autotuner's input — serving.autotune.propose_id_bucket_ladder).
        Best-effort under the GIL, like the serving arrival histogram."""
        hist = program.__dict__.get("_uniq_id_hist")
        if hist is None:
            hist = program.__dict__.setdefault("_uniq_id_hist", {})
        hist[n] = hist.get(n, 0) + 1

    def _sparse_client_pool(self, program, n: int):
        """``n`` DEDICATED PSClients for concurrent per-table pulls (a
        PSClient socket is not thread-safe — interleaved frames corrupt
        the wire).  Pooled on the program and redialed lazily after an
        error closed one.  Returns None when the bound client is a
        duck-typed stub with no endpoints to dial (tests) — the caller
        then pulls serially on its own thread."""
        client = getattr(program, "_ps_client", None)
        endpoints = getattr(client, "endpoints", None)
        if not endpoints:
            return None
        from paddle_tpu.distributed.ps import PSClient

        pool = program.__dict__.setdefault("_sparse_pull_pool", [])
        while len(pool) < n:
            pool.append(PSClient(list(endpoints)))
        return pool[:n]

    def _pull_one_table(self, client, cache, meta, uniq_p, n_uniq, counts):
        """One table's row pull, through the hot-id cache when bound."""
        if cache is not None:
            rows = cache.lookup_through(
                client, meta["table"], uniq_p, n_valid=n_uniq,
                counts=counts)
        else:
            rows = client.pull_sparse(meta["table"], uniq_p)
        return np.asarray(rows, np.float32)

    def _fanout_table_pulls(self, jobs, clients, cache):
        """The shared per-table fan-out: job 0 on the CALLING thread
        with ``clients[0]``, jobs[1:] on worker threads each with its
        dedicated client (one socket per thread — frames never
        interleave).  Returns ``(results, errors)`` with ``errors`` as
        ``[(exc, client)]`` — callers decide the cleanup policy (the
        inline path drops the failed pool client; the overlap thread
        redials its whole set)."""
        results: Dict[str, np.ndarray] = {}
        errors: List = []

        def work(job, cl):
            meta, uniq_p, n, counts, _local = job
            try:
                results[meta["rows_name"]] = self._pull_one_table(
                    cl, cache, meta, uniq_p, n, counts)
            except BaseException as e:  # noqa: BLE001 — caller re-raises
                errors.append((e, cl))

        if len(jobs) == 1:
            work(jobs[0], clients[0])
            return results, errors
        import threading

        threads = [
            threading.Thread(target=work, args=(job, cl),
                             name="ptpu-sparse-pull", daemon=True)
            for job, cl in zip(jobs[1:], clients[1:])
        ]
        for th in threads:
            th.start()
        work(jobs[0], clients[0])
        for th in threads:
            th.join()
        return results, errors

    def _pull_tables_concurrent(self, program, client, cache, jobs):
        """Issue every job's ``pull_sparse`` CONCURRENTLY — job 0 on the
        calling thread with ``client``, the rest on worker threads each
        with a dedicated pool client (DeepFM has one table per sparse
        field; serializing them on one socket was the old behavior).
        Returns {rows_name: rows}; the first worker error propagates
        after all joins, with that worker's client closed and dropped
        from the pool (the next pull redials)."""
        pool = (self._sparse_client_pool(program, len(jobs) - 1)
                if len(jobs) > 1 else None)
        if len(jobs) > 1 and not pool:
            # duck-typed stub client with no endpoints to dial: serial
            results: Dict[str, np.ndarray] = {}
            for meta, uniq_p, n, counts, _local in jobs:
                results[meta["rows_name"]] = self._pull_one_table(
                    client, cache, meta, uniq_p, n, counts)
            return results
        results, errors = self._fanout_table_pulls(
            jobs, [client] + (pool or []), cache)
        if errors:
            exc = errors[0][0]
            pool_list = program.__dict__.get("_sparse_pull_pool", [])
            for e, cl in errors:
                if cl is not client:
                    try:
                        cl.close()
                    finally:
                        if cl in pool_list:
                            pool_list.remove(cl)
            raise exc
        return results

    def _prefetch_distributed_tables(self, program, block, feed,
                                     compiled=None):
        """Resolve each distributed table's rows for this batch's unique
        ids and add them (plus the ids->row map) to the feed.  Returns
        [(table, padded_unique_ids, rows_grad_name)] for tables whose
        grad exists in the program (training) so run() can push after
        the step.  Unique counts bucket (power-of-two, or the autotuned
        ``program._sparse_id_ladder``) to bound recompiles.

        Routing per table: a mesh-resident table (``bind_mesh_tables``)
        serves a device-side sharded gather — no host round-trip; PS
        tables pull host-side, all tables CONCURRENTLY (dedicated
        clients) and through the hot-id embedding cache when one is
        bound; rows already in the feed were supplied by the overlapped
        prefetch (its side-channel carries the unique ids so the grad
        push still happens) or by a manual caller (no push)."""
        dist_tables = getattr(program, "_distributed_tables", None)
        if not dist_tables:
            return []
        mesh_rt = getattr(program, "_mesh_tables", None)
        cache = getattr(program, "_embedding_cache", None)
        side = getattr(program, "_sparse_prefetched_ids", None)
        ladder = getattr(program, "_sparse_id_ladder", None)
        from paddle_tpu.framework import grad_var_name

        ps_push = []
        pulls = []  # PS-backed jobs, pulled concurrently below
        for meta in dist_tables.values():
            tname = meta["table"]
            rows_name = meta["rows_name"]
            if rows_name in feed:
                if side and rows_name in side:
                    # overlapped prefetch: rows landed ahead of run();
                    # the side-channel ids keep the grad push alive
                    uniq_p = side.pop(rows_name)
                    gname = grad_var_name(rows_name)
                    if block._find_var_recursive(gname) is not None:
                        ps_push.append((tname, uniq_p, gname))
                continue  # caller prefetched manually (no push)
            ids_name = meta["ids_name"]
            if ids_name not in feed:
                raise RuntimeError(
                    "distributed table %r needs ids var %r in the feed "
                    "(prefetch happens host-side per batch)" % (tname, ids_name)
                )
            uniq_p, n_uniq, counts, local = self._sparse_expand_ids(
                meta, feed[ids_name], ladder)
            self._record_uniq_count(program, n_uniq)
            feed[meta["local_name"]] = local
            gname = grad_var_name(rows_name)
            if block._find_var_recursive(gname) is not None:
                ps_push.append((tname, uniq_p, gname))
            if mesh_rt is not None and tname in mesh_rt:
                if compiled is None:
                    raise RuntimeError(
                        "table %r is mesh-resident (bind_mesh_tables): "
                        "its rows live sharded on the mesh, so this "
                        "program must run through its CompiledProgram "
                        "— an uncompiled run cannot consume the "
                        "mesh-committed lookup" % tname)
                feed[rows_name] = mesh_rt.lookup(tname, uniq_p)
            else:
                pulls.append((meta, uniq_p, n_uniq, counts, local))
        if pulls:
            client = getattr(program, "_ps_client", None)
            if client is None:
                raise RuntimeError(
                    "program has distributed lookup tables; call "
                    "paddle_tpu.distributed.bind_distributed_tables("
                    "program, endpoints) before running it"
                )
            rows_by_name = self._pull_tables_concurrent(
                program, client, cache, pulls)
            for meta, _uniq_p, _n, _counts, _local in pulls:
                feed[meta["rows_name"]] = rows_by_name[meta["rows_name"]]
        return ps_push

    # ------------------------------------------------------------------
    def train_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100,
                           trainer_desc=None, trace_id=None,
                           checkpoint_dir=None, checkpoint_every=0,
                           checkpoint_epoch=0, resume_from=None,
                           checkpoint_async=False, phase_ledger=None,
                           watchdog=None, train_log=None):
        """Loop the dataset's batches through run() (reference:
        executor.py train_from_dataset -> C++ Trainer/DeviceWorker loop,
        trainer.h:38; here the compiled step is the device worker).

        ``trainer_desc`` (trainer_desc.py): supplies fetch config
        defaults and validates that the chosen device worker matches the
        program (Section needs a PipelineOptimizer-cut program,
        DownpourSGD needs distributed lookup tables).

        Crash-resumable training (TPU-native extension, reference:
        checkpoint_notify + trainer restart from persistables — here
        exact to a step): ``checkpoint_dir`` + ``checkpoint_every=N``
        commits an atomic checkpoint every N completed steps — the
        program's persistables, the PS sparse tables (when the program
        is bound to a ``PSClient``), and the dataset cursor, all via
        tmp+rename (``paddle_tpu.faults.checkpoint.TrainCheckpoint``).
        A SIGKILLed run restarted with ``resume_from=<same dir>``
        restores all three and SKIPS the already-consumed batches, so it
        continues within one checkpoint interval of where it died;
        ``last_resume_step`` reports the restored cursor.  Async PS
        state (the overlapped pull, the Communicator's queued pushes) is
        quiesced before each save so the checkpoint is consistent.
        ``checkpoint_async=True`` moves serialization off the critical
        path: the step pays only a quiesce + copy-on-write gather and a
        background snapshot thread writes/commits (same tmp+rename
        atomicity; the epoch joins the tail save before returning).

        Request-scoped tracing (TPU-native extension): the epoch mints a
        trace id (or joins ``trace_id``) readable back via
        ``last_train_trace_id``; while a trace session or flight
        recorder is live, every step runs under that id inside an
        ``executor/train_step`` span parented to one
        ``executor/train_epoch`` span — a training epoch is correlatable
        in ``/tracez``/the merged Chrome trace exactly like a serving
        request.

        Training control tower (monitor/train.py):
        ``phase_ledger=True`` (or a ``StepPhaseLedger`` instance) arms
        the step-phase ledger — every wall-clock second of the epoch is
        attributed to data_wait / h2d / device_execute / ps_wait /
        checkpoint / restore_fallback / other, exported as
        ``train_phase_seconds_total{phase=}`` plus throughput and MFU
        gauges, and asserted to sum to the elapsed time within 1%.
        ``watchdog=True`` (or a ``TrainWatchdog``) runs EWMA + z-score
        anomaly detection per step (NaN/Inf loss, loss spikes,
        grad-norm blowups, step-time stragglers), emitting
        ``train/anomaly`` events and raising ``TrainAnomalyError`` for
        kinds in its ``halt_on``.  ``train_log=<path>`` streams one
        JSONL record per step (phases, loss, anomalies, trace id),
        replayable offline via ``monitor.train.replay_step_log`` /
        ``train_top --replay``.  ``start_train_admin()`` serves it all
        at ``/trainz``."""
        n_prefetch = int(thread)
        if trainer_desc is not None:
            worker = trainer_desc._worker
            if worker.worker_kind == "Section" and not getattr(program, "_pipeline_plan", None):
                raise ValueError(
                    "Section worker needs a PipelineOptimizer(cut_list=...) program"
                )
            if worker.worker_kind == "DownpourSGD" and not getattr(program, "_distributed_tables", None):
                raise ValueError(
                    "DownpourSGD worker needs embedding(is_distributed=True) tables"
                )
            # worker-specific runtime behavior: Hogwild flips a dense-PS
            # program to async rounds, DownpourSGD installs the async
            # Communicator, Section validates the microbatch plan
            worker._prepare(program)
            fetch_list = fetch_list or trainer_desc._fetch_vars
            fetch_info = fetch_info or trainer_desc._fetch_info
            print_period = trainer_desc._print_period
            n_prefetch = n_prefetch or int(getattr(trainer_desc, "thread_num", 0))
        compiled = (
            program if program is not None
            and getattr(program, "_is_compiled_program", False) else None)
        prog_obj = compiled._program if compiled is not None else (
            program if program is not None else framework.default_main_program())
        # training control tower: build/adopt the ledger, watchdog and
        # step log for this epoch.  The ledger's epoch window opens HERE
        # so a resume restore below is attributed (restore_fallback)
        # inside the same wall-clock the 1% sum contract covers.
        led = None
        if phase_ledger:
            led = (phase_ledger
                   if isinstance(phase_ledger, _mon_train.StepPhaseLedger)
                   else _mon_train.StepPhaseLedger())
            self.last_train_ledger = led
            led.begin_epoch()
        wd = None
        if watchdog:
            wd = (watchdog
                  if isinstance(watchdog, _mon_train.TrainWatchdog)
                  else _mon_train.TrainWatchdog())
            self.last_train_watchdog = wd
        steplog = None
        if train_log:
            steplog = _mon_train.StepLog(train_log)
            self.last_train_log = train_log
        # crash-resume: restore persistables + PS tables + the dataset
        # cursor BEFORE the first batch, then skip the consumed prefix
        ckpt = None
        start_step = 0
        self.last_resume_step = None
        # reset the restore bookkeeping every call — a plain run after a
        # resumed one must not keep reporting the old run's restore
        self.last_restore_path = None
        self.last_restore_fallbacks = 0
        self.last_restore_stats = None
        if checkpoint_dir is not None or resume_from is not None:
            from paddle_tpu.faults.checkpoint import TrainCheckpoint

            ckpt = TrainCheckpoint(checkpoint_dir or resume_from,
                                   every_n_steps=int(checkpoint_every))
            if resume_from is not None:
                # restore from resume_from even when NEW checkpoints go
                # to a different checkpoint_dir (fork-a-run semantics)
                src = (ckpt if checkpoint_dir in (None, resume_from)
                       else TrainCheckpoint(resume_from))
                _led_tok = led.window_begin() if led is not None else None
                cursor = src.restore(
                    prog_obj, scope or global_scope(),
                    ps_client=getattr(prog_obj, "_ps_client", None),
                    compiled=compiled)
                if _led_tok is not None:
                    led.window_end(_led_tok, "restore_fallback")
                # which checkpoint actually served (integrity fallback
                # may have skipped corrupt/pruned ones — the drills and
                # operators read these alongside last_resume_step)
                self.last_restore_path = src.last_restore_path
                self.last_restore_fallbacks = src.last_restore_fallbacks
                self.last_restore_stats = src.last_restore_stats
                if cursor is not None:
                    start_step = int(cursor.get("step", 0))
                    self.last_resume_step = start_step
                # resume/fallback history belongs in /eventz and the
                # step log, not stdout: one severity-tagged event per
                # resume (warning when integrity fallbacks were taken)
                _mon_events.emit(
                    "train/resume",
                    severity=("warning" if self.last_restore_fallbacks
                              else "info"),
                    message="resumed from %s at step %d (%d fallback(s))"
                    % (self.last_restore_path, start_step,
                       self.last_restore_fallbacks),
                    cat="train", step=start_step,
                    path=self.last_restore_path,
                    fallbacks=self.last_restore_fallbacks)
        batches = iter(dataset)
        if start_step:
            import itertools as _itertools

            batches = _itertools.islice(batches, start_step, None)
        if n_prefetch > 1:
            # the reference's reader threads feeding device workers
            # (trainer.h thread_num): a bounded background prefetcher
            # stages batches ON DEVICE ahead of the compiled step
            # (reader.device_buffered), so the run() h2d phase is a
            # passthrough.  A CompiledProgram upgrades this to SHARDED
            # prefetch: each replica's batch slice is device_put straight
            # into its own HBM, and run()'s _shard_inputs passes the
            # pre-placed arrays through.  The prefetcher shuts its
            # producer down when the consumer exits early (exception or
            # break) — the old inline queue left the thread blocked on
            # q.put forever.
            from paddle_tpu import reader as _reader

            if compiled is not None:
                batches = _reader.device_buffered(
                    batches, size=n_prefetch, compiled=compiled)()
            else:
                try:
                    device = self._device_cached()
                except Exception:
                    device = None  # no jax backend: prefetch host-side only
                batches = _reader.device_buffered(
                    batches, size=n_prefetch, device=device)()
        # overlapped SPARSE prefetch: in async (Communicator) mode batch
        # N+1's per-table PS pulls run behind batch N's device compute
        # (the sparse analog of the dense overlap below; same
        # ps_pull_overlap_s accounting, same bounded-staleness trade —
        # sync mode keeps the strict pull-after-push ordering)
        if (getattr(prog_obj, "_distributed_tables", None)
                and getattr(prog_obj, "_ps_communicator", None) is not None
                and getattr(prog_obj, "_sparse_overlap", True)):
            batches = self._sparse_overlap_iter(prog_obj, batches)
        if led is not None:
            # data_wait attribution: each next() on the (possibly
            # prefetch-wrapped) iterator, minus whatever the nested
            # sparse-prefetch join already charged to ps_wait
            batches = led.timed_iter(batches)
        # dense-PS async mode: overlap each step's host param pull with
        # the device compute (the pull thread runs while the chip works;
        # ps_pull_overlap_s counts the hidden seconds).  Sync mode keeps
        # the strict barrier ordering, so the flag only arms async runs.
        ps_ctx = getattr(prog_obj, "_dense_ps_ctx", None)
        overlap_prev = None
        if ps_ctx is not None and not ps_ctx.get("sync", True):
            overlap_prev = ps_ctx.get("overlap_pull")
            ps_ctx["overlap_pull"] = True
        # epoch trace id: minted per call (or joined via trace_id=) so a
        # training epoch's span chain is correlatable like a serving
        # request; the epoch span id parents every step span.  Gated per
        # step on the same single recording() flag the run() phases use —
        # the untraced loop pays two attribute checks, nothing else.
        from paddle_tpu.monitor import flight as _mon_flight

        tid = trace_id or _mon_flight.new_trace_id()
        self.last_train_trace_id = tid
        epoch_sid = None
        epoch_t0 = None
        n_steps = 0
        results = []
        _monitoring = (led is not None or wd is not None
                       or steplog is not None)
        self._train_ledger = led  # arm run()'s phase charges (or clear)
        _t_prev = time.perf_counter()
        try:
            for i, feed in enumerate(batches):
                step = start_step + i  # global step (resume-aware cursor)
                if _mon_spans.recording():
                    if epoch_sid is None:
                        epoch_sid = _mon_spans.new_span_id()
                        epoch_t0 = time.perf_counter()
                    _t0 = time.perf_counter()
                    with _mon_spans.trace_context((tid,)):
                        with _mon_spans.parent_scope(epoch_sid):
                            with _mon_spans.parent_scope() as step_sid:
                                out = self.run(
                                    program, feed=feed,
                                    fetch_list=fetch_list, scope=scope)
                            _mon_spans.record_span(
                                "executor/train_step", _t0,
                                time.perf_counter() - _t0, cat="train",
                                span_id=step_sid, step=step)
                else:
                    out = self.run(program, feed=feed, fetch_list=fetch_list, scope=scope)
                n_steps += 1
                _t_now = time.perf_counter()
                _dur = _t_now - _t_prev  # step period incl. data_wait
                _t_prev = _t_now
                _MON_TRAIN_STEP_HIST.observe(
                    _dur, exemplar={"trace_id": tid})
                if fetch_list:
                    results.append(out)
                    if debug and i % print_period == 0:
                        names = fetch_info or [ _as_fetch_name(f) for f in fetch_list]
                        # stdout stays (the chaos drills parse it); the
                        # event makes the same progress line scrapeable
                        # via /eventz and the step log
                        print("batch %d:" % step, dict(zip(names, [np.asarray(o) for o in out])))
                        _mon_events.emit(
                            "train/progress", severity="info",
                            message="batch %d: %s" % (step, {
                                n: float(np.mean(v))
                                for n, v in zip(names, out)
                                if np.issubdtype(
                                    np.asarray(v).dtype, np.number)
                            }),
                            cat="train", step=step)
                if _monitoring:
                    _ex = _mon_train.batch_examples(feed)
                    loss_val = None
                    if out and fetch_list:
                        _li = wd.loss_index if wd is not None else 0
                        try:
                            loss_val = float(np.mean(out[_li]))
                        except (TypeError, ValueError, IndexError):
                            loss_val = None
                    row = None
                    if led is not None:
                        if led.flops_per_step is None:
                            # static-FLOPs MFU numerator, resolved once
                            # against the first batch's leading dim
                            led.flops_per_step = (
                                _mon_train.estimate_block_flops(
                                    prog_obj, batch=max(1, _ex)))
                        row = led.step_done(
                            step, _dur, examples=_ex, loss=loss_val)
                    anomalies = ()
                    if wd is not None:
                        anomalies = wd.observe_step(
                            step, loss=loss_val, step_time_s=_dur)
                    if steplog is not None:
                        rec = (dict(row) if row is not None
                               else {"step": step,
                                     "duration_s": round(_dur, 6),
                                     "examples": _ex})
                        if loss_val is not None and "loss" not in rec:
                            rec["loss"] = loss_val
                        if anomalies:
                            rec["anomalies"] = list(anomalies)
                        rec["trace_id"] = tid
                        steplog.write(rec)
                    if wd is not None and anomalies:
                        # typed halt (TrainAnomalyError) for kinds in
                        # halt_on — after the step is logged, so the
                        # fatal step is in the record
                        wd.raise_if_halt(anomalies)
                if ckpt is not None and ckpt.should_save(step + 1):
                    _led_tok = (led.window_begin()
                                if led is not None else None)
                    self._train_checkpoint(
                        ckpt, prog_obj, scope or global_scope(),
                        step + 1, int(checkpoint_epoch), ps_ctx,
                        async_=bool(checkpoint_async), compiled=compiled)
                    if _led_tok is not None:
                        # foreground cost only: quiesce + (sync) write or
                        # (async) copy-on-write snapshot.  The quiesce's
                        # dense-pull join stays in ps_wait (exclusive
                        # window) — checkpoint is the save itself.
                        led.window_end(_led_tok, "checkpoint",
                                       detail="sync")
            if ckpt is not None:
                # commit the tail background save before returning (a
                # write error surfaces here, on the epoch's own path)
                _led_tok = led.window_begin() if led is not None else None
                ckpt.wait()
                if _led_tok is not None:
                    # async-commit join: the tail of the background
                    # serialization the step loop didn't hide
                    led.window_end(_led_tok, "checkpoint",
                                   detail="commit")
            if led is not None:
                # clean exit: close the ledger strictly — the remainder
                # lands in `other` and the 1% sum contract is asserted
                led.finish_epoch()
        finally:
            self._train_ledger = None  # disarm run()'s phase charges
            if led is not None:
                # exceptional exit: close the ledger WITHOUT the sum
                # assert (the epoch's own error must propagate; a
                # partial ledger is still worth reading in /trainz)
                led.finish_epoch(strict=False)
            if steplog is not None:
                steplog.close()
            if ckpt is not None and ckpt.in_flight:
                # abnormal exit with a save still writing: join so the
                # writer can't race teardown; the epoch's primary error
                # stays the one that propagates
                try:
                    ckpt.wait()
                except BaseException:  # noqa: BLE001 — deliberate
                    pass
            if epoch_sid is not None:
                with _mon_spans.trace_context((tid,)):
                    _mon_spans.record_span(
                        "executor/train_epoch", epoch_t0,
                        time.perf_counter() - epoch_t0, cat="train",
                        span_id=epoch_sid, steps=n_steps)
            closer = getattr(batches, "close", None)
            if closer is not None:
                closer()  # stop the prefetch producer (GeneratorExit path)
            if ps_ctx is not None:
                # drain the in-flight pull so the scope leaves with the
                # freshest params and no dangling thread, then CLOSE the
                # pull thread's dedicated client — its sockets must not
                # outlive the epoch on any exit path (a fresh epoch
                # redials)
                try:
                    self._dense_ps_join_pending(ps_ctx, scope or global_scope())
                finally:
                    if overlap_prev is None:
                        ps_ctx.pop("overlap_pull", None)
                    else:
                        ps_ctx["overlap_pull"] = overlap_prev
                    pull_client = ps_ctx.get("_pull_client")
                    if pull_client is not None:
                        pull_client.close()  # next epoch redials
        return results

    def _train_checkpoint(self, ckpt, program, scope, step, epoch,
                          ps_ctx, async_: bool = False,
                          compiled=None) -> None:
        """Quiesce async PS state, then commit one atomic checkpoint.
        The overlapped dense-PS pull is joined (its params land in the
        scope first) and the async Communicator is flushed (every queued
        sparse grad reaches the server) so the saved params, PS rows,
        and cursor describe the SAME step.  ``async_``: snapshot on this
        thread (copy-on-write gather), serialize + commit on the
        checkpoint's background writer — the step resumes immediately.
        ``compiled``: the CompiledProgram of a mesh-sharded run — its
        state then checkpoints SHARD-wise (each device's addressable
        shards; no full-tensor host gather)."""
        if ps_ctx is not None:
            self._dense_ps_join_pending(ps_ctx, scope)
        comm = getattr(program, "_ps_communicator", None)
        if comm is not None:
            comm.flush()
        saver = ckpt.save_async if async_ else ckpt.save
        saver(program, scope, step=step, epoch=epoch,
              ps_client=getattr(program, "_ps_client", None),
              compiled=compiled)

    # ------------------------------------------------------------------
    # training control tower: the trainer's scrapeable surface
    # ------------------------------------------------------------------
    def start_train_admin(self, host: str = "127.0.0.1", port: int = 0):
        """Serve this trainer's observability surface over HTTP
        (``port=0`` = ephemeral; returns the bound ``(host, port)``):
        ``/metrics`` (Prometheus/OpenMetrics with exemplars),
        ``/trainz`` (ledger snapshot + last-N step table + watchdog
        state + checkpoint/resume history), ``/statusz``, ``/tracez``,
        ``/eventz``, ``/healthz``.  The same document shapes the fleet
        federation scraper consumes — register the returned address via
        ``FleetBalancer.add_scrape_target`` and the trainer shows up in
        the fleet pane next to the serving backends."""
        return _mon_train.start_train_admin(self, host=host, port=port)

    def stop_train_admin(self) -> None:
        _mon_train.stop_train_admin(self)

    @property
    def train_admin_address(self):
        srv = self._train_admin
        return srv.server_address if srv is not None else None

    def trainz(self):
        """The ``/trainz`` document (see ``monitor.train.trainz_doc``)."""
        return _mon_train.trainz_doc(self)

    def infer_from_dataset(self, program=None, dataset=None, scope=None,
                           thread=0, debug=False, fetch_list=None,
                           fetch_info=None, print_period=100):
        return self.train_from_dataset(
            program, dataset, scope, thread, debug, fetch_list, fetch_info, print_period
        )

    # ------------------------------------------------------------------
    def jit_cache_stats(self) -> Dict[str, int]:
        """Compile-cache accounting for this executor.

        ``misses`` counts newly-built jitted entries (each one is an XLA
        compile on its first dispatch); ``hits`` counts runs served by an
        existing entry; ``entries`` is the live cache size.  Serving's
        zero-recompiles-after-warmup assertion diffs ``misses`` across a
        workload (paddle_tpu/serving/server.py).  ``plan_*`` mirror the
        same accounting for the run-plan cache (the hoisted per-run block
        analysis), and ``dispatch_overhead_s`` accumulates the host-side
        seconds run() spent before each jitted dispatch.
        """
        return {
            "entries": len(self._cache),
            "hits": self._cache_stats["hits"],
            "misses": self._cache_stats["misses"],
            "jit_evictions": self._cache_stats["jit_evictions"],
            "plan_entries": len(self._plans),
            "plan_hits": self._cache_stats["plan_hits"],
            "plan_misses": self._cache_stats["plan_misses"],
            "plan_evictions": self._cache_stats["plan_evictions"],
            "dispatch_overhead_s": self._cache_stats["dispatch_overhead_s"],
            "ps_pull_overlap_s": self._cache_stats["ps_pull_overlap_s"],
            "ps_pull_wait_s": self._cache_stats["ps_pull_wait_s"],
        }

    # ------------------------------------------------------------------
    def close(self):
        self._cache.clear()
        self._plans.clear()


class AsyncExecutor:
    """Legacy filelist-driven trainer facade (reference:
    framework/async_executor.h:62 + executor_thread_worker.cc — pre-
    Trainer API that ran ExecutorThreadWorker threads over a Dataset).

    On TPU the compiled step IS the device worker, so this delegates to
    Executor.train_from_dataset over a Dataset built from the filelist —
    same API shape, one compiled module instead of thread workers.
    """

    def __init__(self, place=None):
        self._exe = Executor(place)

    def run(self, program, data_feed, filelist, thread_num=1, fetch_list=None,
            fetch_info=None, debug=False, mode="", scope=None):
        from paddle_tpu.fluid_dataset import DatasetFactory

        slots = getattr(data_feed, "slots", None)
        if not slots:
            raise ValueError(
                "AsyncExecutor needs a data_feed with a .slots list of the "
                "program's input Variables (DataFeedDesc analog)"
            )
        if isinstance(filelist, str):
            filelist = [filelist]
        dataset = DatasetFactory().create_dataset("InMemoryDataset")
        dataset.set_use_var(slots)
        dataset.set_filelist(list(filelist))
        if hasattr(dataset, "load_into_memory"):
            dataset.load_into_memory()
        return self._exe.train_from_dataset(
            program=program, dataset=dataset, scope=scope,
            fetch_list=fetch_list, fetch_info=fetch_info, debug=debug,
        )
