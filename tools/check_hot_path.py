#!/usr/bin/env python
"""Static hot-path guard: no blocking host-device syncs in annotated regions.

The dispatch fast path's contract is that a steady-state step performs
NO blocking device synchronization on the host thread — `np.asarray` of
a device array, `jax.device_get`, `.block_until_ready()`, or a sleep
anywhere inside the annotated regions would serialize the pipeline the
whole PR series built (run-plan cache -> sharded prefetch -> async
dispatch -> deferred d2h).  Those regressions are easy to introduce and
invisible in unit tests (everything still passes, just slower), so this
checker fails them statically.

Regions are marked in the source:

    # hot-path: begin <label>
    ...code...
    # hot-path: end <label>

A line that legitimately needs a flagged token (e.g. `np.asarray` on a
HOST value) carries an inline waiver comment: `# hot-ok: <reason>`.

Wired into tier-1 via tests/test_hot_path.py; also runnable directly:

    python tools/check_hot_path.py   # exits 1 and prints violations
"""
from __future__ import annotations

import os
import re
import sys
from typing import List, Tuple

# files owning annotated hot regions (repo-root relative).  The wire
# files guard the cross-host request path: codec encode/decode, the
# client POST, and the balancer's acquire->exchange->release dispatch
# must stay free of blocking-sync tokens (sleeps belong only in the
# accept/health/span-merge loops OUTSIDE the regions).
CHECKED_FILES = [
    "paddle_tpu/executor.py",
    "paddle_tpu/serving/server.py",
    "paddle_tpu/serving/admission.py",
    "paddle_tpu/reader.py",
    "paddle_tpu/parallel/compiled_program.py",
    "paddle_tpu/serving/wire/codec.py",
    "paddle_tpu/serving/wire/http.py",
    "paddle_tpu/serving/wire/client.py",
    "paddle_tpu/serving/wire/fleet.py",
    "paddle_tpu/serving/decode.py",
    "paddle_tpu/serving/kv_pool.py",
    # partition-rule resolution is warmup-time only (memoized into
    # NamedShardings before steady state) — these files must never grow
    # a blocking sync inside an annotated region, and keeping them on
    # the list means any future hot-path region added here is guarded
    "paddle_tpu/sharding/rules.py",
    "paddle_tpu/sharding/layouts.py",
    # sharded-training resolution + restage accounting: spec inheritance
    # runs on the compiled program's memo-miss path inside the dispatch
    # region, and the state-bytes pass reads shard METADATA only — a
    # blocking sync creeping into either would stall every train step
    "paddle_tpu/sharding/train.py",
    # the precision-variant dispatch (one dict lookup per run) is a hot
    # region in inference.py; the rewrite/cast/calibration passes run at
    # load/export time only.  autotune.py is pure re-plan arithmetic on
    # the tuner thread — keeping both listed guards against a future
    # blocking sync (or a re-plan) creeping into the request path.
    "paddle_tpu/inference.py",
    "paddle_tpu/serving/autotune.py",
    # the sparse scale-out runtime: the mesh-table lookup/push dispatch
    # (device-side, async by construction) and the embedding cache's
    # probe loop both sit inside the per-batch prefetch — a blocking
    # sync in either serializes every DeepFM step/request
    "paddle_tpu/sharding/sparse.py",
    "paddle_tpu/serving/embedding_cache.py",
    # decode tier 2: the prefix-cache probe runs on the scheduler thread
    # between ticks (prefix_probe — pure host hashing, no device syncs),
    # and the speculative round dispatch is one warmed-executable call
    # (spec_verify) — a blocking sync in either stalls every decode tick
    "paddle_tpu/serving/prefix_cache.py",
    "paddle_tpu/serving/speculative.py",
    # int8 quantize/dequantize helpers run INSIDE jitted step/verify
    # fns and the mesh-table push kernels — any host sync here would
    # land in every decode tick and every sparse train step
    "paddle_tpu/quant.py",
    # long-context serving: the ring-attention K/V rotation body and
    # the GPipe stage hand-off are traced into every sp/pipelined
    # serving executable (ring_step, pipeline_handoff), and the
    # activation constrainer runs per-op-output inside the block trace
    # (activation_constrain) — a host sync in any of them lands inside
    # every long-context warmup trace or compiled schedule
    "paddle_tpu/parallel/ring_attention.py",
    "paddle_tpu/parallel/pipeline_predictor.py",
    "paddle_tpu/sharding/activations.py",
    # the training control tower's ledger charge/window calls run inside
    # every armed train step (ledger-charge) — a blocking sync or event
    # emit creeping in would tax exactly the path the ledger measures
    "paddle_tpu/monitor/train.py",
    # the training attention kernels and their XLA form are traced into
    # every BERT / LM step program: nothing here may ever touch the host
    "paddle_tpu/fused_attention.py",
]

# blocking-sync tokens (substring match on code, not comments)
BANNED_TOKENS = [
    "jax.device_get",
    ".block_until_ready",
    "np.asarray",
    "np.array(",
    "time.sleep",
    ".copy_to_host",
    # observability background work: the SLO evaluator and the
    # federation scraper are background-thread-only by contract — a
    # registry-wide snapshot/evaluate or a child-admin HTTP fetch
    # inside a request hot region would trade tail latency for a
    # dashboard.  (events are transition-rate, also never hot-path.)
    "evaluate_once",
    "_scrape_pass",
    "scrape_once",
    "_scrape_backend",
    ".get_text(",
    "federated_metrics",
    "federated_statusz",
    "federated_tracez",
    "federated_eventz",
    "_events.emit",
    "events.emit",
    ".sloz(",
    ".eventz(",
]

_BEGIN = re.compile(r"#\s*hot-path:\s*begin\b\s*(?P<label>[\w./-]*)")
_END = re.compile(r"#\s*hot-path:\s*end\b")
_WAIVER = "# hot-ok:"


def check_source(text: str, path: str = "<string>") -> List[Tuple[str, int, str, str]]:
    """Return [(path, lineno, token, line)] violations in ``text``."""
    violations = []
    label = None
    opened_at = 0
    for i, line in enumerate(text.splitlines(), start=1):
        m = _BEGIN.search(line)
        if m:
            if label is not None:
                violations.append(
                    (path, i, "<nesting>",
                     "hot-path region %r opened inside %r (line %d)"
                     % (m.group("label"), label, opened_at)))
            label = m.group("label") or "<anonymous>"
            opened_at = i
            continue
        if _END.search(line):
            if label is None:
                violations.append(
                    (path, i, "<orphan-end>", line.strip()))
            label = None
            continue
        if label is None:
            continue
        code = line.split("#", 1)[0]
        if _WAIVER in line:
            continue
        for token in BANNED_TOKENS:
            if token in code:
                violations.append((path, i, token, line.strip()))
    if label is not None:
        violations.append(
            (path, opened_at, "<unclosed>",
             "hot-path region %r never closed" % label))
    return violations


def check_files(repo_root: str = None) -> List[Tuple[str, int, str, str]]:
    root = repo_root or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    out = []
    for rel in CHECKED_FILES:
        path = os.path.join(root, rel)
        with open(path) as f:
            out.extend(check_source(f.read(), rel))
    return out


def main() -> int:
    violations = check_files()
    if not violations:
        n = 0
        for rel in CHECKED_FILES:
            root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
            with open(os.path.join(root, rel)) as f:
                n += sum(1 for ln in f if _BEGIN.search(ln))
        print("check_hot_path: OK (%d regions across %d files clean)"
              % (n, len(CHECKED_FILES)))
        return 0
    for path, lineno, token, line in violations:
        print("%s:%d: blocking call %r in hot-path region: %s"
              % (path, lineno, token, line), file=sys.stderr)
    print("check_hot_path: %d violation(s)" % len(violations), file=sys.stderr)
    return 1


if __name__ == "__main__":
    sys.exit(main())
