#!/usr/bin/env python
"""Metrics/docs parity guard: the registry and README must agree.

Every metric the codebase registers into ``paddle_tpu.monitor.REGISTRY``
must be listed in the README "Observability" metrics table, and every
table row must name a metric that still exists — an undocumented
counter is invisible to operators, and a stale doc row sends them
chasing a series that no longer scrapes.

The registered set comes from IMPORTING the registering modules and
reading the live registry (not from grepping source): serving's
counters are built from a dict comprehension (``"serving_%s_total" %
key``) that no static scan would resolve, and the registry is the
single source of truth anyway.

Wired into tier-1 via tests/test_metrics_docs.py; also runnable
directly::

    python tools/check_metrics_docs.py   # exits 1 and prints the diff
"""
from __future__ import annotations

import os
import re
import sys
from typing import Set, Tuple

# modules whose import registers metrics (the registry is populated at
# import time; an entry here that stops registering is harmless)
REGISTERING_MODULES = [
    "paddle_tpu.monitor",
    "paddle_tpu.monitor.flight",
    "paddle_tpu.monitor.events",
    "paddle_tpu.monitor.slo",
    "paddle_tpu.monitor.push",
    "paddle_tpu.executor",
    "paddle_tpu.fused_attention",
    "paddle_tpu.grouped_matmul",
    "paddle_tpu.decode_attention",
    "paddle_tpu.delta_hybrid_lm",
    "paddle_tpu.decoding",
    "paddle_tpu.reader",
    "paddle_tpu.inference",
    "paddle_tpu.serving.metrics",
    "paddle_tpu.serving.wire.metrics",
    "paddle_tpu.serving.decode",
    "paddle_tpu.faults.metrics",
    "paddle_tpu.sharding.metrics",
    "paddle_tpu.serving.embedding_cache",
    "paddle_tpu.serving.prefix_cache",
    "paddle_tpu.serving.speculative",
    "paddle_tpu.monitor.train",
]

# README table rows look like ``| `metric_name` | type | ... |``
_ROW_RE = re.compile(r"^\|\s*`([a-zA-Z_:][a-zA-Z0-9_:]*)`\s*\|")


def registered_metrics() -> Set[str]:
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import importlib

    for mod in REGISTERING_MODULES:
        importlib.import_module(mod)
    from paddle_tpu.monitor import REGISTRY

    return set(REGISTRY.snapshot())


def documented_metrics(readme_path: str) -> Set[str]:
    names = set()
    with open(readme_path) as f:
        for line in f:
            m = _ROW_RE.match(line.strip())
            if m:
                names.add(m.group(1))
    return names


def check(repo_root: str = None) -> Tuple[Set[str], Set[str]]:
    """Returns (undocumented, stale): metrics registered but missing
    from the README table, and table rows naming no live metric."""
    root = repo_root or os.path.dirname(
        os.path.dirname(os.path.abspath(__file__)))
    sys.path.insert(0, root)
    registered = registered_metrics()
    documented = documented_metrics(os.path.join(root, "README.md"))
    return registered - documented, documented - registered


def main() -> int:
    undocumented, stale = check()
    if not undocumented and not stale:
        print("check_metrics_docs: OK (%d metrics documented)"
              % len(registered_metrics()))
        return 0
    for name in sorted(undocumented):
        print("undocumented metric %r: add a row to README's "
              "Observability metrics table" % name, file=sys.stderr)
    for name in sorted(stale):
        print("stale README row %r: no such metric is registered"
              % name, file=sys.stderr)
    print("check_metrics_docs: %d problem(s)"
          % (len(undocumented) + len(stale)), file=sys.stderr)
    return 1


if __name__ == "__main__":
    sys.exit(main())
