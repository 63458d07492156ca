"""Activation sharding: trace-time ``with_sharding_constraint`` placement.

Param rules place persistable state at restage time (device_put with a
NamedSharding); activation rules have no array to place — they bind
INSIDE the traced computation.  This module is that binding: a
:class:`ActivationConstrainer` built by the CompiledProgram from its
rule set + mesh, installed as a thread-local context around the block
trace (executor wraps the lowered fn), and consulted by
``core.lowering.trace_ops`` for every op output it writes.  A matched
intermediate gets ``jax.lax.with_sharding_constraint`` applied; an
unmatched one is left for GSPMD propagation.

The constrainer also keeps the books: per-name full vs per-device
nbytes of every constrained intermediate, accumulated into a report the
predictor's ``sharding_stats()`` reads — the "activation bytes/device"
number long-context capacity math needs (a 1/sp fraction of the
unsharded footprint when the seq axis shards over sp).

Ops that want to SPECIALIZE under an activation layout (the fused
attention op dispatching to ring attention over the sp axis) read the
installed context via :func:`current` — trace-time only, never on the
steady dispatch path.
"""
from __future__ import annotations

import threading
from contextlib import contextmanager
from typing import Any, Dict, Optional

__all__ = ["ActivationConstrainer", "tracing", "current", "partitioned"]

_TLS = threading.local()


def current() -> Optional["ActivationConstrainer"]:
    """The ActivationConstrainer installed on this thread (trace time
    only), or None."""
    return getattr(_TLS, "ctx", None)


def partitioned() -> bool:
    """True while this thread traces the block of a CompiledProgram: the
    module will be partitioned by GSPMD over the program's mesh, which a
    Mosaic kernel cannot be (the fused attention op then keeps its XLA
    form)."""
    return getattr(_TLS, "partitioned", False)


@contextmanager
def tracing(ctx: Optional["ActivationConstrainer"]):
    """Install ``ctx`` (None: a compiled program without activation
    rules) for the duration of a CompiledProgram's block trace."""
    prev = getattr(_TLS, "ctx", None), getattr(_TLS, "partitioned", False)
    _TLS.ctx, _TLS.partitioned = ctx, True
    try:
        yield ctx
    finally:
        _TLS.ctx, _TLS.partitioned = prev


class ActivationConstrainer:
    """Applies a rule set's activation specs during tracing.

    ``rules``: a PartitionRules carrying activation rules; ``mesh``: the
    jax Mesh the specs bind to; ``axis_sizes``: {axis: size} for the
    divisibility guard.  Resolution is memoized per (name, shape tuple)
    — auto-generated intermediate names repeat across jit keys, and the
    regex scan must not re-run per trace.
    """

    def __init__(self, rules, mesh, axis_sizes: Dict[str, int]):
        self.rules = rules
        self.mesh = mesh
        self.axis_sizes = {str(a): int(n) for a, n in dict(axis_sizes).items()}
        # largest axis group any activation rule shards the seq dim over
        # — the divisor serving lengths must honor (len-ladder rounding)
        self._memo: Dict[Any, Any] = {}
        # name -> (full_nbytes, per_device_nbytes) for every constrained
        # intermediate of the LAST trace (one serve program traces the
        # same set per jit key; last-trace-wins keeps the report sized
        # to one executable, not the sum over warmup rungs)
        self.report: Dict[str, tuple] = {}
        self._trace_report: Dict[str, tuple] = {}

    # the sp axis name, if any activation rule shards over exactly one
    # axis named "sp" (the canonical layout) — what the fused attention
    # op asks for to pick the ring path
    @property
    def sp_axis(self) -> Optional[str]:
        from paddle_tpu.sharding.layouts import AXIS_SP

        if AXIS_SP in self.axis_sizes and self.axis_sizes[AXIS_SP] > 1:
            return AXIS_SP
        return None

    def begin_trace(self) -> None:
        self._trace_report = {}

    def end_trace(self) -> None:
        if self._trace_report:
            self.report = dict(self._trace_report)

    def _shard_factor(self, spec, shape) -> int:
        """Total device count the spec splits ``shape`` over, or 0 when
        a sharded dim is not divisible (→ skip the constraint)."""
        k = 1
        for dim, entry in zip(shape, tuple(spec)):
            if entry is None:
                continue
            axes = (entry,) if isinstance(entry, str) else tuple(entry)
            f = 1
            for a in axes:
                f *= self.axis_sizes.get(a, 1)
            if f > 1:
                if int(dim) % f:
                    return 0
                k *= f
        return k

    # hot-path: begin activation_constrain (runs under jit TRACING — the
    # first dispatch of a cache key, inside the executor's dispatch
    # region.  Pure spec resolution + with_sharding_constraint emission:
    # a blocking sync here would stall every novel-shape warmup)
    def constrain(self, name: str, value):
        """Apply the rule set's constraint for ``name`` to ``value`` (a
        traced array), or return it untouched."""
        shape = getattr(value, "shape", None)
        if shape is None:
            return value
        key = (name, tuple(shape))
        hit = self._memo.get(key, _MISS)
        if hit is _MISS:
            hit = None
            spec = self.rules.activation_spec_for(name, shape=shape)
            if spec is not None:
                k = self._shard_factor(spec, shape)
                if k > 1:
                    from jax.sharding import NamedSharding

                    hit = (NamedSharding(self.mesh, spec), k)
            self._memo[key] = hit
        if hit is None:
            return value
        sharding, k = hit
        import jax
        import numpy as np

        full = int(np.prod(shape)) * value.dtype.itemsize
        self._trace_report[name] = (full, full // k)
        return jax.lax.with_sharding_constraint(value, sharding)
    # hot-path: end activation_constrain

    # ------------------------------------------------------------------
    def stats(self) -> Dict[str, int]:
        """Aggregate bytes of the last traced program's constrained
        intermediates: {'activation_bytes_unsharded', 'activation_bytes
        _per_device', 'n_constrained'}."""
        full = sum(f for f, _ in self.report.values())
        per_dev = sum(p for _, p in self.report.values())
        return {
            "activation_bytes_unsharded": int(full),
            "activation_bytes_per_device": int(per_dev),
            "n_constrained": len(self.report),
        }


_MISS = object()
