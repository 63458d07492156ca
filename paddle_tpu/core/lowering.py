"""Block -> single jitted XLA module.

This replaces the reference's interpreted hot loop
(paddle/fluid/framework/executor.cc:433-437 ``for op in ops: op->Run``)
with whole-block tracing: every op kernel is a pure JAX function, so the
entire block — forward, backward, and optimizer update ops — traces into
ONE XLA computation.  XLA then fuses elementwise chains into the matmuls
(MXU), assigns buffers (subsuming the reference's memory-reuse passes,
ir/memory_optimize_pass/), and schedules collectives.  State (persistable
vars) is threaded functionally and donated, giving in-place param updates.
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

from paddle_tpu import compile_cache
from paddle_tpu.core import registry
from paddle_tpu.core.registry import EMPTY_VAR_NAME

__all__ = ["lower_block", "trace_ops"]


def trace_ops(ops, env: Dict[str, Any], block=None) -> Dict[str, Any]:
    """Run (or trace) a sequence of Operators over an env of name->array.

    When an activation-sharding context is installed on this thread
    (``sharding.activations.tracing`` — the executor wraps a compiled
    program's block trace in one), every op output written to the env
    passes through the constrainer: matched intermediates get
    ``with_sharding_constraint`` applied in-trace, unmatched ones are
    left for GSPMD propagation."""
    from paddle_tpu.sharding import activations as _sh_act

    act = _sh_act.current()
    for op in ops:
        kernel = registry.get_kernel(op.type)
        ins: Dict[str, List[Any]] = {}
        for slot, names in op.inputs.items():
            vals = []
            for n in names:
                if n == EMPTY_VAR_NAME:
                    continue
                if n not in env:
                    raise KeyError(
                        "op %s input %s=%r not produced/fed (block %s)"
                        % (op.type, slot, n, getattr(block, "idx", "?"))
                    )
                vals.append(env[n])
            if vals:
                ins[slot] = vals
        outs = kernel(ins, op.attrs)
        if outs is None:
            continue
        for slot, names in op.outputs.items():
            vals = outs.get(slot)
            if vals is None:
                continue
            if not isinstance(vals, (list, tuple)):
                vals = [vals]
            for n, v in zip(names, vals):
                if n != EMPTY_VAR_NAME and v is not None:
                    env[n] = v if act is None else act.constrain(n, v)
    return env


def lower_block(
    block,
    feed_names: Sequence[str],
    fetch_names: Sequence[str],
    state_names: Sequence[str],
):
    """Build ``fn(state_dict, feed_dict) -> (fetch_list, new_state_dict)``.

    * ``state_names``: persistable vars read/written by the block (params,
      optimizer moments, LR...).  Returned updated so the caller can donate
      the old buffers.
    * Non-persistable intermediates never materialize outside XLA.
    """
    feed_names = tuple(feed_names)
    fetch_names = tuple(fetch_names)
    state_names = tuple(state_names)
    ops = list(block.ops)

    def fn(state: Dict[str, Any], feed: Dict[str, Any]):
        # the host-side cost of tracing the whole block through the op
        # kernels.  This body runs only while jax traces it (the first
        # dispatch of a cache key), never on a steady-state step, so it
        # always reads the clock: the seconds go to the build record as
        # stage ``trace`` of the dispatch's ``executor_step`` build (of
        # no build where jax re-traces a cached entry whose arguments
        # changed type: the program is named here), and the span nests
        # inside the build while a sink is live
        with compile_cache.build_stage("executor_step", "trace",
                                       span="lowering/trace_block",
                                       n_ops=len(ops)):
            env = dict(state)
            env.update(feed)
            trace_ops(ops, env, block)
            fetches = [env[n] for n in fetch_names]
            new_state = {n: env[n] for n in state_names if n in env}
        return fetches, new_state

    return fn
