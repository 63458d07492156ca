"""Pipeline parallelism for fluid Programs (reference: PipelineOptimizer
optimizer.py:2665 cuts the program into sections run by SectionWorker
threads over blocking queues, framework/pipeline_trainer.cc,
section_worker.cc:141).

TPU-native design: the program's forward ops are CUT at the ``cut_list``
vars into K stages; the GPipe microbatch schedule is COMPILED — one
``lax.scan`` over M + K - 1 slots inside ``shard_map`` over the ``pp``
mesh axis, activations streaming stage-to-stage via ``lax.ppermute``
(the queue hop, but on ICI, inside the same XLA module as the compute).
Reverse-mode AD through the scan/ppermute yields the reference's 2K-1
backward sections automatically, and the optimizer update applies the
program optimizer's rule functionally.

Heterogeneous stages run under ``lax.switch`` on the device's pp
coordinate with a uniform padded activation buffer, so parameters are
replicated across the pp group (correct schedule + semantics; for
memory-scaling stage-sharded pipelining use the hybrid engine,
parallel/hybrid.py, where stages are homogeneous and stacked).
"""
from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence

import numpy as np

__all__ = ["build_pipeline_step", "PipelinePlanError", "propose_cut_vars"]


class PipelinePlanError(ValueError):
    """A pipeline stage plan that cannot run: the cut vars don't yield
    the stage count the mesh's ``pp`` axis expects, a cut leaves a
    stage with zero ops, or no single-crossing cut boundary exists for
    the requested stage count.  Raised at plan time with both counts
    named — never as a raw %-format assert or an XLA shape error."""


def _stage_ranges(ops, cut_names: Sequence[str]):
    """Split the op list at the producers of the cut vars.  Returns
    (ranges, ordered_cut_names) with cuts re-sorted into program order so
    boundary i always binds activation cut i-1."""
    bounds = {}
    for c in cut_names:
        idx = None
        for i, op in enumerate(ops):
            if c in op.output_arg_names:
                idx = i
        if idx is None:
            raise PipelinePlanError(
                "cut var %r is not produced by the program" % c)
        bounds[c] = idx + 1
    ordered = sorted(cut_names, key=lambda c: bounds[c])
    cuts = [bounds[c] for c in ordered]
    if len(set(cuts)) != len(cuts):
        raise PipelinePlanError(
            "cut vars %r share a producer boundary" % (cut_names,))
    starts = [0] + cuts
    ends = cuts + [len(ops)]
    ranges = []
    for i, (s, e) in enumerate(zip(starts, ends)):
        if e <= s:
            at = ("before cut var %r" % ordered[i] if i < len(ordered)
                  else "after cut var %r" % ordered[-1])
            raise PipelinePlanError(
                "stage %d of %d (%s) would contain zero ops — the plan's "
                "%d cut vars do not split the program's %d ops into "
                "non-empty stages"
                % (i, len(cut_names) + 1, at, len(cut_names), len(ops)))
        ranges.append(slice(s, e))
    return ranges, ordered


def propose_cut_vars(ops, n_stages: int, skip_names: Sequence[str] = ()
                     ) -> List[str]:
    """Pick ``n_stages - 1`` cut vars that split ``ops`` into balanced
    stages, each boundary crossed by exactly ONE live intermediate (the
    single activation the GPipe hand-off can carry).

    ``skip_names``: names that don't count as crossing activations —
    params and feeds (replicated onto every stage, available everywhere).
    Raises :class:`PipelinePlanError` when fewer than ``n_stages - 1``
    single-crossing boundaries exist (e.g. a program whose layers share
    a materialized attention bias: every boundary carries two live vars,
    so no single cut var can express it — build with fused attention)."""
    if n_stages < 2:
        raise PipelinePlanError(
            "pipeline needs at least 2 stages (got %d)" % n_stages)
    skip = set(skip_names)
    produced_at: Dict[str, int] = {}
    last_use: Dict[str, int] = {}
    for i, op in enumerate(ops):
        for n in op.input_arg_names:
            if n not in skip:
                last_use[n] = i
        for n in op.output_arg_names:
            if n not in skip:
                produced_at[n] = i
    # boundary b (between op b-1 and op b) is cuttable when exactly one
    # live non-param/non-feed var crosses it AND that var's (last)
    # producer is op b-1 — _stage_ranges cuts at the producer, so any
    # other producer position would induce a different boundary
    candidates: Dict[int, str] = {}
    for b in range(1, len(ops)):
        crossing = [n for n, p in produced_at.items()
                    if p < b and last_use.get(n, -1) >= b]
        if len(crossing) == 1 and produced_at[crossing[0]] == b - 1:
            candidates[b] = crossing[0]
    if len(candidates) < n_stages - 1:
        raise PipelinePlanError(
            "program has %d single-crossing boundaries but %d stages "
            "need %d cut vars — multi-var boundaries (e.g. a shared "
            "materialized attention bias crossing every layer) cannot "
            "be pipelined; rebuild the program so each stage boundary "
            "carries one activation" % (len(candidates), n_stages,
                                        n_stages - 1))
    chosen: List[int] = []
    for j in range(1, n_stages):
        ideal = j * len(ops) / float(n_stages)
        best = min((b for b in candidates if b not in chosen),
                   key=lambda b: abs(b - ideal))
        chosen.append(best)
    return [candidates[b] for b in sorted(chosen)]


def build_pipeline_step(program, loss_name: str, plan: Dict[str, Any], mesh):
    """Compile one pipelined training step.

    Returns (step, state_names): ``step(state, feed) -> (loss, new_state)``
    jitted over ``mesh`` (axis 'pp'); state = params (+ momentum slots).
    """
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P

    from paddle_tpu.core import lowering

    block = program.global_block()
    ops = [
        op for op in block.ops
        if op.attrs.get("op_role", "forward") in ("forward", "loss")
    ]
    M = int(plan["num_microbatches"])
    ranges, cut_names = _stage_ranges(ops, list(plan["cut_vars"]))
    K = len(ranges)
    pp_size = mesh.shape["pp"]
    if pp_size != K:
        raise PipelinePlanError(
            "op-stage plan has %d stages (%d cut vars) but the mesh's "
            "pp axis has %d devices — the schedule maps one stage per "
            "pp coordinate, so the counts must agree (add/remove cut "
            "vars or rebuild the mesh)" % (K, len(cut_names), pp_size)
        )

    param_names = sorted(p.name for p in program.all_parameters())
    trainable = {
        p.name for p in program.all_parameters() if getattr(p, "trainable", True)
    }
    feed_names = sorted(plan["feed_names"])

    # per-stage reads/writes to find each stage's params and feeds
    stage_ops = [ops[r] for r in ranges]

    def stage_trace(i):
        def fn(env):
            lowering.trace_ops(stage_ops[i], env, block)
            return env

        return fn

    # the program's own optimizer-update ops, replayed functionally on
    # the (state, grads) pair after AD — any registered optimizer works
    # in sections (reference: optimizer.py:2665 + section_worker.cc)
    update_descs = list(plan["update_descs"])
    grad_of = {d["inputs"]["Param"][0]: d["inputs"]["Grad"][0] for d in update_descs}
    aux_names = set()
    for d in update_descs:
        pname, gname = d["inputs"]["Param"][0], d["inputs"]["Grad"][0]
        for slot, names in d["inputs"].items():
            for nm in names:
                if nm not in (pname, gname):
                    aux_names.add(nm)
        for slot, names in d["outputs"].items():
            for nm in names:
                if nm not in (pname, gname):
                    aux_names.add(nm)
    aux_names -= set(param_names)

    def step(state: Dict[str, Any], feed: Dict[str, Any]):
        # shapes from the actual batch
        some = feed[feed_names[0]]
        B = some.shape[0]
        if B % M:
            raise ValueError("batch %d not divisible by %d microbatches" % (B, M))
        mb = B // M

        # microbatch stacks [M, mb, ...]
        feeds_mb = {
            n: jnp.reshape(feed[n], (M, mb) + tuple(feed[n].shape[1:]))
            for n in feed_names
        }

        params = {n: state[n] for n in param_names}
        # abstract-eval the full forward on one microbatch to size the
        # uniform activation buffer (cut var shapes differ per boundary)
        def full_fwd(params, fd):
            env = dict(params)
            env.update(fd)
            for i in range(K):
                stage_trace(i)(env)
            return {c: env[c] for c in cut_names}

        one_mb = {n: v[0] for n, v in feeds_mb.items()}
        cut_abstract = jax.eval_shape(full_fwd, params, one_mb)
        cut_shapes = {c: tuple(s.shape) for c, s in cut_abstract.items()}
        cut_dtypes = {c: s.dtype for c, s in cut_abstract.items()}
        flat_dims = {
            c: int(np.prod(shp[1:])) if len(shp) > 1 else 1
            for c, shp in cut_shapes.items()
        }
        maxd = max(flat_dims.values())
        # ring buffer dtype: wide enough for every boundary (bf16 cuts
        # travel as-is; mixing promotes)
        buf_dtype = jnp.result_type(*cut_dtypes.values())

        def run_local(params, feeds_mb):
            stage = jax.lax.axis_index("pp")

            def make_branch(i):
                def branch(act_in, mb_idx):
                    env = dict(params)
                    env.update({n: feeds_mb[n][mb_idx] for n in feed_names})
                    if i > 0:
                        cin = cut_names[i - 1]
                        shp = cut_shapes[cin]
                        env[cin] = (
                            act_in[:, : flat_dims[cin]]
                            .reshape(shp)
                            .astype(cut_dtypes[cin])
                        )
                    stage_trace(i)(env)
                    if i < K - 1:
                        cout = cut_names[i]
                        flat = env[cout].reshape(cut_shapes[cout][0], -1)
                        pad = maxd - flat.shape[1]
                        if pad:
                            flat = jnp.pad(flat, ((0, 0), (0, pad)))
                        return flat.astype(buf_dtype), jnp.zeros((), jnp.float32)
                    loss = env[loss_name].reshape(())
                    return jnp.zeros((mb, maxd), buf_dtype), loss.astype(jnp.float32)

                return branch

            branches = [make_branch(i) for i in range(K)]
            T = M + K - 1

            def body(carry, t):
                buf, loss_acc = carry
                mb_idx = jnp.clip(t - stage, 0, M - 1)
                out, loss_mb = jax.lax.switch(stage, branches, buf, mb_idx)
                valid = jnp.logical_and(t - stage >= 0, t - stage < M)
                loss_acc = loss_acc + jnp.where(
                    jnp.logical_and(valid, stage == K - 1), loss_mb, 0.0
                )
                # mask invalid-slot activations so garbage never reaches a
                # valid compute (defensive; the schedule already aligns)
                out = jnp.where(valid, out, 0.0)
                sent = jax.lax.ppermute(
                    out, "pp", [(i, (i + 1) % K) for i in range(K)]
                )
                return (sent, loss_acc), None

            init = (jnp.zeros((mb, maxd), buf_dtype), jnp.zeros((), jnp.float32))
            (_, loss_sum), _ = jax.lax.scan(body, init, jnp.arange(T))
            # PRE-psum local loss (nonzero on the last stage only).
            # Differentiating the replicated post-psum value would scale
            # grads by K: every device seeds cotangent 1 on an identical
            # total, and the joint SPMD reverse pass sums them.
            return loss_sum / M

        def local_step(state, feeds_mb):
            from paddle_tpu.core.registry import get_kernel

            params = {n: state[n] for n in param_names}
            loss_local, grads = jax.value_and_grad(run_local)(params, feeds_mb)
            loss = jax.lax.psum(loss_local, "pp")
            grads = {n: jax.lax.psum(g, "pp") for n, g in grads.items()}
            # weight decay (the program's regularization ops run on the
            # grad side, which AD-replay skips; reference:
            # regularizer.py append_regularization_ops grad += decay)
            for pname, (kind, coeff) in plan.get("decay", {}).items():
                if pname in grads:
                    p = params[pname]
                    extra = coeff * (jnp.sign(p) if kind == "l1" else p)
                    grads[pname] = grads[pname] + extra
            new_state = dict(state)
            for desc in update_descs:
                pname = desc["inputs"]["Param"][0]
                if pname not in trainable:
                    continue  # frozen params stay untouched (backward.py filter)
                gname = desc["inputs"]["Grad"][0]
                ins = {}
                for slot, names in desc["inputs"].items():
                    vals = []
                    for nm in names:
                        if nm == gname and slot == "Grad":
                            vals.append(grads[pname].astype(state[pname].dtype))
                        else:
                            vals.append(new_state[nm])
                    ins[slot] = vals
                outs = get_kernel(desc["type"])(ins, desc["attrs"])
                for slot, names in desc["outputs"].items():
                    val = outs.get(slot)
                    if val is None:
                        continue
                    vals = val if isinstance(val, (list, tuple)) else [val]
                    for nm, v in zip(names, vals):
                        if nm in new_state:
                            new_state[nm] = v.astype(new_state[nm].dtype)
            return loss, new_state

        smapped = jax.shard_map(
            local_step,
            mesh=mesh,
            in_specs=(P(), {n: P() for n in feeds_mb}),
            out_specs=(P(), P()),
            check_vma=False,
        )
        return smapped(state, feeds_mb)

    # state = params + every optimizer aux var (moments, beta pows, lr) —
    # all are startup-initialized program vars pulled from the scope
    state_names = list(param_names) + sorted(aux_names)
    return step, state_names
