"""PipelinePredictor: micro-batched GPipe inference over the ``pp`` axis.

The serving analog of ``pipeline_program.build_pipeline_step``: the
pruned INFERENCE program is cut into K stages at single-crossing
activation boundaries (``propose_cut_vars`` picks balanced ones when the
caller doesn't), and one request batch runs as M micro-batches through a
compiled GPipe schedule — ``lax.scan`` over ``M + K - 1`` slots inside
``shard_map`` over a ``{"pp": K}`` mesh, ``lax.switch`` on the device's
stage coordinate, activations streaming stage-to-stage via
``lax.ppermute``.  The ppermute IS the double buffer: each slot's
hand-off is issued against the buffer the previous slot filled, and XLA
overlaps the send with the next slot's compute.

Serving contract (PR 10's sharded-group shape): a PipelinePredictor is
ONE replica behind ``InferenceServer`` — it duck-types the
``AnalysisPredictor`` surface the server consumes (``run_padded``,
``jit_cache_stats``, ``get_input_names``, ``input_specs``) and adds
``pipeline_stats()``: stage counts, the executed schedule's structural
bubble ratio ``(K-1)/(M+K-1)``, and per-stage occupancy ``M/(M+K-1)`` —
what the ``serving_pipeline_bubble_ratio`` / per-stage occupancy gauges
publish.

Micro-batch selection: the configured ``num_microbatches`` is a CAP.
For each padded batch B the schedule uses the largest divisor of B that
is <= the cap (deterministic per bucket rung, so the warmed compiled
shape set stays closed — the zero-recompile contract).
"""
from __future__ import annotations

from typing import Any, Dict, List, Optional, Sequence, Tuple

import numpy as np

from paddle_tpu.parallel.pipeline_program import (
    PipelinePlanError,
    _stage_ranges,
    propose_cut_vars,
)

__all__ = ["PipelinePredictor"]


def _largest_divisor_leq(b: int, cap: int) -> int:
    for m in range(min(b, cap), 0, -1):
        if b % m == 0:
            return m
    return 1


class PipelinePredictor:
    """Load a saved inference model and serve it pipelined over ``pp``.

    ``model_dir``: a ``save_inference_model`` export.  ``n_stages``:
    pipeline depth K (devices used).  ``num_microbatches``: micro-batch
    cap M (see module docstring).  ``cut_vars``: explicit stage-boundary
    var names; default picks balanced single-crossing boundaries.
    """

    def __init__(self, model_dir: str, n_stages: int = 2,
                 num_microbatches: int = 4,
                 cut_vars: Optional[Sequence[str]] = None,
                 params_filename: Optional[str] = None):
        import jax
        from jax.sharding import NamedSharding, PartitionSpec as P

        import paddle_tpu as fluid
        from paddle_tpu import io
        from paddle_tpu.parallel import mesh as mesh_lib

        self.model_dir = model_dir
        self._scope = fluid.Scope()
        self._exe = fluid.Executor()  # the process default device
        with fluid.scope_guard(self._scope):
            self._program, self._feed_names, self._fetch_vars = (
                io.load_inference_model(model_dir, self._exe,
                                        params_filename=params_filename))
        self._fetch_names = [v.name for v in self._fetch_vars]
        block = self._program.global_block()
        self._block = block
        self._ops = list(block.ops)
        self._param_names = sorted(
            v.name for v in self._program.list_vars()
            if v.persistable and not v.is_data)
        K = int(n_stages)
        if cut_vars is None:
            cut_vars = propose_cut_vars(
                self._ops, K,
                skip_names=list(self._param_names) + list(self._feed_names))
        self._ranges, self._cut_names = _stage_ranges(self._ops,
                                                      list(cut_vars))
        if len(self._ranges) != K:
            raise PipelinePlanError(
                "op-stage plan has %d stages (%d cut vars) but "
                "n_stages=%d was requested — pass cut_vars matching the "
                "stage count" % (len(self._ranges), len(self._cut_names), K))
        self._K = K
        self._M = int(num_microbatches)
        if self._M < 1:
            raise PipelinePlanError(
                "num_microbatches must be >= 1 (got %d)" % self._M)
        self._mesh = mesh_lib.make_mesh({"pp": K})
        # params replicate across the pp group ONCE at construction —
        # heterogeneous stages under lax.switch need every stage's
        # params resident (pipeline_program.py's documented trade)
        rep = NamedSharding(self._mesh, P())
        self._params = {
            n: jax.device_put(np.asarray(self._scope.get(n)), rep)
            for n in self._param_names
        }
        self._cache: Dict[Any, Any] = {}
        self._stats = {"hits": 0, "misses": 0}
        self._last_schedule: Optional[Tuple[int, int]] = None  # (M_eff, T)

    # ------------------------------------------------------------------
    # predictor surface (duck-types AnalysisPredictor for the server)
    # ------------------------------------------------------------------
    def get_input_names(self) -> List[str]:
        return list(self._feed_names)

    def get_output_names(self) -> List[str]:
        return list(self._fetch_names)

    def input_specs(self) -> Dict[str, Any]:
        from paddle_tpu.core import types as core_types

        specs = {}
        for name in self._feed_names:
            var = self._block.var(name)
            shape = tuple(
                1 if int(d) < 0 else int(d) for d in (var.shape or ())[1:])
            specs[name] = (shape, core_types.np_dtype(var.dtype))
        return specs

    def jit_cache_stats(self) -> Dict[str, int]:
        return dict(self._stats)

    def pipeline_stats(self) -> Dict[str, Any]:
        """The serving-visible pipeline contract: stage count, cut vars,
        per-stage op counts, and the LAST executed schedule's structural
        bubble ratio (``(K-1)/(M+K-1)`` — the fraction of stage-slots
        the GPipe ramp leaves idle) + per-stage occupancy (``M/T``;
        every stage is busy exactly M of the T slots)."""
        K = self._K
        if self._last_schedule is not None:
            M, T = self._last_schedule
        else:
            M, T = self._M, self._M + K - 1
        return {
            "n_stages": K,
            "num_microbatches": self._M,
            "microbatches_last": M,
            "schedule_slots": T,
            "bubble_ratio": (K - 1) / float(T),
            "stage_occupancy": {str(i): M / float(T) for i in range(K)},
            "cut_vars": list(self._cut_names),
            "stage_ops": [r.stop - r.start for r in self._ranges],
        }

    # ------------------------------------------------------------------
    def _build(self, B: int, feed_sig):
        """Compile the GPipe executable for padded batch ``B``."""
        import jax
        import jax.numpy as jnp
        from jax.sharding import PartitionSpec as P

        from paddle_tpu.core import lowering
        from paddle_tpu.parallel import mesh as mesh_lib

        K = self._K
        M = _largest_divisor_leq(B, self._M)
        mb = B // M
        T = M + K - 1
        ops_ranges = self._ranges
        cut_names = self._cut_names
        feed_names = list(self._feed_names)
        fetch_names = list(self._fetch_names)
        block = self._block

        def stage_trace(i):
            def fn(env):
                lowering.trace_ops(self._ops[ops_ranges[i]], env, block)
                return env
            return fn

        def full_fwd(params, fd):
            env = dict(params)
            env.update(fd)
            for i in range(K):
                stage_trace(i)(env)
            return ({c: env[c] for c in cut_names},
                    [env[n] for n in fetch_names])

        one_mb = {
            n: jax.ShapeDtypeStruct((mb,) + tuple(shp[1:]), np.dtype(dt))
            for n, shp, dt in feed_sig
        }
        cut_abs, fetch_abs = jax.eval_shape(full_fwd, self._params, one_mb)
        cut_shapes = {c: tuple(s.shape) for c, s in cut_abs.items()}
        cut_dtypes = {c: s.dtype for c, s in cut_abs.items()}
        fetch_shapes = [tuple(s.shape) for s in fetch_abs]
        fetch_dtypes = [s.dtype for s in fetch_abs]
        flat_dims = {
            c: int(np.prod(shp[1:])) if len(shp) > 1 else 1
            for c, shp in cut_shapes.items()
        }
        maxd = max(flat_dims.values())
        buf_dtype = jnp.result_type(*cut_dtypes.values())

        def local_run(params, feeds_mb):
            stage = jax.lax.axis_index("pp")

            def make_branch(i):
                def branch(act_in, mb_idx):
                    env = dict(params)
                    env.update({n: feeds_mb[n][mb_idx] for n in feed_names})
                    if i > 0:
                        cin = cut_names[i - 1]
                        env[cin] = (
                            act_in[:, : flat_dims[cin]]
                            .reshape(cut_shapes[cin])
                            .astype(cut_dtypes[cin])
                        )
                    stage_trace(i)(env)
                    if i < K - 1:
                        cout = cut_names[i]
                        flat = env[cout].reshape(cut_shapes[cout][0], -1)
                        pad = maxd - flat.shape[1]
                        if pad:
                            flat = jnp.pad(flat, ((0, 0), (0, pad)))
                        fz = [jnp.zeros(s, d) for s, d in
                              zip(fetch_shapes, fetch_dtypes)]
                        return flat.astype(buf_dtype), fz
                    fs = [env[n].astype(d)
                          for n, d in zip(fetch_names, fetch_dtypes)]
                    return jnp.zeros((mb, maxd), buf_dtype), fs

                return branch

            branches = [make_branch(i) for i in range(K)]

            # hot-path: begin pipeline_handoff (the compiled GPipe slot
            # loop: switch-dispatched stage compute + the ppermute
            # hand-off, traced into every pipelined executable — pure
            # device ops, any host sync here would serialize the stages)
            def body(carry, t):
                buf, fetch_acc = carry
                mb_idx = jnp.clip(t - stage, 0, M - 1)
                act_out, fetches_mb = jax.lax.switch(
                    stage, branches, buf, mb_idx)
                valid = jnp.logical_and(t - stage >= 0, t - stage < M)
                write = jnp.logical_and(valid, stage == K - 1)
                new_acc = []
                for acc, f in zip(fetch_acc, fetches_mb):
                    upd = jnp.where(write, f, acc[mb_idx])
                    new_acc.append(acc.at[mb_idx].set(upd))
                act_out = jnp.where(valid, act_out, 0.0)
                # the double-buffered stage hand-off: this slot's send
                # overlaps the next slot's switch compute under XLA
                sent = jax.lax.ppermute(
                    act_out, "pp", [(i, (i + 1) % K) for i in range(K)])
                return (sent, tuple(new_acc)), None
            # hot-path: end pipeline_handoff

            init = (
                jnp.zeros((mb, maxd), buf_dtype),
                tuple(jnp.zeros((M,) + s, d)
                      for s, d in zip(fetch_shapes, fetch_dtypes)),
            )
            (_, fetch_acc), _ = jax.lax.scan(body, init, jnp.arange(T))
            # only the last stage wrote real values; psum replicates
            # them onto every pp rank (zeros elsewhere contribute 0)
            return [jax.lax.psum(a, "pp") for a in fetch_acc]

        smapped = jax.shard_map(
            local_run,
            mesh=self._mesh,
            in_specs=(P(), {n: P() for n, _, _ in feed_sig}),
            out_specs=[P() for _ in fetch_names],
            check_vma=False,
        )

        def run(params, feed):
            feeds_mb = {
                n: jnp.reshape(feed[n], (M, mb) + tuple(feed[n].shape[1:]))
                for n in feed_names
            }
            outs = smapped(params, feeds_mb)
            flat = []
            for o, shp in zip(outs, fetch_shapes):
                if len(shp) >= 1 and shp[0] == mb:
                    flat.append(o.reshape((B,) + tuple(shp[1:])))
                else:
                    flat.append(o[-1])  # non-batched fetch: last mb's value
            return flat

        return jax.jit(run), (M, T)

    # ------------------------------------------------------------------
    def run(self, feed, return_numpy: bool = True):
        """One pipelined dispatch over the full batch (micro-batched
        internally; see module docstring for the M_eff rule)."""
        if not isinstance(feed, dict):
            feed = dict(zip(self._feed_names, feed))
        feed = {n: np.asarray(v) for n, v in feed.items()}
        feed_sig = tuple(
            (n, tuple(feed[n].shape), np.dtype(feed[n].dtype).name)
            for n in self._feed_names)
        dims = {np.shape(feed[n])[0] for n in self._feed_names
                if np.ndim(feed[n])}
        if len(dims) != 1:
            raise ValueError(
                "pipelined run needs one consistent batch dim; got %s"
                % sorted(dims))
        (B,) = dims
        entry = self._cache.get(feed_sig)
        if entry is not None:
            self._stats["hits"] += 1
        else:
            self._stats["misses"] += 1
            entry = self._cache[feed_sig] = self._build(int(B), feed_sig)
        fn, schedule = entry
        self._last_schedule = schedule
        outs = fn(self._params, feed)
        if return_numpy:
            outs = [np.asarray(o) for o in outs]
        return outs

    def run_padded(self, feed, n_valid: Optional[int] = None,
                   return_numpy: bool = True):
        """Serving entry for pre-padded bucket feeds (the
        AnalysisPredictor contract: run the padded batch, slice outputs
        back to ``n_valid`` rows)."""
        if not isinstance(feed, dict):
            feed = dict(zip(self._feed_names, feed))
        dims = {np.shape(v)[0] if np.ndim(v) else None
                for v in feed.values()}
        dims.discard(None)
        if len(dims) != 1:
            raise ValueError(
                "run_padded needs one consistent padded leading dim; "
                "got %s" % sorted(dims))
        (padded,) = dims
        if n_valid is None:
            n_valid = padded
        if not 0 < n_valid <= padded:
            raise ValueError(
                "n_valid=%r out of range for padded batch %d"
                % (n_valid, padded))
        outs = self.run(feed, return_numpy=return_numpy)
        if n_valid == padded:
            return outs
        return [
            o[:n_valid] if np.ndim(o) >= 1 and np.shape(o)[0] == padded
            else o
            for o in outs
        ]

