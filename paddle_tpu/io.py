"""Checkpoint / persistence.

Reference: python/paddle/fluid/io.py — save_vars:109, save_persistables:477,
load_vars:529, load_persistables:718, save_inference_model:925,
load_inference_model:1116.  The reference emits ``save``/``load`` *ops*
into tiny programs and runs them through the executor
(operators/save_op.cc); on TPU a graph-side save would force a d2h
transfer anyway, so save/load here are host-side: values are pulled from
the Scope (device→host), written as one ``.npy`` per var plus a manifest,
and pushed back on load.  Format is versioned so checkpoints round-trip
across processes/hosts.
"""
from __future__ import annotations

import json
import os
from typing import Callable, List, Optional, Sequence

import numpy as np

from paddle_tpu import framework
from paddle_tpu.framework import Parameter, Program, Variable
from paddle_tpu.scope import global_scope

__all__ = [
    "save_vars",
    "save_params",
    "save_persistables",
    "load_vars",
    "load_params",
    "load_persistables",
    "save_inference_model",
    "load_inference_model",
    "save_program",
]

_MANIFEST = "__manifest__.json"
_MODEL_FILE = "__model__"


def _is_persistable(var: Variable) -> bool:
    return bool(var.persistable) and not var.is_data


def _collect(program: Program, predicate: Callable[[Variable], bool], vars=None) -> List[Variable]:
    if vars is not None:
        return [v if isinstance(v, Variable) else program.global_block().var(v) for v in vars]
    seen, out = set(), []
    for v in program.list_vars():
        if v.name not in seen and predicate(v):
            seen.add(v.name)
            out.append(v)
    return out


def _var_path(dirname: str, name: str) -> str:
    # var names may contain '/' from name_scope prefixes
    return os.path.join(dirname, name.replace("/", "%2F") + ".npy")


def save_vars(executor, dirname, main_program=None, vars=None, predicate=None,
              filename=None, scope=None):
    """reference: io.py:109.  ``filename`` packs everything into one .npz.
    ``scope`` (TPU-native extension): read values from this scope instead
    of the global one (the training checkpointer runs under caller-owned
    scopes)."""
    program = main_program or framework.default_main_program()
    scope = scope if scope is not None else global_scope()
    to_save = _collect(program, predicate or _is_persistable, vars)
    os.makedirs(dirname, exist_ok=True)
    manifest = {"format_version": 1, "vars": []}
    arrays = {}
    for v in to_save:
        val = scope.get(v.name)
        if val is None:
            raise RuntimeError("variable %r has no value in scope; run startup first" % v.name)
        arr = np.asarray(val)
        arrays[v.name] = arr
        manifest["vars"].append(
            {
                "name": v.name,
                "shape": list(arr.shape),
                "dtype": str(arr.dtype),
                "is_parameter": isinstance(v, Parameter),
            }
        )
    if filename is not None:
        np.savez(os.path.join(dirname, filename), **arrays)
        manifest["packed_file"] = filename
    else:
        for name, arr in arrays.items():
            np.save(_var_path(dirname, name), arr)
    with open(os.path.join(dirname, _MANIFEST), "w") as f:
        json.dump(manifest, f)


def save_params(executor, dirname, main_program=None, filename=None):
    return save_vars(
        executor, dirname, main_program,
        predicate=lambda v: isinstance(v, Parameter), filename=filename,
    )


def save_persistables(executor, dirname, main_program=None, filename=None,
                      scope=None):
    """reference: io.py:477 — params + optimizer state + LR etc."""
    return save_vars(executor, dirname, main_program,
                     predicate=_is_persistable, filename=filename, scope=scope)


def load_vars(executor, dirname, main_program=None, vars=None, predicate=None,
              filename=None, scope=None, to_device=True):
    """reference: io.py:529.  Loads into the current global scope (or
    ``scope`` when given).  ``to_device=False`` stages the values as
    HOST numpy arrays instead of pushing them to a device — a sharded
    endpoint's params are then first touched on device per shard by
    ``CompiledProgram._shard_inputs``, so a full-width device copy is
    never materialized (and the placement-time dtype cast of a composed
    bf16+sharded endpoint sees the cheap host value)."""
    program = main_program or framework.default_main_program()
    scope = scope if scope is not None else global_scope()
    import jax

    # values land on the EXECUTOR's device, not the process default:
    # replica i of a multi-replica server keeps its params on device i
    device = executor._device_cached() if executor is not None else None
    with open(os.path.join(dirname, _MANIFEST)) as f:
        manifest = json.load(f)
    packed = None
    if manifest.get("packed_file"):
        packed = np.load(os.path.join(dirname, manifest["packed_file"] + (".npz" if not manifest["packed_file"].endswith(".npz") else "")))
    wanted = None
    if vars is not None or predicate is not None:
        wanted = {v.name for v in _collect(program, predicate or _is_persistable, vars)}
    for entry in manifest["vars"]:
        name = entry["name"]
        if wanted is not None and name not in wanted:
            continue
        if packed is not None:
            arr = packed[name]
        else:
            arr = np.load(_var_path(dirname, name))
        var = program.global_block()._find_var_recursive(name)
        if var is not None and var.shape is not None:
            expect = tuple(s for s in var.shape)
            if tuple(arr.shape) != expect and -1 not in expect:
                raise ValueError(
                    "shape mismatch loading %r: checkpoint %s vs program %s"
                    % (name, arr.shape, expect)
                )
        scope.set(name, jax.device_put(arr, device) if to_device else arr)


def load_params(executor, dirname, main_program=None, filename=None):
    return load_vars(
        executor, dirname, main_program,
        predicate=lambda v: isinstance(v, Parameter), filename=filename,
    )


def load_persistables(executor, dirname, main_program=None, filename=None,
                      scope=None):
    return load_vars(executor, dirname, main_program,
                     predicate=_is_persistable, filename=filename, scope=scope)


# ---------------------------------------------------------------------------
# Inference model: prune to fetch targets + save (reference io.py:925)
# ---------------------------------------------------------------------------
def _prune_program(program: Program, feed_names: Sequence[str], fetch_names: Sequence[str]) -> Program:
    """Backward slice of block-0 ops from the fetch targets (the
    reference's Prune, framework/prune.cc)."""
    pruned = program.clone(for_test=True)
    block = pruned.global_block()
    needed = set(fetch_names)
    kept = []
    for op in reversed(block.ops):
        if any(n in needed for n in op.output_arg_names):
            kept.append(op)
            needed.update(op.input_arg_names)
    kept.reverse()
    block.ops = kept
    used = set(feed_names) | set(fetch_names)
    for op in kept:
        used.update(op.input_arg_names)
        used.update(op.output_arg_names)
    block.vars = {n: v for n, v in block.vars.items() if n in used}
    return pruned


def _save_model(dirname, program, feed_names, fetch_names, executor,
                model_filename=None, params_filename=None, sharding=None,
                precision=None, scope=None):
    """Shared save path for save_inference_model / save_program: the
    ``__model__`` JSON + persistable ``.npy`` layout consumed by both
    load_inference_model and the native C++ runtime (predictor.cc).
    ``sharding``: the partition-rule manifest (``{"mesh_axes": ...,
    "rules": ...}``) a sharded endpoint carries with its weights.
    ``precision``: the precision-policy manifest (``{"dtype": ...,
    "rtol": ...}``) a mixed-precision endpoint carries so every loader
    reconstructs the same low-precision variant.  ``scope``: read
    values from this scope instead of the current global one (the int8
    variant sub-model saves from its calibration scratch scope)."""
    os.makedirs(dirname, exist_ok=True)
    model = {
        "format_version": 1,
        "program": json.loads(program.to_json()),
        "feed_names": list(feed_names),
        "fetch_names": list(fetch_names),
    }
    if sharding is not None:
        model["sharding"] = sharding
    if precision is not None:
        model["precision"] = precision
    with open(os.path.join(dirname, model_filename or _MODEL_FILE), "w") as f:
        json.dump(model, f)
    save_vars(
        executor, dirname, program,
        predicate=_is_persistable,
        filename=params_filename,
        scope=scope,
    )
    return list(fetch_names)


def save_program(
    dirname,
    feeded_var_names: Sequence[str],
    target_vars: Sequence,
    executor,
    main_program: Optional[Program] = None,
    model_filename=None,
    params_filename=None,
):
    """Save a FULL program — including backward and optimizer ops — plus
    its persistable state in the same ``__model__`` JSON + ``.npy``
    format ``save_inference_model`` uses.  This is the export side of the
    pure-C++ training path (native/predictor.cc runs the saved train
    program's forward+grad+sgd ops without Python — the analog of the
    reference's demo_trainer.cc, which loads a serialized train program
    and runs it through the C++ executor).  Unlike
    ``save_inference_model`` nothing is pruned, so the optimizer state
    (learning rate var, accumulators) rides along."""
    program = main_program or framework.default_main_program()
    fetch_names = [t.name if isinstance(t, Variable) else str(t) for t in target_vars]
    return _save_model(dirname, program, feeded_var_names, fetch_names,
                       executor, model_filename, params_filename)


def _export_precision_variant(dirname, pruned, feed_names, fetch_names,
                              executor, policy):
    """Build + parity-gate a low-precision variant of ``pruned`` and
    return its manifest block (the ``precision`` entry of
    ``__model__``).

    ``policy``: ``{"dtype": "bf16"|"int8", "rtol": float?,
    "custom_white_list"/"custom_black_list": [...]?,
    "calibration": [feed dicts] (int8 only),
    "parity_feeds": [feed dicts]?}``.

    The parity gate runs the variant against the fp32 program on the
    parity feeds and REFUSES the export (typed
    ``PrecisionParityError``) when the measured max relative error
    exceeds the policy's rtol; the measured value rides the manifest as
    the endpoint's advertised accuracy bound.  An int8 variant is
    additionally materialized as a sub-model (frozen program + int8
    weights) under ``dirname/<variant_dir>`` — bf16 needs no extra
    weights on disk (the loader rebuilds the rewrite and casts params
    at placement time)."""
    from paddle_tpu.contrib.mixed_precision import inference as mp_inf
    from paddle_tpu.scope import global_scope, scope_guard

    policy = dict(policy)
    dtype = mp_inf.normalize_dtype(policy.pop("dtype", None) or "")
    if dtype == "fp32":
        raise mp_inf.PrecisionPolicyError(
            "precision_policy dtype 'fp32' is the base model — pass no "
            "policy instead")
    rtol = float(policy.pop("rtol", mp_inf.DEFAULT_RTOL[dtype]))
    parity_feeds = policy.pop("parity_feeds", None) or (
        mp_inf.synthetic_parity_feeds(pruned, feed_names))
    # every known key pops BEFORE dispatching on dtype, so validation
    # is symmetric: an unknown key is typed for both dtypes, and a
    # known key the chosen dtype cannot honor is refused loudly rather
    # than silently discarded (a user who passed calibration feeds must
    # not be left believing calibration happened)
    wl = policy.pop("custom_white_list", None)
    bl = policy.pop("custom_black_list", None)
    calibration = policy.pop("calibration", None)
    if policy:
        raise mp_inf.PrecisionPolicyError(
            "unknown precision_policy keys %s" % sorted(policy))
    manifest = {"dtype": dtype, "rtol": rtol}
    if dtype == "bf16":
        if calibration:
            raise mp_inf.PrecisionPolicyError(
                "'calibration' is an int8-only policy key — the bf16 "
                "rewrite needs no calibration data (drop the key, or "
                "export with dtype='int8')")
        variant, info = mp_inf.build_bf16_variant(
            pruned, fetch_names, custom_white_list=wl,
            custom_black_list=bl)
        vscope = mp_inf.variant_scope(
            variant, global_scope(), set(info["cast_params"]))
        if wl:
            manifest["custom_white_list"] = sorted(wl)
        if bl:
            manifest["custom_black_list"] = sorted(bl)
        manifest["cast_params"] = len(info["cast_params"])
    else:  # int8 via the contrib/quantize seam
        from paddle_tpu.contrib.quantize import calibrate_int8_program

        if wl or bl:
            raise mp_inf.PrecisionPolicyError(
                "custom_white_list/custom_black_list are bf16-only "
                "policy keys — the int8 path quantizes the slim pass's "
                "fixed op set")
        if not calibration:
            raise mp_inf.PrecisionPolicyError(
                "precision_policy dtype 'int8' needs calibration data "
                "(policy['calibration'] = [feed dicts] — "
                "representative batches)")
        variant, vscope = calibrate_int8_program(
            pruned, executor, calibration, fetch_names)
    # parity gate: fp32 vs variant on every parity feed, worst rel err
    worst = 0.0
    for feed in parity_feeds:
        ref = executor.run(pruned, feed=feed, fetch_list=list(fetch_names))
        with scope_guard(vscope):
            outs = executor.run(
                variant, feed=feed, fetch_list=list(fetch_names))
        worst = max(worst, mp_inf.max_rel_err(ref, outs))
    if worst > rtol:
        raise mp_inf.PrecisionParityError(
            "%s variant disagrees with fp32 beyond the policy bound: "
            "max_rel_err=%.4g > rtol=%.4g — loosen the policy rtol or "
            "blacklist the offending ops" % (dtype, worst, rtol))
    manifest["max_rel_err"] = float(worst)
    if dtype == "int8":
        # drop block vars nothing references any more (the freeze pass
        # leaves the original fp32 weights behind) so the sub-model
        # saves only the int8 state — the 4x disk/HBM win is the point
        block = variant.global_block()
        used = set(feed_names) | set(fetch_names)
        for op in block.ops:
            used.update(op.input_arg_names)
            used.update(op.output_arg_names)
        block.vars = {n: v for n, v in block.vars.items() if n in used}
        variant_dir = "__int8__"
        _save_model(os.path.join(dirname, variant_dir), variant,
                    feed_names, fetch_names, executor, scope=vscope)
        manifest["variant_dir"] = variant_dir
    return manifest


def save_inference_model(
    dirname,
    feeded_var_names: Sequence[str],
    target_vars: Sequence,
    executor,
    main_program: Optional[Program] = None,
    model_filename=None,
    params_filename=None,
    sharding_rules=None,
    sharding_mesh=None,
    precision_policy=None,
):
    """reference: io.py:925 — prune + save program and params.

    ``sharding_rules`` (TPU-native extension): a
    ``paddle_tpu.sharding.PartitionRules`` (or ``(regex, spec)`` list)
    embedded in the ``__model__`` manifest together with
    ``sharding_mesh`` (axis→size, e.g. ``{"tp": 2}``) so every loader —
    ``AnalysisPredictor``, a ``ServingProcess`` child — reconstructs
    the SAME model-parallel layout.  The rules are validated against
    the pruned program's persistables HERE (full coverage, rank
    checks), so a bad layout fails at export, not in a serving child.

    ``precision_policy`` (TPU-native extension): a per-endpoint
    low-precision serving policy (``{"dtype": "bf16"|"int8", "rtol":
    float, ...}`` — see :func:`_export_precision_variant`) embedded in
    the manifest after its variant PASSES the parity gate here, so
    every loader serves the same variant and the endpoint's accuracy
    bound is a measured, exported fact."""
    program = main_program or framework.default_main_program()
    fetch_names = [t.name if isinstance(t, Variable) else str(t) for t in target_vars]
    pruned = _prune_program(program, feeded_var_names, fetch_names)
    if precision_policy is not None and sharding_rules is not None:
        from paddle_tpu.contrib.mixed_precision.inference import (
            PrecisionPolicyError,
            normalize_dtype,
        )

        # bf16 composes: hoisting keeps param NAMES and shapes intact,
        # so the partition rules cover the variant's param set verbatim
        # and the loader applies the hoisted casts at shard-placement
        # time.  int8 does not: its variant is a separate frozen
        # sub-model whose quantized weights carry their own names.
        if normalize_dtype(precision_policy.get("dtype") or "") != "bf16":
            raise PrecisionPolicyError(
                "precision_policy dtype %r is not composable with "
                "sharding_rules on one endpoint — only the bf16 variant "
                "shares the base program's param set (hoisted casts); "
                "export the int8 model unsharded or drop one"
                % precision_policy.get("dtype"))
    precision = None
    if precision_policy is not None:
        precision = _export_precision_variant(
            dirname, pruned, list(feeded_var_names), fetch_names,
            executor, precision_policy)
    sharding = None
    if sharding_rules is not None:
        from paddle_tpu.sharding.rules import PartitionRules, ShardingRuleError

        if not isinstance(sharding_rules, PartitionRules):
            sharding_rules = PartitionRules(sharding_rules)
        # a TRAINING layout (sharding.train.TrainPartitionRules) unwraps
        # to its base serving rules: the pruned inference program has no
        # optimizer accumulators, and the manifest a predictor/fleet
        # reconstructs is exactly the serving layout — the train→export→
        # serve round-trip rides through unchanged
        sharding_rules = getattr(sharding_rules, "serving_rules",
                                 sharding_rules)
        # fail-at-export validation: every persistable resolves, the
        # mesh carries every axis the rules shard over, and every
        # sharded dim divides by its axes' size — a layout/mesh
        # mismatch must fail HERE, not in a serving child's load
        shapes = {
            v.name: tuple(v.shape or ())
            for v in pruned.list_vars() if _is_persistable(v)
        }
        axes = sharding_rules.axes()
        if sharding_mesh is not None:
            mesh_axes = dict(sharding_mesh)
            missing = sorted(axes - set(mesh_axes))
            if missing:
                raise ShardingRuleError(
                    "sharding_rules shard over axes %s which are not in "
                    "sharding_mesh %s" % (missing, mesh_axes))
            # coverage + rank + divisibility, one resolution pass
            sharding_rules.validate_shapes(shapes, mesh_axes)
        else:
            if len(axes) > 1:
                raise ShardingRuleError(
                    "sharding_rules span axes %s — pass sharding_mesh= "
                    "to fix their sizes (a loader cannot infer a "
                    "multi-axis mesh shape)" % sorted(axes))
            sharding_rules.match(shapes)  # coverage + rank
        sharding = {
            "mesh_axes": ({str(a): int(n)
                           for a, n in dict(sharding_mesh).items()}
                          if sharding_mesh else None),
            "rules": sharding_rules.to_manifest(),
        }
    if precision is not None and sharding is not None:
        # cross-link the two blocks so a doctored manifest carrying only
        # one of them is a TYPED load error, not a silently-degraded
        # endpoint (fp32-but-sharded, or bf16-but-replicated)
        precision["sharded"] = True
        sharding["precision_dtype"] = precision["dtype"]
    return _save_model(dirname, pruned, feeded_var_names, fetch_names,
                       executor, model_filename, params_filename,
                       sharding=sharding, precision=precision)


def load_inference_model(dirname, executor, model_filename=None, params_filename=None):
    """reference: io.py:1116 — returns (program, feed_names, fetch_vars).
    A saved sharding manifest rides back on the program as
    ``program._sharding_manifest``, a precision-policy manifest as
    ``program._precision_manifest`` (AnalysisPredictor consumes both)."""
    with open(os.path.join(dirname, model_filename or _MODEL_FILE)) as f:
        model = json.load(f)
    program = Program.from_json(json.dumps(model["program"]))
    if model.get("sharding"):
        program._sharding_manifest = model["sharding"]
    if model.get("precision"):
        program._precision_manifest = model["precision"]
    # sharded endpoints stage params host-side: the compiled dispatcher
    # device_puts each param with its NamedSharding on first use, so
    # device memory only ever holds per-shard (and, composed with a
    # bf16 policy, already-cast) bytes — never a full-width fp32 copy
    load_vars(executor, dirname, program, filename=params_filename,
              to_device=not model.get("sharding"))
    fetch_vars = [program.global_block().var(n) for n in model["fetch_names"]]
    return program, model["feed_names"], fetch_vars
