"""Legacy quantize transpiler surface (reference: contrib/quantize/
quantize_transpiler.py QuantizeTranspiler) — delegates to the slim QAT
rewrite (contrib/slim/quantization.py), which is the maintained path.

``calibrate_int8_program`` is the post-training-quantization entry the
mixed-precision SERVING path rides (``save_inference_model``'s
``precision_policy={"dtype": "int8", ...}``): no QAT required — the
slim transform pass inserts moving-average activation quantizers, a
handful of calibration feeds settle their scales through the normal
executor, and the freeze pass folds real int8 weights.  The result is
a frozen inference program + a scratch scope holding its (int8) state,
ready to save as a precision variant sub-model.
"""
from __future__ import annotations

__all__ = ["QuantizeTranspiler", "calibrate_int8_program"]


def calibrate_int8_program(program, executor, calibration_feeds,
                           fetch_names, base_scope=None,
                           weight_bits=8, activation_bits=8,
                           moving_rate=0.5):
    """Post-training int8 calibration of a PRUNED inference program.

    ``program`` is cloned (never mutated); ``calibration_feeds`` is a
    non-empty sequence of feed dicts run through the transformed
    program so the moving-average activation scales converge on real
    data (representative batches, not the training set).  Weights are
    read from ``base_scope`` (default: the current global scope), COPIED
    into a scratch scope, and frozen to int8 there — the caller's fp32
    state is untouched.

    ``moving_rate`` defaults to 0.5 (not QAT's 0.9): post-training
    calibration sees a handful of batches, and the faster decay lets
    the activation scales converge on real magnitudes instead of
    staying anchored to the 0.001 init — with 0.9, even 3 calibration
    batches leave scales ~4x under-estimated and the parity gate
    (rightly) refuses the export.

    Returns ``(frozen_program, scratch_scope)``.
    """
    from paddle_tpu import framework
    from paddle_tpu.contrib.slim.quantization import (
        QuantizationFreezePass,
        QuantizationTransformPass,
    )
    from paddle_tpu.scope import Scope, global_scope, scope_guard

    calibration_feeds = list(calibration_feeds or ())
    if not calibration_feeds:
        raise ValueError(
            "int8 calibration needs at least one calibration feed "
            "(a representative batch per entry)")
    base_scope = base_scope if base_scope is not None else global_scope()
    work = program.clone()
    startup = framework.Program()
    QuantizationTransformPass(
        weight_bits=weight_bits, activation_bits=activation_bits,
        activation_quantize_type="moving_average_abs_max",
        moving_rate=moving_rate,
    ).apply(work, startup_program=startup)
    scratch = Scope()
    for v in work.list_vars():
        if not v.persistable or v.is_data:
            continue
        val = base_scope.get(v.name)
        if val is not None:
            scratch.set(v.name, val)
    with scope_guard(scratch):
        executor.run(startup)
        for feed in calibration_feeds:
            executor.run(work, feed=feed, fetch_list=list(fetch_names))
        QuantizationFreezePass(
            scratch, weight_bits=weight_bits).apply(work)
    return work, scratch


class QuantizeTranspiler:
    def __init__(self, weight_bits=8, activation_bits=8,
                 activation_quantize_type="abs_max",
                 weight_quantize_type="abs_max", window_size=10000):
        self.weight_bits = weight_bits
        self.activation_bits = activation_bits
        self.activation_quantize_type = activation_quantize_type
        self.weight_quantize_type = weight_quantize_type

    def training_transpile(self, program=None, startup_program=None):
        from paddle_tpu import framework
        from paddle_tpu.contrib.slim.quantization import (
            QuantizationTransformPass,
        )

        program = program or framework.default_main_program()
        QuantizationTransformPass(
            weight_bits=self.weight_bits,
            activation_bits=self.activation_bits,
            activation_quantize_type=self.activation_quantize_type,
            weight_quantize_type=self.weight_quantize_type,
        ).apply(program, startup_program=startup_program)
        return program

    def freeze_program(self, program, place=None, scope=None):
        """Fold trained fake-quant scales into real int8 weights
        (reference: quantize_transpiler.py freeze_program →
        slim QuantizationFreezePass, quantization_pass.py:541)."""
        from paddle_tpu.contrib.slim.quantization import (
            QuantizationFreezePass,
        )
        from paddle_tpu.scope import global_scope

        scope = scope or global_scope()
        QuantizationFreezePass(
            scope, place, weight_bits=self.weight_bits
        ).apply(program)
        return program
