"""Per-op microbenchmark harness (reference:
paddle/fluid/operators/benchmark/op_tester.cc + op_tester_config.cc —
config-driven single-op timing through the real runtime).

Each config entry declares (op type, input shapes/dtypes, attrs); the
harness builds a single-op Program, runs it through the Executor with
``steps=CHUNK, per_step_feed=True`` (CHUNK *distinct* stacked inputs per
jitted call — distinct feeds keep XLA from hoisting the pure op out of
the loop, and the chunking amortizes per-dispatch overhead exactly like
bench.py), and reports ms/op.

Usage:
    python bench_ops.py                  # time HOT_OPS, write OPBENCH.json
    python bench_ops.py --check          # compare against OPBENCH.json,
                                         # exit 1 on >25% regression
    python bench_ops.py --config f.json  # external config list

A chip bench: every op runs through ``Executor(TPUPlace(0))`` and the
run fails without the chip.

A checked-in OPBENCH.json is the regression baseline: re-run with
--check after touching an op kernel.
"""
import argparse
import json
import os
import sys
import time

import numpy as np

CHUNK = int(os.environ.get("OPBENCH_CHUNK", "10"))
REPEATS = int(os.environ.get("OPBENCH_REPEATS", "3"))
# --check threshold; override with OPBENCH_REGRESSION_PCT.  On a shared
# CPU box expect 30-50% run-to-run noise (raise the threshold or bump
# OPBENCH_REPEATS); TPU timings through the executor are far steadier.
REGRESSION_PCT = float(os.environ.get("OPBENCH_REGRESSION_PCT", "25"))

# (key, op_type, inputs {slot: [(name, shape, dtype)]}, attrs,
#  output slots — FIRST one is fetched/timed)
# Shapes follow the BERT/ResNet bench configs so regressions here map
# onto the model benches.
HOT_OPS = [
    ("matmul_768", "matmul",
     {"X": [("x", (128, 128, 768), "float32")],
      "Y": [("y", (768, 768), "float32")]}, {}, ["Out"]),
    ("mul_fc", "mul",
     {"X": [("x", (16384, 768), "float32")],
      "Y": [("y", (768, 3072), "float32")]}, {}, ["Out"]),
    ("conv2d_s2", "conv2d",
     {"Input": [("x", (64, 64, 56, 56), "float32")],
      "Filter": [("w", (128, 64, 3, 3), "float32")]},
     {"strides": [2, 2], "paddings": [1, 1]}, ["Output"]),
    ("softmax_attn", "softmax",
     {"X": [("x", (128, 12, 128, 128), "float32")]}, {"axis": -1}, ["Out"]),
    ("layer_norm", "layer_norm",
     {"X": [("x", (16384, 768), "float32")],
      "Scale": [("s", (768,), "float32")],
      "Bias": [("b", (768,), "float32")]},
     {"begin_norm_axis": 1}, ["Y", "Mean", "Variance"]),
    ("batch_norm_infer", "batch_norm",
     {"X": [("x", (32, 128, 56, 56), "float32")],
      "Scale": [("s", (128,), "float32")],
      "Bias": [("b", (128,), "float32")],
      "Mean": [("m", (128,), "float32")],
      "Variance": [("v", (128,), "float32")]},
     {"is_test": True, "epsilon": 1e-5},
     ["Y", "MeanOut", "VarianceOut", "SavedMean", "SavedVariance"]),
    ("relu_big", "relu",
     {"X": [("x", (32, 128, 56, 56), "float32")]}, {}, ["Out"]),
    ("elementwise_add", "elementwise_add",
     {"X": [("x", (128, 128, 768), "float32")],
      "Y": [("y", (128, 128, 768), "float32")]}, {"axis": -1}, ["Out"]),
    ("reduce_mean", "reduce_mean",
     {"X": [("x", (128, 128, 768), "float32")]},
     {"dim": [-1], "keep_dim": False}, ["Out"]),
    ("lookup_table", "lookup_table",
     {"W": [("w", (30522, 768), "float32")],
      "Ids": [("ids", (128, 128, 1), "int32")]}, {}, ["Out"]),
    ("top_k", "top_k",
     {"X": [("x", (256, 30522), "float32")]}, {"k": 4},
     ["Out", "Indices"]),
    ("transpose_attn", "transpose2",
     {"X": [("x", (128, 128, 12, 64), "float32")]},
     {"axis": [0, 2, 1, 3]}, ["Out", "XShape"]),
    ("softmax_ce", "softmax_with_cross_entropy",
     {"Logits": [("x", (512, 30522), "float32")],
      "Label": [("l", (512, 1), "int32")]}, {}, ["Loss", "Softmax"]),
    ("mean_grad_root", "mean",
     {"X": [("x", (16384, 768), "float32")]}, {}, ["Out"]),
    ("dropout_train", "dropout",
     {"X": [("x", (16384, 768), "float32")]},
     {"dropout_prob": 0.1, "is_test": False, "seed": 7,
      "dropout_implementation": "upscale_in_train"}, ["Out", "Mask"]),
]


def _build_program(op_type, inputs, attrs, out_slots):
    import paddle_tpu as fluid
    from paddle_tpu import framework
    from paddle_tpu import unique_name

    prog, startup = framework.Program(), framework.Program()
    feed_specs = []
    with framework.program_guard(prog, startup):
        block = prog.global_block()
        op_inputs = {}
        for slot, entries in inputs.items():
            names = []
            for name, shape, dtype in entries:
                block.create_var(name=name, shape=list(shape), dtype=dtype,
                                 stop_gradient=True, is_data=True)
                feed_specs.append((name, tuple(shape), dtype))
                names.append(name)
            op_inputs[slot] = names
        op_outputs = {}
        for slot in out_slots:
            n = unique_name.generate("opbench_" + slot.lower())
            block.create_var(name=n, dtype="float32")
            op_outputs[slot] = [n]
        block.append_op(type=op_type, inputs=op_inputs,
                        outputs=op_outputs, attrs=dict(attrs))
        fetch = op_outputs[out_slots[0]][0]
    return prog, feed_specs, fetch


def _rand(shape, dtype, rng):
    if np.issubdtype(np.dtype(dtype), np.integer):
        return rng.randint(0, 100, shape).astype(dtype)
    return rng.uniform(-1, 1, shape).astype(dtype)


def time_op(key, op_type, inputs, attrs, out_slots, chunk=CHUNK,
            repeats=REPEATS):
    """Returns (ms_per_op, output_shape_str)."""
    import jax

    import paddle_tpu as fluid

    prog, feed_specs, fetch = _build_program(op_type, inputs, attrs, out_slots)
    rng = np.random.RandomState(0)
    exe = fluid.Executor(fluid.TPUPlace(0))
    dev = exe._device()
    feed = {
        n: jax.device_put(_rand((chunk,) + shape, dtype, rng), dev)
        for n, shape, dtype in feed_specs
    }
    scope = fluid.Scope()
    with fluid.scope_guard(scope):
        run = lambda: exe.run(  # noqa: E731
            prog, feed=feed, fetch_list=[fetch], return_numpy=False,
            steps=chunk, per_step_feed=True)
        (out,) = run()  # compile + warm
        np.asarray(out)
        best = float("inf")
        for _ in range(repeats):
            t0 = time.perf_counter()
            (out,) = run()
            np.asarray(out)
            best = min(best, (time.perf_counter() - t0) / chunk)
    return best * 1e3, "x".join(str(s) for s in np.shape(np.asarray(out)))


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--check", action="store_true",
                    help="compare against OPBENCH.json; exit 1 on "
                         ">%d%% regression" % int(REGRESSION_PCT))
    ap.add_argument("--config", help="external JSON config "
                    "[{key, op, inputs:{slot:[[name,shape,dtype],...]}, attrs}]")
    ap.add_argument("--out", default=os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "OPBENCH.json"))
    args = ap.parse_args()

    if args.config:
        with open(args.config) as f:
            entries = [
                (e["key"], e["op"],
                 {s: [(n, tuple(sh), dt) for n, sh, dt in v]
                  for s, v in e["inputs"].items()},
                 e.get("attrs", {}), e.get("outs", ["Out"]))
                for e in json.load(f)
            ]
    else:
        entries = HOT_OPS

    import jax

    platform = jax.devices()[0].platform
    table, failures = {}, {}
    for key, op_type, inputs, attrs, out_slots in entries:
        try:
            ms, out_shape = time_op(key, op_type, inputs, attrs, out_slots)
            table[key] = round(ms, 4)
            print(json.dumps({"op": key, "type": op_type, "ms": round(ms, 4),
                              "out": out_shape, "platform": platform}),
                  flush=True)
        except Exception as e:  # noqa: BLE001 — a broken op must be visible
            failures[key] = str(e)[:200]
            print(json.dumps({"op": key, "type": op_type,
                              "error": str(e)[:200]}), flush=True)

    if args.check:
        if not os.path.exists(args.out):
            print("no baseline %s to check against" % args.out)
            sys.exit(2)
        with open(args.out) as f:
            base = json.load(f)
        base_table = base.get("table", {})
        if base.get("platform") != platform:
            print("baseline platform %r != current %r — timings are not "
                  "comparable; re-run without --check to regenerate"
                  % (base.get("platform"), platform))
            sys.exit(2)
        regressed = {
            k: (base_table[k], v)
            for k, v in table.items()
            if k in base_table
            and v > base_table[k] * (1 + REGRESSION_PCT / 100.0)
        }
        for k, (b, v) in sorted(regressed.items()):
            print("REGRESSION %s: %.4f ms -> %.4f ms (+%.0f%%)"
                  % (k, b, v, (v / b - 1) * 100))
        if failures:
            print("FAILED ops:", failures)
        sys.exit(1 if (regressed or failures) else 0)

    with open(args.out, "w") as f:
        json.dump({"platform": platform, "chunk": CHUNK,
                   "table": table, "failures": failures}, f, indent=1,
                  sort_keys=True)
        f.write("\n")
    print("wrote %s (%d ops, %d failures)"
          % (args.out, len(table), len(failures)))
    sys.exit(1 if failures else 0)


if __name__ == "__main__":
    main()
