"""Driver benchmark: one JSON line proving the framework's TPU perf story.

Headline metric = the flagship BERT-base pretraining step (BASELINE.json
flagship config; target >=50% MFU, so ``vs_baseline`` = achieved-MFU/0.50).
MFU accounting is the role-split formula in bench_bert.py (embedding
gathers and masked-only heads are not charged full 6ND — the naive rule
overstates MFU ~18% here).

The line also carries ``resnet50``/``nmt``/``deepfm`` blocks (all five
BASELINE.json configs; LeNet is the tests' parity config).  ResNet-50
ships with a measured calibration: ``pure_jax_step_ms`` times a
hand-written, framework-free JAX ResNet-50 step (bench_calibration.py)
in the same regime, and ``framework_overhead_pct`` is
(framework - pure)/pure.

Both paths run CHUNK training steps per jitted call (Executor
``steps=`` fori_loop): one dispatch and one d2h sync per CHUNK steps, as
a real input pipeline (reader.py double-buffering) would run.

One process per chip: in the default ``all`` mode this file is a pure
orchestrator that never imports jax, so it never holds the chip.  Every
stage runs in its own subprocess, one after another, under a hard
wall-clock budget, and the current line is re-printed (flushed) after
every stage — each line a superset of the previous.  A chip stage
constructs ``TPUPlace(0)`` and fails without the chip; a stage that
measures host-side behaviour is DECLARED a CPU stage in ``_stages()``
(``JAX_PLATFORMS=cpu`` in its env) — nothing falls back.  Any failed
stage makes the run exit non-zero.

Env knobs: BENCH_MODEL=<stage>|all (default all), BENCH_BATCH,
BENCH_STEPS, BENCH_CHUNK, BENCH_AMP=0, BENCH_LAYOUT,
BENCH_CALIBRATE=0 to skip the pure-JAX yardstick,
BENCH_TIMEOUT_<NAME>=secs to override a stage budget.
"""
import json
import os
import subprocess
import sys
import time

import numpy as np

BATCH = int(os.environ.get("BENCH_BATCH", "256"))
STEPS = int(os.environ.get("BENCH_STEPS", "20"))
CHUNK = int(os.environ.get("BENCH_CHUNK", "10"))
RESNET50_FWD_FLOPS_PER_IMG = 4.09e9


def run_resnet(batch=BATCH, steps=STEPS, chunk=CHUNK):
    import paddle_tpu as fluid
    from paddle_tpu import device_peaks, framework, models

    place = fluid.TPUPlace(0)  # a chip stage: no chip, no run

    use_amp = os.environ.get("BENCH_AMP", "1") == "1"
    # NHWC, bs256, chunk10 with fresh per-step batches: the regime
    # BENCH_r05.json's resnet50 block was recorded in.
    layout = os.environ.get("BENCH_LAYOUT", "NHWC").upper()
    if layout not in ("NCHW", "NHWC"):
        raise ValueError("BENCH_LAYOUT must be NCHW or NHWC (got %r)" % layout)
    img_shape = [3, 224, 224] if layout == "NCHW" else [224, 224, 3]
    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 42
    with framework.program_guard(prog, startup):
        img = fluid.layers.data("img", img_shape)
        lbl = fluid.layers.data("lbl", [1], dtype="int64")
        avg_loss, acc, _ = models.resnet50(img, lbl, data_format=layout)
        opt = fluid.optimizer.MomentumOptimizer(learning_rate=0.1, momentum=0.9)
        if use_amp:
            opt = fluid.contrib.mixed_precision.decorate(opt)
        opt.minimize(avg_loss)

    # CHUNK distinct batches stacked on a leading axis, one consumed per
    # fori_loop iteration (per_step_feed).  The
    # stack lives in HBM (chunk*batch*3*224*224*4B — 1.5 GB at
    # bs256/chunk10), so BENCH_FRESH=0 falls back to same-batch when a
    # big-batch probe would blow the budget.
    import bench_common

    fresh = bench_common.fresh_enabled()
    stack_bytes = chunk * batch * int(np.prod(img_shape)) * 4
    if fresh and stack_bytes > 6e9:
        fresh = False  # leave HBM for activations at bs512+/chunk40 probes
    rng = np.random.RandomState(0)
    n_b = chunk if fresh else 1
    imgs = rng.uniform(-1, 1, tuple([n_b, batch] + img_shape)).astype(np.float32)
    lbls = rng.randint(0, 1000, (n_b, batch, 1)).astype(np.int32)

    scope = fluid.Scope()
    exe = fluid.Executor(place)
    # pre-stage the batches on device: the benchmark measures chip compute,
    # assuming an overlapped input pipeline (reader.py double-buffering) —
    # not the host link bandwidth of this dev harness
    dev = exe._device()
    with fluid.scope_guard(scope):
        exe.run(startup)
        feed, feed1, run_kw = bench_common.stage_feeds(
            {"img": imgs, "lbl": lbls}, fresh, chunk, dev)
        # warmup (state avals settle after 2 steps -> 2 compiles), then
        # compile+warm the chunked (steps=CHUNK fori_loop) module
        for _ in range(2):
            (l,) = exe.run(prog, feed=feed1, fetch_list=[avg_loss], return_numpy=False)
            np.asarray(l)
        (l,) = exe.run(prog, feed=feed, fetch_list=[avg_loss], **run_kw)
        np.asarray(l)
        done = 0
        t0 = time.perf_counter()
        while done < steps:
            (l,) = exe.run(prog, feed=feed, fetch_list=[avg_loss], **run_kw)
            done += chunk
            lv = np.asarray(l)
        dt = time.perf_counter() - t0

    step_time = dt / done
    ips = batch / step_time
    flops_per_step = 3.0 * RESNET50_FWD_FLOPS_PER_IMG * batch
    mfu = (flops_per_step / step_time) / device_peaks.peak_flops(dev)
    out = {
        "images_per_sec": round(ips, 2),
        "layout": layout,
        "per_step_feed": fresh,
        "chunk": chunk,
        "step_time_ms": round(step_time * 1e3, 2),
        "mfu": round(mfu, 4),
        "batch": batch,
        "loss": float(lv),
    }
    if os.environ.get("BENCH_CALIBRATE", "1") == "1":
        _merge_cal(out, _measure_cal(batch, layout, fresh, chunk, steps))
    return out, dev.platform


def _measure_cal(batch, layout, fresh, chunk, steps=STEPS):
    """Pure-JAX ResNet-50 yardstick in the SAME regime as the framework
    run (layout, chunk, fresh-vs-same-batch).  Returns the cal dict."""
    import bench_calibration

    pure_ms, _ = bench_calibration.measure(
        batch=batch, steps=steps, chunk=chunk, layout=layout, fresh=fresh)
    return {"pure_jax_step_ms": round(pure_ms, 2),
            "calibration_chunk": chunk,
            "calibration_fresh": bool(fresh and chunk > 1),
            "layout": layout}


def _merge_cal(res, cal):
    """Attach the calibration yardstick to a framework resnet block.
    ``framework_overhead_pct`` only when BOTH the chunk and the
    fresh-batch regime match — a cross-regime pct would be skewed."""
    res["pure_jax_step_ms"] = cal["pure_jax_step_ms"]
    res["calibration_chunk"] = cal["calibration_chunk"]
    res["calibration_fresh"] = cal["calibration_fresh"]
    chunk = res.get("chunk", CHUNK)
    regimes_match = (
        cal["calibration_chunk"] == chunk
        and cal["calibration_fresh"] == bool(res.get("per_step_feed"))
    )
    if regimes_match:
        res["framework_overhead_pct"] = round(
            (res["step_time_ms"] - cal["pure_jax_step_ms"])
            / cal["pure_jax_step_ms"] * 100.0, 2)
    else:
        res["framework_overhead_note"] = (
            "calibration regime (chunk=%d fresh=%s) != framework regime "
            "(chunk=%d fresh=%s); overhead_pct omitted"
            % (cal["calibration_chunk"], cal["calibration_fresh"],
               chunk, bool(res.get("per_step_feed")))
        )
    return res


def _stages():
    """The stage table, in run order: ``(name, budget_s, env)``.

    ``budget_s`` is a hard wall-clock limit for the stage's subprocess
    (override with BENCH_TIMEOUT_<NAME>); the table sums to < 3600 s so
    a run whose every stage hangs still ends inside an hour.

    ``env`` is what DECLARES the platform.  The chip stages (bert,
    resnet, cal, nmt, deepfm) add nothing: they inherit the environment,
    construct ``TPUPlace(0)`` and fail when the chip is missing.  Every
    other stage measures host-side behaviour (dispatch overhead, wire
    tax, admission control, byte accounting on a sharded layout) at toy
    model sizes and is declared a CPU stage: ``JAX_PLATFORMS=cpu``,
    plus the virtual multi-device mesh where it shards.  The serving
    stages that launch child processes must be CPU stages — a parent
    that holds the chip cannot start children that need it.  A CPU
    stage's timings are harness numbers, never device metrics.
    """
    import bench_common  # jax-free

    cpu = {"JAX_PLATFORMS": "cpu"}
    mesh = bench_common.virtual_mesh_env()
    # the virtual device count must match the mesh the sparse stage
    # builds (BENCH_DEEPFM_SPARSE_MESH, default 8)
    sparse_mesh = bench_common.virtual_mesh_env(
        int(os.environ.get("BENCH_DEEPFM_SPARSE_MESH", "8")))
    # trimmed storm sizes keep the serving stages inside their budgets
    tr = {
        "BENCH_SERVING_THREADS": os.environ.get("BENCH_SERVING_THREADS", "4"),
        "BENCH_SERVING_REQUESTS": os.environ.get(
            "BENCH_SERVING_REQUESTS", "50"),
    }
    return [
        ("bert", 480, {}),
        ("resnet", 480, {"BENCH_CALIBRATE": "0"}),
        ("cal", 450, {}),  # env derived from the resnet result
        ("nmt", 450, {}),
        ("deepfm", 330, {}),
        ("deepfm_sparse", 120, sparse_mesh),
        ("dispatch_sharded", 90, mesh),
        ("dispatch_sharded_train", 60, mesh),
        ("checkpoint", 60, mesh),
        ("train_obs", 60, cpu),
        ("serving_wire", 120, {
            **cpu, **tr, "BENCH_SERVING_WIRE": "loopback"}),
        ("serving_overload", 90, {
            **cpu, "BENCH_SERVING_OVERLOAD": "1",
            "BENCH_SERVING_THREADS": tr["BENCH_SERVING_THREADS"],
            "BENCH_OVERLOAD_SECONDS": os.environ.get(
                "BENCH_OVERLOAD_SECONDS", "2")}),
        ("serving_decode", 210, {
            **cpu, "BENCH_SERVING_DECODE": "1",
            "BENCH_DECODE_REQUESTS": os.environ.get(
                "BENCH_DECODE_REQUESTS", "24")}),
        ("serving_sharded", 90, {
            **mesh, **tr, "BENCH_SERVING_SHARDED": "1"}),
        ("serving_precision", 150, {
            **mesh, **tr, "BENCH_SERVING_PRECISION": "1"}),
        ("serving_long_context", 150, {
            **mesh, "BENCH_SERVING_LONG_CONTEXT": "1"}),
        ("serving_observability", 90, {
            **cpu, **tr, "BENCH_SERVING_FLEET_OBS": "1"}),
    ]


# --metrics-out PATH (or $BENCH_METRICS_OUT): each subprocess stage dumps
# its own registry snapshot to PATH.<stage>.json, and the orchestrator
# folds them into ONE merged {"stages": {...}} document at PATH after
# every stage (so a killed driver still leaves the stages finished so
# far).  Resolved lazily — bench_common imports no jax.
_metrics_base = None


def _stage_metrics_path(model):
    return "%s.%s.json" % (_metrics_base, model)


def _merge_stage_metrics(names):
    merged = {}
    for name in names:
        p = _stage_metrics_path(name)
        if os.path.exists(p):
            try:
                with open(p) as f:
                    merged[name] = json.load(f)
            except ValueError:
                continue  # stage died mid-write; skip its partial dump
    with open(_metrics_base, "w") as f:
        json.dump({"stages": merged}, f, indent=2, sort_keys=True)
        f.write("\n")


def _run_sub(model, budget, extra_env):
    """Run one stage in a subprocess with a hard wall-clock budget and
    return its parsed JSON line, or an ``{"error": ...}`` block (which
    makes the whole run exit non-zero — see ``_orchestrate``)."""
    env = dict(os.environ, BENCH_MODEL=model)
    if _metrics_base:
        env["BENCH_METRICS_OUT"] = _stage_metrics_path(model)
    else:
        env.pop("BENCH_METRICS_OUT", None)
    env.update(extra_env)
    budget = int(os.environ.get("BENCH_TIMEOUT_%s" % model.upper(), budget))
    t0 = time.perf_counter()
    try:
        p = subprocess.run(
            [sys.executable, os.path.abspath(__file__)],
            env=env, capture_output=True, text=True, timeout=budget,
        )
    except subprocess.TimeoutExpired:
        return {"error": "timeout: %s exceeded %ds budget" % (model, budget)}
    if p.returncode == 0:
        for ln in reversed(p.stdout.strip().splitlines()):
            try:
                out = json.loads(ln)
            except ValueError:
                continue
            out["wall_s"] = round(time.perf_counter() - t0, 1)
            return out
    return {
        "error": "%s rc=%d; stderr tail: %s"
        % (model, p.returncode, (p.stderr or "")[-300:].replace("\n", " | "))
    }


def _orchestrate():
    """BENCH_MODEL=all: one subprocess per stage, in table order, with
    incremental emission.  BERT is the headline; every other stage rides
    as a block under its own name.  Returns the process exit code: 1
    when any stage failed."""
    global _metrics_base
    import bench_common  # jax-free

    stages = _stages()
    names = [name for name, _, _ in stages]
    _metrics_base = bench_common.metrics_out_path()
    if _metrics_base:
        # drop leftovers from a previous orchestrator run, or the merge
        # would present last run's stage snapshots as this run's data
        for name in names:
            try:
                os.remove(_stage_metrics_path(name))
            except OSError:
                pass

    def emit(line):
        # each emission is a superset of the previous, so whatever line
        # is last on stdout when the driver's clock runs out is complete
        # up to that stage
        print(json.dumps(line), flush=True)
        if _metrics_base:
            _merge_stage_metrics(names)

    line = {}
    failed = []
    for name, budget, env in stages:
        if name == "cal":
            res = line.get("resnet50", {})
            if ("error" in res
                    or os.environ.get("BENCH_CALIBRATE", "1") != "1"):
                continue
            # the yardstick runs in the regime the framework run reported
            cal = _run_sub("cal", budget, {
                "BENCH_BATCH": str(res["batch"]),
                "BENCH_LAYOUT": res["layout"],
                "BENCH_FRESH": "1" if res["per_step_feed"] else "0",
                "BENCH_CHUNK": str(res["chunk"]),
            })
            if "error" in cal:
                res["calibration_error"] = cal["error"]
                failed.append(name)
            else:
                cal.pop("wall_s", None)
                _merge_cal(res, cal)
        else:
            out = _run_sub(name, budget, env)
            if "error" in out:
                failed.append(name)
            if name == "bert":
                line = out if "error" not in out else {
                    "metric": "bench_failed", "value": 0, "unit": "",
                    "vs_baseline": 0.0, "bert_error": out["error"]}
            else:
                line["resnet50" if name == "resnet" else name] = out
        emit(line)
    if failed:
        sys.stderr.write("bench.py: failed stages: %s\n" % ", ".join(failed))
    return 1 if failed else 0


def _run_cal():
    """Subprocess worker for the pure-JAX ResNet-50 yardstick."""
    layout = os.environ.get("BENCH_LAYOUT", "NHWC").upper()
    fresh = os.environ.get("BENCH_FRESH", "1") == "1"
    return _measure_cal(BATCH, layout, fresh, CHUNK)


def main():
    model = os.environ.get("BENCH_MODEL", "all")
    if model == "all":
        sys.exit(_orchestrate())
    from paddle_tpu import compile_cache

    compile_cache.configure()
    if model == "resnet":
        res, platform = run_resnet()
        line = {
            "metric": "resnet50_images_per_sec_per_chip",
            "value": res["images_per_sec"],
            "unit": "images/sec",
            "vs_baseline": round(res["mfu"] / 0.50, 4),
            "platform": platform,
        }
        line.update(res)
    elif model == "bert":
        import bench_bert

        line = bench_bert.run()
    elif model == "nmt":
        import bench_nmt

        line = bench_nmt.run()
    elif model == "deepfm":
        import bench_deepfm

        line = bench_deepfm.run()
    elif model == "deepfm_sparse":
        import bench_deepfm

        line = bench_deepfm.run_sparse()
    elif model == "dispatch_sharded":
        import bench_dispatch

        line = bench_dispatch.run_sharded()
    elif model == "dispatch_sharded_train":
        import bench_dispatch

        line = bench_dispatch.run_sharded_train()
    elif model == "checkpoint":
        import bench_dispatch

        line = bench_dispatch.run_checkpoint()
    elif model == "train_obs":
        import bench_dispatch

        line = bench_dispatch.run_train_obs()
    elif model == "serving_wire":
        import bench_serving

        line = bench_serving.run_wire()
    elif model == "serving_overload":
        import bench_serving

        line = bench_serving.run_overload()
    elif model == "serving_decode":
        import bench_serving

        line = bench_serving.run_decode()
    elif model == "serving_sharded":
        import bench_serving

        line = bench_serving.run_sharded()
    elif model == "serving_precision":
        import bench_serving

        line = bench_serving.run_precision()
    elif model == "serving_long_context":
        import bench_serving

        line = bench_serving.run_long_context()
    elif model == "serving_observability":
        import bench_serving

        line = bench_serving.run_fleet_obs()
    elif model == "cal":
        line = _run_cal()
    else:
        raise SystemExit("unknown BENCH_MODEL %r" % model)
    import bench_common

    # one JSON line; dumps the registry snapshot too when --metrics-out /
    # $BENCH_METRICS_OUT is set (the orchestrator sets a per-stage path)
    bench_common.emit_result(line)


if __name__ == "__main__":
    main()
