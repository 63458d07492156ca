"""One run of one benchmark cell, in one process, on the cell's chips.

    python3 benchmark/run.py --workload <config>.<traffic> --seed <n> \
        --seconds <s> --trace <0|1>

Loads the cell's data files (``BENCHMARK.json`` names them), hands them
to the configuration's family (``benchmark/families/<family>.py``),
which sets up, warms, measures for ``--seconds`` and checks its outputs
against the configuration's reference; then prints, as the LAST line of
stdout, one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` and, with ``--trace 1``, ``breakdown``.  With
``--trace 0`` the metrics are the cell's end-to-end metrics; with
``--trace 1`` the profiler runs over the window's last seconds and the
metrics are the cell's per-layer metrics, each from its own reader under
``benchmark/layer_metrics/``.

No TPU, or fewer chips than the cell asks for: exit 2, no result line.
``--rehearse-cpu`` runs the same code at the tiny sizes in the data
files' ``rehearse`` groups on the CPU; its last line says ``rehearsal``
and carries no ``metrics``: a rehearsal is never a chip result.
"""
import time

T_PROCESS_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def find(entries, name, what):
    for e in entries:
        if e["name"] == name:
            return e
    raise SystemExit("benchmark: no %s named %r in BENCHMARK.json (have: %s)"
                     % (what, name, ", ".join(e["name"] for e in entries)))


def metrics_of_cell(entries, cell_name):
    """The metrics a cell reports: those with no ``workloads`` key and
    those that list the cell."""
    return [m for m in entries
            if "workloads" not in m or cell_name in m["workloads"]]


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, default=None)
    ap.add_argument("--trace", type=int, default=0, choices=(0, 1))
    ap.add_argument("--rehearse-cpu", action="store_true",
                    help="tiny sizes on the CPU; NOT a chip run")
    args = ap.parse_args(argv)

    from benchmark.lib import harness
    bench = harness.load_benchmark(ROOT)
    cell = find(bench["workloads"], args.workload, "workload")
    conf = find(bench["configs"], cell["config"], "configuration")
    seconds = (args.seconds if args.seconds is not None
               else bench["run_seconds"])

    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"  # by argument only
        print("REHEARSAL: --rehearse-cpu given. NOT a chip run: tiny sizes "
              "on the CPU; no number below is a device number.", flush=True)
    cache_dir = harness.configure_jax(args.rehearse_cpu)
    import jax

    from benchmark.lib import peaks as peaks_lib
    from benchmark.lib import traffic
    from benchmark.lib.watch import CompileWatch

    devs = jax.devices()
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    if not args.rehearse_cpu and (device["platform"] != "tpu"
                                  or len(devs) < int(cell["chips"])):
        sys.stderr.write(
            "benchmark: cell %r needs %d TPU chip(s); jax reports %r. This "
            "benchmark does not fall back to another device.\n"
            % (cell["name"], cell["chips"], device))
        return 2
    harness.say("header", workload=cell["name"], seed=args.seed,
                seconds=seconds, trace=args.trace, device=device,
                rehearsal=args.rehearse_cpu, compile_cache_dir=cache_dir,
                jax=jax.__version__)

    cfg = harness.load_config(os.path.join(ROOT, conf["file"]),
                              args.rehearse_cpu)
    mix = traffic.load_mix(cell["traffic"], args.rehearse_cpu)
    ctx = harness.Context(
        cell=cell, cfg=cfg, mix=mix, seed=args.seed, seconds=seconds,
        trace=args.trace, rehearse=args.rehearse_cpu, device=devs[0],
        peaks=(None if args.rehearse_cpu
               else peaks_lib.peaks_for(device["kind"])),
        watch=CompileWatch(), t_process_start=T_PROCESS_START)
    with ctx.phase("import"):
        family = harness.load_py(os.path.join(
            harness.BENCH, "families", cfg["family"] + ".py"), cfg["family"])
    try:
        result = family.run(ctx)
        line = report(bench, ctx, result, device)
    finally:
        ctx.tracer.stop()
        ctx.tracer.cleanup()
    if args.rehearse_cpu:
        print(json.dumps({"rehearsal": True, "passed": bool(line["correct"]),
                          "would_report": line}), flush=True)
        return 0 if line["correct"] else 1
    print(json.dumps(line), flush=True)
    return 0


def report(bench, ctx, result, device):
    """Reduce the run to the result line (and the earlier lines)."""
    from benchmark.lib import harness, xplane

    cell = ctx.cell
    setup = dict(ctx.setup_split)
    compiles = ctx.window["compiles"]
    before = ctx.window["compile_mark"]
    harness.say("setup_split", setup_s=ctx.window["setup_s"],
                **{k: round(v, 3) for k, v in setup.items()})
    harness.say("compiles", in_setup=before, in_window=compiles)
    harness.say("checks", **result["checks"])

    stats = ctx.device.memory_stats() or {}
    harness.say("device_memory", **stats)
    # live arrays at their peak plus what the runtime set aside for the
    # loaded programs' temporaries (activations live there, not in
    # peak_bytes_in_use): the two are disjoint parts of the HBM
    device = dict(device, memory_peak_bytes=int(
        stats.get("peak_bytes_in_use", 0)
        + stats.get("peak_bytes_reserved", 0)))
    values = dict(result["end_to_end"], setup_s=ctx.window["setup_s"])
    line = {"correct": bool(result["correct"]),
            "attempted": int(result["attempted"]),
            "failed": int(result["failed"]), "metrics": {}, "device": device}

    if not ctx.tracer.enabled:
        for m in metrics_of_cell(bench["end_to_end"], cell["name"]):
            if m["name"] not in values:
                raise RuntimeError("cell %s did not measure %s"
                                   % (cell["name"], m["name"]))
            line["metrics"][m["name"]] = {"value": values[m["name"]],
                                          "unit": m["unit"]}
        return line

    trace = None
    path = ctx.tracer.xplane_path()
    if path is not None:
        trace = xplane.reduce(path)
        device["busy_s"] = trace.busy_s
        device["window_s"] = trace.window_s
        line["breakdown"] = {"device_ops": trace.top_ops(10),
                             "idle_gaps": trace.top_gaps(10)}
        harness.say("trace", file_bytes=os.path.getsize(path),
                    window_s=trace.window_s, busy_s=trace.busy_s,
                    modules=trace.top_modules(5))
    counters = dict(result["counters"], setup_compile_s=before["compile_s"],
                    window_compiles=compiles["compiles"],
                    memory_peak_bytes=device["memory_peak_bytes"],
                    setup_split=setup)
    info = {"name": cell["name"], "config": ctx.cfg, "traffic": ctx.mix,
            "peaks": ctx.peaks, "end_to_end": values}
    for m in metrics_of_cell(bench["per_layer"], cell["name"]):
        reader = harness.load_py(os.path.join(
            harness.BENCH, "layer_metrics", m["name"] + ".py"), m["name"])
        value = reader.read(trace, ctx.tracer.spans, counters, info)
        if value is not None:
            line["metrics"][m["name"]] = {"value": float(value),
                                          "unit": m["unit"]}
    return line


if __name__ == "__main__":
    sys.exit(main())
