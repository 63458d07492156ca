"""Finds the knee of an open-loop cell ONCE, when the cell is defined:
the same family, configuration and traffic mix run at a list of rates in
one process, one after another, each with its own ramp and a short
window.  Prints one line per rate; the cell's ``rate_per_s`` is then set
by hand to about four fifths of the highest rate the system sustained
(completions keep up with arrivals, the queue is empty at the close).

    python3 benchmark/sweep.py --workload gpt1_117m.chat_steady \
        --rates 30,36,40,44,48 --seconds 20

Not part of a run of the benchmark: the driver never calls it.
"""
import argparse
import json
import os
import sys
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated requests per second")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=1)
    ap.add_argument("--rehearse-cpu", action="store_true")
    args = ap.parse_args(argv)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    import gc

    from benchmark.lib import harness
    harness.configure_jax(args.rehearse_cpu)
    import jax
    import numpy as np

    from benchmark import run as runner
    from benchmark.lib import peaks, traffic
    from benchmark.lib.watch import CompileWatch

    bench = harness.load_benchmark(ROOT)
    cell = runner.find(bench["workloads"], args.workload, "workload")
    conf = runner.find(bench["configs"], cell["config"], "configuration")
    dev = jax.devices()[0]
    if not args.rehearse_cpu and dev.platform != "tpu":
        sys.stderr.write("sweep: needs a TPU\n")
        return 2
    cfg = harness.load_config(os.path.join(ROOT, conf["file"]),
                              args.rehearse_cpu)
    family = harness.load_py(os.path.join(
        harness.BENCH, "families", cfg["family"] + ".py"), cfg["family"])
    watch = CompileWatch()
    for rate in [float(r) for r in args.rates.split(",")]:
        mix = traffic.load_mix(cell["traffic"], args.rehearse_cpu)
        if mix["kind"] != "open_loop":
            raise SystemExit("sweep: %s is not an open-loop mix"
                             % cell["traffic"])
        mix["rate_per_s"] = rate
        ctx = harness.Context(
            cell=cell, cfg=cfg, mix=mix, seed=args.seed,
            seconds=args.seconds, trace=0, rehearse=args.rehearse_cpu,
            device=dev, peaks=None if args.rehearse_cpu
            else peaks.peaks_for(dev.device_kind), watch=watch,
            t_process_start=time.perf_counter())
        res = family.run(ctx)
        c = res["counters"]
        p = lambda v, q: float(np.percentile(v, q)) if len(v) else None
        print("SWEEP " + json.dumps({
            "offered_per_s": rate,
            "ended_per_s": res["attempted"] / c["window_s"],
            "failed": res["failed"], "correct": res["correct"],
            "attainment": c["attainment"],
            "in_flight_at_close": c["in_flight_at_close"],
            "queue_depth_at_close": c["queue_depth_at_close"],
            "ttft_p50_ms": p(c["ttft_ms"], 50),
            "ttft_p95_ms": p(c["ttft_ms"], 95),
            "tpot_p50_ms": p(c["tpot_ms"], 50),
            "tpot_p95_ms": p(c["tpot_ms"], 95),
            "gen_late_p95_ms": p(c["gen_late_ms"], 95),
            "tokens_per_s": res["end_to_end"]["serve_tokens_per_s"],
            "tick_ms": c["window_s"] * 1e3 / c["ticks"] if c["ticks"] else None,
        }), flush=True)
        # the server and its 12 GB pool must be gone before the next one
        del res, ctx, c
        gc.unfreeze()
        gc.collect()
    return 0


if __name__ == "__main__":
    sys.exit(main())
