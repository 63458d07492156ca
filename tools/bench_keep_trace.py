#!/usr/bin/env python
"""Run one benchmark cell with ``--trace 1`` and keep what the harness
throws away: every traced instruction with its self seconds, so that a
per-layer reader's shape needles can be checked against what the chip
really ran.

    chiprun -- python tools/bench_keep_trace.py --workload <cell> --seed <n>

Arguments are ``benchmark/run.py``'s.  The cell's normal output is
printed as always; ``chiprun_out/<cell>.instructions.json`` gets
``[[self seconds, instruction text], ...]``, longest first, and the
modules' run times.  A development aid: the driver never runs it.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)


def main():
    from benchmark import run as bench_run
    from benchmark.lib import harness, xplane

    cell = sys.argv[sys.argv.index("--workload") + 1]
    cleanup = harness.Tracer.cleanup

    def keep(self):
        path = self.xplane_path()
        if path is not None:
            trace = xplane.reduce(path)
            out = os.path.join(ROOT, "chiprun_out")
            os.makedirs(out, exist_ok=True)
            with open(os.path.join(out, cell + ".instructions.json"),
                      "w") as f:
                json.dump({
                    "busy_s": trace.busy_s, "window_s": trace.window_s,
                    "modules": trace.top_modules(10),
                    "instructions": sorted(
                        ([v, k[:600]] for k, v in
                         trace.instructions.items()), reverse=True)[:400]},
                    f, indent=0)
        cleanup(self)

    harness.Tracer.cleanup = keep
    if "--trace" not in sys.argv:
        sys.argv += ["--trace", "1"]
    return bench_run.main(sys.argv[1:])


if __name__ == "__main__":
    sys.exit(main())
