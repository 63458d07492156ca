"""Every cell end to end at the tiny sizes of ``--rehearse-cpu``, and
the rule that a run with no TPU prints no result line."""
import json
import os
import subprocess
import sys

import pytest

from benchmark.lib import harness

CELLS = [w["name"] for w in harness.load_benchmark()["workloads"]]
RUN = [sys.executable, os.path.join(harness.BENCH, "run.py")]


def run_cell(args, root=harness.ROOT, timeout=600):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("XLA_FLAGS", None)
    return subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py")] + args,
        capture_output=True, text=True, timeout=timeout, env=env, cwd=root)


def last_json(stdout):
    return json.loads(stdout.strip().splitlines()[-1])


@pytest.mark.parametrize("cell", CELLS)
@pytest.mark.parametrize("trace", [0, 1])
def test_cell_rehearses(cell, trace):
    bench = harness.load_benchmark()
    p = run_cell(["--workload", cell, "--seed", str(2 ** 31 + 11),
                  "--seconds", "3", "--trace", str(trace), "--rehearse-cpu"])
    assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
    last = last_json(p.stdout)
    # never a line the driver could read as a chip result
    assert last["rehearsal"] is True and "metrics" not in last
    line = last["would_report"]
    assert set(line) >= {"correct", "attempted", "failed", "metrics", "device"}
    assert line["correct"] is True and line["failed"] == 0
    assert line["attempted"] > 0
    group = bench["per_layer" if trace else "end_to_end"]
    allowed = {m["name"]: m["unit"] for m in group
               if cell in m.get("workloads", [cell])}
    assert line["metrics"], "no metric reported"
    for name, m in line["metrics"].items():
        assert allowed[name] == m["unit"]
        assert isinstance(m["value"], float)
    if not trace:
        assert set(line["metrics"]) == set(allowed)
        assert line["metrics"]["setup_s"]["value"] > 0
    else:
        assert line["metrics"]["window_compiles"]["value"] == 0.0
    for tag in ("header ", "setup_split ", "checks ", "compiles "):
        assert any(l.startswith(tag) for l in p.stdout.splitlines()), tag


def test_no_tpu_means_no_result_line():
    p = run_cell(["--workload", CELLS[0], "--seed", "1", "--seconds", "1",
                  "--trace", "0"])
    assert p.returncode != 0
    for l in p.stdout.splitlines():
        assert not l.startswith("{"), l
    assert "does not fall back" in p.stderr


def test_unknown_workload_is_an_error():
    p = run_cell(["--workload", "no.such_cell", "--rehearse-cpu"])
    assert p.returncode != 0 and "no workload named" in p.stderr


def test_benchmark_alone_is_not_enough(tmp_path):
    """In a directory that holds only BENCHMARK.json and the files under
    ``paths`` there is no program to measure: non-zero, no result."""
    import shutil

    root = str(tmp_path / "bare")
    shutil.copytree(harness.BENCH, os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(os.path.join(harness.ROOT, "BENCHMARK.json"), root)
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    env["JAX_PLATFORMS"] = "cpu"
    p = subprocess.run(
        [sys.executable, os.path.join(root, "benchmark", "run.py"),
         "--workload", CELLS[0], "--seed", "1", "--seconds", "1",
         "--trace", "0", "--rehearse-cpu"],
        capture_output=True, text=True, timeout=300, env=env, cwd=root)
    assert p.returncode != 0
    assert not any(l.startswith("{") for l in p.stdout.splitlines())
