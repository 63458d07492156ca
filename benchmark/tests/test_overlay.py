"""A later PR adds a cell by adding FILES and ENTRIES only.  This test
does exactly that in a temporary copy of the benchmark: a configuration,
a traffic mix and a per-layer metric arrive as new files, BENCHMARK.json
gains entries, no file that was there is edited, and the new cell runs.
"""
import hashlib
import json
import os
import shutil
import subprocess
import sys

from benchmark.lib import harness


def digest(root):
    out = {}
    for d, dirs, files in os.walk(os.path.join(root, "benchmark")):
        dirs[:] = [x for x in dirs if x != "__pycache__"]
        for f in files:
            p = os.path.join(d, f)
            out[os.path.relpath(p, root)] = hashlib.sha256(
                open(p, "rb").read()).hexdigest()
    return out


def test_new_cell_from_files_only(tmp_path):
    root = str(tmp_path / "checkout")
    shutil.copytree(os.path.join(harness.ROOT, "benchmark"),
                    os.path.join(root, "benchmark"),
                    ignore=shutil.ignore_patterns("__pycache__"))
    before = digest(root)

    # a configuration: the same family at another size, its reference
    # beside it
    cfg = json.load(open(os.path.join(
        root, "benchmark", "configs", "gpt1_117m.json")))
    cfg["name"] = "gpt_wide"
    cfg["reference"] = "benchmark/configs/gpt_wide_reference.py"
    cfg["rehearse"]["n_embd"] = 96
    cfg["rehearse"]["assumed"]["n_inner"] = 384
    with open(os.path.join(root, "benchmark", "configs",
                           "gpt_wide.json"), "w") as f:
        json.dump(cfg, f)
    shutil.copy(
        os.path.join(root, "benchmark", "configs", "gpt1_117m_reference.py"),
        os.path.join(root, "benchmark", "configs", "gpt_wide_reference.py"))
    # a traffic mix: data only
    mix = json.load(open(os.path.join(
        root, "benchmark", "traffic", "chat_steady.json")))
    mix["rehearse"]["rate_per_s"] = 9.0
    mix["rehearse"]["prompt"]["max"] = 12
    with open(os.path.join(root, "benchmark", "traffic",
                           "short_prompts.json"), "w") as f:
        json.dump(mix, f)
    # a per-layer metric: a small reader of its own
    with open(os.path.join(root, "benchmark", "layer_metrics",
                           "ttft_p50_ms.short.py"), "w") as f:
        f.write("import numpy as np\n\n\n"
                "def read(trace, spans, counters, cell):\n"
                "    v = counters.get('ttft_ms', [])\n"
                "    return float(np.percentile(v, 50)) if len(v) else None\n")

    bench = harness.load_benchmark()
    cell = "gpt_wide.short_prompts"
    bench["configs"].append({
        "name": "gpt_wide", "source": cfg["source"],
        "file": "benchmark/configs/gpt_wide.json", "reduced": [],
        "why": "overlay test"})
    bench["workloads"].append({
        "name": cell, "config": "gpt_wide", "traffic": "short_prompts",
        "chips": 1, "why": "overlay test"})
    for m in bench["end_to_end"]:
        if m["name"] in ("ttft_p95_ms", "tpot_p95_ms"):
            m["workloads"].append(cell)
    bench["per_layer"].append({
        "name": "ttft_p50_ms.short", "unit": "ms", "better": "lower",
        "source": "host_clock", "layer": "DecodeServer (paddle_tpu/serving/decode.py)",
        "moves": "ttft_p95_ms", "workloads": [cell]})
    with open(os.path.join(root, "BENCHMARK.json"), "w") as f:
        json.dump(bench, f)

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=harness.ROOT)  # the program; the overlay has none
    env.pop("XLA_FLAGS", None)
    for trace in ("0", "1"):
        p = subprocess.run(
            [sys.executable, os.path.join(root, "benchmark", "run.py"),
             "--workload", cell, "--seed", "4", "--seconds", "3",
             "--trace", trace, "--rehearse-cpu"],
            capture_output=True, text=True, timeout=600, env=env, cwd=root)
        assert p.returncode == 0, p.stdout[-3000:] + p.stderr[-3000:]
        line = json.loads(p.stdout.strip().splitlines()[-1])["would_report"]
        assert line["correct"] is True
        if trace == "1":
            assert line["metrics"]["ttft_p50_ms.short"]["value"] > 0
        else:
            assert set(line["metrics"]) == {"ttft_p95_ms", "tpot_p95_ms",
                                            "setup_s"}
    after = digest(root)
    assert {k: after[k] for k in before} == before  # nothing edited
    assert sorted(set(after) - set(before)) == [
        "benchmark/configs/gpt_wide.json",
        "benchmark/configs/gpt_wide_reference.py",
        "benchmark/layer_metrics/ttft_p50_ms.short.py",
        "benchmark/traffic/short_prompts.json"]
