"""A lint of ``BENCHMARK.json`` against the contract's mechanical rules
and against the files it names."""
import json
import os
import re

import pytest

from benchmark.lib import harness, traffic

NAME = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$")
UNIT = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$")
PATH = re.compile(r"^[A-Za-z0-9_.\-/]{1,200}$")
SOURCES = {"device_trace", "program_span", "program_counter", "host_clock"}
# a width: a key that names a size.  ``num_hidden_layers`` is the
# contract's own example of a key ``reduced`` may hold, so the word
# "hidden" alone does not make one.
WIDTH = re.compile(r"(_dim|_rank|_size)$|^(n_embd|n_inner|d_model|d_inner)$"
                   r"|expand|expansion|experts_per_tok|head_dim")


@pytest.fixture(scope="module")
def bench():
    return harness.load_benchmark()


def test_top_level_keys_and_sizes(bench):
    assert set(bench) == {"command", "paths", "run_seconds", "configs",
                          "workloads", "end_to_end", "per_layer"}
    assert os.path.getsize(os.path.join(
        harness.ROOT, "BENCHMARK.json")) <= 64 * 1024
    assert 1 <= bench["run_seconds"] <= 51
    assert isinstance(bench["run_seconds"], int)
    assert all(PATH.match(p) and not p.startswith("/") and ".." not in p
               for p in bench["paths"])
    assert len(bench["command"]) <= 32
    # the budget of a full check with all 24 cells
    runs = 2 + 14 * 24
    assert runs * (bench["run_seconds"] + 60) + 24 * 180 + 1200 <= 43200


def test_names_units_and_line_lengths(bench):
    names = []
    for group in ("configs", "workloads", "end_to_end", "per_layer"):
        for e in bench[group]:
            assert NAME.match(e["name"]), e["name"]
            names.append((group in ("end_to_end", "per_layer"), e["name"]))
            for k in ("why", "layer", "source"):
                if k in e and group != "end_to_end" and not (
                        group == "per_layer" and k == "source"):
                    assert 1 <= len(e[k]) <= 200 and "\n" not in e[k] \
                        and "\t" not in e[k], (e["name"], k)
    assert len(names) == len(set(names))
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert UNIT.match(m["unit"]), m
        assert m["better"] in ("lower", "higher")
        assert m["source"] in SOURCES
    for m in bench["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source",
                          "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.1
    for m in bench["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer",
                          "moves", "workloads"}
    assert len(bench["end_to_end"]) <= 16 and len(bench["per_layer"]) <= 128
    assert any(m["name"] == "setup_s" and "workloads" not in m
               for m in bench["end_to_end"])


def test_cells_and_their_files_resolve(bench):
    configs = {c["name"]: c for c in bench["configs"]}
    pairs = set()
    for w in bench["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4)
        assert NAME.match(w["config"]) and NAME.match(w["traffic"])
        assert (w["config"], w["traffic"]) not in pairs
        pairs.add((w["config"], w["traffic"]))
        conf = configs[w["config"]]
        assert any(conf["file"].startswith(p.rstrip("/") + "/")
                   for p in bench["paths"])
        cfg = harness.load_config(
            os.path.join(harness.ROOT, conf["file"]), False)
        for key in ("family", "source", "reference", "reduced", "assumed"):
            assert key in cfg, (conf["name"], key)
        assert cfg["reduced"] == conf["reduced"]
        assert cfg["source"] == conf["source"]
        assert os.path.isfile(os.path.join(
            harness.BENCH, "families", cfg["family"] + ".py"))
        ref = os.path.join(harness.ROOT, cfg["reference"])
        assert os.path.isfile(ref)
        # the plain reference sits beside the configuration's file and
        # imports nothing from the program
        assert os.path.dirname(ref) == os.path.dirname(
            os.path.join(harness.ROOT, conf["file"]))
        assert "paddle_tpu" not in re.sub(
            r'""".*?"""', "", open(ref).read(), flags=re.S)
        traffic.load_mix(w["traffic"])
    assert {c for c, _ in pairs} == set(configs)  # every config is used
    files = [c["file"] for c in bench["configs"]]
    assert len(files) == len(set(files))
    four = sum(w["chips"] == 4 for w in bench["workloads"])
    assert four <= max(1, len(bench["workloads"]) // 4)


def test_reduced_names_no_width(bench):
    for c in bench["configs"]:
        assert len(c["reduced"]) <= 16
        for key in c["reduced"]:
            assert NAME.match(key)
            assert not WIDTH.search(key), key


def test_every_moves_target_is_reported_where_the_metric_is(bench):
    cells = [w["name"] for w in bench["workloads"]]
    e2e = {m["name"]: m for m in bench["end_to_end"]}

    def cells_of(m):
        return set(m.get("workloads", cells))

    for m in bench["end_to_end"] + bench["per_layer"]:
        assert cells_of(m) <= set(cells), m["name"]
    for m in bench["per_layer"]:
        assert m["moves"] in e2e, m["name"]
        assert cells_of(m) <= cells_of(e2e[m["moves"]]), (
            "%s moves %s, which some of its cells do not report"
            % (m["name"], m["moves"]))
        assert os.path.isfile(os.path.join(
            harness.BENCH, "layer_metrics", m["name"] + ".py")), m["name"]
    for cell in cells:
        mine = [m for m in bench["end_to_end"] if cell in cells_of(m)]
        assert any(m["name"] == "setup_s" for m in mine)
        assert len(mine) >= 2, cell
        assert any(cell in cells_of(m) for m in bench["per_layer"]), cell
    layers = {}
    for m in bench["per_layer"]:
        assert "\n" not in m["layer"]
        layers.setdefault(m["layer"].lower(), set()).add(m["layer"])
    assert all(len(v) == 1 for v in layers.values())  # letter for letter


def test_roofline_and_mfu_units(bench):
    for m in bench["per_layer"]:
        if m["name"].split(".")[0].endswith("_roofline") or "mfu" in m["name"]:
            assert m["unit"] == "%"


def test_benchmark_files_use_only_name_characters(bench):
    for base in bench["paths"]:
        for d, dirs, files in os.walk(os.path.join(harness.ROOT, base)):
            dirs[:] = [x for x in dirs if x != "__pycache__"]
            for f in files:
                rel = os.path.relpath(os.path.join(d, f), harness.ROOT)
                assert PATH.match(rel), rel
