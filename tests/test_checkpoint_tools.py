"""Tier-1 wiring for tools/check_checkpoint.py: the offline verifier
must pass a freshly committed (sharded + host-state) checkpoint, and
must FLAG a doctored manifest whose shard set no longer tiles a global
shape, a corrupted file, and a dangling LATEST pointer — the same
failure classes restore() handles at runtime, caught before a resume
is attempted.
"""
import json
import os
import shutil
import sys

import numpy as np
import pytest

from conftest import WAIT

import paddle_tpu as fluid
from paddle_tpu import framework, sharding
from paddle_tpu.faults.checkpoint import hash_file

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(REPO_ROOT, "tools"))

import check_checkpoint  # noqa: E402


@pytest.fixture(scope="module")
def run_dir(tmp_path_factory):
    """One committed SHARD-wise checkpoint (fc stack + Adam on fsdp-2)
    the tests copy and doctor."""
    from jax.sharding import PartitionSpec as P

    from paddle_tpu.sharding.rules import PartitionRules

    base = tmp_path_factory.mktemp("ckpt_tool")
    prog, startup = framework.Program(), framework.Program()
    prog.random_seed = startup.random_seed = 9
    with framework.program_guard(prog, startup):
        x = fluid.layers.data("x", [8])
        y = fluid.layers.data("y", [1])
        h = fluid.layers.fc(x, 8, act="relu")
        pred = fluid.layers.fc(h, 1)
        loss = fluid.layers.mean(fluid.layers.square_error_cost(pred, y))
        opt = fluid.optimizer.AdamOptimizer(0.01)
        opt.minimize(loss)
    compiled = sharding.sharded_train_program(
        prog, PartitionRules([(r".", P("fsdp"))], name="tool/fsdp"),
        optimizer=opt, mesh_axes={"fsdp": 2})
    exe = fluid.Executor(fluid.CPUPlace())
    scope = fluid.Scope()
    rng = np.random.RandomState(0)
    feeds = [{"x": rng.rand(8, 8).astype(np.float32),
              "y": rng.rand(8, 1).astype(np.float32)} for _ in range(4)]
    d = str(base / "run")
    with fluid.scope_guard(scope):
        exe.run(startup)
        exe.train_from_dataset(
            program=compiled, dataset=feeds, scope=scope,
            fetch_list=[loss], checkpoint_dir=d, checkpoint_every=4)
    assert os.path.isdir(os.path.join(d, "ckpt-000004", "shards"))
    return d


def _copy(run_dir, tmp_path, name):
    dst = str(tmp_path / name)
    shutil.copytree(run_dir, dst)
    return dst


def _rehash(ck_dir, rel):
    """Refresh one file's integrity entry after a deliberate doctoring
    — so the COVERAGE check is what fires, not the tamper gate."""
    integ = os.path.join(ck_dir, "integrity.json")
    with open(integ) as f:
        doc = json.load(f)
    p = os.path.join(ck_dir, rel)
    doc["files"][rel] = {"sha256": hash_file(p),
                         "bytes": os.path.getsize(p)}
    with open(integ, "w") as f:
        json.dump(doc, f)


def test_verifier_green_on_committed_checkpoint(run_dir):
    assert check_checkpoint.check(run_dir) == []


def test_doctored_manifest_fails_coverage(run_dir, tmp_path):
    """The pinned failure: drop one shard record from the manifest —
    the surviving indexes no longer tile the var's global shape, and
    the verifier says so naming the var."""
    d = _copy(run_dir, tmp_path, "doctored")
    ck = os.path.join(d, "ckpt-000004")
    mpath = os.path.join(ck, "shards", "manifest.json")
    with open(mpath) as f:
        man = json.load(f)
    victim = next(n for n, e in sorted(man["vars"].items())
                  if len(e["shards"]) == 2)
    man["vars"][victim]["shards"] = man["vars"][victim]["shards"][:1]
    with open(mpath, "w") as f:
        json.dump(man, f)
    _rehash(ck, "shards/manifest.json")
    problems = check_checkpoint.check(d)
    assert any(victim in p and "tile" in p for p in problems), problems


def test_flipped_byte_fails_hash(run_dir, tmp_path):
    d = _copy(run_dir, tmp_path, "flipped")
    sdir = os.path.join(d, "ckpt-000004", "shards")
    victim = next(os.path.join(sdir, f) for f in sorted(os.listdir(sdir))
                  if f.endswith(".npy"))
    with open(victim, "r+b") as f:
        f.seek(100)
        b = f.read(1)
        f.seek(100)
        f.write(bytes([b[0] ^ 0xFF]))
    problems = check_checkpoint.check(d)
    assert any("hash" in p for p in problems), problems


def test_shard_file_shape_vs_index_mismatch(run_dir, tmp_path):
    """A shard file whose array no longer matches its recorded index
    extents is flagged (a mis-sized file would device_put garbage)."""
    d = _copy(run_dir, tmp_path, "misshaped")
    ck = os.path.join(d, "ckpt-000004")
    sdir = os.path.join(ck, "shards")
    with open(os.path.join(sdir, "manifest.json")) as f:
        man = json.load(f)
    name, ent = next((n, e) for n, e in sorted(man["vars"].items())
                     if len(e["shape"]) == 2)
    rel = "shards/" + ent["shards"][0]["file"]
    np.save(os.path.join(ck, rel), np.zeros((1, 1), np.float32))
    _rehash(ck, rel)
    problems = check_checkpoint.check(d)
    assert any(name in p and "implies" in p for p in problems), problems


def test_malformed_manifest_is_a_problem_not_a_crash(run_dir, tmp_path):
    """Any malformed metadata shape (junk JSON structure in a shards
    manifest) must surface as a reported problem — a crash would
    swallow every finding already collected."""
    d = _copy(run_dir, tmp_path, "malformed")
    ck = os.path.join(d, "ckpt-000004")
    with open(os.path.join(ck, "shards", "manifest.json"), "w") as f:
        f.write('{"vars": {"x": 3}}')
    _rehash(ck, "shards/manifest.json")
    problems = check_checkpoint.check(d)
    assert any("malformed" in p for p in problems), problems


def test_dangling_latest_and_missing_params_flagged(run_dir, tmp_path):
    d = _copy(run_dir, tmp_path, "dangling")
    with open(os.path.join(d, "LATEST"), "w") as f:
        f.write("ckpt-999999\n")
    problems = check_checkpoint.check(d)
    assert any("LATEST" in p and "ckpt-999999" in p for p in problems)
    # a param file deleted out from under its manifest is two problems:
    # the integrity manifest AND the params manifest both notice
    pdir = os.path.join(d, "ckpt-000004", "params")
    victim = next(f for f in sorted(os.listdir(pdir))
                  if f.endswith(".npy"))
    os.remove(os.path.join(pdir, victim))
    problems = check_checkpoint.check(d)
    assert any("missing" in p for p in problems), problems


def test_cli_exit_codes(run_dir, tmp_path):
    """The tool is a CLI: exit 0 + OK line on a clean dir, exit 1 with
    the problem list on a broken one."""
    import subprocess

    env = dict(os.environ, JAX_PLATFORMS="cpu",
               PYTHONPATH=REPO_ROOT + os.pathsep
               + os.environ.get("PYTHONPATH", ""))
    tool = os.path.join(REPO_ROOT, "tools", "check_checkpoint.py")
    ok = subprocess.run([sys.executable, tool, run_dir], timeout=WAIT,
                        capture_output=True, text=True, env=env)
    assert ok.returncode == 0, ok.stderr
    assert "OK" in ok.stdout
    bad = subprocess.run([sys.executable, tool, str(tmp_path / "nope")],
                         timeout=WAIT,
                         capture_output=True, text=True, env=env)
    assert bad.returncode == 1
    assert "does not exist" in bad.stderr
