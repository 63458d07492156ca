"""The two edges of chip_smoke.py that a CPU box can check: the rehearsal
(tiny sizes, explicit argument) runs every leg to completion, and the
plain command refuses to run without a TPU instead of falling back."""
import json
import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
SMOKE = os.path.join(REPO_ROOT, "chip_smoke.py")


def _run(*args):
    # conftest's environment: JAX_PLATFORMS=cpu, 8 virtual devices, the
    # session's compile cache
    return subprocess.run(
        [sys.executable, SMOKE, *args], cwd=REPO_ROOT,
        capture_output=True, text=True, timeout=240)


def test_rehearsal_runs_every_leg_and_is_never_a_chip_result():
    proc = _run("--rehearse-cpu")
    assert proc.returncode == 0, proc.stderr[-4000:]
    lines = proc.stdout.strip().splitlines()
    assert lines[0].startswith("REHEARSAL") and "NOT a chip run" in lines[0]
    tags = [ln.split(" ", 1)[0] for ln in lines]
    # the 8 virtual devices make the four-chip leg run too
    for leg in ("header", "train_leg", "serve_leg", "four_chip_leg"):
        assert leg in tags, (leg, tags)
    last = json.loads(lines[-1])
    assert last["rehearsal"] is True and last["passed"] is True
    assert "ok" not in last and last["device"]["platform"] == "cpu"
    serve = json.loads(
        next(ln for ln in lines if ln.startswith("serve_leg ")).split(" ", 1)[1])
    assert serve["recompiles_after_warmup"] == 0


def test_without_a_tpu_it_fails_and_says_so():
    proc = _run()
    assert proc.returncode != 0
    assert "no TPU found" in proc.stderr
    # no result line: nothing on stdout parses as the ok object
    for ln in proc.stdout.splitlines():
        assert '"ok"' not in ln, ln
