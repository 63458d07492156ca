"""The CPU buffer-donation / persistent-compile-cache aliasing hazard,
pinned by a TWO-PROCESS regression drill.

PR 3 found (and fixed) a latent corruption: with buffer donation
enabled on the CPU backend, an executable RELOADED from jax's
persistent compilation cache returns fetches computed with the
in-place-mutated (post-update) parameters — cold compiles are always
correct, so single-process tests can never see it.  The fix is the
``executor._donate_kwargs`` carve-out (donate everywhere except CPU),
which until this file was guarded only by a unit assertion on the
kwargs dict and a comment.  This drill exercises the REAL failure
path: two fresh processes share one persistent cache dir; the second
(warm-cache) process must fetch exactly what the first (cold-compile)
process did.  Re-enabling donation on CPU makes the second process
print a different loss and fails this test.
"""
import json
import os
import subprocess
import sys

REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_CHILD = os.path.join(REPO_ROOT, "tests", "_donation_child.py")


def _run_child(cache_dir):
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    prev = env.get("PYTHONPATH")
    env["PYTHONPATH"] = (
        REPO_ROOT + os.pathsep + prev if prev else REPO_ROOT)
    proc = subprocess.run(
        [sys.executable, _CHILD, str(cache_dir)],
        capture_output=True, text=True, timeout=240, env=env)
    assert proc.returncode == 0, (
        "donation child failed (rc=%s):\n%s" % (proc.returncode,
                                                proc.stderr[-4000:]))
    for line in proc.stdout.splitlines():
        if line.startswith("RESULT "):
            return json.loads(line[len("RESULT "):])
    raise AssertionError(
        "child printed no RESULT line:\n%s" % proc.stdout[-2000:])


def test_warm_cache_process_matches_cold(tmp_path):
    """Process 1 compiles cold and populates the shared persistent
    cache; process 2 reloads the executable from it.  Identical seeds,
    identical feeds — the fetches must agree bitwise.  Under the
    donation bug they don't: the reloaded aliased executable's loss
    observes post-Adam-update weights."""
    cache = tmp_path / "xla_cache"
    cache.mkdir()
    cold = _run_child(cache)
    # the drill is only meaningful if the first run actually left cache
    # entries for the second to reload — guard against a future jax
    # knob rename silently disabling the persistent cache
    entries = [p for p in cache.rglob("*") if p.is_file()]
    assert entries, (
        "cold run left no persistent-cache entries — the drill is "
        "vacuous; check the JAX_COMPILATION_CACHE_* wiring in "
        "tests/_donation_child.py")
    warm = _run_child(cache)
    assert warm["loss"] == cold["loss"], (
        "warm-cache process disagrees with cold compile: %r vs %r — "
        "the CPU buffer-donation carve-out (executor._donate_kwargs) "
        "has regressed; a donated executable reloaded from the "
        "persistent cache observes in-place-mutated params"
        % (warm["loss"], cold["loss"]))
