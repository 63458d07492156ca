"""Test config: force an 8-device virtual CPU mesh so sharding/collective
tests run without TPU hardware (reference tests use multi-GPU/multi-process;
see SURVEY.md §4.4).

Compile-heavy tests are marked ``slow`` and deselected by default; run
them with ``--runslow`` or ``-m slow``.  A persistent XLA compilation
cache (``paddle_tpu.compile_cache``: ``$JAX_COMPILATION_CACHE_DIR``,
else ``<checkout>/.jax_cache``) makes repeat runs cheap.
"""
import contextlib
import faulthandler
import os
import signal
import sys

import pytest

# before the first jax import: the platform and the virtual device count
# are read once, when jax initializes its backends
os.environ["JAX_PLATFORMS"] = "cpu"
os.environ["PADDLE_TPU_BACKEND"] = "cpu"
_flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in _flags:
    os.environ["XLA_FLAGS"] = (_flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
from paddle_tpu import compile_cache  # noqa: E402

# subprocess workers (distributed.launch two-process tests) inherit the
# exported variable and share the cache
compile_cache.configure()


# One test may take LIMIT seconds (nine times the slowest case of a whole
# six-worker run, 32 s; a hang then costs 300 s of the driver's 1,470 s,
# with a name), and every wait a test makes on a future, a thread, an
# event or a checkpoint handle takes WAIT: well over that slowest case,
# under LIMIT, so that the wait's own assert names the line before the
# limit has to.
LIMIT = 300.0
WAIT = 120.0

_real_stderr = 2  # pytest_configure: fd 2 as it is with no capture on it


def pytest_configure(config):
    global _real_stderr
    _real_stderr = os.dup(2)


@contextlib.contextmanager
def limit_one_test(name, limit=LIMIT, grace=60.0):
    """At ``limit`` seconds SIGALRM's handler writes every thread's
    stack to the run's own stderr and fails the test from the main
    thread (a Python-level wait is interrupted).  A main thread stuck in
    native code never runs the handler: ``grace`` seconds later
    faulthandler writes the stacks and ends the process, which xdist
    reports as this one test failed (``node down``) before it starts
    another worker and goes on."""
    def over(signum, frame):
        faulthandler.dump_traceback(file=_real_stderr, all_threads=True)
        pytest.fail("over the limit of %g s a test (tests/conftest.py "
                    "LIMIT): %s" % (limit, name))

    before = signal.signal(signal.SIGALRM, over)
    signal.setitimer(signal.ITIMER_REAL, limit)
    faulthandler.dump_traceback_later(limit + grace, exit=True,
                                      file=_real_stderr)
    try:
        yield
    finally:
        faulthandler.cancel_dump_traceback_later()
        signal.setitimer(signal.ITIMER_REAL, 0)
        signal.signal(signal.SIGALRM, before)


@pytest.hookimpl(wrapper=True)
def pytest_runtest_protocol(item):
    # the whole of a test: a module's fixtures are set up inside its
    # first test (servers warm up and fleets start children there),
    # which a function-scoped fixture would not cover
    with limit_one_test(item.nodeid):
        return (yield)


def pytest_addoption(parser):
    parser.addoption("--runslow", action="store_true", default=False,
                     help="also run tests marked slow")


def pytest_collection_modifyitems(config, items):
    if config.getoption("--runslow") or config.getoption("-m"):
        return
    skip = pytest.mark.skip(reason="slow; use --runslow")
    for item in items:
        if "slow" in item.keywords:
            item.add_marker(skip)


@pytest.fixture
def check_batch_admit():
    """``check(pool, state, seats)``: ONE ``pool.admit`` of ``seats`` —
    ``(slot, prompt, total_len, spec)`` each — must leave the pool state
    leaf-for-leaf what admitting them one call at a time, in the same
    order, leaves; returns that state."""
    import jax
    import numpy as np

    def check(pool, state, seats):
        one = state
        for slot, prompt, total, spec in seats:
            one = pool.admit(one, slot, prompt, len(prompt), total,
                             spec=spec)
        many = pool.admit(
            state, [s[0] for s in seats], [s[1] for s in seats],
            [len(s[1]) for s in seats], [s[2] for s in seats],
            spec=[s[3] for s in seats])
        assert jax.tree.structure(one) == jax.tree.structure(many)
        for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(one),
                                jax.tree.leaves(many)):
            a, b = np.asarray(a), np.asarray(b)
            assert a.dtype == b.dtype and np.array_equal(a, b), (
                jax.tree_util.keystr(path))
        return many

    return check
