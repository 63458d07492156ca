"""Test config: force an 8-device virtual CPU mesh so sharding/collective
tests run without TPU hardware (reference tests use multi-GPU/multi-process;
see SURVEY.md §4.4).

Compile-heavy tests are marked ``slow`` and deselected by default; run
them with ``--runslow`` or ``-m slow``.  A persistent XLA compilation
cache (``paddle_tpu.compile_cache``: ``$JAX_COMPILATION_CACHE_DIR``,
else ``<checkout>/.jax_cache``) makes repeat runs cheap.
"""
import os
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
