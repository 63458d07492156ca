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
