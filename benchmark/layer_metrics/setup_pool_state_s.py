"""Per-layer metric ``setup_pool_state_s``: see ``benchmark/lib/readers_setup.setup_pool_state_s``."""
from benchmark.lib.readers_setup import setup_pool_state_s as read  # noqa: F401
