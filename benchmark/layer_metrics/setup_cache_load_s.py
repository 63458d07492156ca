"""Per-layer metric ``setup_cache_load_s``: see ``benchmark/lib/readers_setup.setup_cache_load_s``."""
from benchmark.lib.readers_setup import setup_cache_load_s as read  # noqa: F401
