"""Per-layer metric ``setup_accounted_share``: see ``benchmark/lib/readers_setup.setup_accounted_share``."""
from benchmark.lib.readers_setup import setup_accounted_share as read  # noqa: F401
