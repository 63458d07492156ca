"""Per-layer metric ``kv_held_share.serve``: see ``benchmark/lib/readers_windowed.kv_held_share``."""
from benchmark.lib.readers_windowed import kv_held_share as read  # noqa: F401
