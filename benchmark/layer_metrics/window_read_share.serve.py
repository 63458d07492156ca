"""Per-layer metric ``window_read_share.serve``: see ``benchmark/lib/readers_windowed.window_read_share``."""
from benchmark.lib.readers_windowed import window_read_share as read  # noqa: F401
