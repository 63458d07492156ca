"""Per-layer metric ``window_attention_time_share.serve``: see ``benchmark/lib/readers_windowed.window_attention_time_share``."""
from benchmark.lib.readers_windowed import window_attention_time_share as read  # noqa: F401
