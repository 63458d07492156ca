"""Per-layer metric ``global_attention_time_share.serve``: see ``benchmark/lib/readers_windowed.global_attention_time_share``."""
from benchmark.lib.readers_windowed import global_attention_time_share as read  # noqa: F401
