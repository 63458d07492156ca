"""Per-layer metric ``short_conv_time_share.serve``: see ``benchmark/lib/readers_delta_hybrid.short_conv_time_share``."""
from benchmark.lib.readers_delta_hybrid import short_conv_time_share as read  # noqa: F401
