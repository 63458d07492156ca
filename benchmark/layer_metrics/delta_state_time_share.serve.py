"""Per-layer metric ``delta_state_time_share.serve``: see ``benchmark/lib/readers_delta_hybrid.delta_state_time_share``."""
from benchmark.lib.readers_delta_hybrid import delta_state_time_share as read  # noqa: F401
