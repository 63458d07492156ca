"""Per-layer metric ``linear_state_time_share.serve``: see ``benchmark/lib/readers_sparse_linear.linear_state_time_share``."""
from benchmark.lib.readers_sparse_linear import linear_state_time_share as read  # noqa: F401
