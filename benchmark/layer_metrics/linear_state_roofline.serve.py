"""Per-layer metric ``linear_state_roofline.serve``: see ``benchmark/lib/readers_sparse_linear.linear_state_roofline``."""
from benchmark.lib.readers_sparse_linear import linear_state_roofline as read  # noqa: F401
