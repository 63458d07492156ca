"""Per-layer metric ``sparse_attention_time_share.serve``: see ``benchmark/lib/readers_sparse_linear.sparse_attention_time_share``."""
from benchmark.lib.readers_sparse_linear import sparse_attention_time_share as read  # noqa: F401
