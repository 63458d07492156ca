"""Per-layer metric ``sparse_read_share.serve``: see ``benchmark/lib/readers_sparse_linear.sparse_read_share``."""
from benchmark.lib.readers_sparse_linear import sparse_read_share as read  # noqa: F401
