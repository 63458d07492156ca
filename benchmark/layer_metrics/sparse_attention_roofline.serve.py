"""Per-layer metric ``sparse_attention_roofline.serve``: see ``benchmark/lib/readers_sparse_linear.sparse_attention_roofline``."""
from benchmark.lib.readers_sparse_linear import sparse_attention_roofline as read  # noqa: F401
