"""Per-layer metric ``index_select_time_share.serve``: see ``benchmark/lib/readers_latent_sparse.index_select_time_share``."""
from benchmark.lib.readers_latent_sparse import index_select_time_share as read  # noqa: F401
