"""Per-layer metric ``index_selected_share.serve``: see ``benchmark/lib/readers_latent_sparse.index_selected_share``."""
from benchmark.lib.readers_latent_sparse import index_selected_share as read  # noqa: F401
