"""Per-layer metric ``index_score_time_share.serve``: see ``benchmark/lib/readers_latent_sparse.index_score_time_share``."""
from benchmark.lib.readers_latent_sparse import index_score_time_share as read  # noqa: F401
