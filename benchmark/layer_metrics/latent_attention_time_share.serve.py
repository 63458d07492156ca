"""Per-layer metric ``latent_attention_time_share.serve``: see ``benchmark/lib/readers_latent_sparse.latent_attention_time_share``."""
from benchmark.lib.readers_latent_sparse import latent_attention_time_share as read  # noqa: F401
