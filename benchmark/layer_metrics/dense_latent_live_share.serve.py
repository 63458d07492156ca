"""Per-layer metric ``dense_latent_live_share.serve``: see ``benchmark/lib/readers_latent_mtp.dense_latent_live_share``."""
from benchmark.lib.readers_latent_mtp import dense_latent_live_share as read  # noqa: F401
