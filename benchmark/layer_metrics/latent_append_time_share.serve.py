"""Per-layer metric ``latent_append_time_share.serve``: see ``benchmark/lib/readers_latent_mtp.latent_append_time_share``."""
from benchmark.lib.readers_latent_mtp import latent_append_time_share as read  # noqa: F401
