"""Per-layer metric ``dense_latent_roofline.serve``: see ``benchmark/lib/readers_latent_mtp.dense_latent_roofline``."""
from benchmark.lib.readers_latent_mtp import dense_latent_roofline as read  # noqa: F401
