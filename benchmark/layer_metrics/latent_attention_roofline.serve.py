"""Per-layer metric ``latent_attention_roofline.serve``: see ``benchmark/lib/readers_latent_sparse.latent_attention_roofline``."""
from benchmark.lib.readers_latent_sparse import latent_attention_roofline as read  # noqa: F401
