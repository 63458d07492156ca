"""Per-layer metric ``doc_prefill_tokens_per_s.setup``: see ``benchmark/lib/readers_kda_latent.doc_prefill_tokens_per_s``."""
from benchmark.lib.readers_kda_latent import doc_prefill_tokens_per_s as read  # noqa: F401
