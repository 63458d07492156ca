"""Per-layer metric ``full_attention_read_share.serve``: see ``benchmark/lib/readers_delta_hybrid.full_attention_read_share``."""
from benchmark.lib.readers_delta_hybrid import full_attention_read_share as read  # noqa: F401
