"""Per-layer metric ``spec_tokens_per_row_round.serve``: see ``benchmark/lib/readers_mtp.spec_tokens_per_row_round``."""
from benchmark.lib.readers_mtp import spec_tokens_per_row_round as read  # noqa: F401
