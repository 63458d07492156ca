"""Per-layer metric ``recurrent_state_share.serve``: see ``benchmark/lib/readers_hybrid_ssm.recurrent_state_share``."""
from benchmark.lib.readers_hybrid_ssm import recurrent_state_share as read  # noqa: F401
