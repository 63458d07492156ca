"""Per-layer metric ``shared_expert_time_share.serve``: see ``benchmark/lib/readers_mtp.shared_expert_time_share``."""
from benchmark.lib.readers_mtp import shared_expert_time_share as read  # noqa: F401
