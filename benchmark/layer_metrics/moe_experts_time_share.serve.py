"""Per-layer metric ``moe_experts_time_share.serve``: see ``benchmark/lib/readers_moe.moe_experts_time_share``."""
from benchmark.lib.readers_moe import moe_experts_time_share as read  # noqa: F401
