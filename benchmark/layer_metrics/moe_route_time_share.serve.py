"""Per-layer metric ``moe_route_time_share.serve``: see ``benchmark/lib/readers_moe.moe_route_time_share``."""
from benchmark.lib.readers_moe import moe_route_time_share as read  # noqa: F401
