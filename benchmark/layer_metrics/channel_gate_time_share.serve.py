"""Per-layer metric ``channel_gate_time_share.serve``: see ``benchmark/lib/readers_kda_routed.channel_gate_time_share``."""
from benchmark.lib.readers_kda_routed import channel_gate_time_share as read  # noqa: F401
