"""Per-layer metric ``moe_experts_roofline.serve``: see ``benchmark/lib/readers_moe.moe_experts_roofline``."""
from benchmark.lib.readers_moe import moe_experts_roofline as read  # noqa: F401
