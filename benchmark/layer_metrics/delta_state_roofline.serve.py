"""Per-layer metric ``delta_state_roofline.serve``: see ``benchmark/lib/readers_delta_hybrid.delta_state_roofline``."""
from benchmark.lib.readers_delta_hybrid import delta_state_roofline as read  # noqa: F401
