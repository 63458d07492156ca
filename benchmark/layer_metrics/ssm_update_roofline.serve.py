"""Per-layer metric ``ssm_update_roofline.serve``: see ``benchmark/lib/readers_hybrid_ssm.ssm_update_roofline``."""
from benchmark.lib.readers_hybrid_ssm import ssm_update_roofline as read  # noqa: F401
