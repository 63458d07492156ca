"""Per-layer metric ``ssm_update_time_share.serve``: see ``benchmark/lib/readers_hybrid_ssm.ssm_update_time_share``."""
from benchmark.lib.readers_hybrid_ssm import ssm_update_time_share as read  # noqa: F401
