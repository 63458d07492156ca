"""Per-layer metric ``expert_peak_over_mean.serve``: see ``benchmark/lib/readers_moe.expert_peak_over_mean``."""
from benchmark.lib.readers_moe import expert_peak_over_mean as read  # noqa: F401
