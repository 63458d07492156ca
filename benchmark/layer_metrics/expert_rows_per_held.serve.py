"""Per-layer metric ``expert_rows_per_held.serve``: see ``benchmark/lib/readers_kda_routed.expert_rows_per_held``."""
from benchmark.lib.readers_kda_routed import expert_rows_per_held as read  # noqa: F401
