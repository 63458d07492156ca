"""Per-layer metric ``host_ms_per_step.train``: see ``benchmark/lib/readers.host_ms_per_step``."""
from benchmark.lib.readers import host_ms_per_step as read  # noqa: F401
