"""Per-layer metric ``gen_late_p95_ms``: see ``benchmark/lib/readers.gen_late_p95_ms``."""
from benchmark.lib.readers import gen_late_p95_ms as read  # noqa: F401
