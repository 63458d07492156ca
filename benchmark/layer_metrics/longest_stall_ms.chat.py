"""Per-layer metric ``longest_stall_ms.chat``: see ``benchmark/lib/readers.longest_stall_ms``."""
from benchmark.lib.readers import longest_stall_ms as read  # noqa: F401
