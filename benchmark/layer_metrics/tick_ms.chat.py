"""Per-layer metric ``tick_ms.chat``: see ``benchmark/lib/readers.tick_ms``."""
from benchmark.lib.readers import tick_ms as read  # noqa: F401
