"""Per-layer metric ``turn_dispatch_ms.chat``: see ``benchmark/lib/readers_turn.turn_dispatch_ms``."""
from benchmark.lib.readers_turn import turn_dispatch_ms as read  # noqa: F401
