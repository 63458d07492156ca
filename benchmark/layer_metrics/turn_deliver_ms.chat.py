"""Per-layer metric ``turn_deliver_ms.chat``: see ``benchmark/lib/readers_turn.turn_deliver_ms``."""
from benchmark.lib.readers_turn import turn_deliver_ms as read  # noqa: F401
