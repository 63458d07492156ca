"""Per-layer metric ``turn_copy_ms.chat``: see ``benchmark/lib/readers_turn.turn_copy_ms``."""
from benchmark.lib.readers_turn import turn_copy_ms as read  # noqa: F401
