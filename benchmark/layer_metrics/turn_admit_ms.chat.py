"""Per-layer metric ``turn_admit_ms.chat``: see ``benchmark/lib/readers_turn.turn_admit_ms``."""
from benchmark.lib.readers_turn import turn_admit_ms as read  # noqa: F401
