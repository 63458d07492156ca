"""Per-layer metric ``turn_off_cpu_ms.offline``: see ``benchmark/lib/readers_turn.turn_off_cpu_ms``."""
from benchmark.lib.readers_turn import turn_off_cpu_ms as read  # noqa: F401
