"""Per-layer metric ``tpot_p50_ms.chat``: see ``benchmark/lib/readers.tpot_p50_ms``."""
from benchmark.lib.readers import tpot_p50_ms as read  # noqa: F401
