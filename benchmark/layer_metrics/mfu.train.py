"""Per-layer metric ``mfu.train``: see ``benchmark/lib/readers.mfu``."""
from benchmark.lib.readers import mfu as read  # noqa: F401
