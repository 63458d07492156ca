"""Per-layer metric ``window_compiles``: see ``benchmark/lib/readers.window_compiles``."""
from benchmark.lib.readers import window_compiles as read  # noqa: F401
