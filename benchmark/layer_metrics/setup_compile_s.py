"""Per-layer metric ``setup_compile_s``: see ``benchmark/lib/readers.setup_compile_s``."""
from benchmark.lib.readers import setup_compile_s as read  # noqa: F401
