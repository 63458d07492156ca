"""Per-layer metric ``setup_trace_lower_s``: see ``benchmark/lib/readers_setup.setup_trace_lower_s``."""
from benchmark.lib.readers_setup import setup_trace_lower_s as read  # noqa: F401
