"""Per-layer metric ``decode_step_roofline.offline``: see ``benchmark/lib/readers.decode_step_roofline``."""
from benchmark.lib.readers import decode_step_roofline as read  # noqa: F401
