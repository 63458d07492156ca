"""Per-layer metric ``device_idle_share.train``: see ``benchmark/lib/readers.device_idle_share``."""
from benchmark.lib.readers import device_idle_share as read  # noqa: F401
