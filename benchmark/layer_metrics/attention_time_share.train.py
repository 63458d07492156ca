"""Per-layer metric ``attention_time_share.train``: see ``benchmark/lib/readers.attention_time_share``."""
from benchmark.lib.readers import attention_time_share as read  # noqa: F401
