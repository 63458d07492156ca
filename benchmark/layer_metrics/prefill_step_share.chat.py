"""Per-layer metric ``prefill_step_share.chat``: see ``benchmark/lib/readers.prefill_step_share``."""
from benchmark.lib.readers import prefill_step_share as read  # noqa: F401
