"""Per-layer metric ``peak_hbm_share.offline``: see ``benchmark/lib/readers.peak_hbm_share``."""
from benchmark.lib.readers import peak_hbm_share as read  # noqa: F401
