"""Per-layer metric ``mtp_accept_rate.serve``: see ``benchmark/lib/readers_mtp.mtp_accept_rate``."""
from benchmark.lib.readers_mtp import mtp_accept_rate as read  # noqa: F401
