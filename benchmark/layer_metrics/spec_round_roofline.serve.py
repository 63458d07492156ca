"""Per-layer metric ``spec_round_roofline.serve``: see ``benchmark/lib/readers_mtp.spec_round_roofline``."""
from benchmark.lib.readers_mtp import spec_round_roofline as read  # noqa: F401
