"""Per-layer metric ``mixed_attention_roofline.serve``: see ``benchmark/lib/readers_windowed.mixed_attention_roofline``."""
from benchmark.lib.readers_windowed import mixed_attention_roofline as read  # noqa: F401
