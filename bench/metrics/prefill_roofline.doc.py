"""Per-layer metric `prefill_roofline.doc`: see `bench.readers.prefill_roofline`."""
from bench.readers import prefill_roofline as read  # noqa: F401
