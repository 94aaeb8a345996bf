"""Per-layer metric `decode_hbm_roofline.rwkv6-gen`: see `bench.readers.decode_hbm_roofline`."""
from bench.readers import decode_hbm_roofline as read  # noqa: F401
