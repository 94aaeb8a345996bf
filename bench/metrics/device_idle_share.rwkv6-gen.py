"""Per-layer metric `device_idle_share.rwkv6-gen`: see `bench.readers.device_idle_share`."""
from bench.readers import device_idle_share as read  # noqa: F401
