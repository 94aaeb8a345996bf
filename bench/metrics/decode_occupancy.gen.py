"""Per-layer metric `decode_occupancy.gen`: see `bench.readers.decode_occupancy`."""
from bench.readers import decode_occupancy as read  # noqa: F401
