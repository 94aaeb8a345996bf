"""Per-layer metric `prefill_ms_per_call.rwkv6-gen`: see `bench.readers.prefill_ms_per_call`."""
from bench.readers import prefill_ms_per_call as read  # noqa: F401
