"""On-chip benchmark of the RWKV serving engine (see PERF.md)."""
