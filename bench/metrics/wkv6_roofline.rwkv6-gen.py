"""Per-layer metric `wkv6_roofline.rwkv6-gen`: the chunked prefill's WKV-6
sequence kernel (`wkv6_seq_pallas`) against its roofline, in %.

The least time of its calls in the window, one call per layer of each
prefill call, each computing the family's `wkv_call_flops` and moving its
`wkv_call_bytes` at the chip's peaks, over the kernel's self time among
the trace's top operations.  None when the run has no trace, no prefill
call, or the kernel does not rank among the top operations."""
from bench import work

KERNEL = "wkv6_seq_pallas"


def read(run):
    if run.trace is None or run.peak is None or not run.counts["prefill_calls"]:
        return None
    seconds = dict(getattr(run.trace, "top_ops", ())).get(KERNEL)
    if not seconds:
        return None
    calls = run.counts["prefill_calls"] * run.config["model"]["n_layers"]
    least = calls * work.roofline_seconds(
        run.family.wkv_call_flops(run.config),
        run.family.wkv_call_bytes(run.config), run.peak)
    return 100.0 * least / seconds
