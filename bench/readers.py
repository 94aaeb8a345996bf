"""Per-layer metric arithmetic, shared by the readers in `metrics/`.

Each function takes a finished run (`harness.Run`) and returns the metric's
value, or None where the run holds nothing to read.  Rooflines and FLOP
counts come from `work.py` and `peaks.json`; device times from the trace.
"""
from __future__ import annotations

from bench import work


def decode_occupancy(run):
    """Lanes decoded over lanes offered, in %: active lanes summed over
    the window's decode calls, over calls x pool width."""
    c = run.counts
    if not c["decode_calls"]:
        return None
    return 100.0 * c["decode_lanes"] / (
        c["decode_calls"] * run.config["engine"]["max_batch"])


def _program_call_s(run, program: str):
    if run.trace is None or program not in run.trace.modules:
        return None
    calls, seconds = run.trace.modules[program]
    return seconds / calls if calls else None


def decode_hbm_roofline(run):
    """The decode program's share of its roofline, in %: the least time a
    step over the window's mean active lanes could take, over its mean
    device time per call."""
    per_call = _program_call_s(run, "decode")
    c = run.counts
    if per_call is None or run.peak is None or not c["decode_calls"]:
        return None
    active = c["decode_lanes"] / c["decode_calls"]
    model = run.config["model"]
    least = work.roofline_seconds(
        work.token_flops(run.family, model, active, active),
        work.decode_step_bytes(run.family, run.config, active), run.peak)
    return 100.0 * least / per_call


def prefill_roofline(run):
    """The prefill program's share of its roofline, in %: the least time a
    call over the window's mean valid prompt tokens could take (their
    FLOPs through the blocks, the head left out; every stored weight, the
    pool's state read and written, the tokens' embedding rows), over the
    program's mean device time per call.  Padded positions are waste."""
    per_call = _program_call_s(run, "prefill")
    c = run.counts
    if per_call is None or run.peak is None or not c["prefill_calls"]:
        return None
    tokens = c["prefill_tokens"] / c["prefill_calls"]
    least = work.roofline_seconds(
        work.token_flops(run.family, run.config["model"], tokens, 0),
        work.prefill_call_bytes(run.family, run.config, tokens), run.peak)
    return 100.0 * least / per_call


def mfu(run):
    """Model FLOPs of every token the window processed over the chip's
    bf16 peak times the wall time of the engine's ticks, in %."""
    c = run.counts
    if run.peak is None or not c["tick_wall_s"]:
        return None
    flops = work.token_flops(run.family, run.config["model"],
                             c["prefill_tokens"] + c["decode_lanes"],
                             c["decode_lanes"] + c["first_tokens"])
    return 100.0 * flops / (run.peak["bf16_flops_per_s"] * c["tick_wall_s"])


def device_idle_share(run):
    """Share of the traced window in which no operation ran, in %."""
    if run.trace is None:
        return None
    return 100.0 * run.trace.idle_share


def prefill_ms_per_call(run):
    """Device time of one chunked-prefill call, in ms."""
    per_call = _program_call_s(run, "prefill")
    return None if per_call is None else 1e3 * per_call
