"""Metric arithmetic: work counts, percentiles, due-time TTFT, traffic."""
import json
from types import SimpleNamespace

import numpy as np
import pytest

from bench import harness, readers, spec, stats, work
from bench.traffic import Mix, quantile_lengths
from conftest import ROOT


def config(name):
    return json.loads((ROOT / "bench" / "configs" / f"{name}.json").read_text())


def family(name):
    return spec.family(config(name)["model"])


# hand counts: per block 5 D^2 + 2 D F matmul weights, head D V
HAND = {
    "rwkv4-169m-w8": dict(layer=5 * 768**2 + 2 * 768 * 3072,      # 7,667,712
                          matmul=130_625_280, head=768 * 50277),
    "rwkv4-1b5-w8": dict(layer=5 * 2048**2 + 2 * 2048 * 8192,     # 54,525,952
                         matmul=1_411_590_144, head=2048 * 50277),
}


@pytest.mark.parametrize("name", sorted(HAND))
def test_weights_and_flops(name):
    model, fam = config(name)["model"], family(name)
    hand = HAND[name]
    assert fam.layer_matmul_weights(model) == hand["layer"]
    assert fam.head_weights(model) == hand["head"]
    assert work.matmul_weights(fam, model) == hand["matmul"]
    # a decode token: every matmul weight twice, head included
    assert work.token_flops(fam, model, 1, 1) == 2 * hand["matmul"]
    # a prompt token that produces no logits skips the head
    assert work.token_flops(fam, model, 1, 0) == \
        2 * (hand["matmul"] - hand["head"])


def test_decode_step_bytes_169m():
    cfg = config("rwkv4-169m-w8")
    D, F, V, L = 768, 3072, 50277, 12
    weights = 130_625_280 + 4 * (5 * D + F + V) + 2 * (11 * D * L + 4 * D)
    lane = 2 * D + 2 * (2 * L * 5 * D) + 2 * V
    fam = family("rwkv4-169m-w8")
    assert work.decode_step_bytes(fam, cfg, 0) == weights == 131_062_932
    assert work.decode_step_bytes(fam, cfg, 32) == weights + 32 * lane \
        == 140_228_052


def test_decode_step_bytes_1b5():
    cfg = config("rwkv4-1b5-w8")
    D, F, V, L = 2048, 8192, 50277, 24
    weights = 1_411_590_144 + 4 * (5 * D + F + V) + 2 * (11 * D * L + 4 * D)
    lane = 2 * D + 2 * (2 * L * 5 * D) + 2 * V
    fam = family("rwkv4-1b5-w8")
    assert work.decode_step_bytes(fam, cfg, 32) == weights + 32 * lane
    assert work.decode_step_bytes(fam, cfg, 32) == 1_447_768_788


def test_prefill_call_bytes_1b5():
    """Every stored weight, 32 lanes' state read and written, and the
    absorbed tokens' embedding rows, all by hand."""
    cfg = config("rwkv4-1b5-w8")
    D, F, V, L = 2048, 8192, 50277, 24
    weights = 1_411_590_144 + 4 * (5 * D + F + V) + 2 * (11 * D * L + 4 * D)
    fam = family("rwkv4-1b5-w8")
    assert work.weight_bytes(fam, cfg) == weights
    assert work.prefill_call_bytes(fam, cfg, 1000) == \
        weights + 32 * 2 * (2 * L * 5 * D) + 1000 * 2 * D


def test_prefill_roofline_by_hand():
    """1,024 valid tokens a call at 1.5B: FLOPs-bound, 2 x 1,024 x the
    blocks' matmul weights at 197 TFLOP/s, over a 40 ms call."""
    cfg = config("rwkv4-1b5-w8")
    peak = work.peaks("TPU v5 lite")
    trace = SimpleNamespace(modules={"prefill": (10, 0.4)})
    run = harness.Run(cfg, family("rwkv4-1b5-w8"), peak,
                      {"prefill_calls": 4, "prefill_tokens": 4096}, trace)
    least = 2 * 1024 * 24 * 54_525_952 / 197e12
    assert readers.prefill_roofline(run) == pytest.approx(
        100 * least / 0.04, rel=1e-12)
    assert readers.prefill_roofline(
        harness.Run(cfg, run.family, peak, run.counts, None)) is None
    assert readers.prefill_roofline(harness.Run(
        cfg, run.family, peak, {"prefill_calls": 0, "prefill_tokens": 0},
        trace)) is None


def test_peaks_table():
    peak = work.peaks("TPU v5 lite")
    assert peak == {"bf16_flops_per_s": 197e12, "hbm_bytes_per_s": 819e9}
    with pytest.raises(KeyError):
        work.peaks("cpu")
    # 1.41 GB at 819 GB/s bounds a 1.5B decode step, not its FLOPs
    cfg, fam = config("rwkv4-1b5-w8"), family("rwkv4-1b5-w8")
    least = work.roofline_seconds(work.token_flops(fam, cfg["model"], 32, 32),
                                  work.decode_step_bytes(fam, cfg, 32), peak)
    assert least == pytest.approx(work.decode_step_bytes(fam, cfg, 32) / 819e9)


def test_nearest_rank():
    xs = list(range(1, 101))
    assert stats.nearest_rank(xs, 0.95) == 95
    assert stats.nearest_rank(xs, 1.0) == 100
    assert stats.nearest_rank([3, 1, 2, 4], 0.5) == 2
    assert stats.nearest_rank([7.0, 1.0, 5.0, 3.0, 9.0], 0.95) == 9.0
    assert stats.nearest_rank([2.5], 0.95) == 2.5
    with pytest.raises(ValueError):
        stats.nearest_rank([], 0.5)


def test_quartile_spread():
    assert stats.quartile_spread([1, 2, 3, 4, 5, 6]) == pytest.approx(
        (5.25 - 1.75) / 3.5)


def _req(due, first, submitted=None):
    return harness.Req(item=0, handle=None, due=due,
                       submitted=due if submitted is None else submitted,
                       seen=1 if first is not None else 0, first=first)


def test_ttft_from_due_time_with_censoring():
    """TTFT runs from when the request was due; one still without a first
    token at the window's close counts with its age then; requests due
    outside the window do not count."""
    client = SimpleNamespace(
        open=10.0, close=20.0, window_tokens=50, gaps=[0.01] * 19 + [0.5],
        done=[_req(9.0, 9.5), _req(10.0, 10.2, submitted=10.1),
              _req(11.0, 11.4)],
        live=dict(a=_req(19.0, None), b=_req(20.5, None)))
    out = harness.end_to_end(client, setup_s=3.0)
    # window samples: 0.2, 0.4 and the censored 20.0 - 19.0 = 1.0
    assert out["ttft_p95_ms"] == pytest.approx(1000.0)
    assert out["itl_p95_ms"] == pytest.approx(10.0)
    assert out["output_tokens_per_s"] == pytest.approx(5.0)
    assert out["setup_s"] == 3.0
    assert harness.lateness(client) == pytest.approx([0.1, 0.0, 0.0])


def test_quantile_lengths():
    lens = quantile_lengths({"dist": "log_uniform", "min": 64, "max": 2048},
                            1000)
    assert lens.min() >= 64 and lens.max() <= 2048
    assert np.median(lens) == pytest.approx(np.sqrt(64 * 2049), rel=0.02)
    uni = quantile_lengths({"dist": "uniform", "min": 16, "max": 64}, 49)
    assert sorted(uni) == list(range(16, 65))


@pytest.mark.parametrize("traffic", ["chat", "gen", "doc"])
def test_traffic_is_a_function_of_the_seed(traffic):
    t = json.loads((ROOT / "bench" / "traffic" / f"{traffic}.json")
                   .read_text())
    a = Mix(t, 3_000_000_001, 10, 50277)
    b = Mix(t, 3_000_000_001, 10, 50277)
    c = Mix(t, 17, 10, 50277)
    assert a.items == b.items
    assert a.items != c.items
    # another seed orders the same sizes (and gaps) differently
    for size in (lambda i: len(i.prompt), lambda i: i.max_new_tokens):
        assert sorted(map(size, a.items)) == sorted(map(size, c.items))
        assert list(map(size, a.items)) != list(map(size, c.items))
    if t["loop"] == "open":
        gaps = lambda m: np.sort(np.diff([0.0] + [i.due for i in m.items]))
        assert gaps(a) == pytest.approx(gaps(c))
    assert all(0 <= tok < 50277 for i in a.items[:50] for tok in i.prompt)
