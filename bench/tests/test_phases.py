"""The phase reduction of a trace, on a hand-built trace with a known
answer: device-idle time split exactly among the innermost `serve.*`
span over each instant."""
import pytest
from jax.profiler import ProfileData

from bench.phases import OUTSIDE, innermost, reduce, split
from bench.trace import WINDOW_SPAN
from test_trace import _plane

# times in ns.  Window [1000, 11000]; the device runs [1000, 2000] and
# [5000, 6000], so it is idle over [2000, 5000] and [6000, 11000]: 8000 ns.
OPS = [("%fusion.1 = f32[2] fusion(...)", 1000, 1000),
       ("%fusion.2 = bf16[2] fusion(...)", 5000, 400),
       ("%fusion.3 = bf16[2] fusion(...)", 5400, 600)]
MODULES = [("jit_prefill(1)", 1000, 1000), ("jit_decode(2)", 5000, 1000)]
HOST = [(WINDOW_SPAN, 1000, 10000),
        ("engine.step", 1400, 8100),        # harness spans
        ("decode_call", 1800, 1100),
        ("idle_sleep", 9600, 1200),
        ("serve.tick", 1500, 7500),         # program spans
        ("serve.decode", 1600, 6400),
        ("serve.dispatch", 1700, 1300),
        ("serve.fetch", 3000, 1500),
        ("serve.sample", 6200, 800),
        ("serve.emit", 7000, 500)]
# the innermost span over each idle piece (a program span before a
# harness span, however deep the harness span lies)
WANT_NS = {"serve.dispatch": 1000,      # [2000, 3000], under decode_call
           "serve.fetch": 1500,         # [3000, 4500]
           "serve.decode": 1200,        # [4500, 5000], [6000, 6200],
                                        # [7500, 8000]
           "serve.sample": 800,
           "serve.emit": 500,
           "serve.tick": 1000,          # [8000, 9000]
           "engine.step": 500,          # [9000, 9500]
           "idle_sleep": 1200,
           OUTSIDE: 300}                # [9500, 9600], [10800, 11000]


@pytest.fixture(scope="module")
def phases():
    text = (_plane(1, "/device:TPU:0",
                   {"XLA Ops": OPS, "XLA Modules": MODULES}, {})
            + _plane(2, "/host:CPU", {"python3": HOST}, {}))
    return reduce(ProfileData.from_text_proto(text),
                  ("engine.step", "decode_call", "idle_sleep"))


def test_idle_split_exactly_among_innermost_spans(phases):
    assert phases.window_s == pytest.approx(10e-6)
    assert phases.idle_s == pytest.approx(8e-6)
    assert phases.idle_by_span == {k: pytest.approx(v * 1e-9)
                                   for k, v in WANT_NS.items()}


def test_parts_sum_to_the_idle_time(phases):
    assert sum(phases.idle_by_span.values()) == pytest.approx(
        phases.idle_s, rel=1e-12)


def test_program_spans_name_the_longest_gaps(phases):
    # [6000, 11000] (mid 8500: serve.tick), [2000, 5000] (mid 3500:
    # serve.fetch)
    assert phases.idle_gaps == [["serve.tick", pytest.approx(5e-6)],
                                ["serve.fetch", pytest.approx(3e-6)]]


def test_span_counts_ticks_and_per_tick(phases):
    assert phases.ticks == 1
    assert phases.spans["serve.fetch"] == [1, pytest.approx(1.5e-6)]
    assert set(phases.spans) == {n for n, *_ in HOST
                                 if n.startswith("serve.")}
    assert phases.per_tick_ms(["serve.admit", "serve.inputs",
                               "serve.dispatch"]) == pytest.approx(1e-3)
    # idle under a phase span over idle under any program span
    assert phases.phase_share() == pytest.approx(3800 / 6000)


def test_innermost_and_split_by_hand():
    spans = [(0, 10, "engine.step"), (2, 8, "serve.tick"),
             (3, 5, "decode_call"), (4, 6, "serve.fetch")]
    segs = innermost(spans, 0, 12)
    # cut at every edge, decode_call's too, though it never wins
    assert segs == [(0, 2, "engine.step"), (2, 3, "serve.tick"),
                    (3, 4, "serve.tick"), (4, 5, "serve.fetch"),
                    (5, 6, "serve.fetch"), (6, 8, "serve.tick"),
                    (8, 10, "engine.step"), (10, 12, OUTSIDE)]
    got = split([(1, 5), (7, 11)], segs)
    assert got == {"engine.step": 3, "serve.tick": 3, "serve.fetch": 1,
                   OUTSIDE: 1}
