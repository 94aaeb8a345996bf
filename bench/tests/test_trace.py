"""The trace reduction, on a hand-built trace with a known answer."""
import pytest
from jax.profiler import ProfileData

from bench.trace import WINDOW_SPAN, op_name, self_times, summarize

# times in ns; the device's op line, its module line, and the host spans.
# Window [1000, 11000].  Ops: a while op [1000, 4000] holding two kernels
# [1500, 2500] and [2500, 3500]; a decode kernel [6000, 7000]; one op
# [10500, 12000] that the window cuts at 11000.  Busy: [1000, 4000],
# [6000, 7000], [10500, 11000] = 4500 ns; idle 5500 ns.
EVENTS = {
    "XLA Ops": [("%while.3 = (f32[2]) while(...)", 1000, 3000),
                ("%wkv4_pallas.7 = f32[2] custom-call(...)", 1500, 1000),
                ("%dpot_chunk_matmul.55 = bf16[2] custom-call(...)",
                 2500, 1000),
                ("%decode.1 = bf16[2] custom-call(...)", 6000, 1000),
                ("%fusion.2 = f32[2] fusion(...)", 10500, 1500)],
    "XLA Modules": [("jit_prefill(123)", 1000, 3000),
                    ("jit_decode(456)", 6000, 1000),
                    ("jit_decode(456)", 10500, 1500)],
}
HOST = [(WINDOW_SPAN, 1000, 10000), ("engine.step", 3900, 3300),
        ("decode_call", 5800, 300), ("idle_sleep", 8000, 2400)]


def _plane(pid, name, lines, names):
    out = [f'planes {{ id: {pid} name: "{name}"']
    for lid, (line, events) in enumerate(lines.items(), 1):
        out.append(f'lines {{ id: {lid} name: "{line}" timestamp_ns: 0')
        for ev, start, dur in events:
            mid = names.setdefault(ev, len(names) + 1)
            out.append(f"events {{ metadata_id: {mid} "
                       f"offset_ps: {start * 1000} duration_ps: {dur * 1000} }}")
        out.append("}")
    for ev, mid in names.items():
        out.append(f'event_metadata {{ key: {mid} value {{ id: {mid} '
                   f'name: "{ev}" }} }}')
    out.append("}")
    return "\n".join(out)


@pytest.fixture(scope="module")
def summary():
    text = (_plane(1, "/device:TPU:0", EVENTS, {})
            + _plane(2, "/host:CPU", {"python3": HOST}, {}))
    return summarize(ProfileData.from_text_proto(text),
                     ("engine.step", "decode_call", "idle_sleep"))


def test_busy_union_and_idle_share(summary):
    assert summary.window_s == pytest.approx(10e-6)
    assert summary.busy_s == pytest.approx(4.5e-6)
    assert summary.idle_share == pytest.approx(0.55)
    assert summary.devices == 1


def test_program_device_time(summary):
    # calls that start inside the window, with their whole duration
    assert summary.modules["prefill"] == (1, pytest.approx(3e-6))
    assert summary.modules["decode"] == (2, pytest.approx(2.5e-6))


def test_op_self_times(summary):
    ops = dict(summary.top_ops)
    assert ops["while"] == pytest.approx(1e-6)     # 3000 - 2 x 1000 nested
    assert ops["wkv4_pallas"] == pytest.approx(1e-6)
    assert ops["dpot_chunk_matmul"] == pytest.approx(1e-6)
    assert ops["decode"] == pytest.approx(1e-6)
    assert ops["fusion"] == pytest.approx(0.5e-6)  # cut by the window


def test_idle_gaps_named_by_host_span(summary):
    # gaps: [4000, 6000] (mid 5000: engine.step), [7000, 10500] (mid 8750:
    # idle_sleep); the innermost span covering the midpoint names a gap
    assert summary.idle_gaps == [["idle_sleep", pytest.approx(3.5e-6)],
                                 ["engine.step", pytest.approx(2e-6)]]


def test_self_times_nesting():
    out = self_times([(0, 10, "outer"), (2, 4, "inner"), (4, 6, "inner"),
                      (12, 13, "next")])
    assert out["outer"] == pytest.approx(6e-9)
    assert out["inner"] == pytest.approx(4e-9)
    assert out["next"] == pytest.approx(1e-9)


def test_op_name():
    assert op_name("%wkv4_pallas.7 = (f32[1]) custom-call(x)") == \
        "wkv4_pallas"
    assert op_name("%copy-start = (bf16[2]) copy-start(y)") == "copy-start"
