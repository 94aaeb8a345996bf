"""The serving engine's own telemetry: `serve.*` host spans around each
phase of a tick (`runtime.monitor.span`) and the `ServingCounters` that
count where the work happens (queue wait, program calls, tick time).

Span order and counts are checked on a scheduler driven by a slot pool
without device state and stub programs, with `span` replaced by a
recorder; one test runs a real engine under `jax.profiler.trace` and
finds the spans in the written `.xplane.pb`."""
import contextlib
import gc

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.profiler import ProfileData

import repro.kernels.common as kernels_common
import repro.runtime.monitor as monitor
import repro.serving.scheduler as scheduler_mod
from repro.kernels.common import exact_jit
from repro.models.registry import get_model
from repro.runtime.monitor import ServingCounters, trace_gc
from repro.serving import Request, Scheduler, ServingEngine, build_plan
from test_slo import FakeClock, FakePool


class Programs:
    """Stub decode/prefill programs that count their calls the way the
    benchmark's probe does (calls, and the lanes each decode call's mask
    selects), and advance a fake clock by a fixed time per call."""

    def __init__(self, n_slots: int, clock: FakeClock, *, vocab: int = 5,
                 decode_s: float = 0.25, prefill_s: float = 1.0):
        self.n, self.vocab, self.clock = n_slots, vocab, clock
        self.decode_s, self.prefill_s = decode_s, prefill_s
        self.decode_calls = self.decode_lanes = self.prefill_calls = 0

    def decode(self, state, toks, mask):
        self.decode_calls += 1
        self.decode_lanes += int(np.count_nonzero(mask))
        self.clock.t += self.decode_s
        return np.zeros((self.n, 1, self.vocab), np.float32), state

    def prefill(self, state, toks, valid, fresh):
        self.prefill_calls += 1
        self.clock.t += self.prefill_s
        return state, np.zeros((self.n, 1, self.vocab), np.float32)


def _sched(n_slots=1, *, chunk=4, clock=None):
    clock = clock if clock is not None else FakeClock()
    progs = Programs(n_slots, clock)
    counters = ServingCounters(clock=clock)
    sched = Scheduler(FakePool(n_slots), progs.decode, progs.prefill,
                      prefill_chunk=chunk, counters=counters)
    return sched, progs, counters


def _req(rid, prompt, mnt):
    return Request(rid=rid, prompt=list(prompt), max_new_tokens=mnt)


class SpanRecorder:
    """Stands in for `monitor.span`: records (depth, name) as spans open."""

    def __init__(self):
        self.opened: list[tuple[int, str]] = []
        self.depth = 0

    @contextlib.contextmanager
    def __call__(self, name: str):
        self.opened.append((self.depth, name))
        self.depth += 1
        try:
            yield
        finally:
            self.depth -= 1

    def take(self) -> list[tuple[int, str]]:
        out, self.opened = self.opened, []
        return out


@pytest.fixture
def spans(monkeypatch):
    rec = SpanRecorder()
    monkeypatch.setattr(scheduler_mod, "span", rec)
    monkeypatch.setattr(kernels_common, "span", rec)
    monkeypatch.setattr(monitor, "span", rec)
    # an engine built earlier in this process installed the gc hook, which
    # would record its collections here too
    hooks = [cb for cb in gc.callbacks if isinstance(cb, monitor._GcSpans)]
    for cb in hooks:
        gc.callbacks.remove(cb)
    yield rec
    gc.callbacks.extend(hooks)


PHASES = ["serve.inputs", "serve.dispatch", "serve.fetch", "serve.sample",
          "serve.emit"]


# ---------------------------------------------------------------------------
# spans
# ---------------------------------------------------------------------------


def test_decode_tick_span_nesting_and_order(spans):
    sched, _, _ = _sched()
    sched.enqueue(_req(0, [1, 2], mnt=3))
    sched.tick()        # admits, prefills the whole prompt, decodes once
    assert spans.take() == (
        [(0, "serve.tick"), (1, "serve.admit"), (1, "serve.prefill")]
        + [(2, name) for name in PHASES]
        + [(1, "serve.decode")] + [(2, name) for name in PHASES])
    sched.tick()        # decode only
    assert spans.take() == ([(0, "serve.tick"), (1, "serve.admit"),
                             (1, "serve.decode")]
                            + [(2, name) for name in PHASES])
    assert spans.depth == 0


def test_prefill_only_tick_opens_no_decode_span(spans):
    sched, _, _ = _sched()
    sched.enqueue(_req(0, range(1, 9), mnt=1))   # two chunks of 4
    sched.tick()
    # no lane finished its prompt: nothing to fetch, sample or decode
    assert spans.take() == [(0, "serve.tick"), (1, "serve.admit"),
                            (1, "serve.prefill"), (2, "serve.inputs"),
                            (2, "serve.dispatch")]


def test_idle_tick_opens_only_tick_and_admit(spans):
    sched, _, _ = _sched()
    assert sched.tick() is False
    assert spans.take() == [(0, "serve.tick"), (1, "serve.admit")]


def test_compile_span_opens_only_on_a_cache_miss(spans):
    f = exact_jit(lambda x: x * 2)
    f(jnp.ones((3,), jnp.float32))
    f(jnp.zeros((3,), jnp.float32))             # same avals: a hit
    assert spans.take() == [(0, "serve.compile")]
    f(jnp.ones((4,), jnp.float32))              # a new shape compiles
    assert spans.take() == [(0, "serve.compile")]


def test_gc_span_brackets_each_collection(spans):
    hook = monitor._GcSpans()
    gc.callbacks.append(hook)
    try:
        gc.collect()
    finally:
        gc.callbacks.remove(hook)
    names = spans.take()
    assert names and set(names) == {(0, "serve.gc")}
    assert spans.depth == 0 and hook._open is None


def test_trace_gc_installs_one_hook():
    trace_gc()
    trace_gc()
    hooks = [cb for cb in gc.callbacks if isinstance(cb, monitor._GcSpans)]
    assert len(hooks) == 1


# ---------------------------------------------------------------------------
# counters
# ---------------------------------------------------------------------------


def test_queue_wait_is_enqueue_to_admission_on_the_counters_clock():
    clk = FakeClock()
    sched, progs, c = _sched(clock=clk)
    progs.prefill_s = progs.decode_s = 0.0
    sched.enqueue(_req(0, [1], mnt=1))
    clk.t = 1.0
    sched.enqueue(_req(1, [1], mnt=1))
    clk.t = 2.0
    sched.tick()        # rid 0 admitted (waited 2.0) and finished
    clk.t = 5.0
    sched.tick()        # rid 1 admitted into the freed slot (waited 4.0)
    assert c.queue_wait_s == [2.0, 4.0]
    snap = c.snapshot()
    assert snap["queue_wait_p50_s"] == 2.0
    assert snap["queue_wait_p99_s"] == 4.0


def test_program_call_counts_match_a_scripted_run():
    clk = FakeClock()
    sched, progs, c = _sched(2, clock=clk)
    sched.enqueue(_req(0, range(1, 7), mnt=3))  # 6-token prompt: 2 chunks
    sched.enqueue(_req(1, [1, 2], mnt=2))
    sched.run()
    # tick 1: one prefill call (rid 1 finishes its prompt), decode rid 1
    # (done); tick 2: prefill finishes rid 0, decode rid 0; tick 3:
    # decode rid 0 (done)
    assert (c.prefill_calls, c.decode_calls, c.decode_lanes) == (2, 3, 3)
    assert (c.prefill_calls, c.decode_calls, c.decode_lanes) == (
        progs.prefill_calls, progs.decode_calls, progs.decode_lanes)
    assert c.decode_slots == 3 * 2
    # the stubs are the only thing that moves the clock: 1.0 s a prefill
    # call and 0.25 s a decode call, all of it inside ticks
    assert c.tick_wall_s == 2 * 1.0 + 3 * 0.25
    snap = c.snapshot()
    assert snap["mean_decode_occupancy"] == 0.5
    assert (snap["decode_calls"], snap["decode_lanes"],
            snap["prefill_calls"]) == (3, 3, 2)
    assert snap["tick_wall_s"] == 2.75
    assert snap["ticks"] == 3


def test_state_roundtrip_keeps_the_new_fields():
    clk = FakeClock()
    sched, _, c = _sched(2, clock=clk)
    sched.enqueue(_req(0, range(1, 7), mnt=3))
    clk.t = 0.5
    sched.enqueue(_req(1, [1, 2], mnt=2))
    clk.t = 1.5
    sched.run()
    state = c.state_dict()
    fresh = ServingCounters(clock=FakeClock(100.0))
    fresh.load_state(state)
    assert fresh.queue_wait_s == c.queue_wait_s == [1.5, 1.0]
    for f in ("decode_calls", "decode_lanes", "decode_slots",
              "prefill_calls", "tick_wall_s"):
        assert getattr(fresh, f) == getattr(c, f)
    assert fresh.snapshot()["mean_decode_occupancy"] == \
        c.snapshot()["mean_decode_occupancy"]


def test_capture_from_before_the_new_fields_loads():
    c = ServingCounters()
    c.on_tick(active=1, queued=0)
    state = c.state_dict()
    for f in ("queue_wait_s", "decode_calls", "decode_lanes",
              "decode_slots", "prefill_calls", "tick_wall_s"):
        del state[f]
    fresh = ServingCounters()
    fresh.load_state(state)
    assert fresh.ticks == 1
    assert fresh.queue_wait_s == [] and fresh.decode_calls == 0


# ---------------------------------------------------------------------------
# the profiler's trace
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def rwkv4():
    model = get_model("rwkv4-169m", smoke=True)
    return model, model.init_params(jax.random.PRNGKey(0))


def test_w8_unpack_scope_reaches_compiled_metadata(rwkv4):
    """The per-op decode's weight unpack carries the `w8_unpack` name
    scope into the compiled program's op metadata, which a device trace
    shows as each operation's name stack."""
    model, params = rwkv4
    plan = build_plan(model, params, quantized=True)
    assert plan.decode_desc.name == "per_op"
    state = model.init_slot_state(2, plan.max_len, plan.state_dtype)
    hlo = jax.jit(plan._decode_step()).lower(
        plan.prepared.decode, state, jnp.zeros((2, 1), jnp.int32)
    ).compile().as_text()
    assert 'op_name="jit(<lambda>)/w8_unpack/' in hlo


def test_engine_spans_land_in_the_profiler_trace(rwkv4, tmp_path):
    model, params = rwkv4
    eng = ServingEngine(model, params=params, max_batch=2, prefill_chunk=4)
    with jax.profiler.trace(str(tmp_path)):
        eng.submit([1, 2, 3, 4, 5], max_new_tokens=3)
        snap = eng.run()
        gc.collect()
    assert snap["decode_calls"] == 2 and snap["prefill_calls"] == 2
    xplanes = sorted(tmp_path.rglob("*.xplane.pb"))
    assert xplanes
    names = {ev.name
             for plane in ProfileData.from_file(str(xplanes[-1])).planes
             for line in plane.lines for ev in line.events}
    assert {"serve.tick", "serve.admit", "serve.prefill", "serve.decode",
            "serve.compile", "serve.gc", *PHASES} <= names
