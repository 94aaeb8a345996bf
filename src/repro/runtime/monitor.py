"""Fault-tolerance + serving-telemetry runtime (host-side; no device code).

At thousands-of-nodes scale the failure model is: some host stops making
progress (hardware fault, preemption, network partition) or makes progress
anomalously slowly (straggler).  JAX SPMD programs cannot "route around" a
dead participant mid-step — the recovery unit is the *job*: detect, restore
the latest checkpoint onto the surviving topology (elastic reshard), resume.
This module provides the detection half plus a supervisor loop implementing
that policy, testable in-process via FailureInjector.

  ServingCounters   — throughput/latency telemetry for the continuous-
                      batching engine (repro.serving): tokens/s, TTFT
                      (with its prefill decomposition: per-request prefill
                      ticks, admit -> first-token wall time, and the
                      prefix-cache probe/state-copy slices split out so a
                      cache hit's TTFT is attributed honestly), per-token
                      inter-token-latency samples with p50/p90/p99 TTFT
                      and ITL in `snapshot()`, SLO-layer outcome counters
                      (shed / deadline-evicted / backpressured / cache
                      errors / budget-deferred prefill tokens), prefix-
                      cache hit/miss/eviction/spill counts with cached-vs-
                      prefilled token accounting, per-request latency,
                      slot occupancy
  HeartbeatMonitor  — per-host last-seen tracking with a dead-host predicate
  StragglerDetector — per-step duration EMA; flags hosts slower than
                      `threshold` x the fleet median (mitigation hook: the
                      caller re-balances or excludes the host at the next
                      restart boundary)
  FailureInjector   — deterministic fault schedule for training drills
  ServingFaultInjector — tick-indexed fault schedule for the serving
                      scheduler (cache-probe failures, forced evictions —
                      including from inside a token callback, i.e. mid-
                      speculation — forced deadline expiry, and the
                      crash-safety drills: in-process/SIGKILL crashes,
                      torn snapshot writes, poisoned state lanes)
  DegradedMode      — typed telemetry event for an automatic path
                      fallback (a repeatedly-faulting fused decode or
                      prefill path demoted to its per-op twin)
  EngineCrash       — the injected in-process crash (crash_at_tick)
  TrainingSupervisor— retry-with-restore driver around a step function
  span / trace_gc   — host spans on the profiler's clock: the serving
                      scheduler names each phase of a tick `serve.*`,
                      and `trace_gc` puts every Python collection under
                      `serve.gc`, so a device trace can say what the host
                      was doing while the chip waited

`ServingCounters` also exposes `state_dict()`/`load_state()` so the
serving snapshot layer (repro.serving.snapshot) can carry telemetry
across a crash: a restored engine's counters continue from the
snapshot, with per-request wall-clock anchors rebased onto the new
process's clock.
"""
from __future__ import annotations

import collections
import dataclasses
import gc
import math
import time
from typing import Any, Callable, Optional

from jax.profiler import TraceAnnotation


def span(name: str) -> TraceAnnotation:
    """A host span named `name`, recorded into the profiler's trace (the
    `.xplane.pb` that holds the device's events, on the same clock) while
    a profiler session is active.  With none active it costs about a
    microsecond, so spans stay on with no switch."""
    return TraceAnnotation(name)


class _GcSpans:
    """`gc.callbacks` hook: a `serve.gc` span from each collection's
    start to its stop."""

    def __init__(self):
        self._open = None

    def __call__(self, phase: str, info: dict):
        if phase == "start":
            self._open = span("serve.gc")
            self._open.__enter__()
        elif self._open is not None:
            self._open.__exit__(None, None, None)
            self._open = None


def trace_gc():
    """Install the `serve.gc` collection hook, once per process."""
    if not any(isinstance(cb, _GcSpans) for cb in gc.callbacks):
        gc.callbacks.append(_GcSpans())


def percentile(xs: list, q: float) -> float:
    """Nearest-rank percentile (q in [0, 1]) — 0.0 on an empty sample.

    Nearest-rank (not interpolated) so a p99 over latency samples is an
    actually-observed latency, never an average of two."""
    if not xs:
        return 0.0
    s = sorted(xs)
    return s[min(len(s) - 1, max(0, math.ceil(q * len(s)) - 1))]


class ServingCounters:
    """Serving-engine telemetry. The engine calls the on_* hooks; callers
    read `snapshot()` — a plain dict safe to log/export.  Timestamps use an
    injectable clock so tests are deterministic."""

    def __init__(self, clock: Callable[[], float] = time.monotonic):
        self._clock = clock
        self.t_start = clock()
        self.prefill_tokens = 0
        self.decode_tokens = 0
        self.ticks = 0
        self.admitted = 0
        self.finished = 0
        self.cancelled = 0
        self.peak_active = 0
        self.peak_queued = 0
        self._enqueue_t: dict[int, float] = {}
        self._admit_t: dict[int, float] = {}
        self._prefill_ticks: dict[int, int] = {}
        self.ttft_s: list[float] = []      # enqueue -> first token
        self.queue_wait_s: list[float] = []  # enqueue -> admission
        self.latency_s: list[float] = []   # enqueue -> completion
        # time-to-first-token decomposition: how many prefill calls each
        # request's prompt took, and the admit -> first-token wall time
        # (the part of TTFT the prefill path controls — queueing excluded).
        # prefill_s EXCLUDES the prefix-cache probe and state-copy time,
        # which land in their own lists below: attributing the whole admit
        # tick to "prefill" would make a cache hit look like prefill work.
        self.prefill_ticks: list[int] = []
        self.prefill_s: list[float] = []
        # prefix-cache telemetry (repro.serving.prefix_cache): probe
        # outcomes + token accounting from the scheduler, eviction/spill
        # flow from the cache itself
        self.cache_hits = 0
        self.cache_misses = 0
        self.cache_inserts = 0
        self.cache_evictions = 0
        self.cache_spills = 0
        self.cached_tokens = 0          # prompt tokens restored, not run
        self.cache_probe_s: list[float] = []
        self.state_copy_s: list[float] = []
        self._admit_overhead: dict[int, float] = {}  # rid -> probe+copy s
        # self-speculative decode telemetry (repro.serving scheduler's
        # _spec_tick): drafted counts every token the drafter proposed,
        # accepted the ones the verifier confirmed AND the lane consumed,
        # rejected the rest — acceptance_rate = accepted / drafted is the
        # one number that says whether a (K, draft_depth) choice pays
        self.drafted_tokens = 0
        self.accepted_tokens = 0
        self.rejected_tokens = 0
        self.spec_ticks = 0             # per-lane window walks, not ticks
        # SLO-layer telemetry (repro.serving.slo): per-token inter-token
        # latency samples (gap between a lane's consecutive emitted
        # tokens — THE user-visible jitter the prefill budget bounds),
        # explicit-overload outcome counters, and robustness counters.
        self.itl_s: list[float] = []
        self._last_token_t: dict[int, float] = {}
        self.shed = 0
        self.deadline_evicted = 0
        self.backpressured = 0
        self.cache_errors = 0
        self.budget_deferred_tokens = 0
        # crash-safety telemetry (repro.serving.snapshot): snapshot writes
        # and their synchronous capture wall time, restores and the lanes
        # they resumed, sentinel quarantines, integrity-checksum failures,
        # and fused-path demotions (with their typed DegradedMode events)
        self.snapshots_written = 0
        self.snapshot_wall_s: list[float] = []
        self.restores = 0
        self.resumed_lanes = 0
        self.quarantined_lanes = 0
        self.checksum_failures = 0
        self.path_fallbacks = 0
        self.degraded_events: list[dict] = []
        # occupancy accumulators: mean active lanes / queue depth per tick
        # give the bench its latency-vs-occupancy axis
        self._active_sum = 0
        self._queued_sum = 0
        # device program calls of the plain (non-speculative) tick: decode
        # calls with the lanes they decoded out of the lanes they offered,
        # prefill calls, and the summed wall time of the ticks
        self.decode_calls = 0
        self.decode_lanes = 0
        self.decode_slots = 0
        self.prefill_calls = 0
        self.tick_wall_s = 0.0

    def now(self) -> float:
        """The counters' clock (injectable) — the scheduler times its
        cache probe/copy slices on the same clock the latency samples
        use, so the decomposition is exact under a fake clock."""
        return self._clock()

    # -- hooks (called by the engine/scheduler) ----------------------------
    def on_enqueue(self, rid: int):
        self._enqueue_t[rid] = self._clock()

    def on_admit(self, rid: int):
        self.admitted += 1
        now = self._admit_t[rid] = self._clock()
        if rid in self._enqueue_t:
            self.queue_wait_s.append(now - self._enqueue_t[rid])

    def on_prefill(self, rid: int, n_tokens: int):
        """One prefill call absorbed `n_tokens` of request `rid`'s prompt."""
        self.prefill_tokens += n_tokens
        self._prefill_ticks[rid] = self._prefill_ticks.get(rid, 0) + 1

    def on_prefill_call(self):
        """One prefill program call (every prefilling lane's chunk)."""
        self.prefill_calls += 1

    def on_decode_call(self, lanes: int, slots: int):
        """One decode program call over `slots` pool lanes, `lanes` of
        them decoding."""
        self.decode_calls += 1
        self.decode_lanes += lanes
        self.decode_slots += slots

    def on_cache_probe(self, rid: int, *, hit: bool, n_cached: int = 0,
                       probe_s: float = 0.0, copy_s: float = 0.0):
        """One prefix-cache probe at request `rid`'s admission: outcome,
        tokens restored from the hit state (0 on miss), and the wall time
        of the probe and of the state copy into the slot.  Probe+copy are
        subtracted from the request's `prefill_s` sample — they are cache
        time, not prefill time."""
        if hit:
            self.cache_hits += 1
            self.cached_tokens += n_cached
        else:
            self.cache_misses += 1
        self.cache_probe_s.append(probe_s)
        if hit:
            self.state_copy_s.append(copy_s)
        self._admit_overhead[rid] = \
            self._admit_overhead.get(rid, 0.0) + probe_s + copy_s

    def on_cache_insert(self):
        self.cache_inserts += 1

    def on_cache_evict(self):
        self.cache_evictions += 1

    def on_cache_spill(self):
        self.cache_spills += 1

    def on_speculate(self, rid: int, *, drafted: int, accepted: int):
        """One lane finished one speculative window walk: the drafter
        proposed `drafted` tokens, the verifier confirmed `accepted` of
        them (0 <= accepted <= drafted; the window's base token is not a
        draft and is not counted).  Emitted-token accounting stays with
        `on_token` — speculation changes how many decode tokens a tick
        produces, not what a token is."""
        del rid
        self.spec_ticks += 1
        self.drafted_tokens += drafted
        self.accepted_tokens += accepted
        self.rejected_tokens += drafted - accepted

    def on_token(self, rid: int, *, first: bool = False):
        self.decode_tokens += 1
        now = self._clock()
        if first:
            if rid in self._enqueue_t:
                self.ttft_s.append(now - self._enqueue_t[rid])
            t_admit = self._admit_t.pop(rid, None)
            if t_admit is not None:
                self.prefill_s.append(now - t_admit -
                                      self._admit_overhead.pop(rid, 0.0))
            self.prefill_ticks.append(self._prefill_ticks.pop(rid, 0))
        else:
            t_prev = self._last_token_t.get(rid)
            if t_prev is not None:
                self.itl_s.append(now - t_prev)
        self._last_token_t[rid] = now

    def on_finish(self, rid: int):
        self.finished += 1
        t0 = self._enqueue_t.pop(rid, None)
        if t0 is not None:
            self.latency_s.append(self._clock() - t0)
        self._last_token_t.pop(rid, None)

    def _drop(self, rid: int):
        """Forget a request that will never complete (cancel/shed/
        deadline): no latency sample, no stale per-rid state."""
        self._enqueue_t.pop(rid, None)
        self._admit_t.pop(rid, None)
        self._prefill_ticks.pop(rid, None)
        self._admit_overhead.pop(rid, None)
        self._last_token_t.pop(rid, None)

    def on_cancel(self, rid: int):
        """Evicted before completion: not a completion, no latency sample."""
        self.cancelled += 1
        self._drop(rid)

    def on_shed(self, rid: int):
        """Dropped from the queue by the shed overload policy."""
        self.shed += 1
        self._drop(rid)

    def on_deadline_evict(self, rid: int):
        """Deadline exceeded (queued or in-flight): evicted, not finished."""
        self.deadline_evicted += 1
        self._drop(rid)

    def on_backpressure(self):
        """An `enqueue` was refused with `Overloaded` (queue full)."""
        self.backpressured += 1

    def on_cache_error(self):
        """A prefix-cache probe/insert raised; serving degraded to a miss
        instead of dying — counted so faults are observable."""
        self.cache_errors += 1

    def on_budget_defer(self, n_tokens: int):
        """The prefill budget deferred `n_tokens` of ready prompt chunks
        to a later tick (lanes left out of this tick's prefill call)."""
        self.budget_deferred_tokens += n_tokens

    def on_snapshot(self, wall_s: float):
        """One engine snapshot committed to the store; `wall_s` is the
        SYNCHRONOUS capture time (host copies + checksum verify — the part
        decode actually waits on; the file write is async)."""
        self.snapshots_written += 1
        self.snapshot_wall_s.append(wall_s)

    def on_restore(self, *, resumed_lanes: int):
        """The engine was rebuilt from a snapshot, resuming
        `resumed_lanes` in-flight/queued requests."""
        self.restores += 1
        self.resumed_lanes += resumed_lanes

    def on_quarantine(self, rid: int):
        """A NaN/Inf state sentinel quarantined `rid`'s lane; the request
        is re-enqueued for a deterministic replay (its per-rid latency
        anchors reset with it — the requeue re-arms them)."""
        self.quarantined_lanes += 1
        self._drop(rid)

    def on_checksum_failure(self, n: int = 1):
        """Integrity sentinels found `n` corrupt weight planes."""
        self.checksum_failures += n

    def on_path_fallback(self, event):
        """A repeatedly-faulting fused path was demoted to its per-op
        twin; `event` is the typed `DegradedMode` record."""
        self.path_fallbacks += 1
        self.degraded_events.append(dataclasses.asdict(event)
                                    if dataclasses.is_dataclass(event)
                                    else dict(event))

    def on_tick(self, *, active: int, queued: int, wall_s: float = 0.0):
        self.ticks += 1
        self.tick_wall_s += wall_s
        self.peak_active = max(self.peak_active, active)
        self.peak_queued = max(self.peak_queued, queued)
        self._active_sum += active
        self._queued_sum += queued

    # -- export ------------------------------------------------------------
    def snapshot(self) -> dict:
        dt = max(self._clock() - self.t_start, 1e-9)
        mean = lambda xs: sum(xs) / len(xs) if xs else 0.0
        return {
            "elapsed_s": dt,
            "ticks": self.ticks,
            "admitted": self.admitted,
            "finished": self.finished,
            "cancelled": self.cancelled,
            "prefill_tokens": self.prefill_tokens,
            "decode_tokens": self.decode_tokens,
            "decode_tokens_per_s": self.decode_tokens / dt,
            "total_tokens_per_s":
                (self.prefill_tokens + self.decode_tokens) / dt,
            "mean_ttft_s": mean(self.ttft_s),
            "ttft_p50_s": percentile(self.ttft_s, 0.50),
            "ttft_p90_s": percentile(self.ttft_s, 0.90),
            "ttft_p99_s": percentile(self.ttft_s, 0.99),
            "mean_itl_s": mean(self.itl_s),
            "itl_p50_s": percentile(self.itl_s, 0.50),
            "itl_p90_s": percentile(self.itl_s, 0.90),
            "itl_p99_s": percentile(self.itl_s, 0.99),
            "mean_latency_s": mean(self.latency_s),
            "latency_p99_s": percentile(self.latency_s, 0.99),
            "shed": self.shed,
            "deadline_evicted": self.deadline_evicted,
            "backpressured": self.backpressured,
            "cache_errors": self.cache_errors,
            "budget_deferred_tokens": self.budget_deferred_tokens,
            "mean_active_slots": self._active_sum / self.ticks
                if self.ticks else 0.0,
            "mean_queue_depth": self._queued_sum / self.ticks
                if self.ticks else 0.0,
            "queue_wait_p50_s": percentile(self.queue_wait_s, 0.50),
            "queue_wait_p99_s": percentile(self.queue_wait_s, 0.99),
            "decode_calls": self.decode_calls,
            "decode_lanes": self.decode_lanes,
            "mean_decode_occupancy": self.decode_lanes / self.decode_slots
                if self.decode_slots else 0.0,
            "prefill_calls": self.prefill_calls,
            "tick_wall_s": self.tick_wall_s,
            "mean_prefill_ticks": mean(self.prefill_ticks),
            "mean_prefill_s": mean(self.prefill_s),
            "peak_active_slots": self.peak_active,
            "peak_queue_depth": self.peak_queued,
            "cache_hits": self.cache_hits,
            "cache_misses": self.cache_misses,
            "cache_hit_rate": self.cache_hits /
                (self.cache_hits + self.cache_misses)
                if self.cache_hits + self.cache_misses else 0.0,
            "cache_inserts": self.cache_inserts,
            "cache_evictions": self.cache_evictions,
            "cache_spills": self.cache_spills,
            "cached_tokens": self.cached_tokens,
            "mean_cache_probe_s": mean(self.cache_probe_s),
            "mean_state_copy_s": mean(self.state_copy_s),
            "spec_ticks": self.spec_ticks,
            "drafted_tokens": self.drafted_tokens,
            "accepted_tokens": self.accepted_tokens,
            "rejected_tokens": self.rejected_tokens,
            "acceptance_rate": self.accepted_tokens / self.drafted_tokens
                if self.drafted_tokens else 0.0,
            "snapshots_written": self.snapshots_written,
            "snapshot_wall_s": mean(self.snapshot_wall_s),
            "restores": self.restores,
            "resumed_lanes": self.resumed_lanes,
            "quarantined_lanes": self.quarantined_lanes,
            "checksum_failures": self.checksum_failures,
            "path_fallbacks": self.path_fallbacks,
        }

    # -- snapshot/restore (repro.serving.snapshot) -------------------------

    _COUNTER_FIELDS = (
        "prefill_tokens", "decode_tokens", "ticks", "admitted", "finished",
        "cancelled", "peak_active", "peak_queued", "cache_hits",
        "cache_misses", "cache_inserts", "cache_evictions", "cache_spills",
        "cached_tokens", "drafted_tokens", "accepted_tokens",
        "rejected_tokens", "spec_ticks", "shed", "deadline_evicted",
        "backpressured", "cache_errors", "budget_deferred_tokens",
        "snapshots_written", "restores", "resumed_lanes",
        "quarantined_lanes", "checksum_failures", "path_fallbacks",
        "_active_sum", "_queued_sum", "decode_calls", "decode_lanes",
        "decode_slots", "prefill_calls", "tick_wall_s")
    _LIST_FIELDS = (
        "ttft_s", "queue_wait_s", "latency_s", "prefill_ticks", "prefill_s",
        "cache_probe_s", "state_copy_s", "itl_s", "snapshot_wall_s",
        "degraded_events")
    _TIME_DICT_FIELDS = (    # rid -> absolute clock time, rebased on load
        "_enqueue_t", "_admit_t", "_last_token_t")

    def state_dict(self) -> dict:
        """Everything `load_state` needs to continue this telemetry in a
        NEW process: plain JSON.  Absolute clock anchors (the per-rid
        enqueue/admit/last-token times and the run start) are stored as
        seconds-before-capture, so a restore on a different monotonic
        clock keeps elapsed/latency math consistent."""
        now = self._clock()
        out = {f: getattr(self, f) for f in self._COUNTER_FIELDS}
        out.update({f: list(getattr(self, f)) for f in self._LIST_FIELDS})
        out["elapsed_s"] = now - self.t_start
        for f in self._TIME_DICT_FIELDS:
            out[f] = {str(rid): now - t
                      for rid, t in getattr(self, f).items()}
        out["_prefill_ticks"] = {str(r): n
                                 for r, n in self._prefill_ticks.items()}
        out["_admit_overhead"] = {str(r): v
                                  for r, v in self._admit_overhead.items()}
        return out

    def load_state(self, state: dict):
        """Install a `state_dict` capture, rebasing clock anchors onto
        this counters object's own clock."""
        now = self._clock()
        # a capture from before a field existed leaves it as constructed
        for f in self._COUNTER_FIELDS:
            setattr(self, f, state.get(f, getattr(self, f)))
        for f in self._LIST_FIELDS:
            setattr(self, f, list(state.get(f, getattr(self, f))))
        self.t_start = now - state["elapsed_s"]
        for f in self._TIME_DICT_FIELDS:
            setattr(self, f, {int(r): now - ago
                              for r, ago in state[f].items()})
        self._prefill_ticks = {int(r): n
                               for r, n in state["_prefill_ticks"].items()}
        self._admit_overhead = {int(r): v
                                for r, v in state["_admit_overhead"].items()}


class HeartbeatMonitor:
    def __init__(self, hosts: list[int], timeout_s: float = 60.0,
                 clock: Callable[[], float] = time.monotonic):
        self.timeout_s = timeout_s
        self._clock = clock
        self._last = {h: clock() for h in hosts}

    def beat(self, host: int, at: Optional[float] = None):
        self._last[host] = self._clock() if at is None else at

    def dead_hosts(self) -> list[int]:
        now = self._clock()
        return [h for h, t in self._last.items()
                if now - t > self.timeout_s]

    def alive_hosts(self) -> list[int]:
        dead = set(self.dead_hosts())
        return [h for h in self._last if h not in dead]


class StragglerDetector:
    """EMA of per-host step durations; flags hosts above threshold x median."""

    def __init__(self, hosts: list[int], *, alpha: float = 0.2,
                 threshold: float = 1.5, warmup_steps: int = 5):
        self.alpha = alpha
        self.threshold = threshold
        self.warmup_steps = warmup_steps
        self._ema = {h: None for h in hosts}
        self._n = collections.Counter()

    def record(self, host: int, duration_s: float):
        prev = self._ema[host]
        self._ema[host] = (duration_s if prev is None
                           else self.alpha * duration_s +
                           (1 - self.alpha) * prev)
        self._n[host] += 1

    def stragglers(self) -> list[int]:
        vals = [(h, e) for h, e in self._ema.items()
                if e is not None and self._n[h] >= self.warmup_steps]
        if len(vals) < 3:
            return []
        ordered = sorted(e for _, e in vals)
        median = ordered[len(ordered) // 2]
        return [h for h, e in vals if e > self.threshold * median]


@dataclasses.dataclass
class FailureInjector:
    """step -> host failures, for drills. `check(step)` raises HostFailure."""

    schedule: dict[int, list[int]] = dataclasses.field(default_factory=dict)
    enabled: bool = True

    def check(self, step: int):
        if self.enabled and step in self.schedule:
            hosts = self.schedule.pop(step)
            raise HostFailure(step=step, hosts=hosts)


class HostFailure(RuntimeError):
    def __init__(self, step: int, hosts: list[int]):
        super().__init__(f"hosts {hosts} failed at step {step}")
        self.step = step
        self.hosts = hosts


@dataclasses.dataclass(frozen=True)
class DegradedMode:
    """Typed telemetry event for an automatic path fallback: the serving
    scheduler demoted a repeatedly-faulting fused `kind` ("decode" or
    "prefill") path to its per-op `PathDescriptor` twin.  Streams are
    unchanged — per-op and fused paths are bit-identical by construction
    — so a demotion costs throughput, never correctness; the event makes
    the degradation observable (`ServingCounters.degraded_events`)."""
    kind: str           # "decode" | "prefill"
    tick: int           # scheduler tick the demotion happened on
    failures: int       # consecutive failures that triggered it
    from_path: str      # the demoted PathDescriptor name
    to_path: str        # the twin now serving ("per_op")
    error: str          # repr of the last exception


class EngineCrash(RuntimeError):
    """The injected in-process serving crash (`crash_at_tick`): raised at
    the top of the scheduled tick, BEFORE any of that tick's work — the
    crash point every committed snapshot must be consistent against."""

    def __init__(self, tick: int):
        super().__init__(f"injected engine crash at tick {tick}")
        self.tick = tick


@dataclasses.dataclass
class ServingFaultInjector:
    """Tick-indexed fault schedule for the serving scheduler — the
    serving-side sibling of `FailureInjector` (which targets training
    steps).  The scheduler drains `pop(tick)` at the top of each tick
    and applies the faults, so churn tests can force the nasty cases at
    exact points in a request's life:

      ("cache_probe_error", None) — the next prefix-cache probe raises;
          the scheduler must degrade to a miss, never crash or leak a
          lease.
      ("evict", rid)              — evict `rid` at the top of the tick
          (queued or in-flight), exercising mid-prefill cancellation.
      ("evict_on_token", rid)     — evict `rid` from INSIDE its next
          token callback, i.e. mid-tick / mid-speculation: drafts must
          be discarded and the tick must finish cleanly.
      ("deadline", rid)           — force `rid`'s deadline to expire
          now, whether or not it had one.
      ("crash_at_tick", None|"raise"|"sigkill") — kill the engine at the
          top of the tick: raise `EngineCrash` (default), or SIGKILL the
          process ("sigkill" — the CI crash-recovery smoke, nothing gets
          to flush).  Restore-from-snapshot must resume bit-identically.
      ("torn_snapshot_write", None) — the NEXT snapshot write is torn:
          a partial `.tmp-step_X` with no COMMIT, exactly what a crash
          mid-write leaves behind.  Restore must refuse it and fall back
          to the previous committed step.
      ("corrupt_state_leaf", rid) — poison `rid`'s live lane state with
          NaNs; the sentinel sweep must quarantine-and-requeue it
          without leaking the slot or any cache lease.

    `fired` records (tick, kind, payload) for every fault actually
    delivered, so tests can assert the drill ran."""

    schedule: dict[int, list[tuple[str, Any]]] = \
        dataclasses.field(default_factory=dict)
    enabled: bool = True
    fired: list[tuple[int, str, Any]] = \
        dataclasses.field(default_factory=list)

    KINDS = ("cache_probe_error", "evict", "evict_on_token", "deadline",
             "crash_at_tick", "torn_snapshot_write", "corrupt_state_leaf")

    def pop(self, tick: int) -> list[tuple[str, Any]]:
        if not self.enabled:
            return []
        faults = self.schedule.pop(tick, [])
        for kind, _ in faults:
            if kind not in self.KINDS:
                raise ValueError(f"unknown serving fault kind {kind!r}")
        self.fired.extend((tick, k, p) for k, p in faults)
        return list(faults)


class TrainingSupervisor:
    """Retry-with-restore driver.

    run(n_steps) calls `step_fn(step)`; on HostFailure it invokes
    `restore_fn(failed_hosts)` (which reloads the latest checkpoint, possibly
    onto a smaller/elastic mesh) and resumes from the step the restore
    reports.  Gives up after `max_restarts`.
    """

    def __init__(self, step_fn: Callable[[int], None],
                 restore_fn: Callable[[list[int]], int],
                 max_restarts: int = 3):
        self.step_fn = step_fn
        self.restore_fn = restore_fn
        self.max_restarts = max_restarts
        self.restarts = 0
        self.log: list[str] = []

    def run(self, n_steps: int, start_step: int = 0) -> int:
        step = start_step
        while step < n_steps:
            try:
                self.step_fn(step)
                step += 1
            except HostFailure as f:
                self.restarts += 1
                self.log.append(f"failure at step {f.step}: hosts {f.hosts}")
                if self.restarts > self.max_restarts:
                    raise
                step = self.restore_fn(f.hosts)
                self.log.append(f"restored, resuming at step {step}")
        return step
