"""Continuous-batching scheduler: admission, chunked prefill, fused decode.

One `tick()` is the software analog of the paper's pipeline reordering
(PAPER.md §3: overlap data movement with computation so the datapath never
stalls).  Per tick the scheduler

  1. ADMITS queued requests into free pool slots — and, when a prefix
     cache is wired in (`repro.serving.prefix_cache`), probes it with the
     request's prompt: the longest cached ancestor prefix's state is
     copied into the slot via the pool's per-lane write machinery and
     only the uncached SUFFIX is prefilled (the slot starts at
     n_prefilled = hit length with its fresh-reset suppressed, so the
     prefill call advances the restored state instead of wiping it),
  2. advances EVERY prefilling slot by up to one fixed-size prompt chunk
     in ONE fused call (per-op: a jitted scan of `decode_step` over the
     whole pool; fused: the chunk-matmul + on-chip-WKV `prefill_chunk`
     path — bit-identical either way), with a per-slot-per-token validity
     mask so every prompt length and slot combination reuses the same
     compiled shape; newly admitted slots are reset to the fresh state
     inside the same call via a fresh-slot mask, and
  3. runs ONE fused decode step over the whole pool for all DECODE slots,
     with an active-slot mask selecting which lanes' states commit.

Because the pool, the chunk, and the fused step all have fixed shapes,
serving runs on exactly two device programs (fused prefill chunk +
fused decode step) no matter how requests arrive, finish, or interleave
— admission and retirement are pure host bookkeeping.  The prefix cache
rides the same two programs: a cache hit is a per-lane state write at
admission (the pool's traced-once `write_slot`), chunk-boundary capture
is a per-lane `read_slot`, and the resumed suffix prefills through the
unchanged chunk program at the same tick boundaries a full prefill would
have used — which is exactly why cached-state resume is bit-identical to
full prefill (tests/test_prefix_cache.py).  The scheduler
does not build (or select) those programs: it is handed the two
callables by the engine, which takes them from an `ExecutionPlan`'s
compiled-program cache (`repro.serving.plan`) — path choice, param
preparation and mesh placement all live there.  Under a mesh the
callables place each tick's token/mask arrays onto the data-parallel
sharding themselves; nothing here is sharding-aware.

Masking semantics: inactive lanes are *computed* (wasted flops, bought
deliberately — fixed shapes beat recompiles) but their state updates are
discarded via `where(mask, stepped, old)`, so a lane mid-prefill or free
is never disturbed by decode traffic.  Lane results are bitwise equal to
a batch-1 decode of the same sequence (verified in tests/test_scheduler).

SLO layer (repro.serving.slo): a `ServingSLO` adds priority/deadline/
cache-aware admission, a per-tick prefill budget, a bounded queue with
typed `Overloaded` backpressure or lowest-priority shedding, and a
`run()` hang watchdog.  The default `ServingSLO()` preserves historical
behavior: unbounded queue, no deadlines, unlimited budget (admission
order is unchanged too — with every priority equal and no cache hits the
selection scan degenerates to FIFO).  A `ServingFaultInjector`
(repro.runtime.monitor) can force cache-probe failures, evictions —
including from inside a token callback, i.e. mid-speculation — and
deadline expiry at chosen ticks; the churn tests drive every fault and
assert pool/lease/RNG invariants hold.
"""
from __future__ import annotations

import collections
import dataclasses
import os
import signal
import time
from typing import Callable, Optional

import numpy as np

from repro.kernels.common import CompileError
from repro.runtime.monitor import DegradedMode, EngineCrash, span
from repro.serving.slo import (SHED, Overloaded, SchedulerHang,
                               ServingSLO)


@dataclasses.dataclass
class Request:
    """One generation request (host-side; tokens are python ints).

    priority   — admission class, higher = more urgent (ties FIFO);
                 also the shed-victim order under overload.
    deadline_s — seconds from enqueue until the request is evicted with
                 outcome "deadline" (None = ServingSLO.default_deadline_s,
                 which itself defaults to no deadline)."""
    rid: int
    prompt: list[int]
    max_new_tokens: int = 16
    temperature: float = 0.0
    seed: int = 0
    eos_token: Optional[int] = None
    priority: int = 0
    deadline_s: Optional[float] = None


PREFILL, DECODE = "prefill", "decode"

FINISHED, CANCELLED, SHED_OUT, DEADLINE = \
    "finished", "cancelled", "shed", "deadline"


@dataclasses.dataclass
class _Slot:
    """Host metadata for one occupied pool slot."""
    req: Request
    phase: str = PREFILL
    fresh: bool = True              # lane still needs its state reset
    n_prefilled: int = 0
    next_token: int = -1            # token the next decode tick consumes
    generated: list[int] = dataclasses.field(default_factory=list)
    rng: Optional[np.random.Generator] = None
    # prefix-cache bookkeeping: tokens restored from a probe hit, the
    # prompt's rolling boundary digests (hashed once at enqueue), and
    # boundary states captured during prefill, published at completion
    cached_tokens: int = 0
    digests: Optional[dict] = None
    pending_inserts: list = dataclasses.field(default_factory=list)
    # speculation bookkeeping: the tokens the drafter proposed for the
    # CURRENT tick's verify window (tick-local; cleared at emission or on
    # eviction — a drafted token is never engine output until the verifier
    # confirms it)
    drafted: list[int] = dataclasses.field(default_factory=list)
    # SLO bookkeeping: admission sequence number (budget-ordering
    # tiebreak) and the absolute deadline inherited from the queue entry
    seq: int = 0
    deadline_t: Optional[float] = None


@dataclasses.dataclass
class _Queued:
    """Host metadata for one QUEUED request: enqueue order/tick (aging +
    FIFO tiebreaks), absolute deadline, and the prompt's cache digests
    (hashed once so per-tick admission peeks never re-hash)."""
    seq: int
    enqueue_tick: int
    deadline_t: Optional[float] = None
    digests: Optional[dict] = None


def sample_token(logits_row: np.ndarray, temperature: float,
                 rng: Optional[np.random.Generator]) -> int:
    """Greedy argmax at temperature<=0 (ties -> first index, matching
    jnp.argmax, which keeps the engine bit-compatible with the sequential
    loop); Gumbel-max sampling otherwise.  Single-row reference for
    `sample_tokens`, the batched form the scheduler's hot path uses."""
    if temperature <= 0.0 or rng is None:
        return int(np.argmax(logits_row))
    g = rng.gumbel(size=logits_row.shape)
    return int(np.argmax(logits_row.astype(np.float64) / temperature + g))


def greedy_accept(window_row, argmax_rows) -> tuple[list[int], int]:
    """The pure acceptance rule for ONE lane of a speculative tick.

    window_row  — the K verified tokens: the lane's pending token followed
                  by K-1 drafted tokens
    argmax_rows — the verifier's greedy choice after consuming each window
                  prefix (argmax of verify logits row j)

    Emission walks the window: position j's verifier choice e_j is EMITTED
    (it came from true logits — losslessness is unconditional), and the
    walk continues only while e_j confirms the NEXT drafted token.
    Returns (emitted tokens, window tokens consumed); consumed == j+1 and
    the accepted draft prefix is exactly the verifier argmax prefix — the
    property the hypothesis suite drives directly.  `Scheduler._spec_tick`
    follows this walk shape with sampling/retire/evict handling around
    it."""
    emitted, j, k = [], 0, len(window_row)
    while True:
        e = int(argmax_rows[j])
        emitted.append(e)
        if j + 1 < k and e == int(window_row[j + 1]):
            j += 1
            continue
        return emitted, j + 1


def sample_tokens(rows: np.ndarray, metas) -> np.ndarray:
    """Vectorized sampling for one tick's emitting slots.

    rows (n, V) are the slots' last-logits rows (f32), metas the matching
    `_Slot`s.  The Gumbel noise is still drawn from EACH SLOT'S OWN
    Generator — a seeded request's RNG stream consumes exactly the draws
    it would alone, in the same order, so its output never depends on who
    shares the tick — but the temperature scale, the noise add, and above
    all the argmax over the (n, V) block happen in single numpy calls
    instead of one call per slot.  Greedy rows ride the same batched
    argmax: the f32 -> f64 cast is exact, so ties resolve identically to
    `sample_token`'s per-row `np.argmax` (bit-stable either way)."""
    n, V = rows.shape
    sampling = [i for i, meta in enumerate(metas)
                if meta.req.temperature > 0.0 and meta.rng is not None]
    if not sampling:
        # all-greedy tick (the default): one f32 argmax, no temporaries
        return np.argmax(rows, axis=1)
    temps = np.ones((n, 1))
    noise = np.zeros((n, V))
    for i in sampling:
        temps[i, 0] = metas[i].req.temperature
        noise[i] = metas[i].rng.gumbel(size=V)
    # one vectorized scale+add+argmax over the whole block; /1.0 and +0.0
    # are exact, so greedy rows match their per-row argmax bit-for-bit
    return np.argmax(rows.astype(np.float64) / temps + noise, axis=1)


class Scheduler:
    """Drives a SlotStatePool with two compiled functions.

    decode_fn(pool_state, tokens (S,1) i32, mask (S,) bool)
        -> (logits (S,1,V), new_pool_state)           [fused, masked]
    prefill_fn(pool_state, tokens (S,C) i32, valid (S,C) bool,
               fresh (S,) bool)
        -> (new_pool_state, last_logits (S,1,V))      [fused, chunked]

    With `speculative=K` the decode tick is replaced by the speculative
    draft -> verify -> accept tick (`_spec_tick`), driven by three more
    plan programs instead of decode_fn:

    draft_fn(pool_state, tokens (S,1))    -> drafted (S, K-1) i32
    verify_fn(pool_state, tokens (S,K), valid (S,K))
        -> (logits (S,K,V), new_pool_state)           [commit-all, no
                                                       donation: input
                                                       state = snapshot]
    rollback_fn(committed, snapshot, reject (S,))     -> pool_state

    `slo` (a ServingSLO) layers admission control on top: priority +
    deadline + cache-aware selection, a bounded queue with `Overloaded`
    backpressure / lowest-priority shedding, a per-tick prefill lane
    budget (`prefill_quota`, lanes per tick — the engine derives it
    bucket-aware via `ExecutionPlan.prefill_quota`; left None the
    scheduler derives it from `slo.prefill_budget` itself), and the
    `run()` hang watchdog.  `on_finish` is called as
    `on_finish(req, outcome)` with outcome in {"finished", "cancelled",
    "shed", "deadline"}.  `fault_injector` (ServingFaultInjector) is
    drained at the top of every tick for fault drills.
    """

    def __init__(self, pool, decode_fn: Callable, prefill_fn: Callable, *,
                 prefill_chunk: int, counters=None,
                 on_token: Optional[Callable] = None,
                 on_finish: Optional[Callable] = None,
                 prefix_cache=None, cache_variant=None,
                 speculative: int = 0,
                 draft_fn: Optional[Callable] = None,
                 verify_fn: Optional[Callable] = None,
                 rollback_fn: Optional[Callable] = None,
                 slo: Optional[ServingSLO] = None,
                 prefill_quota: Optional[int] = None,
                 fault_injector=None, sentinel_every: int = 0,
                 on_requeue: Optional[Callable] = None,
                 fallback_decode: Optional[Callable] = None,
                 fallback_prefill: Optional[Callable] = None,
                 path_fault_limit: int = 2,
                 path_names: Optional[dict] = None):
        self.pool = pool
        self.decode_fn = decode_fn
        self.prefill_fn = prefill_fn
        self.prefill_chunk = int(prefill_chunk)
        self.counters = counters
        # self-speculative decode (repro.serving.plan.SpeculativePath):
        # with speculative=K >= 1 the decode tick becomes
        # draft -> verify -> accept, driven by the plan's three extra
        # programs.  draft_fn is only needed for K > 1 (K=1 is the
        # degenerate verify-only window).
        self.spec_k = int(speculative)
        self.draft_fn = draft_fn
        self.verify_fn = verify_fn
        self.rollback_fn = rollback_fn
        if self.spec_k:
            if verify_fn is None or rollback_fn is None:
                raise ValueError(
                    "speculative decode needs verify_fn and rollback_fn")
            if self.spec_k > 1 and draft_fn is None:
                raise ValueError(
                    f"speculative={self.spec_k} needs a draft_fn "
                    "(K=1 is the only drafterless window)")
        # tick-local speculation state, exposed for leak auditing: the
        # rollback snapshot and the lanes whose drafts are in flight.
        # ALWAYS empty between ticks (cleared in a finally) — the churn
        # invariant test asserts exactly that.
        self._spec_snapshot = None
        self._spec_inflight: dict[int, _Slot] = {}
        self.on_token = on_token or (lambda req, tok: None)
        self.on_finish = on_finish or (lambda req, outcome: None)
        self.slo = slo if slo is not None else ServingSLO()
        # prefill lane quota per tick (None = unlimited): prefer the
        # engine's bucket-aware ExecutionPlan.prefill_quota; standalone
        # construction derives the same whole-chunks / one-lane-floor
        # rule from the budget directly
        if prefill_quota is not None:
            self._prefill_quota: Optional[int] = int(prefill_quota)
        elif self.slo.prefill_budget > 0:
            self._prefill_quota = max(
                1, self.slo.prefill_budget // self.prefill_chunk)
        else:
            self._prefill_quota = None
        self.fault_injector = fault_injector
        # NaN/Inf sentinels: every `sentinel_every` ticks (0 = off) one
        # jitted reduction over the whole pool flags non-finite lanes;
        # a flagged lane is QUARANTINED — slot released, drafts and
        # staged cache inserts discarded — and its request requeued for
        # a from-scratch deterministic replay (`on_requeue` lets the
        # engine reset the request's handle first).  The re-enqueue
        # bypasses admission bounds: the request was already accepted
        # once and must not be lost to its own quarantine.
        self.sentinel_every = int(sentinel_every)
        self.on_requeue = on_requeue or (lambda req: None)
        # automatic path fallback (degraded mode): `fallback_decode` /
        # `fallback_prefill` are ZERO-ARG PROVIDERS (the engine passes
        # the plan's lazily-built per-op twins) invoked only at demotion
        # time.  After `path_fault_limit` CONSECUTIVE primary-program
        # failures the path is demoted for the life of the scheduler and
        # a DegradedMode event is recorded; below the limit the primary
        # is retried.  Retry/demote-and-rerun are only sound when the
        # failure was raised before the program consumed its donated
        # pool state (host wrapper errors, dispatch failures) — a
        # mid-execution device fault invalidates the donated buffers and
        # the rerun will surface that instead of corrupting state.
        self.fallback_decode = fallback_decode
        self.fallback_prefill = fallback_prefill
        self.path_fault_limit = int(path_fault_limit)
        self.path_names = path_names or {}
        self._path_failures: dict[str, int] = {}
        self._fallback_progs: dict[str, Optional[Callable]] = {}
        self._demoted: set[str] = set()
        # tick-boundary hooks, assigned post-construction by the engine:
        # `after_tick(tick_no)` fires after counters.on_tick — the
        # snapshot cadence lives there (repro.serving.snapshot);
        # `on_torn_snapshot(tick_no)` is the torn-write fault drill.
        self.after_tick: Optional[Callable] = None
        self.on_torn_snapshot: Optional[Callable] = None
        self._tick_no = 0
        self._seq = 0               # plain int: snapshots serialize it
        self._queued: dict[int, _Queued] = {}
        self._has_deadlines = False
        # monotone progress counter (admissions + prefill tokens +
        # emitted tokens + retirements/sheds): the run() watchdog's
        # wedge detector
        self._progress = 0
        # armed fault state (ServingFaultInjector)
        self._fail_next_probe = False
        self._evict_on_token: set[int] = set()
        # prefix cache (repro.serving.prefix_cache.PrefixCache) + the
        # CacheVariant this scheduler's states are filed under; both or
        # neither.  The cache's chunk granularity must equal
        # prefill_chunk — boundaries must be tick boundaries, or resumed
        # suffixes would re-chunk differently from a full prefill and
        # lose bit parity (the engine validates this at construction).
        self.prefix_cache = prefix_cache
        self.cache_variant = cache_variant
        if prefix_cache is not None and cache_variant is None:
            raise ValueError("prefix_cache needs a cache_variant")
        self.queue: collections.deque[Request] = collections.deque()
        self.slots: dict[int, _Slot] = {}

    # -- public ------------------------------------------------------------

    def enqueue(self, req: Request):
        """Queue a request for admission.  With a bounded queue
        (`AdmissionPolicy.max_queue`) a full queue either raises
        `Overloaded` (backpressure — the request was NOT accepted) or
        sheds the lowest-effective-priority queued request when it is
        strictly less urgent than this arrival (otherwise this arrival
        is backpressured: equal classes stay FIFO-fair)."""
        if not req.prompt:
            raise ValueError("empty prompt")
        if req.max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1 (the first token "
                             "is sampled from the prompt's last logits)")
        pol = self.slo.admission
        if pol.max_queue and len(self.queue) >= pol.max_queue:
            if pol.overload == SHED:
                victim = self._shed_victim(req)
                if victim is None:
                    self._backpressure()
                self._shed(victim)
            else:
                self._backpressure()
        deadline_s = (req.deadline_s if req.deadline_s is not None
                      else self.slo.default_deadline_s)
        qm = _Queued(
            seq=self._next_seq(), enqueue_tick=self._tick_no,
            deadline_t=(None if deadline_s is None
                        else self._now() + deadline_s),
            digests=(self.prefix_cache.digests(req.prompt)
                     if self.prefix_cache is not None else None))
        if qm.deadline_t is not None:
            self._has_deadlines = True
        self._queued[req.rid] = qm
        self.queue.append(req)
        if self.counters is not None:
            self.counters.on_enqueue(req.rid)

    def tick(self) -> bool:
        """One scheduling round; returns True while work remains.
        Ticks are numbered from 1 (`ServingFaultInjector` schedules are
        keyed on this number and drained at the top of the tick).

        Each phase of the plain tick runs under a `serve.*` host span
        (`runtime.monitor.span`): `serve.tick` around it all, then
        `serve.admit`, and inside `serve.prefill` / `serve.decode` the
        steps `serve.inputs` -> `serve.dispatch` -> `serve.fetch` ->
        `serve.sample` -> `serve.emit`.  In a profiler trace they say
        what the host was doing while the device waited."""
        t0 = self._now()
        with span("serve.tick"):
            self._tick_no += 1
            if self.fault_injector is not None:
                self._apply_faults()
            with span("serve.admit"):
                self._sentinel_sweep()
                self._expire_deadlines()
                self._admit()
            self._prefill_tick()
            if self.spec_k:
                self._spec_tick()
            else:
                self._decode_tick()
            if self.counters is not None:
                self.counters.on_tick(active=len(self.slots),
                                      queued=len(self.queue),
                                      wall_s=self._now() - t0)
            if self.after_tick is not None:
                # tick-boundary hook (snapshots): fires with every
                # boundary invariant holding — no speculation in flight,
                # no lease held, all lane states committed
                self.after_tick(self._tick_no)
        return bool(self.queue or self.slots)

    def run(self, *, max_idle_ticks: Optional[int] = None):
        """Tick until no work remains.  Watchdog: `max_idle_ticks`
        (default `ServingSLO.max_idle_ticks`; 0 disables) consecutive
        ticks with work remaining but zero progress — no admission,
        prefill token, emitted token, or retirement — raise
        `SchedulerHang` with a state summary instead of spinning
        forever (e.g. a leaked pool slot leaving queued work
        unadmittable)."""
        limit = (self.slo.max_idle_ticks if max_idle_ticks is None
                 else max_idle_ticks)
        idle, last = 0, self._progress
        while self.tick():
            if self._progress != last:
                idle, last = 0, self._progress
                continue
            idle += 1
            if limit and idle >= limit:
                phases = collections.Counter(
                    m.phase for m in self.slots.values())
                raise SchedulerHang(
                    idle_ticks=idle, queued=len(self.queue),
                    active=len(self.slots), n_free=self.pool.n_free,
                    phases=dict(phases))

    def evict(self, rid: int) -> bool:
        """Cancel an in-flight or queued request and free its slot; counted
        as a cancellation, not a completion (no latency sample)."""
        for slot, meta in list(self.slots.items()):
            if meta.req.rid == rid:
                self._retire(slot, meta, outcome=CANCELLED)
                return True
        for req in list(self.queue):
            if req.rid == rid:
                self._dequeue(req)
                if self.counters is not None:
                    self.counters.on_cancel(rid)
                self.on_finish(req, CANCELLED)
                return True
        return False

    # -- SLO layer ---------------------------------------------------------

    def _backpressure(self):
        if self.counters is not None:
            self.counters.on_backpressure()
        raise Overloaded(queue_depth=len(self.queue),
                         max_queue=self.slo.admission.max_queue,
                         retry_after_s=self._retry_after())

    def _retry_after(self) -> float:
        """Retry hint for `Overloaded`: mean completed-request latency
        scaled by how many queue-lengths of work stand in front of a
        new arrival (0.0 before any completion — no estimate beats a
        made-up one)."""
        c = self.counters
        if c is None or not getattr(c, "latency_s", None):
            return 0.0
        mean_lat = sum(c.latency_s) / len(c.latency_s)
        return mean_lat * (len(self.queue) + 1) / max(self.pool.max_slots, 1)

    def _eff_priority(self, req: Request, qm: _Queued) -> int:
        """Effective priority = class + anti-starvation aging bonus
        (one level per `aging_ticks` ticks spent queued)."""
        aging = self.slo.admission.aging_ticks
        bonus = (self._tick_no - qm.enqueue_tick) // aging if aging else 0
        return req.priority + bonus

    def _shed_victim(self, incoming: Request) -> Optional[Request]:
        """Lowest-effective-priority queued request (youngest on ties —
        it has the least sunk wait), IF strictly less urgent than the
        incoming request; else None (the incoming is backpressured)."""
        best, best_key = None, None
        for r in self.queue:
            qm = self._queued[r.rid]
            key = (self._eff_priority(r, qm), -qm.seq)
            if best is None or key < best_key:
                best, best_key = r, key
        if best is not None and best_key[0] < incoming.priority:
            return best
        return None

    def _shed(self, req: Request):
        self._dequeue(req)
        self._progress += 1
        if self.counters is not None:
            self.counters.on_shed(req.rid)
        self.on_finish(req, SHED_OUT)

    def _dequeue(self, req: Request):
        self.queue.remove(req)
        self._queued.pop(req.rid, None)

    def _apply_faults(self):
        """Drain this tick's `ServingFaultInjector` schedule (see its
        docstring for the fault kinds)."""
        for kind, payload in self.fault_injector.pop(self._tick_no):
            if kind == "cache_probe_error":
                self._fail_next_probe = True
            elif kind == "evict":
                self.evict(int(payload))
            elif kind == "evict_on_token":
                self._evict_on_token.add(int(payload))
            elif kind == "deadline":
                self._force_deadline(int(payload))
            elif kind == "crash_at_tick":
                # the crash-recovery drill: die at the TOP of this tick,
                # BEFORE any of its work — every committed snapshot is
                # consistent with respect to this crash point
                if payload == "sigkill":
                    os.kill(os.getpid(), signal.SIGKILL)
                raise EngineCrash(self._tick_no)
            elif kind == "torn_snapshot_write":
                if self.on_torn_snapshot is not None:
                    self.on_torn_snapshot(self._tick_no)
            elif kind == "corrupt_state_leaf":
                for slot, m in self.slots.items():
                    if m.req.rid == int(payload):
                        self.pool.poison_slot(slot)
                        break

    def _force_deadline(self, rid: int):
        """Fault drill: expire `rid`'s deadline NOW (whether or not it
        had one) — it is evicted by this tick's deadline sweep."""
        for meta in self.slots.values():
            if meta.req.rid == rid:
                meta.deadline_t = float("-inf")
                self._has_deadlines = True
                return
        qm = self._queued.get(rid)
        if qm is not None:
            qm.deadline_t = float("-inf")
            self._has_deadlines = True

    def _next_seq(self) -> int:
        s = self._seq
        self._seq += 1
        return s

    # -- integrity sentinels + quarantine ----------------------------------

    def _sentinel_sweep(self):
        """Every `sentinel_every` ticks: ONE jitted all-lane finiteness
        reduction over the pool; every occupied lane holding a NaN/Inf
        state is quarantined (see `_quarantine`).  Free lanes may hold
        stale garbage legitimately — only occupied ones are judged."""
        if (not self.sentinel_every or not self.slots
                or self._tick_no % self.sentinel_every):
            return
        ok = self.pool.lane_finite()
        if ok is None:              # no floating state leaves: nothing
            return                  # can go non-finite
        for slot in [s for s in self.slots if not ok[s]]:
            self._quarantine(slot, self.slots[slot])

    def _quarantine(self, slot: int, meta: _Slot):
        """Evict a poisoned lane and REQUEUE its request for a clean
        replay: staged cache inserts and drafts are discarded (never
        publish from a poisoned lane), the slot is released (its state is
        fresh-reset in-call at the next admission, like any reacquired
        lane), the engine resets the request's handle via `on_requeue`,
        and the request re-enqueues BYPASSING admission bounds with a
        fresh RNG/deadline at admission.  Decode is deterministic, so the
        replayed stream is bit-identical to an unpoisoned run — the
        quarantine costs latency, never correctness."""
        req = meta.req
        meta.pending_inserts.clear()
        meta.drafted.clear()
        self._spec_inflight.pop(req.rid, None)
        del self.slots[slot]
        self.pool.release(slot)
        self._progress += 1
        if self.counters is not None:
            self.counters.on_quarantine(req.rid)
        self.on_requeue(req)
        deadline_s = (req.deadline_s if req.deadline_s is not None
                      else self.slo.default_deadline_s)
        qm = _Queued(
            seq=self._next_seq(), enqueue_tick=self._tick_no,
            deadline_t=(None if deadline_s is None
                        else self._now() + deadline_s),
            digests=(self.prefix_cache.digests(req.prompt)
                     if self.prefix_cache is not None else None))
        if qm.deadline_t is not None:
            self._has_deadlines = True
        self._queued[req.rid] = qm
        self.queue.append(req)
        if self.counters is not None:
            self.counters.on_enqueue(req.rid)

    # -- path fallback (degraded mode) -------------------------------------

    @property
    def demoted(self) -> frozenset:
        """The paths currently demoted to their per-op twins."""
        return frozenset(self._demoted)

    def _fallback(self, kind: str) -> Optional[Callable]:
        if kind not in self._fallback_progs:
            prov = (self.fallback_decode if kind == "decode"
                    else self.fallback_prefill)
            self._fallback_progs[kind] = None if prov is None else prov()
        return self._fallback_progs[kind]

    def _run_program(self, kind: str, fn: Callable, *args):
        """Run a primary decode/prefill program with consecutive-failure
        tracking: below `path_fault_limit` the primary is retried; at the
        limit the path is demoted to its per-op twin (bit-identical
        stream, DegradedMode event) for the life of the scheduler.  With
        no twin available the error propagates.  Only a program that has
        compiled can fault: a trace or compile failure (`CompileError`)
        always propagates.  See the ctor comment for the donation caveat
        on retries."""
        if kind in self._demoted:
            return self._fallback(kind)(*args)
        while True:
            try:
                out = fn(*args)
            except (EngineCrash, KeyboardInterrupt, CompileError):
                # injected crashes are not path faults, and a program that
                # cannot compile is a bug to surface, not a device fault
                raise
            except Exception as e:
                n = self._path_failures[kind] = \
                    self._path_failures.get(kind, 0) + 1
                if n < self.path_fault_limit:
                    continue
                fb = self._fallback(kind)
                if fb is None:
                    raise
                self._demote(kind, n, e)
                return fb(*args)
            self._path_failures[kind] = 0
            return out

    def _demote(self, kind: str, failures: int, err: Exception):
        self._demoted.add(kind)
        if self.counters is not None:
            self.counters.on_path_fallback(DegradedMode(
                kind=kind, tick=self._tick_no, failures=failures,
                from_path=self.path_names.get(kind, kind),
                to_path="per_op", error=repr(err)))

    def _expire_deadlines(self):
        """Evict every queued or in-flight request whose deadline has
        passed (outcome "deadline").  In-flight lanes go through the
        `_retire` path — slot released, drafts discarded; like
        cancellation, captured boundary states are NOT published (only
        completed requests publish, keeping write-once semantics
        simple)."""
        if not self._has_deadlines:
            return
        now = self._now()
        for slot, meta in list(self.slots.items()):
            if meta.deadline_t is not None and now >= meta.deadline_t:
                self._retire(slot, meta, outcome=DEADLINE)
        expired = [r for r in self.queue
                   if (qm := self._queued[r.rid]).deadline_t is not None
                   and now >= qm.deadline_t]
        for r in expired:
            self._dequeue(r)
            self._progress += 1
            if self.counters is not None:
                self.counters.on_deadline_evict(r.rid)
            self.on_finish(r, DEADLINE)

    # -- phases ------------------------------------------------------------

    def _pop_next(self) -> Request:
        """Admission selection: highest effective priority first
        (class + aging), ties broken toward the longest cached ancestor
        prefix (`AdmissionPolicy.prefer_cache_hits`, a side-effect-free
        `PrefixCache.hit_length` peek over enqueue-time digests), then
        FIFO.  With every priority equal and no cache hits this is
        exactly the historical FIFO order."""
        if len(self.queue) == 1:
            req = self.queue.popleft()
            return req
        peek = (self.prefix_cache is not None
                and self.slo.admission.prefer_cache_hits)
        best, best_key = None, None
        for r in self.queue:
            qm = self._queued[r.rid]
            hit = (self.prefix_cache.hit_length(
                self.cache_variant, r.prompt, qm.digests) if peek else 0)
            key = (self._eff_priority(r, qm), hit, -qm.seq)
            if best is None or key > best_key:
                best, best_key = r, key
        self.queue.remove(best)
        return best

    def _admit(self):
        while self.queue and self.pool.n_free:
            req = self._pop_next()
            qm = self._queued.pop(req.rid)
            slot = self.pool.acquire()
            meta = _Slot(req=req, rng=np.random.default_rng(req.seed),
                         seq=qm.seq, deadline_t=qm.deadline_t,
                         digests=qm.digests)
            self.slots[slot] = meta
            self._progress += 1
            if self.counters is not None:
                self.counters.on_admit(req.rid)
            if self.prefix_cache is not None:
                self._cache_probe(slot, meta)

    def _now(self) -> float:
        return self.counters.now() if self.counters is not None \
            else time.monotonic()

    def _cache_probe(self, slot: int, meta: _Slot):
        """Admission-side cache path: probe for the longest cached
        ancestor prefix of the prompt and, on a hit, install its state
        into the freshly acquired lane.  The slot then starts mid-prefill
        (n_prefilled = hit length) with `fresh=False`, so the next
        prefill call advances the restored state instead of resetting the
        lane, and only the uncached suffix is ever computed.  Probe and
        state-copy wall time are reported separately from prefill time
        (ServingCounters.on_cache_probe) — a hit's TTFT is cache time
        plus suffix prefill, and the decomposition should say so.

        Robustness: a probe that RAISES (storage fault, injected via
        ServingFaultInjector's "cache_probe_error") degrades to a miss —
        counted in `ServingCounters.cache_errors` — and the lane
        prefills from scratch; the serving loop never dies on cache
        trouble and no lease is held when the probe fails."""
        req = meta.req
        if meta.digests is None:        # enqueue-time hashing is the norm
            meta.digests = self.prefix_cache.digests(req.prompt)
        t0 = self._now()
        try:
            if self._fail_next_probe:
                self._fail_next_probe = False
                raise RuntimeError("injected cache-probe failure")
            lease = self.prefix_cache.probe(self.cache_variant, req.prompt,
                                            meta.digests)
        except Exception:
            if self.counters is not None:
                self.counters.on_cache_error()
                self.counters.on_cache_probe(req.rid, hit=False,
                                             probe_s=self._now() - t0)
            return
        t_probe = self._now() - t0
        if lease is None:
            if self.counters is not None:
                self.counters.on_cache_probe(req.rid, hit=False,
                                             probe_s=t_probe)
            return
        t0 = self._now()
        self.pool.write_slot(slot, lease.state)
        self.pool.sync()            # block so the copy time is honest
        t_copy = self._now() - t0
        meta.fresh = False
        meta.n_prefilled = meta.cached_tokens = lease.n_tokens
        if self.counters is not None:
            self.counters.on_cache_probe(req.rid, hit=True,
                                         n_cached=lease.n_tokens,
                                         probe_s=t_probe, copy_s=t_copy)
        lease.release()

    def _cache_capture(self, slot: int, meta: _Slot):
        """After a prefill tick: if the lane now holds exactly a
        chunk-boundary prefix that is not already cached, copy it out
        (pool.read_slot) and stage it on the slot.  Staged states are
        published to the cache only when the request COMPLETES (_retire)
        — write-once, and cancelled requests never publish."""
        n = meta.n_prefilled
        if n == 0 or n % self.prefill_chunk or n <= meta.cached_tokens:
            return
        if self.prefix_cache.contains(self.cache_variant, meta.req.prompt,
                                      n, meta.digests):
            return
        meta.pending_inserts.append((n, self.pool.read_slot(slot)))

    def _prefill_tick(self):
        prefilling = [(s, m) for s, m in self.slots.items()
                      if m.phase == PREFILL]
        if not prefilling:
            return
        with span("serve.prefill"):
            S, C = self.pool.max_slots, self.prefill_chunk
            with span("serve.inputs"):
                quota = self._prefill_quota
                if (quota is not None and quota < len(prefilling)
                        and any(m.phase == DECODE
                                for m in self.slots.values())):
                    # prefill budget (ServingSLO.prefill_budget): while lanes
                    # are DECODING, only `quota` prefilling lanes join this
                    # tick's call — highest priority first, then earliest
                    # deadline, then admission order.  The (S, C) program
                    # shape never changes (deferred lanes just keep empty
                    # validity rows), so the compiled-program cache is
                    # untouched; with no decode lane live there is no
                    # inter-token latency to protect and prefill runs
                    # unthrottled.
                    prefilling.sort(key=lambda sm: (
                        -sm[1].req.priority,
                        sm[1].deadline_t if sm[1].deadline_t is not None
                        else float("inf"),
                        sm[1].seq))
                    deferred = prefilling[quota:]
                    prefilling = prefilling[:quota]
                    if self.counters is not None and deferred:
                        self.counters.on_budget_defer(sum(
                            min(len(m.req.prompt) - m.n_prefilled, C)
                            for _, m in deferred))
                toks = np.zeros((S, C), np.int32)
                valid = np.zeros((S, C), bool)
                fresh = np.zeros((S,), bool)
                parts = {}
                for slot, meta in prefilling:
                    part = meta.req.prompt[
                        meta.n_prefilled:meta.n_prefilled + C]
                    toks[slot, :len(part)] = part
                    valid[slot, :len(part)] = True
                    fresh[slot] = meta.fresh
                    parts[slot] = len(part)
            with span("serve.dispatch"):
                self.pool.state, last_logits = self._run_program(
                    "prefill", self.prefill_fn, self.pool.state, toks, valid,
                    fresh)
            if self.counters is not None:
                self.counters.on_prefill_call()
            finishing = []
            for slot, meta in prefilling:
                meta.fresh = False
                meta.n_prefilled += parts[slot]
                self._progress += parts[slot]
                if self.counters is not None:
                    self.counters.on_prefill(meta.req.rid, parts[slot])
                if self.prefix_cache is not None:
                    self._cache_capture(slot, meta)
                if meta.n_prefilled == len(meta.req.prompt):
                    # prompt fully absorbed: the last prompt token's logits
                    # yield the first generated token; the slot joins the
                    # fused decode batch from this tick on.
                    meta.phase = DECODE
                    finishing.append((slot, meta))
            if finishing:
                with span("serve.fetch"):
                    rows = np.asarray(last_logits[:, -1], np.float32)
                self._emit([(s, m, rows[s]) for s, m in finishing])

    def _decode_tick(self):
        active = [(s, m) for s, m in self.slots.items()
                  if m.phase == DECODE]
        if not active:
            return
        with span("serve.decode"):
            S = self.pool.max_slots
            with span("serve.inputs"):
                toks = np.zeros((S, 1), np.int32)
                mask = np.zeros((S,), bool)
                for slot, meta in active:
                    toks[slot, 0] = meta.next_token
                    mask[slot] = True
            with span("serve.dispatch"):
                logits, self.pool.state = self._run_program(
                    "decode", self.decode_fn, self.pool.state, toks, mask)
            if self.counters is not None:
                self.counters.on_decode_call(len(active), S)
            with span("serve.fetch"):
                rows = np.asarray(logits[:, -1], np.float32)
            self._emit([(s, m, rows[s]) for s, m in active])

    def _spec_tick(self):
        """The speculative decode tick: draft -> verify -> accept.

        One drafter call proposes K-1 tokens per lane (greedy chain of the
        truncated stack over a SLICE of the live pool state), one verify
        call — the chunked-prefill machinery with an all-position head —
        scores the lane's pending token plus every draft in parallel and
        commits state through the whole window, and the host accepts the
        longest prefix the verifier agrees with, sampling every emitted
        token from VERIFIER logits (losslessness does not depend on the
        drafter).  Lanes that consumed fewer than K window tokens roll
        back to the pre-verify snapshot (`rollback_fn` = the engine's one
        `masked_state_commit`) and re-advance by their accepted prefix
        through the same verify program.  Worst case (every draft
        rejected) each lane still emits one token per tick, exactly like
        `_decode_tick`."""
        active = [(s, m) for s, m in self.slots.items()
                  if m.phase == DECODE]
        if not active:
            return
        S, K = self.pool.max_slots, self.spec_k
        toks = np.zeros((S, 1), np.int32)
        for slot, meta in active:
            toks[slot, 0] = meta.next_token
        # the pre-verify pool state IS the rollback snapshot: verify_fn
        # never donates its input, so holding this reference is enough
        snapshot = self.pool.state
        window = np.zeros((S, K), np.int32)
        window[:, 0] = toks[:, 0]
        if K > 1:
            window[:, 1:] = np.asarray(self.draft_fn(snapshot, toks))
        valid = np.zeros((S, K), bool)
        for slot, meta in active:
            valid[slot] = True
            meta.drafted = [int(t) for t in window[slot, 1:]]
        self._spec_snapshot = snapshot
        self._spec_inflight = {m.req.rid: m for _, m in active}
        try:
            logits, committed = self.verify_fn(snapshot, window, valid)
            rows = np.asarray(logits, np.float32)          # (S, K, V)
            consumed = self._spec_emit(active, rows, window)
            # lanes still live that consumed < K window tokens: restore
            # the snapshot, then re-advance by the accepted prefix only
            # (retired/evicted lanes are left as-committed — their lane
            # is fresh-reset or prefilled on reacquisition)
            reject = np.zeros((S,), bool)
            readvance = np.zeros((S, K), bool)
            for slot, meta in active:
                if slot not in self.slots or self.slots[slot] is not meta:
                    continue
                n = consumed.get(slot, 0)
                if n < K:
                    reject[slot] = True
                    readvance[slot, :n] = True
            if reject.any():
                rolled = self.rollback_fn(committed, snapshot, reject)
                _, self.pool.state = self.verify_fn(rolled, window,
                                                    readvance)
            else:
                self.pool.state = committed
        finally:
            self._spec_snapshot = None
            self._spec_inflight = {}

    def _spec_emit(self, active, rows, window) -> dict[int, int]:
        """Per-lane acceptance walk of one verify window (the
        `greedy_accept` rule, with sampling and lifecycle handling).
        Returns {slot: window tokens consumed} for every lane that
        emitted.  Sampling is per-row `sample_token` from EACH SLOT'S OWN
        Generator, one draw per EMITTED token — a seeded stream advances
        by accepted tokens only, so its output is bit-stable no matter
        how many drafts were rejected (tests/test_speculative.py pins
        this).  `on_token` callbacks may evict lanes mid-tick; membership
        checks keep a dead lane's drafts from emitting."""
        K = self.spec_k
        consumed: dict[int, int] = {}
        for slot, meta in active:
            if slot not in self.slots or self.slots[slot] is not meta:
                continue    # evicted by an earlier lane's callback
            req, j = meta.req, 0
            while True:
                tok = sample_token(rows[slot, j], req.temperature,
                                   meta.rng)
                consumed[slot] = j + 1
                meta.generated.append(tok)
                meta.next_token = tok
                self._progress += 1
                if self.counters is not None:
                    self.counters.on_token(
                        req.rid, first=len(meta.generated) == 1)
                self.on_token(req, tok)
                self._check_token_fault(req.rid)
                if slot not in self.slots or self.slots[slot] is not meta:
                    break   # evicted by its own token callback / a fault
                if (len(meta.generated) >= req.max_new_tokens or
                        (req.eos_token is not None and
                         tok == req.eos_token)):
                    self._retire(slot, meta)
                    break
                if j + 1 < K and tok == int(window[slot, j + 1]):
                    j += 1  # verifier confirmed the next draft: keep going
                    continue
                break
            meta.drafted = []
            if self.counters is not None and K > 1:
                self.counters.on_speculate(req.rid, drafted=K - 1,
                                           accepted=consumed[slot] - 1)
        return consumed

    # -- helpers -----------------------------------------------------------

    def _emit(self, emitting: list):
        """Sample + book-keep one tick's emitting slots.  Sampling is the
        batched `sample_tokens` (ONE argmax call for the whole block);
        bookkeeping stays per-slot."""
        with span("serve.sample"):
            toks = sample_tokens(
                np.stack([row for _, _, row in emitting]),
                [meta for _, meta, _ in emitting])
        with span("serve.emit"):
            for (slot, meta, _), tok in zip(emitting, toks):
                req, tok = meta.req, int(tok)
                meta.generated.append(tok)
                meta.next_token = tok
                self._progress += 1
                if self.counters is not None:
                    self.counters.on_token(req.rid,
                                           first=len(meta.generated) == 1)
                self.on_token(req, tok)
                self._check_token_fault(req.rid)
                if slot not in self.slots or self.slots[slot] is not meta:
                    continue    # evicted by its token callback / a fault
                done = (len(meta.generated) >= req.max_new_tokens or
                        (req.eos_token is not None
                         and tok == req.eos_token))
                if done:
                    self._retire(slot, meta)

    def _check_token_fault(self, rid: int):
        """ServingFaultInjector "evict_on_token": evict `rid` from inside
        its own token emission — the mid-tick / mid-speculation eviction
        drill.  Callers re-check slot membership right after."""
        if rid in self._evict_on_token:
            self._evict_on_token.discard(rid)
            self.evict(rid)

    def _retire(self, slot: int, meta: _Slot, *, outcome: str = FINISHED):
        """Release `slot` and report `meta.req` with `outcome` (one of
        "finished" / "cancelled" / "deadline"; queued-only exits use
        "shed" / "cancelled" without reaching here).  Only FINISHED
        requests publish their captured boundary states — a cancelled or
        deadline-evicted lane's pending inserts are discarded."""
        if outcome == FINISHED and self.prefix_cache is not None:
            # publish the boundary states captured during prefill —
            # write-once (the cache keeps the first state for a key;
            # any rival is bit-identical by the resume oracle).  A
            # failing insert degrades to "not cached", never a crash.
            for n, state in meta.pending_inserts:
                try:
                    self.prefix_cache.insert(self.cache_variant,
                                             meta.req.prompt, n, state,
                                             meta.digests)
                except Exception:
                    if self.counters is not None:
                        self.counters.on_cache_error()
        meta.pending_inserts.clear()
        # mid-speculation eviction: the lane's drafted tokens die with it
        # and its in-flight marker clears NOW (not at tick end), so a
        # snapshot can never outlive the request that caused it
        meta.drafted.clear()
        self._spec_inflight.pop(meta.req.rid, None)
        del self.slots[slot]
        self.pool.release(slot)
        self._progress += 1
        if self.counters is not None:
            if outcome == CANCELLED:
                self.counters.on_cancel(meta.req.rid)
            elif outcome == DEADLINE:
                self.counters.on_deadline_evict(meta.req.rid)
            else:
                self.counters.on_finish(meta.req.rid)
        self.on_finish(meta.req, outcome)
