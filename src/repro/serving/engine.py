"""Serving engine front-end: submit(prompt) -> token stream.

Wires the slotted state pool and the scheduler to an `ExecutionPlan`
(`repro.serving.plan`) — the engine no longer builds device programs
itself.  The plan owns path selection (registry PathDescriptors instead
of boolean capability flags), one-pass param preparation
(`core.quant.serving.PreparedParams`), the compiled-program cache keyed
by (path, batch bucket, dtype), and mesh placement; the engine's job is
request lifecycle: handles, streaming, scheduler callbacks.

The two programs the plan serves to the scheduler:

  * the FUSED DECODE STEP — the selected decode path (`per_op` oracle,
    `block` single-launch kernel, or the whole-`model` megakernel) over
    the full pool with an active-slot mask; packed Δ-PoT weights unpack
    in-trace (per-op) or decode in-kernel (fused), so int8 codes are what
    crosses HBM — the paper's bandwidth win riding along for free, and
  * the PREFILL CHUNK — absorbing up to `prefill_chunk` prompt tokens for
    EVERY prefilling slot in one device call, per-slot-per-token validity
    masked, fresh lanes reset in-call; the `per_op` scan and the fused
    `chunked` path both compile with defined rounding semantics
    (`kernels.common.exact_jit`) and are bit-identical
    (tests/test_prefill.py).

On a mesh (`mesh=` or a pre-built `plan=`), the pool and per-tick batch
shard data-parallel while weights replicate — bit-identical tokens to the
single-device engine (tests/test_plan.py).  All programs are traced
exactly once (`trace_counts` proves it in tests).  See docs/serving.md
for the API walkthrough and docs/architecture.md for the request
lifecycle and the plan diagram.
"""
from __future__ import annotations

import collections
import dataclasses
from typing import Any, Iterator, Optional

import jax.numpy as jnp

from repro.models.registry import Model
from repro.runtime.monitor import ServingCounters, trace_gc
from repro.serving.plan import ExecutionPlan, build_plan
from repro.serving.prefix_cache import PrefixCache, PrefixCacheConfig
from repro.serving.scheduler import Request, Scheduler
from repro.serving.slo import ServingSLO
from repro.serving.state_pool import SlotStatePool


@dataclasses.dataclass(frozen=True)
class SamplingParams:
    max_new_tokens: int = 16
    temperature: float = 0.0
    seed: int = 0
    eos_token: Optional[int] = None


class RequestHandle:
    """Live view of one submitted request; tokens stream in as generated.

    `outcome` is None while in flight and then one of "finished",
    "cancelled", "shed" (dropped by the overload policy — resubmit;
    with a prefix cache any completed boundary of the prompt resumes
    free), or "deadline" (evicted past its deadline)."""

    def __init__(self, request: Request):
        self.request = request
        self.tokens: list[int] = []        # everything generated so far
        # tokens generated BEFORE a snapshot restore (the pre-crash
        # output): a handle re-registered by `ServingEngine.restore`
        # carries them here, so the request's full stream is
        # `resumed + tokens` — bitwise equal to a never-crashed run
        # (repro.serving.snapshot).  Always [] on a fresh submit.
        self.resumed: list[int] = []
        self.done = False
        self.outcome: Optional[str] = None
        self._pending: collections.deque[int] = collections.deque()

    @property
    def rid(self) -> int:
        return self.request.rid

    def drain(self) -> list[int]:
        """Take (and clear) the tokens generated since the last drain.
        The polling counterpart to engine.stream()/astream(); mixing the
        two on one handle splits the stream between them."""
        out = list(self._pending)
        self._pending.clear()
        return out


class ServingEngine:
    """Continuous-batching RWKV serving (see module docstring).

    model      — a Model handle, or an arch id string (resolved with
                 `smoke=` like the rest of the launchers)
    params     — optional pre-built weights (f32/bf16 tree); initialized
                 from `seed` when omitted
    quantized  — pack weights to Δ-PoT W8 once at startup; per-op paths
                 dequantize inside the jit, fused paths in-kernel
    max_batch  — pool width: max concurrent sequences (compiled shape)
    prefill_chunk — prompt tokens absorbed per tick per prefilling slot
    fused_decode — decode path: False (per-op oracle) | "block" (one
                 Pallas launch per block) | "model" (the whole-model
                 megakernel); `True` is accepted as "block" (PR 2
                 compatibility).  All modes are bit-identical
                 (tests/test_fused_decode.py).
    fused_prefill — prefill path: False (per-op scan of decode_step) |
                 True (the fused chunked `prefill_chunk` path).
                 Bit-identical (tests/test_prefill.py).
    speculative — K >= 1: self-speculative decode.  Each decode tick a
                 truncated-stack drafter proposes K-1 tokens per lane,
                 one chunk-shaped verify call scores pending + drafts in
                 parallel, and the longest verifier-agreed prefix is
                 accepted (rejected lanes roll back through
                 masked_state_commit).  Every emitted token is sampled
                 from verifier logits, so the token streams are
                 bit-identical to the non-speculative engine
                 (tests/test_speculative.py) — K only moves throughput.
    draft_depth — layers the drafter keeps (default half the stack).
    mesh       — a `jax.sharding.Mesh` for data-parallel serving: the
                 slot pool and per-tick batch shard over the DP axes,
                 weights replicate (see docs/serving.md §multi-device);
                 tokens are bit-identical to the 1-device engine.
    plan       — a pre-built ExecutionPlan; overrides every path/quant/
                 mesh argument above (they describe a plan, and the plan
                 is the source of truth).
    prefix_cache — recurrent-state prefix cache (docs/serving.md §prefix
                 cache): True builds one with default sizing, a
                 `PrefixCacheConfig` sizes the device/host tiers, and a
                 `PrefixCache` instance is SHARED (its chunk granularity
                 must equal the plan's prefill_chunk).  On admission the
                 scheduler restores the longest cached ancestor prefix's
                 state into the slot and prefills only the uncached
                 suffix — bit-identical tokens to cache-off serving
                 (tests/test_prefix_cache.py).  Entries are keyed by the
                 plan's `cache_variant()` so packed/fp, rwkv4/rwkv6 and
                 per-op/chunked states never alias.
    slo        — a `ServingSLO` (repro.serving.slo): priority/deadline/
                 cache-aware admission, per-tick prefill budget
                 (translated bucket-aware via the plan's
                 `prefill_quota`), bounded queue with `Overloaded`
                 backpressure or load shedding, and the run() hang
                 watchdog.  The default preserves historical behavior
                 (docs/serving.md §"SLOs and overload").
    fault_injector — a `ServingFaultInjector` (repro.runtime.monitor)
                 for fault drills: forces cache-probe failures,
                 mid-speculation evictions, deadline expiry, in-process
                 crashes/SIGKILL, torn snapshot writes and state-leaf
                 corruption at chosen ticks (tests/test_faults.py).
    snapshot   — crash safety (repro.serving.snapshot, docs/operations
                 .md): a `SnapshotConfig` (or a directory string with
                 default cadence) makes the engine write tick-boundary
                 snapshots; `ServingEngine.restore(dir)` resumes every
                 stream bit-identically.
    sentinel_every — every N ticks (0 = off) one jitted reduction flags
                 NaN/Inf lanes; poisoned lanes are quarantined and
                 their requests requeued for a clean replay.
    path_fallback / path_fault_limit — automatic degraded mode: after
                 `path_fault_limit` consecutive fused decode/prefill
                 failures the scheduler demotes to the plan's per-op
                 twin (bit-identical stream, `DegradedMode` event in
                 `counters.degraded_events`).
    """

    def __init__(self, model: Model | str, *, params: Any = None,
                 smoke: bool = True, max_batch: int = 8,
                 prefill_chunk: int = 16, max_len: int = 0,
                 state_dtype=jnp.bfloat16, quantized: bool = False,
                 plane_policy=None,
                 fused_decode: bool | str | None = False,
                 fused_prefill: bool = False, seed: int = 0,
                 speculative: Optional[int] = None,
                 draft_depth: Optional[int] = None,
                 mesh=None, plan: Optional[ExecutionPlan] = None,
                 counters: Optional[ServingCounters] = None,
                 prefix_cache=None, slo: Optional[ServingSLO] = None,
                 fault_injector=None, snapshot=None,
                 sentinel_every: int = 0, path_fallback: bool = True,
                 path_fault_limit: int = 2):
        if plan is None:
            plan = build_plan(model, params, smoke=smoke, mesh=mesh,
                              quantized=quantized,
                              plane_policy=plane_policy,
                              fused_decode=fused_decode,
                              fused_prefill=fused_prefill,
                              prefill_chunk=prefill_chunk,
                              max_len=max_len, state_dtype=state_dtype,
                              seed=seed, speculative=speculative,
                              draft_depth=draft_depth)
        self.plan = plan
        self.model = plan.model
        self.quantized = plan.prepared.quantized
        self.fused_decode = False if plan.decode_desc.name == "per_op" \
            else plan.decode_desc.name
        self.fused_prefill = plan.prefill_desc.name == "chunked"
        self.params = plan.prepared.raw
        self.counters = counters if counters is not None else \
            ServingCounters()
        trace_gc()
        self.pool = SlotStatePool(self.model, max_batch,
                                  max_len=plan.max_len,
                                  dtype=plan.state_dtype,
                                  shardings=plan.state_shardings(max_batch))
        self.prefix_cache = self._build_cache(prefix_cache)
        sp = plan.speculative
        self.speculative = 0 if sp is None else sp.k
        self.slo = slo if slo is not None else ServingSLO()
        self.scheduler = Scheduler(
            self.pool, plan.decode_fn(max_batch), plan.prefill_fn(max_batch),
            prefill_chunk=plan.prefill_chunk, counters=self.counters,
            on_token=self._on_token, on_finish=self._on_finish,
            prefix_cache=self.prefix_cache,
            cache_variant=None if self.prefix_cache is None
            else self.plan.cache_variant(),
            speculative=self.speculative,
            draft_fn=plan.draft_fn(max_batch)
            if sp is not None and sp.k > 1 else None,
            verify_fn=plan.verify_fn(max_batch) if sp is not None else None,
            rollback_fn=plan.rollback_fn(max_batch)
            if sp is not None else None,
            slo=self.slo,
            prefill_quota=plan.prefill_quota(self.slo.prefill_budget,
                                             max_batch)
            if self.slo.prefill_budget > 0 else None,
            fault_injector=fault_injector,
            sentinel_every=sentinel_every, on_requeue=self._on_requeue,
            fallback_decode=(lambda: plan.fallback_decode_fn(max_batch))
            if path_fallback else None,
            fallback_prefill=(lambda: plan.fallback_prefill_fn(max_batch))
            if path_fallback else None,
            path_fault_limit=path_fault_limit,
            path_names={"decode": plan.decode_desc.name,
                        "prefill": plan.prefill_desc.name})
        self._handles: dict[int, RequestHandle] = {}
        self._next_rid = 0          # plain int: snapshots serialize it
        # crash safety (repro.serving.snapshot): a SnapshotConfig (or a
        # directory string) wires tick-boundary snapshots through the
        # scheduler's after_tick hook — and the torn-write fault drill
        # through on_torn_snapshot
        self.snapshot_manager = None
        if snapshot is not None and snapshot is not False:
            from repro.serving.snapshot import (SnapshotConfig,
                                                SnapshotManager)
            cfg = snapshot if isinstance(snapshot, SnapshotConfig) \
                else SnapshotConfig(directory=str(snapshot))
            self.snapshot_manager = SnapshotManager(self, cfg)
            self.scheduler.after_tick = self.snapshot_manager.maybe_save
            self.scheduler.on_torn_snapshot = self.snapshot_manager.\
                write_torn

    def _build_cache(self, prefix_cache) -> Optional[PrefixCache]:
        """Resolve the `prefix_cache=` ctor arg (None/False | True |
        PrefixCacheConfig | a shared PrefixCache) into a cache whose chunk
        granularity matches the plan — cached boundaries must be tick
        boundaries or a resumed suffix would re-chunk differently from a
        full prefill and lose bit parity."""
        if prefix_cache is None or prefix_cache is False:
            return None
        if isinstance(prefix_cache, PrefixCache):
            if prefix_cache.chunk != self.plan.prefill_chunk:
                raise ValueError(
                    f"shared prefix cache has chunk={prefix_cache.chunk} but "
                    f"the plan prefills in chunks of {self.plan.prefill_chunk}"
                    " — boundary states would not land on tick boundaries")
            cache = prefix_cache
        else:
            cfg = prefix_cache if isinstance(prefix_cache, PrefixCacheConfig) \
                else PrefixCacheConfig()
            cache = PrefixCache(self.plan.prefill_chunk, config=cfg)
        if cache.counters is None:
            cache.counters = self.counters
        return cache

    @property
    def trace_counts(self) -> dict:
        """The plan's trace counters ({"decode": 1, "prefill": 1} after
        any amount of serving — the no-recompile guarantee)."""
        return self.plan.trace_counts

    # -- request API ---------------------------------------------------------

    def submit(self, prompt: list[int],
               sampling: Optional[SamplingParams] = None, *,
               priority: int = 0, deadline_s: Optional[float] = None,
               **kw) -> RequestHandle:
        """Queue a request; returns a handle whose tokens fill in as the
        engine steps.  `kw` shorthand: max_new_tokens/temperature/seed/
        eos_token override the SamplingParams fields.  `priority` and
        `deadline_s` are SLO fields (repro.serving.slo).  With a bounded
        queue (`AdmissionPolicy.max_queue`) a full queue raises
        `Overloaded` — the request was NOT accepted and NO handle exists
        for it — or, under the shed policy, drops a strictly-less-urgent
        queued request (its handle completes with outcome "shed")."""
        sp = sampling or SamplingParams()
        if kw:
            sp = dataclasses.replace(sp, **kw)
        rid, self._next_rid = self._next_rid, self._next_rid + 1
        req = Request(rid=rid,
                      prompt=[int(t) for t in prompt],
                      max_new_tokens=sp.max_new_tokens,
                      temperature=sp.temperature, seed=sp.seed,
                      eos_token=sp.eos_token, priority=priority,
                      deadline_s=deadline_s)
        handle = RequestHandle(req)
        # register BEFORE enqueue (a shed victim's on_finish fires inside
        # enqueue and needs its own handle), but unregister if THIS
        # request is refused: a raised Overloaded leaves no handle behind
        self._handles[req.rid] = handle
        try:
            self.scheduler.enqueue(req)
        except BaseException:
            self._handles.pop(req.rid, None)
            raise
        return handle

    def cancel(self, handle: RequestHandle) -> bool:
        ok = self.scheduler.evict(handle.rid)
        return ok

    @property
    def handles(self) -> dict:
        """Live rid -> RequestHandle map (a copy).  Handles are popped as
        requests retire, so grab this BEFORE `run()` when you need every
        stream afterwards — in particular right after `restore`, where
        the resumed requests' handles are pre-registered here."""
        return dict(self._handles)

    def step(self) -> bool:
        """One scheduler tick; True while any request is in flight."""
        return self.scheduler.tick()

    def run(self) -> dict:
        """Drive until drained (with the scheduler's hang watchdog —
        see `ServingSLO.max_idle_ticks`); returns a counters snapshot."""
        self.scheduler.run()
        return self.counters.snapshot()

    def stream(self, handle: RequestHandle) -> Iterator[int]:
        """Synchronous token stream for one request; steps the engine
        (advancing ALL in-flight requests) whenever the stream runs dry."""
        while True:
            while handle._pending:
                yield handle._pending.popleft()
            if handle.done:
                return
            self.step()

    async def astream(self, handle: RequestHandle):
        """Async token stream; yields control to the event loop between
        engine ticks so concurrent consumers interleave."""
        import asyncio
        while True:
            while handle._pending:
                yield handle._pending.popleft()
            if handle.done:
                return
            self.step()
            await asyncio.sleep(0)

    # -- scheduler callbacks -------------------------------------------------

    def _on_token(self, req: Request, tok: int):
        h = self._handles[req.rid]
        h.tokens.append(tok)
        h._pending.append(tok)

    def _on_finish(self, req: Request, outcome: str = "finished"):
        h = self._handles.pop(req.rid)
        h.outcome = outcome
        h.done = True

    def _on_requeue(self, req: Request):
        """Quarantine callback: the request replays from scratch, so its
        handle forgets everything emitted from the poisoned lane — the
        deterministic replay re-delivers an identical (clean) stream."""
        h = self._handles.get(req.rid)
        if h is not None:
            h.tokens.clear()
            h.resumed = []
            h._pending.clear()

    # -- crash recovery ------------------------------------------------------

    @classmethod
    def restore(cls, directory: str, **kw) -> "ServingEngine":
        """Rebuild an engine from its newest committed snapshot and
        continue every stream bit-identically — pre-crash output is on
        each handle's `.resumed`, so `resumed + tokens` equals the
        never-crashed stream.  See `repro.serving.snapshot.restore_engine`
        for the keyword arguments (params/step/mesh/snapshot/...)."""
        from repro.serving.snapshot import restore_engine
        return restore_engine(directory, **kw)
