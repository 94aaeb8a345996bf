"""Execution plans: the serving engine's declarative path + placement layer.

PRs 1–4 grew the engine an ad-hoc capability matrix — `has_decode` /
`has_fused_decode` / `has_fused_model_decode` / `has_fused_prefill`
boolean flags, three separately-wired `prepare_*` param transforms, and
program building inlined in `ServingEngine._build_steps`.  An
`ExecutionPlan` collapses that matrix into one object:

    plan = build_plan(model, params, mesh=mesh,
                      fused_decode="model", fused_prefill=True,
                      prefill_chunk=16, max_batch=8)

owning, in order:

  * PATH SELECTION — the decode and prefill paths are picked from the
    registry's `PathDescriptor` tables (`models.registry.DECODE_PATHS` /
    `PREFILL_PATHS`), not from booleans: a path exists iff the model ships
    its entry point, and its descriptor says how params are prepared and
    whether packed Δ-PoT leaves decode in-kernel.
  * PARAM PREPARATION — `pack_params` (when quantized) plus each selected
    path's one-time prep run in ONE pass, producing a
    `core.quant.serving.PreparedParams` (raw / decode / prefill forms);
    the engine never re-derives a transform per flag again.
  * PROGRAM CACHE — compiled decode/prefill programs keyed by
    (path, batch bucket, state dtype).  A key is traced exactly once for
    the life of the plan (`trace_counts` proves it, exactly as the engine
    tests always asserted); re-requesting a bucket is a cache hit, never a
    recompile.  The masking semantics every program commits state through
    live here too (`masked_state_commit`) — the single definition shared
    with the sequential test oracle.
  * MESH PLACEMENT — on a `jax.sharding.Mesh` the slot state pool and the
    per-tick token batch shard data-parallel over the DP axes
    (`parallel.sharding.pool_shardings` / `batch_sharding`, with the
    divisibility fallback), while every prepared weight form — including
    the megakernel's L-stacked `FusedLayerStack` slabs — is placed ONCE,
    replicated, at plan build.  Slots are independent sequences, so DP
    sharding introduces no step-time collectives and the sharded engine's
    tokens are bit-identical to the 1-device engine's
    (tests/test_plan.py runs the 8-virtual-device proof).

See docs/architecture.md for the plan diagram and docs/serving.md for the
multi-device serving walkthrough.
"""
from __future__ import annotations

import dataclasses
from typing import Any, Optional

import jax
import jax.numpy as jnp

from repro.core.quant.serving import PreparedParams
from repro.kernels.common import exact_jit
from repro.models.registry import DraftDescriptor, Model, PathDescriptor

# ---------------------------------------------------------------------------
# Shared semantics: masked state commits + in-trace Δ-PoT unpack
# ---------------------------------------------------------------------------


def masked_state_commit(new_state, old_state, mask, axes):
    """Commit `new_state` only where `mask` is set along each leaf's slot
    axis: `where(mask, new, old)` with the mask broadcast into position
    `axes[i]` of leaf i (the `Model.decode_state_batch_axes` layout).

    THE masking semantics of the serving engine — a lane whose mask is
    False is *computed* (fixed shapes beat recompiles) but its state never
    moves, so free or mid-prefill slots are undisturbed by decode traffic.
    Defined once here and shared by every plan program AND the sequential
    test oracle (tests/test_prefill.py), so the engine and its
    bit-identity reference can never drift."""
    new_l = jax.tree_util.tree_leaves(new_state)
    old_l = jax.tree_util.tree_leaves(old_state)
    tdef = jax.tree_util.tree_structure(old_state)
    out = []
    for n, o, ax in zip(new_l, old_l, axes):
        m = mask.reshape(tuple(
            -1 if i == ax else 1 for i in range(n.ndim)))
        out.append(jnp.where(m, n, o))
    return jax.tree_util.tree_unflatten(tdef, out)


def maybe_unpack(params, quantized: bool):
    """In-trace Δ-PoT decode of the whole tree, for the per-op prefill,
    draft and verify programs: packed trees unpack INSIDE the jit (uint8
    codes cross HBM; the exp2 decode fuses into the consumer matmuls).
    The per-op DECODE program does not call this: its step decodes each
    layer inside the layer scan (`core.quant.serving.unpack_layer`), so it
    never holds the whole bf16 tree.  Fused paths never call this either —
    their descriptors carry `fused=True` and the kernels decode per leaf.
    The unpack runs under the name scope `w8_unpack`, which the compiled
    program's operations carry in their metadata, so a device trace can
    tell the decode of the weights from the matmuls that use them."""
    if quantized:
        from repro.core.quant.serving import UNPACK_SCOPE, unpack_params
        with jax.named_scope(UNPACK_SCOPE):
            return unpack_params(params)
    return params


# ---------------------------------------------------------------------------
# The plan
# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class SpeculativePath:
    """The plan's self-speculative decode configuration.

    Each decode tick becomes draft -> verify -> accept: a cheap drafter
    (the first `draft_depth` layers of the SAME model, running the per-op
    decode step on a slice of the live pool state) proposes k-1 tokens per
    lane, ONE chunk-shaped verify call — the PR 4 prefill restructuring,
    `exact_jit`-pinned — scores the lane's pending token plus all drafts
    in parallel, and the scheduler accepts the longest prefix the verifier
    agrees with, rolling rejected lanes back through the same
    `masked_state_commit` every other program uses.  The drafter's quality
    only moves the ACCEPTANCE RATE: every emitted token is sampled from
    verifier logits, so the output stream is bit-identical to the
    non-speculative engine by construction (tests/test_speculative.py).

    k           — verify window width per tick: the lane's pending token
                  plus k-1 drafted tokens (k=1 is the degenerate
                  verify-only tick: no drafter, no draft program)
    draft_depth — layers the truncated-stack drafter keeps
    desc        — the registry DraftDescriptor this path was built from
    """
    k: int
    draft_depth: int
    desc: DraftDescriptor


def _normalize_decode(fused_decode) -> str:
    if fused_decode is True:          # PR 2 compatibility
        fused_decode = "block"
    if fused_decode in (False, None):
        return "per_op"
    if fused_decode in ("block", "model"):
        return fused_decode
    raise ValueError(
        f"fused_decode={fused_decode!r}: expected False, 'block' "
        "or 'model'")


class ExecutionPlan:
    """One model's executable serving configuration (see module docstring).

    Attributes:
      model         — the registry Model handle
      prepared      — PreparedParams (raw / decode / prefill forms, placed
                      on the mesh when one is set)
      decode_desc / prefill_desc — the selected PathDescriptors
      prefill_chunk — prompt tokens absorbed per prefill call per slot
      mesh          — the serving mesh, or None (single device)
      trace_counts  — {"decode": n, "prefill": n} trace counters; stays at
                      1 per used (path, bucket, dtype) key for the life of
                      the plan (the no-recompile guarantee)
    """

    def __init__(self, model: Model, prepared: PreparedParams,
                 decode_desc: PathDescriptor, prefill_desc: PathDescriptor,
                 *, prefill_chunk: int = 16, max_len: int = 0,
                 state_dtype=jnp.bfloat16, mesh=None,
                 speculative: Optional[SpeculativePath] = None):
        self.model = model
        self.prepared = prepared
        self.decode_desc = decode_desc
        self.prefill_desc = prefill_desc
        self.prefill_chunk = int(prefill_chunk)
        self.max_len = int(max_len)
        self.state_dtype = jnp.dtype(state_dtype)
        self.mesh = mesh
        self.speculative = speculative
        self.state_axes = model.decode_state_batch_axes()
        self.trace_counts = {"decode": 0, "prefill": 0}
        if speculative is not None:
            # speculative programs get their own counters; the keys exist
            # only when the path is configured, so non-speculative plans
            # keep the exact historical {"decode", "prefill"} shape
            self.trace_counts.update({"verify": 0, "rollback": 0})
            if speculative.k > 1:
                self.trace_counts["draft"] = 0
        self._programs: dict = {}
        self._batch_shardings: dict = {}
        self._fresh_lane_cache = None
        # per-op twin params for path-fallback demotion, prepared lazily
        # the first time a fallback program is requested
        self._fallback_decode_params = None
        self._fallback_prefill_params = None
        # build_plan records its keyword inputs here so a snapshot can
        # rebuild an identical plan from config alone (repro.serving
        # .snapshot); None on hand-constructed plans, which are then not
        # snapshot-restorable
        self.build_config: Optional[dict] = None
        if mesh is not None:
            self._place_params()

    # -- mesh placement ----------------------------------------------------

    def _place_params(self):
        """Replicate every prepared weight form across the mesh ONCE at
        startup — including the megakernel's L-stacked FusedLayerStack
        slabs — so no step ever moves a weight.  Placement is LEAF-wise
        with an identity cache: a prepared form that rebuilt the tree but
        kept most weight leaves (e.g. rwkv6's prefill prep, which decodes
        4 small leaves and aliases every matmul weight) shares the raw
        form's device buffers instead of replicating the model twice."""
        from repro.parallel.sharding import replicated_sharding
        rep = replicated_sharding(self.mesh)
        placed: dict = {}   # id(leaf) -> (leaf pin, placed leaf)

        def put(leaf):
            key = id(leaf)
            if key not in placed:
                placed[key] = (leaf, jax.device_put(leaf, rep))
            return placed[key][1]

        self.prepared = dataclasses.replace(
            self.prepared,
            raw=jax.tree_util.tree_map(put, self.prepared.raw),
            decode=jax.tree_util.tree_map(put, self.prepared.decode),
            prefill=jax.tree_util.tree_map(put, self.prepared.prefill),
            draft=None if self.prepared.draft is None else
            jax.tree_util.tree_map(put, self.prepared.draft))

    def cache_variant(self, *, numerics: str = "exact"):
        """The prefix-cache `CacheVariant` this plan's prefill states file
        under — derived HERE so the isolation key can never drift from
        what actually executes: arch from the model config, quant form
        from the prepared params' ACTUAL per-tensor planes
        (`core.quant.serving.plane_fingerprint` — "fp" / "dpot_w8" /
        "dpot_mix_<hash>", so two plane policies can never alias one
        cache entry), prefill path from the selected descriptor, state
        dtype from the pool dtype.  The engine's paths all run exact
        numerics; `numerics="hw_lut"` exists for callers driving the
        paper's LUT/PWL variant directly (tests/test_prefix_cache.py)."""
        from repro.core.quant.serving import plane_fingerprint
        from repro.serving.prefix_cache import CacheVariant
        return CacheVariant(
            arch=self.model.cfg.name,
            quant=plane_fingerprint(self.prepared.raw),
            numerics=numerics,
            prefill=self.prefill_desc.name,
            state_dtype=self.state_dtype.name)

    def prefill_quota(self, budget_tokens: int, batch: int) -> int:
        """Per-tick prefill LANE quota for a chunk-token budget — the
        bucket-aware translation the SLO layer uses (repro.serving.slo).

        The prefill program's shape is (batch bucket, prefill_chunk)
        regardless of load, so a budget can never shrink a call — it can
        only choose HOW MANY lanes' validity rows are populated this
        tick.  The budget therefore rounds down to whole chunks
        (budget // prefill_chunk lanes) with a floor of ONE lane, so
        prefill always makes forward progress (a budget below one chunk
        throttles to one lane per tick, never zero — no budget-induced
        wedge).  Because the compiled-program cache key (path, batch
        bucket, dtype) never sees the budget, the traced-once guarantee
        is untouched: budgeted and unbudgeted serving hit the same
        compiled programs (tests assert `trace_counts` stays 1)."""
        if budget_tokens <= 0:
            return int(batch)
        return max(1, min(int(batch),
                          int(budget_tokens) // self.prefill_chunk))

    def state_shardings(self, batch: int):
        """NamedSharding tree for a `batch`-slot pool on this plan's mesh
        (None without a mesh): slot axis data-parallel, divisibility
        fallback to replication."""
        if self.mesh is None:
            return None
        from repro.parallel.sharding import pool_shardings
        ab = jax.eval_shape(
            lambda: self.model.init_slot_state(batch, self.max_len,
                                               self.state_dtype))
        return pool_shardings(self.model.decode_state_axes(), ab, self.mesh)

    def _place_batch(self, x):
        """Per-tick host batch (tokens / masks) -> device, slot axis
        sharded like the pool.  The NamedSharding is cached per shape —
        tick shapes are fixed for a program's life, so the spec-building
        Python never runs in the serving hot loop."""
        x = jnp.asarray(x)
        if self.mesh is None:
            return x
        sh = self._batch_shardings.get(x.shape)
        if sh is None:
            from repro.parallel.sharding import batch_sharding
            sh = self._batch_shardings[x.shape] = batch_sharding(
                x.shape, self.mesh)
        return jax.device_put(x, sh)

    def _pin_state(self, state):
        """Keep a program's output state in the pool's sharding.  Left to
        itself the compiler may choose another layout (a Pallas call's
        outputs come back replicated), and the next program, compiled for
        the pool's layout, would refuse the state."""
        if self.mesh is None:
            return state
        from repro.parallel.sharding import pool_shardings
        return jax.lax.with_sharding_constraint(
            state, pool_shardings(self.model.decode_state_axes(), state,
                                  self.mesh))

    # -- program cache -----------------------------------------------------

    def _key(self, kind: str, batch: int):
        desc = self.decode_desc if kind == "decode" else self.prefill_desc
        return (kind, desc.name, int(batch), self.state_dtype.name)

    def _fresh_lane(self):
        # batch-1 template; leaves broadcast per slot inside the programs
        if self._fresh_lane_cache is None:
            self._fresh_lane_cache = self.model.init_slot_state(
                1, self.max_len, self.state_dtype)
        return self._fresh_lane_cache

    def decode_fn(self, batch: int):
        """The compiled decode program for a `batch`-slot pool:
        fn(state, tokens (S,1), mask (S,)) -> (logits, new_state).
        Cached by (path, batch bucket, dtype) — the same key always
        returns the same program, traced once."""
        key = self._key("decode", batch)
        if key not in self._programs:
            self._programs[key] = self._build_decode()
        return self._programs[key]

    def prefill_fn(self, batch: int):
        """The compiled prefill program for a `batch`-slot pool:
        fn(state, tokens (S,C), valid (S,C), fresh (S,))
        -> (new_state, last-valid logits).  Cached like `decode_fn`."""
        key = self._key("prefill", batch)
        if key not in self._programs:
            self._programs[key] = self._build_prefill(batch)
        return self._programs[key]

    def draft_fn(self, batch: int):
        """The compiled drafter for a `batch`-slot pool:
        fn(state, tokens (S,1)) -> drafted (S, K-1) int32 — a greedy
        argmax chain of the truncated layer stack, its state sliced from
        the live pool IN-trace (`Model.truncate_state`; never a second
        pool).  Cached like `decode_fn`; only exists for K > 1."""
        sp = self.speculative
        key = ("draft", sp.desc.name, int(batch), self.state_dtype.name)
        if key not in self._programs:
            self._programs[key] = self._build_draft()
        return self._programs[key]

    def verify_fn(self, batch: int):
        """The compiled speculative verifier for a `batch`-slot pool:
        fn(state, tokens (S,K), valid (S,K))
        -> (logits (S,K,V), new_state).  Row j holds the logits the plain
        decode tick would produce after consuming tokens[:, :j+1]; state
        commits through every valid position (the chunked-prefill
        machinery, all-position head).  NOT donating its input state —
        the caller's pre-verify pool-state reference IS the rollback
        snapshot.  Cached like `decode_fn`."""
        key = ("verify", self.prefill_desc.name, int(batch),
               self.state_dtype.name)
        if key not in self._programs:
            self._programs[key] = self._build_verify()
        return self._programs[key]

    def rollback_fn(self, batch: int):
        """The compiled speculation rollback for a `batch`-slot pool:
        fn(committed, snapshot, reject (S,)) -> state where rejected
        lanes take the pre-verify snapshot and everyone else keeps the
        verified commit — `masked_state_commit`, the engine's one masking
        semantics.  Donates `committed` (consumed); the snapshot
        survives.  Cached like `decode_fn`."""
        key = ("rollback", "masked", int(batch), self.state_dtype.name)
        if key not in self._programs:
            self._programs[key] = self._build_rollback()
        return self._programs[key]

    # -- program builders (the former ServingEngine._build_steps) ----------

    def _decode_step(self, name: Optional[str] = None):
        """The selected decode path (or an explicit `name` override — the
        fallback twins use "per_op") as a uniform
        (params, state, tokens) -> (logits, new_state) step."""
        model = self.model
        if name is None:
            name = self.decode_desc.name
        if name == "model":
            # whole-model megakernel: ONE launch for the layer stack;
            # packed Δ-PoT leaves pass through whole and decode inside
            return lambda p, s, t: model.decode_step_fused_model(
                p, s, t, jnp.int32(0))
        if name == "block":
            # single-launch block kernel; packed leaves decode per launch
            return lambda p, s, t: model.decode_step_fused(
                p, s, t, jnp.int32(0))
        # per-op: a packed tree decodes layer by layer inside the step
        return lambda p, s, t: model.decode_step(p, s, t, jnp.int32(0))

    def _build_decode(self, *, path: Optional[str] = None,
                      path_params=None, count_key: str = "decode"):
        axes = self.state_axes
        step = self._decode_step(path)

        def decode(params, state, tokens, mask):
            self.trace_counts[count_key] += 1  # increments only on trace
            logits, new_state = step(params, state, tokens)
            return logits, self._pin_state(
                masked_state_commit(new_state, state, mask, axes))

        # exact_jit like every other token-producing program: defined
        # rounding semantics make the speculative verifier's bit-parity
        # with this step STRUCTURAL, not an accident of fusion choices
        # (bits unchanged vs. the former plain jit — PR 2/3 pins hold)
        j_decode = exact_jit(decode, donate_argnums=(1,))
        params = (self.prepared.decode if path_params is None
                  else path_params)
        return lambda state, toks, mask: j_decode(
            params, state, self._place_batch(toks), self._place_batch(mask))

    def _build_prefill(self, batch: int, *,
                       chunked: Optional[bool] = None,
                       path_params=None, count_key: str = "prefill"):
        model, axes = self.model, self.state_axes
        quantized = self.prepared.quantized
        fresh_lane = self._fresh_lane()
        if chunked is None:
            chunked = self.prefill_desc.name == "chunked"
        # logits shape/dtype for the scan carry, without running anything
        ab_logits = jax.eval_shape(
            lambda p, s, t: model.decode_step(p, s, t, jnp.int32(0))[0],
            jax.eval_shape(lambda p: maybe_unpack(p, quantized),
                           self.prepared.raw),
            jax.eval_shape(
                lambda: model.init_slot_state(batch, self.max_len,
                                              self.state_dtype)),
            jax.ShapeDtypeStruct((batch, 1), jnp.int32))

        def prefill(params, state, tokens, valid, fresh):
            self.trace_counts[count_key] += 1  # increments only on trace
            # reset newly admitted lanes to the fresh state in-call (the
            # batch-1 fresh template broadcasts into the masked-off lanes)
            state = masked_state_commit(state, fresh_lane, ~fresh, axes)
            if chunked:
                # fused chunked path: chunk-shaped matmuls + on-chip WKV
                # scan; packed Δ-PoT leaves decode INSIDE the kernels, so
                # no maybe_unpack here — codes cross HBM, not bf16
                new_state, last = model.prefill_chunk(params, state, tokens,
                                                      valid)
                return self._pin_state(new_state), last
            p = maybe_unpack(params, quantized)

            def body(carry, xs):
                state, last = carry
                tok, ok = xs                    # tok (S,), ok (S,)
                logits, stepped = model.decode_step(
                    p, state, tok[:, None], jnp.int32(0))
                state = masked_state_commit(stepped, state, ok, axes)
                last = jnp.where(ok[:, None, None], logits, last)
                return (state, last), None

            last0 = jnp.zeros(ab_logits.shape, ab_logits.dtype)
            (state, last), _ = jax.lax.scan(
                body, (state, last0), (tokens.T, valid.T))
            return self._pin_state(state), last

        # BOTH prefill structures compile with defined rounding semantics
        # (exact_jit: no excess-precision folding) — the property that
        # makes the fused chunked path bit-identical to the per-op scan.
        j_prefill = exact_jit(prefill, donate_argnums=(1,))
        params = (self.prepared.prefill if path_params is None
                  else path_params)
        return lambda state, toks, valid, fresh: j_prefill(
            params, state, self._place_batch(toks),
            self._place_batch(valid), self._place_batch(fresh))

    # -- path-fallback twins (degraded mode) -------------------------------

    def fallback_decode_fn(self, batch: int):
        """The per-op twin of the selected decode path — built lazily the
        first time the scheduler demotes a repeatedly-faulting fused
        decode path (DegradedMode, docs/operations.md).  Returns None
        when the selected path already IS per_op (nothing to demote to).
        Per-op and fused paths are bit-identical by the repo's parity
        pins, so a demotion never changes the token stream.  The twin's
        params and programs cache like every other plan program; the
        "decode_fallback" trace key is added lazily so undemoted plans
        keep the historical trace_counts shape."""
        if self.decode_desc.name == "per_op":
            return None
        key = ("decode_fallback", "per_op", int(batch),
               self.state_dtype.name)
        if key not in self._programs:
            self.trace_counts.setdefault("decode_fallback", 0)
            if self._fallback_decode_params is None:
                desc = self.model.decode_paths()["per_op"]
                self._fallback_decode_params = \
                    self.model.prepare_path_params(desc, self.prepared.raw)
            self._programs[key] = self._build_decode(
                path="per_op", path_params=self._fallback_decode_params,
                count_key="decode_fallback")
        return self._programs[key]

    def fallback_prefill_fn(self, batch: int):
        """The per-op-scan twin of the chunked prefill path, for prefill
        demotion.  Returns None when prefill is already per_op.  Same
        caching and bit-parity story as `fallback_decode_fn`."""
        if self.prefill_desc.name == "per_op":
            return None
        key = ("prefill_fallback", "per_op", int(batch),
               self.state_dtype.name)
        if key not in self._programs:
            self.trace_counts.setdefault("prefill_fallback", 0)
            if self._fallback_prefill_params is None:
                desc = self.model.prefill_paths()["per_op"]
                self._fallback_prefill_params = \
                    self.model.prepare_path_params(desc, self.prepared.raw)
            self._programs[key] = self._build_prefill(
                batch, chunked=False,
                path_params=self._fallback_prefill_params,
                count_key="prefill_fallback")
        return self._programs[key]

    def _build_draft(self):
        sp = self.speculative
        model, quantized = self.model, self.prepared.quantized
        dmodel = model.truncated(sp.draft_depth)
        depth, steps = sp.draft_depth, sp.k - 1

        def draft(params, state, tokens):
            self.trace_counts["draft"] += 1    # increments only on trace
            p = maybe_unpack(params, quantized)
            tstate = model.truncate_state(state, depth)

            def body(carry, _):
                tok, st = carry
                logits, st = dmodel.decode_step(p, st, tok, jnp.int32(0))
                nxt = jnp.argmax(logits[:, 0].astype(jnp.float32),
                                 axis=-1).astype(jnp.int32)[:, None]
                return (nxt, st), nxt[:, 0]

            _, toks = jax.lax.scan(body, (tokens, tstate), None,
                                   length=steps)
            return toks.T                      # (S, K-1)

        # ONE device call proposes the whole window (greedy feedback runs
        # in the scan, not in K-1 host round-trips).  NO donation: the
        # pool state this slices from is the tick's rollback snapshot.
        j_draft = exact_jit(draft)
        params = self.prepared.draft
        return lambda state, toks: j_draft(params, state,
                                           self._place_batch(toks))

    def _build_verify(self):
        model, axes = self.model, self.state_axes
        quantized = self.prepared.quantized
        chunked = self.prefill_desc.name == "chunked"

        def verify(params, state, tokens, valid):
            self.trace_counts["verify"] += 1   # increments only on trace
            if chunked:
                # the PR 4 chunk-shaped restructuring with an all-position
                # head: every valid window token's logits in one call
                new_state, logits = model.prefill_chunk_logits(
                    params, state, tokens, valid)
                return logits, self._pin_state(new_state)
            p = maybe_unpack(params, quantized)

            def body(state, xs):
                tok, ok = xs                   # (S,), (S,)
                logits, stepped = model.decode_step(
                    p, state, tok[:, None], jnp.int32(0))
                state = masked_state_commit(stepped, state, ok, axes)
                row = jnp.where(ok[:, None], logits[:, 0],
                                jnp.zeros_like(logits[:, 0]))
                return state, row

            state, rows = jax.lax.scan(body, state, (tokens.T, valid.T))
            return jnp.swapaxes(rows, 0, 1), self._pin_state(state)

        # exact_jit (same rounding semantics as the decode step — the
        # losslessness theorem); NO donation: the caller's pre-verify
        # pool-state reference is the rollback snapshot.
        j_verify = exact_jit(verify)
        params = self.prepared.prefill
        return lambda state, toks, valid: j_verify(
            params, state, self._place_batch(toks),
            self._place_batch(valid))

    def _build_rollback(self):
        axes = self.state_axes

        def rollback(committed, snapshot, reject):
            self.trace_counts["rollback"] += 1  # increments only on trace
            return self._pin_state(
                masked_state_commit(snapshot, committed, reject, axes))

        # `committed` is consumed (donated); the snapshot survives — the
        # scheduler re-advances rejected lanes from the rolled-back state
        # through the verifier with accepted-prefix validity masks.
        j_rollback = exact_jit(rollback, donate_argnums=(0,))
        return lambda committed, snapshot, reject: j_rollback(
            committed, snapshot, self._place_batch(reject))


def _registry_arch_id(cfg_name: str, smoke: bool) -> str:
    """The registry arch id whose (smoke) config produced `cfg_name`.
    Smoke configs don't always embed the full id (rwkv6-7b's smoke cfg is
    named "rwkv6-smoke"), so stripping the suffix isn't enough — scan the
    registry for the id whose config name matches, so a snapshot's
    `build_config["arch"]` always round-trips through `get_model`."""
    from repro.configs.base import get_config, list_configs, smoke_config
    base = cfg_name[:-len("-smoke")] if smoke else cfg_name
    known = list_configs()
    for arch in ([base] if base in known else []) + known:
        try:
            cfg = smoke_config(arch) if smoke else get_config(arch)
        except Exception:
            continue
        if cfg.name == cfg_name:
            return arch
    return base     # unregistered/ad-hoc config: best effort


def _check_megakernel_fits(decode_params):
    """Refuse a megakernel plan whose weight stream cannot fit the chip's
    VMEM (kernels.fused_decode.megakernel_vmem_bytes) at construction —
    never a silent fallback.  Interpreted kernels (the CPU backend) model
    no VMEM, so only a compiling backend is checked."""
    from repro.core.quant.serving import FusedLayerStack
    from repro.kernels.common import interpret_default
    from repro.kernels.fused_decode import megakernel_vmem_bytes
    stack = decode_params.get("blocks") if isinstance(decode_params, dict) \
        else None
    if isinstance(stack, FusedLayerStack) and not interpret_default(None):
        megakernel_vmem_bytes(stack, "stream")


def build_plan(model: Model | str, params: Any = None, *,
               mesh=None, smoke: bool = True, quantized: bool = False,
               plane_policy=None,
               fused_decode: bool | str | None = False,
               fused_prefill: bool = False, prefill_chunk: int = 16,
               max_len: int = 0, state_dtype=jnp.bfloat16,
               seed: int = 0, speculative: Optional[int] = None,
               draft_depth: Optional[int] = None,
               decode_prepare_kw: Optional[dict] = None) -> ExecutionPlan:
    """Select paths, prepare params (one pass) and build an ExecutionPlan.

    model         — a Model handle or arch id (resolved with `smoke=`)
    params        — pre-built weights (f32/bf16 tree); initialized from
                    `seed` when omitted
    mesh          — a jax Mesh for data-parallel serving, or None
    quantized     — pack weights once; per-op paths unpack in-trace, fused
                    paths decode in-kernel.  Default plane is Δ-PoT W8.
    plane_policy  — a `core.quant.PlanePolicy` choosing W8 / W4-nibble /
                    VQ-codebook per tensor (requires quantized=True); None
                    keeps the historical all-W8 packing
    fused_decode  — False | "block" | "model" (True means "block")
    fused_prefill — False (per-op scan) | True (fused chunked path)
    speculative   — K >= 1: self-speculative decode with a K-token verify
                    window per tick (SpeculativePath; K=1 is verify-only)
    draft_depth   — layers the truncated-stack drafter keeps (default:
                    the registry DraftDescriptor's, else half the stack)

    Raises ValueError with the engine's historical messages when the model
    lacks a requested path — the descriptor tables are the source of
    truth."""
    from repro.models.registry import get_model
    if isinstance(model, str):
        model = get_model(model, smoke=smoke)
    decode_paths = model.decode_paths()
    prefill_paths = model.prefill_paths()
    if "per_op" not in decode_paths:
        raise ValueError(f"{model.cfg.name} has no decode_step")
    if not model.position_free_decode:
        raise ValueError(
            f"{model.cfg.name}: decode_step consumes `pos`; the slotted "
            "engine needs a position-free recurrent state (rwkv4/rwkv6)")
    decode_name = _normalize_decode(fused_decode)
    if decode_name == "block" and "block" not in decode_paths:
        raise ValueError(
            f"{model.cfg.name} has no decode_step_fused; fused_decode "
            "needs a model with the single-launch Pallas block kernel")
    if decode_name == "model" and "model" not in decode_paths:
        raise ValueError(
            f"{model.cfg.name} has no decode_step_fused_model; "
            "fused_decode='model' needs a model with the whole-model "
            "Pallas megakernel")
    prefill_name = "chunked" if fused_prefill else "per_op"
    if prefill_name == "chunked" and "chunked" not in prefill_paths:
        raise ValueError(
            f"{model.cfg.name} has no prefill_chunk; fused_prefill "
            "needs a model with the fused chunked-prefill entry "
            "(kernels/fused_prefill.py)")
    decode_desc = decode_paths[decode_name]
    prefill_desc = prefill_paths[prefill_name]

    # -- speculative path selection ----------------------------------------
    spec_path = None
    if speculative is not None:
        k = int(speculative)
        if k < 1:
            raise ValueError(
                f"speculative={k}: the verify window needs at least the "
                "lane's pending token (K >= 1)")
        drafts = model.draft_paths()
        if "truncated" not in drafts:
            raise ValueError(
                f"{model.cfg.name} has no truncated-stack drafter; "
                "speculative decode needs a position-free decode_step, "
                "stacked `blocks` params and a named `layers` state axis")
        desc = drafts["truncated"]
        depth = draft_depth if draft_depth is not None else (
            desc.depth if desc.depth is not None
            else max(1, model.cfg.n_layers // 2))
        model.truncated(int(depth))     # validates 1 <= depth <= n_layers
        spec_path = SpeculativePath(k=k, draft_depth=int(depth), desc=desc)
    elif draft_depth is not None:
        raise ValueError("draft_depth without speculative=K does nothing")

    # -- param preparation: ONE pass over one weight set -------------------
    from_seed = params is None
    if params is None:
        params = model.init_params(jax.random.PRNGKey(seed))
    if plane_policy is not None and not quantized:
        raise ValueError("plane_policy selects quantized weight planes; "
                         "it does nothing without quantized=True")
    if quantized:
        from repro.core.quant.serving import pack_params
        params = pack_params(params, plane_policy)
    prepared = PreparedParams(
        raw=params,
        decode=model.prepare_path_params(decode_desc, params,
                                         **(decode_prepare_kw or {})),
        prefill=model.prepare_path_params(prefill_desc, params),
        quantized=quantized,
        decode_path=decode_name, prefill_path=prefill_name,
        # the drafter consumes the raw (possibly packed) tree: its per-op
        # step unpacks in-trace exactly like the per-op decode path
        draft=None if spec_path is None or spec_path.k == 1 else
        model.truncate_params(params, spec_path.draft_depth))
    if decode_name == "model":
        _check_megakernel_fits(prepared.decode)
    plan = ExecutionPlan(model, prepared, decode_desc, prefill_desc,
                         prefill_chunk=prefill_chunk, max_len=max_len,
                         state_dtype=state_dtype, mesh=mesh,
                         speculative=spec_path)
    # record the build inputs so a serving snapshot can reconstruct this
    # exact plan from config alone (repro.serving.snapshot).  `from_seed`
    # says whether `seed` alone reproduces the weights; restore verifies
    # param checksums either way, so externally-supplied weights still
    # restore — the caller just has to pass them back in.
    name = model.cfg.name
    smoke_flag = name.endswith("-smoke")
    plan.build_config = {
        "arch": _registry_arch_id(name, smoke_flag),
        "smoke": smoke_flag,
        "quantized": bool(quantized),
        "plane_policy": None if plane_policy is None
        else plane_policy.to_config(),
        "fused_decode": decode_name,
        "fused_prefill": prefill_name == "chunked",
        "prefill_chunk": int(prefill_chunk),
        "max_len": int(max_len),
        "state_dtype": jnp.dtype(state_dtype).name,
        "seed": int(seed),
        "from_seed": from_seed,
        "speculative": None if spec_path is None else spec_path.k,
        "draft_depth": None if spec_path is None else spec_path.draft_depth,
        "mesh_devices": None if mesh is None else int(mesh.devices.size),
    }
    return plan
