"""Model registry: arch family -> module implementing the uniform model API.

Every model module exposes:
  spec(cfg)                         -> P tree
  forward(params, batch, cfg)       -> (logits, aux_loss)
  init_decode_state(cfg, B, maxlen) -> state tree            (decoders only)
  decode_state_axes(cfg)            -> logical-axes tree
  decode_step(params, state, tokens, pos, cfg) -> (logits, new_state)

`get_model(cfg)` dispatches on the config family / rwkv_version and returns
a Model handle bundling those functions with the config.
"""
from __future__ import annotations

import dataclasses
from types import ModuleType
from typing import Any, Callable, Optional

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig, get_config, smoke_config
from repro.models import param as PM


def _is_axes_tuple(x) -> bool:
    return isinstance(x, tuple) and all(
        isinstance(a, (str, type(None))) for a in x)


@dataclasses.dataclass(frozen=True)
class PathDescriptor:
    """Declarative description of ONE executable serving path.

    The registry used to expose a matrix of boolean capability flags
    (`has_decode` / `has_fused_decode` / `has_fused_model_decode` /
    `has_fused_prefill`) that the serving engine cross-referenced with
    three separately-wired `prepare_*` transforms.  A PathDescriptor is
    that row of the matrix as data: which module attribute implements the
    step, which (if any) prepares its params, and whether packed Δ-PoT
    leaves decode in-kernel (`fused=True`: codes pass through the trace
    whole) or must be unpacked in-trace by the caller (`fused=False`, the
    per-op oracle).  `repro.serving.plan.build_plan` selects one decode
    and one prefill descriptor and builds programs from them; the old
    `has_*` properties survive as thin views over the descriptor tables.

    name    — plan key ("per_op" | "block" | "model" | "chunked")
    kind    — "decode" | "prefill"
    entry   — module attribute implementing the step
    prepare — module attribute for one-time host-side param prep (None:
              params pass through)
    fused   — packed leaves decode inside the kernels (no in-trace unpack)
    """
    name: str
    kind: str
    entry: str
    prepare: Optional[str] = None
    fused: bool = False


DECODE_PATHS = (
    PathDescriptor("per_op", "decode", "decode_step"),
    PathDescriptor("block", "decode", "decode_step_fused", fused=True),
    PathDescriptor("model", "decode", "decode_step_fused_model",
                   prepare="prepare_fused_model_params", fused=True),
)

PREFILL_PATHS = (
    # the per-op prefill is a scan of decode_step; the plan builds the scan
    PathDescriptor("per_op", "prefill", "decode_step"),
    PathDescriptor("chunked", "prefill", "prefill_chunk",
                   prepare="prepare_prefill_params", fused=True),
)


@dataclasses.dataclass(frozen=True)
class DraftDescriptor:
    """Declarative drafter contract for self-speculative decoding.

    A drafter is a CHEAP proposal model whose guesses a bit-exact verifier
    (the chunked-prefill path scoring the whole draft window in one call)
    either confirms or corrects — so the drafter's quality only moves the
    ACCEPTANCE RATE, never the output (repro.serving.plan.SpeculativePath).
    The "truncated" drafter is the first `depth` layers of the SAME model:
    because layer l's state transition depends only on layers below it, a
    truncated stack's recurrent state is exactly the full model's first
    `depth` state slices — the draft state is a (static) slice of the live
    pool state, never a second pool (`Model.truncate_state`).

    name    — plan key ("truncated")
    entry   — module attribute the draft loop chains per proposed token
              (the per-op `decode_step`, run on a depth-`n_layers` config)
    depth   — default layers kept when the plan does not pick one
              (None: half the stack, at least one layer)
    """
    name: str
    entry: str = "decode_step"
    depth: Optional[int] = None


DRAFT_PATHS = (
    DraftDescriptor("truncated"),
)


def _module_for(cfg: ModelConfig) -> ModuleType:
    if cfg.rwkv_version == 4:
        from repro.models import rwkv4
        return rwkv4
    if cfg.rwkv_version == 6:
        from repro.models import rwkv6
        return rwkv6
    if cfg.family == "hybrid":
        from repro.models import zamba2
        return zamba2
    if cfg.family == "audio":
        from repro.models import encdec
        return encdec
    from repro.models import transformer
    return transformer


@dataclasses.dataclass(frozen=True)
class Model:
    cfg: ModelConfig
    module: ModuleType

    # -- parameters --------------------------------------------------------
    def spec(self):
        return self.module.spec(self.cfg)

    def init_params(self, rng, dtype=jnp.float32):
        return PM.init_params(self.spec(), rng, dtype)

    def abstract_params(self, dtype=jnp.float32):
        return PM.abstract_params(self.spec(), dtype)

    def param_axes(self):
        return PM.logical_axes(self.spec())

    def param_count(self) -> int:
        return PM.param_count(self.spec())

    # -- compute -----------------------------------------------------------
    def forward(self, params, batch):
        return self.module.forward(self.cast_params(params), batch, self.cfg)

    def cast_params(self, params):
        """f32 master params -> compute dtype (standard mixed precision;
        grads flow back to the f32 masters through the cast). Leaves that
        must stay f32 are re-cast inside the model where it matters."""
        dt = jnp.dtype(self.cfg.dtype)

        def cast(a):
            if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating):
                return a.astype(dt)
            return a
        return jax.tree_util.tree_map(cast, params)

    # -- serving paths (plan descriptors) ----------------------------------
    def decode_paths(self) -> dict[str, PathDescriptor]:
        """The decode paths this model can execute, keyed by plan name —
        the declarative replacement for the has_* capability flags.  A
        path is present iff the module ships its entry point."""
        return {d.name: d for d in DECODE_PATHS
                if hasattr(self.module, d.entry)}

    def prefill_paths(self) -> dict[str, PathDescriptor]:
        """The prefill paths this model can execute, keyed by plan name.
        "per_op" (a scan of decode_step, built by the plan) is present for
        any decoder; "chunked" needs the fused `prefill_chunk` entry."""
        return {d.name: d for d in PREFILL_PATHS
                if hasattr(self.module, d.entry)}

    def draft_paths(self) -> dict[str, DraftDescriptor]:
        """The self-speculative drafters this model can run, keyed by plan
        name.  The "truncated" drafter needs (1) the per-op decode step on
        a position-free recurrent state, (2) a stacked `blocks` param tree
        whose leaves carry the layer axis first (so the first-`depth`
        slice IS the truncated model's weights), and (3) a `layers`-named
        axis in every decode-state leaf (so the draft state is a slice of
        the live pool state)."""
        if not (hasattr(self.module, "decode_step")
                and self.position_free_decode):
            return {}
        try:
            self.decode_state_layer_axes()
        except (ValueError, AttributeError):
            return {}
        if "blocks" not in self.spec():
            return {}
        return {d.name: d for d in DRAFT_PATHS}

    def truncated(self, depth: int) -> "Model":
        """The first-`depth`-layers model as a registry handle: same module,
        config with `n_layers=depth`.  Combined with `truncate_params` /
        `truncate_state` this IS the truncated-stack drafter — its
        decode_step runs the same per-op math over the shallow stack."""
        if not 1 <= depth <= self.cfg.n_layers:
            raise ValueError(
                f"draft depth {depth} outside [1, {self.cfg.n_layers}] "
                f"for {self.cfg.name}")
        return Model(cfg=dataclasses.replace(self.cfg, n_layers=depth),
                     module=self.module)

    def truncate_params(self, params, depth: int):
        """Truncated-stack drafter weights: the first `depth` layers of the
        stacked block tree; embedding, outer norms and head are SHARED with
        the full model (aliased leaves, no copy).  Works on packed Δ-PoT
        trees too — code and scale planes both carry the layer axis
        first."""
        blocks = jax.tree_util.tree_map(lambda leaf: leaf[:depth],
                                        params["blocks"])
        return {**params, "blocks": blocks}

    def decode_state_layer_axes(self) -> list[int]:
        """Position of the layer axis in every decode-state leaf, aligned
        with tree_leaves(state) — the truncation analog of
        `decode_state_batch_axes`."""
        axes = self.decode_state_axes()
        flat, _ = jax.tree_util.tree_flatten(axes, is_leaf=_is_axes_tuple)
        return [ax.index("layers") for ax in flat]

    def truncate_state(self, state, depth: int):
        """The first `depth` layer slices of a decode-state tree — exactly
        the truncated model's state, because layer l's transition depends
        only on layers below it.  Static slice; traceable (the plan's draft
        program slices the live pool state in-trace every tick)."""
        axes = self.decode_state_layer_axes()
        leaves = jax.tree_util.tree_leaves(state)
        tdef = jax.tree_util.tree_structure(state)
        out = [jax.lax.slice_in_dim(leaf, 0, depth, axis=ax)
               for leaf, ax in zip(leaves, axes)]
        return jax.tree_util.tree_unflatten(tdef, out)

    def prepare_path_params(self, desc: PathDescriptor, params, **kw):
        """One-time host-side param prep for one path, dispatched through
        its descriptor: runs the module's `desc.prepare` (identity when the
        descriptor or the module has none).  `kw` forwards model extras
        (rwkv4 megakernel: `hw=True` attaches the LUT operands)."""
        prep = getattr(self.module, desc.prepare, None) if desc.prepare \
            else None
        return params if prep is None else prep(params, self.cfg, **kw)

    @property
    def has_decode(self) -> bool:
        return "per_op" in self.decode_paths()

    def init_decode_state(self, batch: int, max_len: int,
                          dtype=jnp.bfloat16):
        return self.module.init_decode_state(self.cfg, batch, max_len, dtype)

    def decode_state_axes(self):
        return self.module.decode_state_axes(self.cfg)

    def decode_step(self, params, state, tokens, pos):
        """The per-op decode step.  The cast is packed-aware
        (`core.quant.serving.cast_compute`, equal to `cast_params` on a
        plain tree), so a packed tree reaches the module whole and its
        leaves decode layer by layer inside the step."""
        from repro.core.quant.serving import cast_compute
        return self.module.decode_step(
            cast_compute(params, jnp.dtype(self.cfg.dtype)), state, tokens,
            pos, self.cfg)

    @property
    def has_fused_decode(self) -> bool:
        """True when the model ships a single-launch Pallas decode step
        (`decode_step_fused`) alongside the per-op oracle."""
        return "block" in self.decode_paths()

    def decode_step_fused(self, params, state, tokens, pos):
        """Fused-kernel decode (kernels.fused_decode): one Pallas launch
        per block.  Params pass through UNcast — the model applies the
        packed-aware compute cast itself (core.quant.serving.cast_compute)
        so Δ-PoT `{"packed","scale"}` leaves reach the kernel intact."""
        return self.module.decode_step_fused(params, state, tokens, pos,
                                             self.cfg)

    @property
    def has_fused_model_decode(self) -> bool:
        """True when the model ships the whole-model megakernel
        (`decode_step_fused_model`): ONE Pallas launch per decode step,
        grid over layers, residual carried in VMEM scratch."""
        return "model" in self.decode_paths()

    def decode_step_fused_model(self, params, state, tokens, pos):
        """Megakernel decode (kernels.fused_decode.fused_model_decode):
        the entire layer stack in one launch.  Params pass through UNcast,
        as in `decode_step_fused` — or pre-prepared via
        `prepare_fused_model_params` (the serving hot path)."""
        return self.module.decode_step_fused_model(params, state, tokens,
                                                   pos, self.cfg)

    def prepare_fused_model_params(self, params, **kw):
        """One-time host-side prep for the megakernel: compute-dtype cast +
        per-layer weight chunking (core.quant.serving.fuse_layer_stack).
        Run OUTSIDE the step; the result feeds decode_step_fused_model
        without per-token repacking.  `kw` forwards model extras (rwkv4:
        `hw=True` attaches the LUT operands — the decode's `hw` flag must
        match the prepared form)."""
        return self.prepare_path_params(self.decode_paths()["model"],
                                        params, **kw)

    @property
    def has_fused_prefill(self) -> bool:
        """True when the model ships the fused chunked-prefill entry
        (`prefill_chunk`): a whole prompt chunk per device program —
        chunk-shaped matmuls + the masked on-chip WKV sequence kernel —
        bit-identical to scanning `decode_step` over the chunk."""
        return "chunked" in self.prefill_paths()

    def prefill_chunk(self, params, state, tokens, valid):
        """Fused chunked prefill (kernels.fused_prefill): tokens (B, C)
        with a per-slot PREFIX validity mask -> (new_state, last-valid
        logits).  Params pass through UNcast, as in `decode_step_fused` —
        the model applies the packed-aware compute cast itself so Δ-PoT
        `{"packed","scale"}` leaves reach the matmul kernels intact."""
        return self.module.prefill_chunk(params, state, tokens, valid,
                                         jnp.int32(0), self.cfg)

    def prefill_chunk_logits(self, params, state, tokens, valid):
        """All-position variant of `prefill_chunk` for the speculative
        VERIFIER: tokens (B, K) with a prefix validity mask -> (new_state,
        logits (B, K, V)) where row k scores token k+1 — the same program
        the plain decode path would run on each position, so greedy
        acceptance against it is lossless by construction.  Invalid
        positions return zero logits and leave state untouched."""
        return self.module.prefill_chunk(params, state, tokens, valid,
                                         jnp.int32(0), self.cfg,
                                         all_logits=True)

    def prepare_prefill_params(self, params):
        """One-time host-side prep for the fused prefill: pre-decode any
        packed leaves the chunk datapath consumes outside a matmul (rwkv6's
        maa_w2; rwkv4 needs nothing).  Run OUTSIDE
        the step, like `prepare_fused_model_params`."""
        desc = self.prefill_paths().get("chunked")
        return params if desc is None else \
            self.prepare_path_params(desc, params)

    # -- per-slot decode-state contract (serving engine) -------------------
    @property
    def position_free_decode(self) -> bool:
        """True when decode_step ignores `pos` (pure recurrent state, no
        KV write index) — the property the slotted serving pool relies on
        to run many requests at unrelated sequence offsets in one step."""
        return bool(getattr(self.module, "DECODE_POS_FREE", False))

    def init_slot_state(self, n_slots: int = 1, max_len: int = 0,
                        dtype=jnp.bfloat16):
        """Decode state sized for a slot pool: the batch axis is the slot
        axis (one independent sequence per slot)."""
        return self.module.init_decode_state(self.cfg, n_slots, max_len,
                                             dtype)

    def decode_state_batch_axes(self) -> list[int]:
        """Position of the batch (slot) axis in every decode-state leaf,
        as a flat list aligned with jax.tree_util.tree_leaves(state).
        Derived from decode_state_axes(), so any model that names its
        state axes gets slot addressing for free."""
        axes = self.decode_state_axes()
        flat, _ = jax.tree_util.tree_flatten(axes, is_leaf=_is_axes_tuple)
        return [ax.index("batch") for ax in flat]


def get_model(cfg_or_id: ModelConfig | str, *, smoke: bool = False) -> Model:
    if isinstance(cfg_or_id, str):
        cfg = smoke_config(cfg_or_id) if smoke else get_config(cfg_or_id)
    else:
        cfg = cfg_or_id
    return Model(cfg=cfg, module=_module_for(cfg))


# ---------------------------------------------------------------------------
# Loss / step builders shared by the launcher, examples and dry-run
# ---------------------------------------------------------------------------


def loss_fn(model: Model, params, batch):
    """Causal-LM cross-entropy (mean over non-masked tokens) + MoE aux."""
    logits, aux = model.forward(params, batch)
    labels = batch["labels"]
    # VLM: logits cover [patches + tokens]; labels align to the text tail
    if logits.shape[1] != labels.shape[1]:
        logits = logits[:, -labels.shape[1]:]
    logits = logits.astype(jnp.float32)
    logp = jax.nn.log_softmax(logits, axis=-1)
    ll = jnp.take_along_axis(logp, labels[..., None], axis=-1)[..., 0]
    mask = batch.get("mask")
    if mask is None:
        mask = jnp.ones_like(ll)
    loss = -jnp.sum(ll * mask) / jnp.maximum(jnp.sum(mask), 1.0)
    return loss + 0.01 * aux, {"loss": loss, "aux": aux}
