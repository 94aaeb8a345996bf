"""Packed-weight serving: the paper's deployment mode on TPU.

The accelerator stores Δ-PoT codes in (HBM-equivalent) memory and decodes
on-chip (§3.1, §4.1).  TPU translation: matmul weights live in device HBM as
ONE uint8 per weight (sign + ks=(3,4) codes, FORMAT_W8) plus an f32 scale
per output channel; `unpack_params` runs INSIDE the jitted serve step, so
XLA reads int8 from HBM and fuses the decode into the consumer matmuls —
weight traffic halves vs bf16 (the paper's bandwidth win), at the Table-1
accuracy cost.

Two sub-8-bit planes extend the stack (RWKVQuant direction, PAPERS.md):
a W4 plane packing TWO sign+3-bit Δ-PoT codes per uint8 along the
contraction axis ({"packed4", "scale"} — half the W8 slab bytes), and a
per-tensor VQ plane of uint8 codebook indices ({"vq_idx", "codebook"} —
the bf16 codebook rides the resident const maps like the shared scales).
`core.quant.policy.PlanePolicy` picks the plane per tensor; every decode
goes through the same `unpack_leaf`, so mixed-plane trees stay
bit-identical across the per-op and fused paths.

API:
  pack_params(params)          -> packed tree (+ additive leaves cast bf16);
                                 already-packed leaves pass through
  pack_layerwise(top, make_layer, n_layers, operands)
                               -> the same W8 tree, made and packed one
                                 layer at a time (a model whose f32 tree
                                 does not fit the device)
  unpack_params(packed)        -> compute tree (call inside jit)
  unpack_leaf(leaf)            -> decode ONE packed leaf (shared by the
                                 fused decode kernels so in-kernel decode is
                                 bit-identical to the per-op path)
  broadcast_packed_scales(t,L) -> make stacked packed leaves layer-sliceable
                                 (scan / per-block kernel operands)
  cast_compute(tree, dtype)    -> packed-aware compute-dtype cast
  packed_abstract(spec)        -> ShapeDtypeStruct tree (dry-run input)
  packed_axes(spec_axes)       -> logical-sharding tree for the packed form

Param preparation (one pass, shared by every serving path):
  PreparedParams               -> container holding every per-path form of
                                 one weight set (raw / decode / prefill) —
                                 built once by serving.plan.build_plan
  prepare_layer_stack_params   -> generic megakernel prep (compute cast +
                                 fuse_layer_stack); models wrap it instead
                                 of duplicating the plumbing
  predecode_packed_leaves      -> decode named packed leaves in place (the
                                 generic form of rwkv6's prefill prep)
"""
from __future__ import annotations

import dataclasses
import logging
import time
from typing import Any

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.quant.delta_pot import (
    FORMAT_W4, FORMAT_W8, dpot_channel_scale, dpot_decode_codes,
    dpot_pack_int8, dpot_pack_nibbles, dpot_quantize, dpot_quantize_scaled)
from repro.core.quant.policy import PlanePolicy, classify_param
from repro.core.quant.vq import vq_dequantize, vq_quantize
from repro.runtime.monitor import span

# The three quantized weight-plane leaf forms (one dict shape per plane):
#   w8 — {"packed":  uint8 (..., K, N),   "scale": f32 (1,...,N)}  sign+7b
#   w4 — {"packed4": uint8 (..., K/2, N), "scale": f32 (1,...,N)}  2/byte
#   vq — {"vq_idx":  uint8 (..., K, N),   "codebook": bf16 (1, C)} gather
_PLANE_KEYS = {
    frozenset({"packed", "scale"}): "w8",
    frozenset({"packed4", "scale"}): "w4",
    frozenset({"vq_idx", "codebook"}): "vq",
}


def leaf_plane(leaf) -> str | None:
    """"w8" | "w4" | "vq" for a quantized plane leaf, None otherwise."""
    if not isinstance(leaf, dict):
        return None
    return _PLANE_KEYS.get(frozenset(leaf))


def is_packed_leaf(leaf) -> bool:
    """True for ANY quantized weight-plane leaf (scalar Δ-PoT W8, nibble-
    packed W4, or VQ codebook) — THE predicate for the packed formats (the
    fused kernels and models import it from here so the formats have a
    single source of truth)."""
    return leaf_plane(leaf) is not None


_is_packed = is_packed_leaf


def pack_params(params, policy: PlanePolicy | None = None):
    """Quantize every matmul weight to a packed plane; cast the rest bf16.

    Without a `policy` every matmul weight gets scalar Δ-PoT W8 (the
    historical behavior).  With a `PlanePolicy`, each tensor's plane is
    selected per tensor (proxy-guided or forced) — "w4" halves the stored
    code bytes via nibble pairing (falling back to W8 when the contraction
    axis is odd, so any tree packs), "vq" stores uint8 codebook indices.
    A leaf that is already packed passes through unchanged, so a tree that
    `pack_layerwise` made packs to itself."""
    flat, tdef = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=is_packed_leaf)
    out = []
    for path, leaf in flat:
        key = jax.tree_util.keystr(path)
        if is_packed_leaf(leaf):
            out.append(leaf)
        elif classify_param(key, leaf) == "matmul":
            plane = "w8" if policy is None else policy.plane_for(key, leaf)
            if plane == "w4" and (leaf.ndim < 2 or leaf.shape[-2] % 2):
                plane = "w8"        # nibble pairing needs an even axis -2
            if plane == "vq":
                idx, codebook = vq_quantize(leaf, policy.vq_codes)
                out.append({"vq_idx": idx, "codebook": codebook})
            elif plane == "w4":
                q = dpot_quantize(leaf, FORMAT_W4, axis=-1)
                out.append({"packed4": dpot_pack_nibbles(q),
                            "scale": q.scale.astype(jnp.float32)})
            else:
                q = dpot_quantize(leaf, FORMAT_W8, axis=-1)
                out.append({"packed": dpot_pack_int8(q),
                            "scale": q.scale.astype(jnp.float32)})
        else:
            out.append(leaf.astype(jnp.bfloat16)
                       if hasattr(leaf, "astype") else leaf)
    return jax.tree_util.tree_unflatten(tdef, out)


def pack_layerwise(top, make_layer, n_layers: int, operands=(), *,
                   log=None):
    """`pack_params({**top, "blocks": stacked layers})` on the W8 plane,
    made one layer at a time: the stacked f32 tree is never held.

    `make_layer(l, *operands)` gives layer l's f32 tree and may be traced
    in l; `operands` (a seed's key, say) are arguments of the jitted
    passes, not constants, so every seed runs the same programs.  The
    W8 scale is one per output column of the STACKED leaf, so two passes,
    each one jitted loop over the layers:

      amax  — each matmul leaf's per-column max |w| over every layer
              (host span `setup.w8_amax`);
      pack  — each layer made again, coded with that shared scale and
              written into preallocated stacked code buffers; every other
              leaf cast to bf16 into its stacked buffer (`setup.w8_pack`).

    The peak is the packed tree plus about one f32 layer.  The result
    equals the whole-tree `pack_params` bit for bit: the same codes, the
    scales in their (1, ..., N) shape, the same bf16 leaves.  `log` gets
    each pass's seconds (default: this module's logger)."""
    log = log or logging.getLogger(__name__).info
    shapes = jax.eval_shape(make_layer, jnp.int32(0), *operands)
    flat, tdef = jax.tree_util.tree_flatten_with_path(shapes)
    blocks_key = (jax.tree_util.DictKey("blocks"),)
    # classified as the stacked leaf of the whole tree would be
    matmul = [classify_param(
        jax.tree_util.keystr(blocks_key + tuple(path)),
        jax.ShapeDtypeStruct((n_layers, *leaf.shape), leaf.dtype))
        == "matmul" for path, leaf in flat]
    coded = [leaf for (_, leaf), mm in zip(flat, matmul) if mm]

    @jax.jit
    def amax_pass(operands):
        def body(l, acc):
            got = [w for w, mm in zip(
                jax.tree_util.tree_leaves(make_layer(l, *operands)), matmul)
                   if mm]
            return tuple(jnp.maximum(a, jnp.max(
                jnp.abs(w.astype(jnp.float32)),
                axis=tuple(range(w.ndim - 1)))) for a, w in zip(acc, got))
        init = tuple(jnp.zeros(leaf.shape[-1:], jnp.float32)
                     for leaf in coded)
        return jax.lax.fori_loop(0, n_layers, body, init)

    @jax.jit
    def pack_pass(scales, operands):
        def body(l, bufs):
            it = iter(scales)
            out = []
            layer = jax.tree_util.tree_leaves(make_layer(l, *operands))
            for buf, w, mm in zip(bufs, layer, matmul):
                if mm:
                    v = dpot_pack_int8(dpot_quantize_scaled(
                        w, next(it)[0], FORMAT_W8))
                else:
                    v = w.astype(jnp.bfloat16)
                out.append(jax.lax.dynamic_update_index_in_dim(
                    buf, v, l, 0))
            return tuple(out)
        bufs = tuple(jnp.zeros((n_layers, *leaf.shape),
                               jnp.uint8 if mm else jnp.bfloat16)
                     for (_, leaf), mm in zip(flat, matmul))
        return jax.lax.fori_loop(0, n_layers, body, bufs)

    t0 = time.perf_counter()
    with span("setup.w8_amax"):
        amax = jax.block_until_ready(amax_pass(tuple(operands)))
    t1 = time.perf_counter()
    # the scale `dpot_quantize(axis=-1)` takes from the stacked leaf
    scales = tuple(
        dpot_channel_scale(a, FORMAT_W8).reshape((1,) * leaf.ndim + a.shape)
        for a, leaf in zip(amax, coded))
    with span("setup.w8_pack"):
        codes = jax.block_until_ready(pack_pass(scales, tuple(operands)))
    t2 = time.perf_counter()
    log(f"layer-wise W8 pack of {n_layers} layers: amax pass "
        f"{t1 - t0:.3f} s, pack pass {t2 - t1:.3f} s")
    it = iter(scales)
    blocks = [{"packed": c, "scale": next(it)} if mm else c
              for c, mm in zip(codes, matmul)]
    return {**pack_params(top),
            "blocks": jax.tree_util.tree_unflatten(tdef, blocks)}


def _sign(bit):
    """±1.0 from a sign bit, as arithmetic: a select between two constants
    needs a replicated mask layout that Mosaic cannot produce."""
    return 1.0 - 2.0 * bit.astype(jnp.float32)


def unpack_leaf(leaf):
    """Decode one quantized plane leaf -> bf16 weights (identity on
    anything else).  The single source of truth for the decode numerics:
    both `unpack_params` (per-op path, whole tree before the matmuls) and
    the fused kernels (per leaf, inside the launch) call this, which is
    what makes the paths bit-identical.  W4 re-interleaves the nibble
    pairs along the contraction axis before the same exp2 decode; VQ is a
    flat codebook gather (shape-agnostic: resident (1, C), in-kernel (C,)
    and scan-broadcast forms all index identically)."""
    plane = leaf_plane(leaf)
    if plane is None:
        return leaf
    if plane == "vq":
        return vq_dequantize(leaf["vq_idx"],
                             leaf["codebook"]).astype(jnp.bfloat16)
    if plane == "w4":
        p = leaf["packed4"].astype(jnp.int32)
        lo = p & 0xF
        hi = (p >> 4) & 0xF
        words = jnp.stack([lo, hi], axis=-2).reshape(
            p.shape[:-2] + (2 * p.shape[-2], p.shape[-1]))
        codes = words & 0x7
        sign = _sign((words >> 3) & 1)
        lvl = dpot_decode_codes(codes, FORMAT_W4.ks)
        return (sign * lvl * leaf["scale"]).astype(jnp.bfloat16)
    p = leaf["packed"].astype(jnp.int32)   # Mosaic has no u8 -> f32 cast
    codes = p & 0x7F
    sign = _sign((p >> 7) & 1)
    lvl = dpot_decode_codes(codes, FORMAT_W8.ks)
    return (sign * lvl * leaf["scale"]).astype(jnp.bfloat16)


def broadcast_packed_scales(blocks, n_layers: int):
    """Make a packed stacked-blocks tree sliceable along the layer axis.

    `pack_params` gives a stacked weight (L, ...) one shared scale with a
    broadcast leading 1 (e.g. (1, 1, D)) — and a VQ leaf one shared (1, C)
    codebook; consumers that *slice* the tree per layer — `lax.scan` over
    blocks, or the per-block fused kernel's scanned operands — need every
    leaf to carry the L axis, so the shared leaf is broadcast to (L, ...)
    here.  The per-layer slice then decodes element-for-element exactly as
    the whole-tree broadcast would, keeping the decode bit-identical.  The
    whole-model megakernel does NOT need this:
    `kernels.fused_decode.fused_model_decode` recognizes leading-1 leaves
    and streams them with a constant index map instead (the shared scale /
    codebook stays resident while the uint8 codes are layer-sliced
    in-kernel)."""
    def fix(leaf):
        if not is_packed_leaf(leaf):
            return leaf
        out = {}
        for k, v in leaf.items():
            if k in ("scale", "codebook") and v.shape[0] == 1:
                v = jnp.broadcast_to(v, (n_layers,) + tuple(v.shape[1:]))
            out[k] = v
        return out
    return jax.tree_util.tree_map(fix, blocks, is_leaf=is_packed_leaf)


def plane_fingerprint(params) -> str:
    """The quant-form fingerprint of a (possibly packed) tree, for the
    prefix-cache variant key and snapshot `build_config`.

    "fp" when nothing is packed and exactly "dpot_w8" when every quant
    leaf is scalar W8 (the historical CacheVariant strings, so existing
    cache entries / snapshots stay valid); any other mix hashes the
    ordered (path, plane) selection — two different per-tensor policies
    can NEVER alias to the same variant."""
    import hashlib
    flat, _ = jax.tree_util.tree_flatten_with_path(
        params, is_leaf=is_packed_leaf)
    kinds = [(jax.tree_util.keystr(path), leaf_plane(leaf))
             for path, leaf in flat if is_packed_leaf(leaf)]
    if not kinds:
        return "fp"
    if all(k == "w8" for _, k in kinds):
        return "dpot_w8"
    h = hashlib.blake2b(repr(kinds).encode(), digest_size=4).hexdigest()
    return f"dpot_mix_{h}"


def unpack_params(packed):
    """Packed tree -> bf16 compute tree.  Runs inside jit: the uint8 codes
    are what crosses HBM; the exp2 decode fuses into the matmul."""
    return jax.tree_util.tree_map(unpack_leaf, packed, is_leaf=_is_packed)


# the name scope every in-trace decode of a packed weight runs under, so a
# device trace can tell the decode of the weights from the matmuls that
# use them (the compiled operations carry it in their metadata)
UNPACK_SCOPE = "w8_unpack"


def unpack_layer(tree, dtype):
    """Decode the packed leaves of `tree` (one layer's weights, or the
    head) to `dtype` under the `w8_unpack` name scope; plain leaves pass
    through.  Element for element what `unpack_params` followed by the
    compute cast gives, so a per-op decode that unpacks each layer inside
    its layer scan is bit-identical to one that unpacks the whole tree
    before it — while holding one layer's bf16 weights, not the model's."""
    with jax.named_scope(UNPACK_SCOPE):
        return jax.tree_util.tree_map(
            lambda l: unpack_leaf(l).astype(dtype) if _is_packed(l) else l,
            tree, is_leaf=_is_packed)


# ---------------------------------------------------------------------------
# Fused layer stack: per-layer weights as contiguous slab blocks
# ---------------------------------------------------------------------------
#
# The paper's weight stream (§4.2) is chunked: the accelerator fetches each
# layer's weights as one contiguous block and double-buffers the next
# layer's chunk behind the current layer's compute.  `fuse_layer_stack`
# realizes that layout on the host — every stacked (L, ...) leaf of a
# block tree is stacked row-wise into an (L, R, W) slab shared by all
# leaves of one dtype and one last-axis width W (uint8 Δ-PoT code planes
# and bf16 weights each get their own slabs; a vector leaf is one row),
# while broadcast leading-1 leaves (shared packed scales, LUT tables) stay
# separate as resident operands.  Each leaf starts on a whole TPU tile row
# (8 rows at 32 bits, 16 at 16, 32 at 8) and spans the slab's full width,
# so a layer's slab block tiles exactly and each leaf is a STATIC,
# tile-aligned row slice.  The whole-model decode megakernel
# (`kernels.fused_decode.fused_model_decode`) then fetches layer l as one
# slab block per (dtype, width) and re-materializes the per-layer tree
# with those static slices (`unfuse_layer`) — one memory stream per slab
# per layer instead of one gather per leaf.  Packing reshapes, pads and
# concatenates only, so the decoded weights are bit-identical to the
# unfused tree.


def _tile_rows(dtype) -> int:
    """Rows of one TPU tile for `dtype`: 8 at 32 bits, 16 at 16, 32 at 8."""
    return 32 // min(4, jnp.dtype(dtype).itemsize)


@jax.tree_util.register_pytree_node_class
class FusedLayerStack:
    """A stacked per-layer parameter tree in chunked-stream form.

    slabs    — {"<dtype>:<width>": (L, R, W) array}: layer l's weights of
               that dtype and last-axis width, stacked row-wise.
    aux      — tuple of broadcast leading-1 leaves kept out of the slabs
               (shared Δ-PoT scales, LUT tables): VMEM-resident operands.
    manifest — static per-leaf recipe aligned with the original tree's
               flatten order: ("slab", key, row offset, per-layer shape)
               or ("aux", index).
    tdef     — the original tree's treedef (packed {"packed","scale"}
               dicts reassemble automatically).
    """

    def __init__(self, slabs, aux, manifest, tdef):
        self.slabs = dict(slabs)
        self.aux = tuple(aux)
        self.manifest = tuple(manifest)
        self.tdef = tdef

    @property
    def n_layers(self) -> int:
        return next(iter(self.slabs.values())).shape[0]

    def tree_flatten(self):
        keys = tuple(sorted(self.slabs))
        children = tuple(self.slabs[k] for k in keys) + self.aux
        return children, (keys, len(self.aux), self.manifest, self.tdef)

    @classmethod
    def tree_unflatten(cls, static, children):
        keys, n_aux, manifest, tdef = static
        slabs = dict(zip(keys, children[:len(keys)]))
        aux = children[len(keys):len(keys) + n_aux]
        return cls(slabs, aux, manifest, tdef)


def _leaf_rows(shape) -> tuple[int, int]:
    """(rows, width) of one per-layer leaf laid out in a slab."""
    width = shape[-1] if shape else 1
    rows = int(np.prod(shape[:-1], dtype=np.int64)) if len(shape) > 1 else 1
    return rows, width


def fuse_layer_stack(blocks, n_layers: int) -> FusedLayerStack:
    """Pack a stacked per-layer block tree into (L, R, W) slabs, one per
    (dtype, last-axis width).

    Values are only reshaped/padded/concatenated, never converted —
    unfusing is bit-identical.  Do this ONCE outside the decode step (the
    serving engine and `Model.prepare_fused_model_params` do): repacking
    inside a jitted step would copy every weight per token."""
    flat, tdef = jax.tree_util.tree_flatten(blocks)
    manifest, aux, parts, offs = [], [], {}, {}
    for leaf in flat:
        if leaf.ndim and leaf.shape[0] == n_layers:
            shape = tuple(leaf.shape[1:])
            rows, width = _leaf_rows(shape)
            key = f"{jnp.dtype(leaf.dtype).name}:{width}"
            manifest.append(("slab", key, offs.get(key, 0), shape))
            padded = -(-rows // _tile_rows(leaf.dtype)) * \
                _tile_rows(leaf.dtype)
            part = jnp.reshape(leaf, (n_layers, rows, width))
            parts.setdefault(key, []).append(
                jnp.pad(part, ((0, 0), (0, padded - rows), (0, 0))))
            offs[key] = offs.get(key, 0) + padded
        elif leaf.ndim and leaf.shape[0] == 1:
            manifest.append(("aux", len(aux)))
            aux.append(leaf)
        else:
            raise ValueError(
                f"per-layer leaf has shape {getattr(leaf, 'shape', None)}; "
                f"expected a leading axis of {n_layers} (stacked) or 1 "
                "(broadcast)")
    slabs = {k: (jnp.concatenate(v, axis=1) if len(v) > 1 else v[0])
             for k, v in parts.items()}
    return FusedLayerStack(slabs, aux, manifest, tdef)


def unfuse_layer(rows, aux_vals, manifest, tdef):
    """Rebuild ONE layer's parameter tree from its slab blocks.

    rows     — {slab key: (R, W) block for layer l} — an array, or any
               object whose `[a:b]` gives rows a..b (the megakernel
               passes ref views, so only the rows a leaf needs are read).
    aux_vals — broadcast leaves with the leading 1 squeezed.
    All slices are STATIC and tile-aligned (offsets come from the
    manifest), so inside a kernel this compiles to views feeding the
    consumers — the only per-layer memory stream is the slab fetch."""
    leaves = []
    for entry in manifest:
        if entry[0] == "slab":
            _, key, off, shape = entry
            n, _ = _leaf_rows(shape)
            leaves.append(rows[key][off:off + n].reshape(shape))
        else:
            leaves.append(aux_vals[entry[1]])
    return jax.tree_util.tree_unflatten(tdef, leaves)


@dataclasses.dataclass(frozen=True)
class PreparedParams:
    """Every per-path form of one weight set, prepared ONCE at startup.

    The serving engine used to keep three ad-hoc param transforms
    (`pack_params` at init, `prepare_fused_model_params` for the
    megakernel, `prepare_prefill_params` for the fused prefill) as
    separate attributes wired by boolean flags.  This container is the
    single product of that pipeline — built by
    `repro.serving.plan.build_plan` in one pass:

      raw      — the tree as stored (packed Δ-PoT when `quantized`);
                 per-op paths consume it, unpacking IN-TRACE when packed.
      decode   — the form the selected decode path consumes (e.g. the
                 megakernel's pre-cast `FusedLayerStack` slabs; == raw for
                 per-op / per-block paths).
      prefill  — the form the selected prefill path consumes (e.g. rwkv6's
                 pre-decoded elementwise leaves; == raw for per-op).

    quantized / decode_path / prefill_path record which pipeline produced
    the forms, so consumers (and error messages) never re-derive it."""
    raw: Any
    decode: Any
    prefill: Any
    quantized: bool = False
    decode_path: str = "per_op"
    prefill_path: str = "per_op"
    # truncated-stack drafter weights for the speculative path (the first
    # `draft_depth` layers of `raw`, leaves aliased — see
    # Model.truncate_params); None when the plan has no SpeculativePath
    draft: Any = None


def prepare_layer_stack_params(params, cfg, extra_block_operands=None):
    """Generic host-side prep for the whole-model megakernel: apply the
    packed-aware compute cast, attach any extra per-block kernel operands
    (rwkv4's hw LUT tables), and chunk the stacked per-layer weights into
    per-dtype contiguous slabs (`fuse_layer_stack`) — the paper's per-layer
    weight chunk, fetched as ONE stream per layer instead of one gather per
    leaf.  Models' `prepare_fused_model_params` entries wrap this instead
    of each duplicating the cast + fuse plumbing."""
    params = cast_compute(params, jnp.dtype(cfg.dtype))
    blocks = params["blocks"]
    if extra_block_operands:
        blocks = {**blocks, **extra_block_operands}
    return {**params, "blocks": fuse_layer_stack(blocks, cfg.n_layers)}


def predecode_packed_leaves(params, paths):
    """Decode the packed leaves at the given key-paths (tuples of dict
    keys) with `unpack_leaf`, leaving everything else — including plain
    leaves at those paths — untouched.  The generic form of "this path
    consumes a few leaves element-wise, so pre-decode them once at startup
    and let every remaining uint8 code plane stream into a kernel"
    (rwkv6's fused-prefill prep).  Same `unpack_leaf` as the per-op
    oracle, so bits match."""
    def update(node, path):
        if not path:
            return unpack_leaf(node) if _is_packed(node) else node
        head, rest = path[0], path[1:]
        return {**node, head: update(node[head], rest)}

    for path in paths:
        params = update(params, tuple(path))
    return params


def cast_compute(tree, dtype):
    """Packed-aware mixed-precision cast: floating leaves go to `dtype`
    (exactly `Model.cast_params`), packed Δ-PoT leaves pass through intact
    so their uint8 codes and f32 scales reach the fused kernel unchanged
    (casting the scale would perturb the decode vs the per-op path)."""
    dt = jnp.dtype(dtype)

    def cast(a):
        if _is_packed(a):
            return a
        if hasattr(a, "dtype") and jnp.issubdtype(a.dtype, jnp.floating):
            return a.astype(dt)
        return a
    return jax.tree_util.tree_map(cast, tree, is_leaf=_is_packed)


def packed_abstract(spec_tree, abstract_params):
    """ShapeDtypeStruct tree of the packed form (for the dry-run)."""
    flat, tdef = jax.tree_util.tree_flatten_with_path(abstract_params)
    out = []
    for path, leaf in flat:
        key = jax.tree_util.keystr(path)
        if classify_param(key, leaf) == "matmul":
            scale_shape = tuple(1 for _ in leaf.shape[:-1]) + \
                (leaf.shape[-1],)
            out.append({
                "packed": jax.ShapeDtypeStruct(leaf.shape, jnp.uint8),
                "scale": jax.ShapeDtypeStruct(scale_shape, jnp.float32),
            })
        else:
            out.append(jax.ShapeDtypeStruct(leaf.shape, jnp.bfloat16))
    return jax.tree_util.tree_unflatten(tdef, out)


def serving_axes(param_axes_tree, abstract_packed_tree):
    """Axes tree matching the *packed* structure: for packed leaves the
    codes get the original axes and the scale gets (None..., last-axis)."""
    flat_axes, adef = jax.tree_util.tree_flatten(
        param_axes_tree, is_leaf=lambda x: isinstance(x, tuple))
    flat_pk = adef.flatten_up_to(abstract_packed_tree)
    out = []
    for axes, leaf in zip(flat_axes, flat_pk):
        if isinstance(leaf, dict) and set(leaf) == {"packed", "scale"}:
            out.append({
                "packed": axes,
                "scale": tuple([None] * (len(axes) - 1)) + (axes[-1],),
            })
        else:
            out.append(axes)
    return jax.tree_util.tree_unflatten(adef, out)


def replicate_fsdp(axes_tree):
    """Serving sharding policy: drop the FSDP axis (weights replicated over
    'data'; TP only).  Kills the per-step weight all-gather that FSDP
    sharding would force during decode — see EXPERIMENTS.md §Perf."""
    def strip(axes):
        return tuple(None if a == "fsdp" else a for a in axes)
    return jax.tree_util.tree_map(
        strip, axes_tree,
        is_leaf=lambda x: isinstance(x, tuple) and all(
            isinstance(a, (str, type(None))) for a in x))
