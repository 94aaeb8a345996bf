"""Mixed-precision quantization policy over a parameter tree (paper §3.2).

Classification rule (the paper's):
  * weights that MULTIPLY activations (all ≥2-D projection matrices,
    including MoE expert tensors)            -> Δ-PoT
  * weights used ADDITIVELY or element-wise (token-shift μ, decay w, bonus u,
    LayerNorm γ/β, biases — everything 1-D)  -> 9-bit uniform symmetric
  * embedding tables (gather, no multiply)   -> 9-bit uniform symmetric
  * activations                              -> 9-bit uniform (applied inside
    the quantized model's forward pass, not here)

The classifier is path-based with a ndim fallback so it works on any of the
registered architectures' parameter trees without per-model code.
"""
from __future__ import annotations

import dataclasses
import re
from typing import Any, Callable

import jax
import jax.numpy as jnp

from repro.core.quant.delta_pot import (
    DPotFormat, FORMAT_W9, dpot_quantize, dpot_fake_quant, DPotQuantized,
)
from repro.core.quant.uniform import (
    uniform_quantize, uniform_fake_quant, uniform_dequantize,
)

# path substrings that force the uniform branch even for 2-D tensors (a
# stacked layer axis makes RWKV-6's token-shift mu `time_maa_x` (L, D) and
# `time_maa` (L, 5, D) and its bonus u `time_faaaa` (L, H, N) 2-D or more;
# its LoRA matrices `maa_w*` / `td_w*` multiply activations and stay Δ-PoT)
_ADDITIVE_HINTS = re.compile(
    r"(embed|emb_|ln|norm|scale|bias|mu_|time_mix|time_maa|time_faaaa|"
    r"time_decay|time_first|decay|bonus|gamma|beta|_shift|pos_emb|a_log|"
    r"dt_bias|conv)",
    re.IGNORECASE,
)


@dataclasses.dataclass(frozen=True)
class QuantPolicy:
    """The mixed-precision operating point."""

    matmul_fmt: DPotFormat = FORMAT_W9   # Δ-PoT format for projection matrices
    additive_bits: int = 9               # uniform bits for additive weights
    activation_bits: int = 9             # uniform bits for activations
    channel_axis: int = -1               # per-output-channel scales
    mse_search: bool = False

    def act_fq(self, x: jnp.ndarray) -> jnp.ndarray:
        """Activation fake-quant, per-tensor (the paper's A9)."""
        return uniform_fake_quant(x, self.activation_bits, None)


def classify_param(path: str, leaf: Any) -> str:
    """'matmul' | 'additive' | 'skip' for a parameter leaf."""
    if not hasattr(leaf, "ndim"):
        return "skip"
    if leaf.ndim < 2:
        return "additive"
    if _ADDITIVE_HINTS.search(path):
        return "additive"
    return "matmul"


# ---------------------------------------------------------------------------
# Per-tensor plane selection (RWKVQuant direction, arXiv 2505.03803): pick
# scalar-W8 / scalar-W4 / VQ per matmul tensor with a cheap weight-outlier
# proxy.  Scalar Δ-PoT sets each channel's scale from its max |w|, so a few
# extreme weights crush the resolution of everything else in the channel —
# outlier-heavy tensors want a codebook (VQ); well-behaved near-Gaussian
# tensors tolerate the 4-bit single-term format; the middle keeps W8.
# ---------------------------------------------------------------------------

PLANES = ("w8", "w4", "vq")


def weight_outlier_proxy(w, sample: int = 1 << 16) -> float:
    """Excess kurtosis of the weight distribution — the outlier/curvature
    proxy.  ~0 for Gaussian weights, large and positive for heavy tails
    (the regime where per-channel scalar scales degrade).  Deterministic
    strided subsample keeps it cheap on big tensors."""
    import numpy as np
    v = np.asarray(w, np.float32).reshape(-1)
    if v.size > sample:
        v = v[:: (v.size + sample - 1) // sample]
    v = v - v.mean()
    var = float((v * v).mean())
    if var <= 0:
        return 0.0
    return float((v ** 4).mean() / (var * var) - 3.0)


@dataclasses.dataclass(frozen=True)
class PlanePolicy:
    """Which quantized plane each matmul tensor gets.

    default       — "proxy" (threshold `weight_outlier_proxy`) or a fixed
                    plane name ("w8" | "w4" | "vq")
    w4_max_proxy  — proxy <= this -> W4 (well-behaved tails)
    vq_min_proxy  — proxy >= this -> VQ (outlier-heavy); between the two
                    thresholds the tensor keeps scalar W8
    vq_codes      — codebook entries (<= 256, uint8 indices)
    overrides     — ((path regex, plane), ...) checked first, in order

    Serializes to/from a plain dict (`to_config` / `from_config`) so a
    snapshot's `build_config` can rebuild the exact same per-tensor
    selection — part of the plane-policy fingerprint that keys the prefix
    cache (serving.plan.ExecutionPlan.cache_variant)."""

    default: str = "proxy"
    w4_max_proxy: float = 1.5
    vq_min_proxy: float = 8.0
    vq_codes: int = 256
    overrides: tuple = ()

    def __post_init__(self):
        if self.default not in PLANES + ("proxy",):
            raise ValueError(f"default={self.default!r}: expected one of "
                             f"{PLANES + ('proxy',)}")
        for pat, plane in self.overrides:
            if plane not in PLANES:
                raise ValueError(f"override {pat!r} -> {plane!r}: expected "
                                 f"one of {PLANES}")

    def plane_for(self, path: str, leaf) -> str:
        """The plane for one matmul leaf (callers classify first)."""
        for pat, plane in self.overrides:
            if re.search(pat, path):
                return plane
        if self.default != "proxy":
            return self.default
        p = weight_outlier_proxy(leaf)
        if p >= self.vq_min_proxy:
            return "vq"
        if p <= self.w4_max_proxy:
            return "w4"
        return "w8"

    def to_config(self) -> dict:
        return {"default": self.default,
                "w4_max_proxy": float(self.w4_max_proxy),
                "vq_min_proxy": float(self.vq_min_proxy),
                "vq_codes": int(self.vq_codes),
                "overrides": [list(o) for o in self.overrides]}

    @classmethod
    def from_config(cls, cfg) -> "PlanePolicy | None":
        if cfg is None:
            return None
        return cls(default=cfg["default"],
                   w4_max_proxy=cfg["w4_max_proxy"],
                   vq_min_proxy=cfg["vq_min_proxy"],
                   vq_codes=cfg["vq_codes"],
                   overrides=tuple(tuple(o) for o in cfg["overrides"]))


# Presets: the ablation sweep's named operating points.
PLANE_W8 = PlanePolicy(default="w8")
PLANE_W4 = PlanePolicy(default="w4")     # bandwidth point (nibble planes)
PLANE_VQ = PlanePolicy(default="vq")     # accuracy fallback (codebooks)
PLANE_PROXY = PlanePolicy()              # RWKVQuant-style mixed selection


def _iter_with_paths(tree):
    flat, treedef = jax.tree_util.tree_flatten_with_path(tree)
    for path, leaf in flat:
        yield jax.tree_util.keystr(path), leaf
    return treedef


def fake_quantize_tree(params, policy: QuantPolicy = QuantPolicy()):
    """quantize→dequantize every weight per the policy (for accuracy evals).

    Returns a tree with the same structure/dtypes as `params`.
    """
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        p = jax.tree_util.keystr(path)
        kind = classify_param(p, leaf)
        if kind == "matmul":
            out.append(dpot_fake_quant(
                leaf, policy.matmul_fmt.ks, policy.channel_axis,
                policy.mse_search))
        elif kind == "additive":
            out.append(uniform_fake_quant(leaf, policy.additive_bits, None))
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def fake_quantize_tree_with(params, scheme_fn: Callable, bits: int = 9,
                            axis=None):
    """Apply an arbitrary Table-1 scheme to every matmul weight; additive
    weights always get W9 uniform (the paper quantizes those uniformly under
    every scheme — the ablation varies only the matrix-weight scheme)."""
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    for path, leaf in flat:
        p = jax.tree_util.keystr(path)
        kind = classify_param(p, leaf)
        if kind == "matmul":
            out.append(scheme_fn(leaf, bits, axis))
        elif kind == "additive":
            out.append(uniform_fake_quant(leaf, 9, None))
        else:
            out.append(leaf)
    return jax.tree_util.tree_unflatten(treedef, out)


def quantize_tree(params, policy: QuantPolicy = QuantPolicy(), *,
                  planes: "PlanePolicy | None" = None):
    """Real quantization for the serving path: matmul weights become
    DPotQuantized containers, additive weights (codes, scale) pairs.

    With `planes`, each matmul tensor's format follows the per-tensor
    plane selection instead of the single `policy.matmul_fmt`: "w8" keeps
    FORMAT_W8 scalar codes, "w4" the 4-bit FORMAT_W4 (byte accounting at
    4 bits/weight), "vq" a `{"vq_idx", "codebook"}` pair (1 byte/weight +
    the codebook).  Stats gain a per-plane breakdown and the selection map.

    Returns (quantized_tree, stats) where stats has byte accounting used by
    the Table-2 style resource benchmark.
    """
    from repro.core.quant.delta_pot import FORMAT_W4, FORMAT_W8
    from repro.core.quant.vq import vq_quantize
    flat, treedef = jax.tree_util.tree_flatten_with_path(params)
    out = []
    bytes_fp16 = 0
    bytes_quant = 0
    by_plane: dict = {}
    plane_map: dict = {}
    for path, leaf in flat:
        p = jax.tree_util.keystr(path)
        kind = classify_param(p, leaf)
        if kind == "skip":
            out.append(leaf)
            continue
        bytes_fp16 += leaf.size * 2
        if kind == "matmul":
            if planes is None:
                q = dpot_quantize(leaf, policy.matmul_fmt,
                                  axis=policy.channel_axis,
                                  mse_search=policy.mse_search)
                bytes_quant += q.nbytes_hardware()
                out.append(q)
                continue
            plane = planes.plane_for(p, leaf)
            if plane == "w4" and (leaf.ndim < 2 or leaf.shape[-2] % 2):
                plane = "w8"        # nibble pairing needs an even axis -2
            if plane == "vq":
                idx, codebook = vq_quantize(leaf, planes.vq_codes)
                nb = idx.size + codebook.size * 2
                out.append({"vq_idx": idx, "codebook": codebook})
            else:
                fmt = FORMAT_W4 if plane == "w4" else FORMAT_W8
                q = dpot_quantize(leaf, fmt, axis=policy.channel_axis,
                                  mse_search=policy.mse_search)
                nb = q.nbytes_hardware()
                out.append(q)
            plane_map[p] = plane
            by_plane[plane] = by_plane.get(plane, 0) + nb
            bytes_quant += nb
        else:
            codes, scale = uniform_quantize(leaf, policy.additive_bits,
                                            axis=None)
            bytes_quant += (leaf.size * policy.additive_bits + 7) // 8 + 4
            out.append({"codes": codes.astype(jnp.int16), "scale": scale})
    stats = {"bytes_fp16": bytes_fp16, "bytes_quant": bytes_quant,
             "compression": bytes_fp16 / max(bytes_quant, 1)}
    if planes is not None:
        stats["bytes_by_plane"] = by_plane
        stats["planes"] = plane_map
    return jax.tree_util.tree_unflatten(treedef, out), stats


def dequantize_tree(qparams):
    """Inverse of quantize_tree (reference path for tests)."""
    def deq(leaf):
        if isinstance(leaf, DPotQuantized):
            from repro.core.quant.delta_pot import dpot_dequantize
            return dpot_dequantize(leaf)
        return leaf

    def deq_dict(leaf):
        if isinstance(leaf, dict) and set(leaf) == {"codes", "scale"}:
            return uniform_dequantize(leaf["codes"], leaf["scale"])
        if isinstance(leaf, dict) and set(leaf) == {"vq_idx", "codebook"}:
            from repro.core.quant.vq import vq_dequantize
            return vq_dequantize(leaf["vq_idx"],
                                 leaf["codebook"]).astype(jnp.float32)
        return leaf

    tree = jax.tree_util.tree_map(
        deq, qparams, is_leaf=lambda x: isinstance(x, DPotQuantized))
    return jax.tree_util.tree_map(
        deq_dict, tree,
        is_leaf=lambda x: isinstance(x, dict) and set(x) in
        ({"codes", "scale"}, {"vq_idx", "codebook"}))
