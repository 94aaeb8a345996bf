"""Δ-PoT quantization (paper §3.1).

A quantized level is a sum of n powers-of-two terms

    w_q = sign(w) * 2γ * Σ_{i<n} p_i ,   p_i ∈ {0, p_{i-1}·2^{-1}, …, p_{i-1}·2^{-(2^{k_i}-1)}},  p_{-1} = 1

and what is *stored* is the differential exponent Δq_i = q_i − q_{i-1}
(k_i bits per term; Δq_i = 0 encodes "term absent", which also zeroes every
later term).  Compared to APoT with fixed k = b/n, Δ-PoT allows distinct k_i
per term and covers a wider dynamic range at the same bit budget.

Implementation notes
--------------------
* `DPotFormat(ks)` fixes the per-term widths, e.g. ks=(4, 4) is the paper's
  "proposed" 8-code-bit format (9 bits with sign — the W9 row of Table 1);
  ks=(3, 4) is the 7-code-bit variant that packs *with* its sign into one
  int8 word for the Pallas serving kernel; ks=(4,) degenerates to plain PoT.
* Levels are enumerated once per format (≤ 2^8 = 256 entries) and quantization
  is nearest-level, ties to the lower level — exact nearest rounding.  Formats
  of one or two terms find the code in closed form from the exponent and
  mantissa (`_closed_form_code`: elementwise, no table lookup, so packing a
  7B model costs seconds); longer ones search the sorted midpoints.
* The scale γ is chosen per-channel (`axis` = the *output*-channel axis of a
  weight matrix) so that the maximum representable level hits the channel's
  max |w|; an optional MSE grid-search refines it, matching how the paper
  calibrates ("algorithmically refined to balance precision and resources").
* `dpot_fake_quant` is the straight-through-estimator version used for the
  Table-1 accuracy ablation and for QAT-style experiments.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Sequence

import jax
import jax.numpy as jnp
import numpy as np


@dataclasses.dataclass(frozen=True)
class DPotFormat:
    """Static description of a Δ-PoT code format."""

    ks: tuple[int, ...] = (4, 4)

    @property
    def n_terms(self) -> int:
        return len(self.ks)

    @property
    def code_bits(self) -> int:
        return int(sum(self.ks))

    @property
    def total_bits(self) -> int:
        """Code bits + 1 sign bit (what HBM traffic accounting should use)."""
        return self.code_bits + 1

    @property
    def n_codes(self) -> int:
        return 1 << self.code_bits

    def __post_init__(self):
        if not self.ks:
            raise ValueError("need at least one term")
        if any(k < 1 for k in self.ks):
            raise ValueError(f"term widths must be >= 1, got {self.ks}")
        if self.code_bits > 8:
            raise ValueError(
                f"code bits {self.code_bits} > 8 unsupported (uint8 storage)")


# The paper's formats --------------------------------------------------------
#   W9 "proposed": sign + ks=(4,4)  -> Table-1 accuracy row
#   W8 kernel fmt: sign + ks=(3,4)  -> packs into a single int8 for Pallas
#   W4 sub-byte  : sign + ks=(3,)   -> TWO weights per uint8 (nibble pair);
#                  the RWKVQuant-direction bandwidth plane — single-term PoT
#                  levels {0, 2^-1 .. 2^-7}, half the slab traffic of W8
FORMAT_W9 = DPotFormat(ks=(4, 4))
FORMAT_W8 = DPotFormat(ks=(3, 4))
FORMAT_W4 = DPotFormat(ks=(3,))
FORMAT_POT4 = DPotFormat(ks=(4,))  # degenerate single-term = classic PoT


@functools.lru_cache(maxsize=None)
def _level_table_np(ks: tuple[int, ...]) -> np.ndarray:
    """All 2^Σk levels (unsigned, before the 2γ scale), indexed by code.

    Code layout: term 0 in the LOW k0 bits, term 1 in the next k1 bits, …
    (low-to-high), so decoding is successive shift/mask — identical to the
    paper's hardware decoder which peels terms off a shift register.
    """
    n = len(ks)
    n_codes = 1 << sum(ks)
    levels = np.zeros((n_codes,), dtype=np.float64)
    for code in range(n_codes):
        c = code
        p_prev = 1.0
        total = 0.0
        alive = True
        for i in range(n):
            dq = c & ((1 << ks[i]) - 1)
            c >>= ks[i]
            if not alive or dq == 0:
                alive = False
                continue
            p_i = p_prev * (2.0 ** (-dq))
            total += p_i
            p_prev = p_i
        levels[code] = total
    return levels


@functools.lru_cache(maxsize=None)
def _sorted_levels_np(ks: tuple[int, ...]):
    """(sorted unique levels, code for each sorted level, midpoints)."""
    levels = _level_table_np(ks)
    order = np.argsort(levels, kind="stable")
    sorted_levels = levels[order]
    # Dedup keeping the first (lowest) code for each distinct level.
    uniq_mask = np.ones_like(sorted_levels, dtype=bool)
    uniq_mask[1:] = sorted_levels[1:] != sorted_levels[:-1]
    sorted_levels = sorted_levels[uniq_mask]
    codes = order[uniq_mask].astype(np.int32)
    mids = 0.5 * (sorted_levels[1:] + sorted_levels[:-1])
    return sorted_levels, codes, mids


def dpot_levels(fmt: DPotFormat) -> jnp.ndarray:
    """Dense code→level table (length 2^code_bits), unsigned, pre-scale."""
    return jnp.asarray(_level_table_np(fmt.ks), dtype=jnp.float32)


def dpot_max_level(fmt: DPotFormat) -> float:
    return float(_level_table_np(fmt.ks).max())


@jax.tree_util.register_pytree_node_class
@dataclasses.dataclass
class DPotQuantized:
    """A Δ-PoT-quantized tensor.

    codes : uint8, same shape as the original tensor (Δq terms packed
            low-to-high, sign NOT included)
    signs : int8 in {-1, +1}, same shape
    scale : f32, broadcastable to the tensor shape (per-channel 2γ absorbed)
    """

    codes: jnp.ndarray
    signs: jnp.ndarray
    scale: jnp.ndarray
    ks: tuple[int, ...] = (4, 4)

    @property
    def fmt(self) -> DPotFormat:
        return DPotFormat(self.ks)

    @property
    def shape(self):
        return self.codes.shape

    def nbytes_hardware(self) -> int:
        """HBM footprint at the *hardware* packing (code_bits+1 per weight,
        plus one f32 scale per channel)."""
        n = int(np.prod(self.codes.shape))
        return (n * self.fmt.total_bits + 7) // 8 + self.scale.size * 4

    def tree_flatten(self):
        return (self.codes, self.signs, self.scale), (self.ks,)

    @classmethod
    def tree_unflatten(cls, aux, children):
        codes, signs, scale = children
        return cls(codes=codes, signs=signs, scale=scale, ks=aux[0])


def dpot_channel_scale(amax: jnp.ndarray, fmt: DPotFormat) -> jnp.ndarray:
    """The scale s = 2γ that maps a channel's max |w| to the largest level
    (1 for an all-zero channel)."""
    base = amax / dpot_max_level(fmt)
    return jnp.where(base <= 0, 1.0, base)


def _choose_scale(absw: jnp.ndarray, axis, fmt: DPotFormat,
                  mse_search: bool, x_for_mse: jnp.ndarray | None):
    """Per-channel scale s = 2γ so that s * max_level covers max|w|."""
    if axis is None:
        amax = jnp.max(absw)
        keep_shape = ()
    else:
        axes = (axis,) if isinstance(axis, int) else tuple(axis)
        reduce_axes = tuple(i for i in range(absw.ndim) if i not in
                            tuple(a % absw.ndim for a in axes))
        amax = jnp.max(absw, axis=reduce_axes, keepdims=True)
    base = dpot_channel_scale(amax, fmt)
    if not mse_search:
        return base
    # grid-search a multiplicative refinement of the scale, minimizing MSE
    cands = jnp.asarray([0.6, 0.7, 0.8, 0.9, 1.0, 1.1, 1.2], jnp.float32)

    def err_for(c):
        s = base * c
        q = _nearest_level(x_for_mse / s, fmt) * s
        d = (q - x_for_mse) ** 2
        if axis is None:
            return jnp.sum(d)
        return jnp.sum(d, axis=reduce_axes, keepdims=True)

    errs = jnp.stack([err_for(c) for c in cands], axis=0)
    best = jnp.argmin(errs, axis=0)
    return base * cands[best]


def _nearest_level(x_abs_scaled: jnp.ndarray, fmt: DPotFormat) -> jnp.ndarray:
    """Map |x|/s to the nearest representable level value (not the code)."""
    sorted_levels, _, mids = _sorted_levels_np(fmt.ks)
    lv = jnp.asarray(sorted_levels, jnp.float32)
    md = jnp.asarray(mids, jnp.float32)
    idx = jnp.searchsorted(md, x_abs_scaled.astype(jnp.float32))
    return lv[idx]


def _searched_code(x_abs_scaled: jnp.ndarray, fmt: DPotFormat) -> jnp.ndarray:
    """The code of the level nearest |x|/s (ties low) by a search of the
    sorted midpoints, for any format."""
    sorted_levels, codes, mids = _sorted_levels_np(fmt.ks)
    cd = jnp.asarray(codes, jnp.int32)
    md = jnp.asarray(mids, jnp.float32)
    idx = jnp.searchsorted(md, x_abs_scaled.astype(jnp.float32))
    return cd[idx].astype(jnp.uint8)


def _closed_form_code(y: jnp.ndarray, ks: tuple[int, ...]) -> jnp.ndarray:
    """The code of the level nearest y >= 0 (ties low) for a format of one
    or two terms, elementwise and exact.

    With A = 2^k0 - 1 and B = 2^k1 - 1 the levels are 0, 2^-a (a in 1..A)
    and, with a second term, 2^-a (1 + 2^-b) (b in 1..B).  Writing
    y = 2^-a (1 + t), t in [0, 1) (`frexp`, exact), the nearest level in
    that octave rounds t to 0, to a power of two 2^-b, or up to 1 (the next
    octave's 2^-(a-1)); between 2^k and 2^(k+1) the midpoint is
    1.5 x 2^k.  Every midpoint is exact in f32, so the comparisons decide
    exactly what a search of the midpoints decides.  Below 2^-A only 0
    and 2^-A remain; above the largest level, the largest level's code."""
    k0 = ks[0]
    A = (1 << k0) - 1
    B = (1 << ks[1]) - 1 if len(ks) == 2 else 0
    y = y.astype(jnp.float32)
    mant, ex = jnp.frexp(y)
    a = 1 - ex                                  # y in [2^-a, 2^-(a-1))
    t = 2.0 * mant - 1.0
    if B:
        tm, tk = jnp.frexp(t)                   # t in [2^(tk-1), 2^tk)
        up = tm > 0.75
        carry = up & (tk == 0)                  # t > 3/4: up to 2^-(a-1)
        b = jnp.minimum(jnp.where(up, -tk, 1 - tk), B)
        b = jnp.where(carry | (t <= 2.0 ** -(B + 1)), 0, b)
        max_level, max_code = 0.75, 1 + (1 << k0)
    else:
        carry = t > 0.5
        b = jnp.zeros_like(a)
        max_level, max_code = 0.5, 1
    code = (a - carry.astype(a.dtype)) + (b << k0)
    code = jnp.where(a > A, A, code)            # (2^-(A+1), 2^-A): 2^-A
    code = jnp.where(y <= 2.0 ** -(A + 1), 0, code)
    code = jnp.where(y > max_level, max_code, code)
    return code.astype(jnp.uint8)


def _nearest_code(x_abs_scaled: jnp.ndarray, fmt: DPotFormat) -> jnp.ndarray:
    if fmt.n_terms <= 2:
        return _closed_form_code(x_abs_scaled, fmt.ks)
    return _searched_code(x_abs_scaled, fmt)


def dpot_quantize(w: jnp.ndarray, fmt: DPotFormat = FORMAT_W9, *,
                  axis: int | None = 0, mse_search: bool = False
                  ) -> DPotQuantized:
    """Quantize a weight tensor to Δ-PoT codes.

    axis: the output-channel axis that receives an independent scale
          (None = a single tensor-wide scale).
    """
    w = jnp.asarray(w, jnp.float32)
    scale = _choose_scale(jnp.abs(w), axis, fmt, mse_search, w)
    return dpot_quantize_scaled(w, scale, fmt)


def dpot_quantize_scaled(w: jnp.ndarray, scale: jnp.ndarray,
                         fmt: DPotFormat) -> DPotQuantized:
    """Quantize `w` to Δ-PoT codes with a given (broadcastable) scale: the
    nearest level of |w| / scale, and the sign."""
    w = jnp.asarray(w, jnp.float32)
    codes = _nearest_code(jnp.abs(w) / scale, fmt)
    signs = jnp.where(w < 0, -1, 1).astype(jnp.int8)
    return DPotQuantized(codes=codes, signs=signs, scale=scale, ks=fmt.ks)


def dpot_decode_codes(codes: jnp.ndarray, ks: Sequence[int]) -> jnp.ndarray:
    """Vectorized code → level decode (the VPU analogue of the paper's
    shift-register decoder): peel Δq_i terms, accumulate 2^(−Σ Δq)."""
    ks = tuple(ks)
    c = codes.astype(jnp.int32)
    total = jnp.zeros(codes.shape, jnp.float32)
    q_cum = jnp.zeros(codes.shape, jnp.float32)
    alive = None    # no all-true constant mask: Mosaic cannot relayout it
    for k in ks:
        dq = c & ((1 << k) - 1)
        c = c >> k
        alive = (dq > 0) if alive is None else alive & (dq > 0)
        q_cum = q_cum + dq.astype(jnp.float32)
        term = jnp.where(alive, jnp.exp2(-q_cum), 0.0)
        total = total + term
    return total


def dpot_dequantize(q: DPotQuantized) -> jnp.ndarray:
    lvl = dpot_decode_codes(q.codes, q.ks)
    return q.signs.astype(jnp.float32) * lvl * q.scale


@functools.partial(jax.custom_vjp, nondiff_argnums=(1, 2, 3))
def dpot_fake_quant(w, ks: tuple[int, ...] = (4, 4), axis: int | None = 0,
                    mse_search: bool = False):
    """quantize→dequantize with a straight-through gradient."""
    fmt = DPotFormat(tuple(ks))
    q = dpot_quantize(w, fmt, axis=axis, mse_search=mse_search)
    return dpot_dequantize(q).astype(w.dtype)


def _fq_fwd(w, ks, axis, mse_search):
    return dpot_fake_quant(w, ks, axis, mse_search), None


def _fq_bwd(ks, axis, mse_search, _, g):
    return (g,)


dpot_fake_quant.defvjp(_fq_fwd, _fq_bwd)


# ---------------------------------------------------------------------------
# Hardware packing: sign+codes in one int8 word (requires code_bits <= 7).
# Bit layout (matching the paper's "concatenated off-chip, decoded on-chip"):
#   bit 7   : sign (1 = negative)
#   bits 6:0: code (term 0 in low bits)
# ---------------------------------------------------------------------------

def dpot_pack_int8(q: DPotQuantized) -> jnp.ndarray:
    fmt = q.fmt
    if fmt.code_bits > 7:
        raise ValueError(
            f"format {fmt.ks} needs {fmt.code_bits} code bits; only <=7 pack "
            "into int8 with the sign — use FORMAT_W8 (ks=(3,4)) for kernels")
    sign_bit = (q.signs < 0).astype(jnp.uint8) << 7
    return (q.codes | sign_bit).astype(jnp.uint8)


def dpot_unpack_int8(packed: jnp.ndarray, scale: jnp.ndarray,
                     ks: Sequence[int]) -> DPotQuantized:
    ks = tuple(ks)
    codes = (packed & 0x7F).astype(jnp.uint8)
    signs = jnp.where((packed >> 7) & 1, -1, 1).astype(jnp.int8)
    return DPotQuantized(codes=codes, signs=signs, scale=scale, ks=ks)


# ---------------------------------------------------------------------------
# Sub-byte packing: TWO sign+code nibbles per uint8 (requires code_bits <= 3).
# Nibble layout mirrors the int8 word at quarter width:
#   bit 3   : sign (1 = negative)
#   bits 2:0: code (term 0 in low bits)
# Elements pair along axis -2 — the CONTRACTION axis of a (K, N) weight — so
# row 2k lands in the low nibble and row 2k+1 in the high nibble of packed
# row k, and the output-channel axis (per-channel scales, slab column
# layout) is untouched.  A (K, N) weight becomes a (K/2, N) uint8 plane:
# half the HBM bytes of the int8 packing above.
# ---------------------------------------------------------------------------


def dpot_pack_nibbles(q: DPotQuantized) -> jnp.ndarray:
    fmt = q.fmt
    if fmt.code_bits > 3:
        raise ValueError(
            f"format {fmt.ks} needs {fmt.code_bits} code bits; only <=3 pack "
            "into a nibble with the sign — use FORMAT_W4 (ks=(3,))")
    if q.codes.ndim < 2 or q.codes.shape[-2] % 2 != 0:
        raise ValueError(
            f"nibble packing pairs along axis -2; shape {q.codes.shape} "
            "needs >= 2 dims and an even axis -2")
    word = (q.codes | ((q.signs < 0).astype(jnp.uint8) << 3)).astype(
        jnp.uint8)
    lo = word[..., 0::2, :]
    hi = word[..., 1::2, :]
    return (lo | (hi << 4)).astype(jnp.uint8)


def dpot_unpack_nibbles(packed: jnp.ndarray, scale: jnp.ndarray,
                        ks: Sequence[int]) -> DPotQuantized:
    ks = tuple(ks)
    lo = packed & 0xF
    hi = (packed >> 4) & 0xF
    words = jnp.stack([lo, hi], axis=-2)           # (..., K/2, 2, N)
    full = packed.shape[:-2] + (2 * packed.shape[-2], packed.shape[-1])
    words = words.reshape(full)                    # rows re-interleave
    codes = (words & 0x7).astype(jnp.uint8)
    signs = jnp.where((words >> 3) & 1, -1, 1).astype(jnp.int8)
    return DPotQuantized(codes=codes, signs=signs, scale=scale, ks=ks)
