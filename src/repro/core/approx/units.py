"""Software models of the paper's complex-operation hardware units.

These are *bit-accurate* models (matching the stated LUT sizes and index
widths), not fast paths: on TPU the VPU evaluates exp/sigmoid natively, so
the value of these units here is (a) faithfully reproducing the accelerator's
numerics for the quantized-model evaluation and (b) serving as oracles for
the Pallas kernels in `repro.kernels.expsig` / `repro.kernels.divlut`.

All units follow the paper's precision contract (§3.2): 9-bit I/O quantized
activations, 16-bit internal arithmetic. The models below operate on f32
carriers but round intermediates to the stated grids.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

# ---------------------------------------------------------------------------
# Exponential unit (mode=0 of the EXP–σ unit, §4.4)
#
#   e^x = 2^(x·log2 e);   y = u + v (integer + fraction);
#   2^u by shift, 2^v from a 256-entry LUT on the top-8 fraction bits.
#   The multiply by log2(e) ≈ 1.0111_2 is one add, one sub, two shifts:
#       x·log2e ≈ x + x>>2 + x>>3 + x>>4  (= x·1.4375; true value 1.442695)
#   The paper's ≈1.0111₂ = 1.4375 — we reproduce exactly that constant so the
#   model's error matches the hardware's.
# ---------------------------------------------------------------------------

_LOG2E_HW = 1.0 + 0.25 + 0.125 + 0.0625  # 1.0111_2 = 1.4375

# 256-entry fraction LUT: 2^(i/256) rounded to 8 fractional bits (paper:
# "eight-bit precision"), stored once as a module constant.
EXP_LUT_TABLE = np.round(np.exp2(np.arange(256) / 256.0) * 256.0) / 256.0
_EXP_LUT = EXP_LUT_TABLE.astype(np.float32)   # numpy: no backend at import


def exp_lut(x: jnp.ndarray, *, table: jnp.ndarray | None = None
            ) -> jnp.ndarray:
    """e^x per the paper's EXP unit.  Valid (as in hardware) for the WKV
    operator's argument range; inputs are clamped to the representable
    exponent window of the 16-bit internal format.

    `table` lets a caller supply the 256-entry fraction LUT as an explicit
    operand — the fused decode kernel must do this because Pallas kernels
    cannot capture array constants (the LUT becomes a VMEM-resident input,
    exactly the paper's on-chip table)."""
    x = jnp.asarray(x, jnp.float32)
    y = x * _LOG2E_HW
    # 16-bit internal: clamp the base-2 exponent so 2^u fits s7.8 arithmetic
    y = jnp.clip(y, -24.0, 24.0)
    u = jnp.floor(y)
    v = y - u
    idx = jnp.clip((v * 256.0).astype(jnp.int32), 0, 255)
    frac = jnp.asarray(_EXP_LUT if table is None else table)[idx]
    return jnp.exp2(u) * frac


# ---------------------------------------------------------------------------
# Sigmoid unit (mode=1), paper Eq. (9): 4-segment PWL, dyadic slopes.
# ---------------------------------------------------------------------------

def sigmoid_pwl(x: jnp.ndarray) -> jnp.ndarray:
    x = jnp.asarray(x, jnp.float32)
    ax = jnp.abs(x)
    f = jnp.where(
        ax >= 5.0, 1.0,
        jnp.where(
            ax >= 2.375, 0.03125 * ax + 0.84375,
            jnp.where(ax >= 1.0, 0.125 * ax + 0.625, 0.25 * ax + 0.5)))
    return jnp.where(x >= 0, f, 1.0 - f)


# ---------------------------------------------------------------------------
# Leading-one detector (Algorithm 1): hierarchical binary search.
# Software model over int32 words; returns -1 for zero input, else the bit
# position of the most significant set bit.
# ---------------------------------------------------------------------------

def lod(x: jnp.ndarray, width: int = 16) -> jnp.ndarray:
    """Vectorized LOD via the paper's successive-halving loop."""
    x = jnp.asarray(x, jnp.int32)
    d = x & ((1 << width) - 1) if width < 32 else x
    p = jnp.zeros_like(d)
    w = width
    while w > 1:
        h = w // 2
        upper = d >> h
        has_upper = upper != 0
        p = jnp.where(has_upper, p + h, p)
        d = jnp.where(has_upper, upper, d & ((1 << h) - 1))
        w = h
    return jnp.where(x == 0, -1, p)


# ---------------------------------------------------------------------------
# Unsigned division unit (§4.3):
#   X = 2^k1·x, Y = 2^k2·y with 1 <= x,y < 2;
#   Q = (x/y) << (k1 - k2);   x/y from a 256-entry 2-D LUT indexed by the
#   4 MSBs after the leading one of x and y, 8-bit quotient precision.
# ---------------------------------------------------------------------------

def _build_div_lut() -> np.ndarray:
    """table[i, j] ≈ (1 + (i+0.5)/16) / (1 + (j+0.5)/16), 8-bit rounded.

    Midpoint-of-bin evaluation (i+0.5) is the standard LUT construction and
    halves the worst-case error vs. bin-left-edge.
    """
    i = (1.0 + (np.arange(16)[:, None] + 0.5) / 16.0)
    j = (1.0 + (np.arange(16)[None, :] + 0.5) / 16.0)
    t = i / j
    return np.round(t * 256.0) / 256.0


DIV_LUT_TABLE = _build_div_lut()
_DIV_LUT = DIV_LUT_TABLE.reshape(-1).astype(np.float32)


def div_lut(x: jnp.ndarray, y: jnp.ndarray, *,
            table: jnp.ndarray | None = None) -> jnp.ndarray:
    """x / y per the paper's DIVU, generalized to f32 carriers.

    Signs are separated first (the unit is unsigned); magnitudes are
    decomposed with frexp (the LOD+normalize step), the mantissa ratio comes
    from the 2-D LUT, and the exponent difference is applied as a shift.
    Division by (quantized) zero saturates, as hardware would.
    `table` (flat 256-entry) has the same role as in `exp_lut`: an explicit
    operand for Pallas kernels that cannot capture array constants.
    """
    x = jnp.asarray(x, jnp.float32)
    y = jnp.asarray(y, jnp.float32)
    sign = jnp.sign(x) * jnp.where(y < 0, -1.0, 1.0)
    ax, ay = jnp.abs(x), jnp.abs(y)
    mx, ex = jnp.frexp(jnp.maximum(ax, 1e-38))   # m in [0.5, 1)
    my, ey = jnp.frexp(jnp.maximum(ay, 1e-38))
    # convert to [1, 2) normalization as in the paper
    mx, ex = mx * 2.0, ex - 1
    my, ey = my * 2.0, ey - 1
    ix = jnp.clip(((mx - 1.0) * 16.0).astype(jnp.int32), 0, 15)
    iy = jnp.clip(((my - 1.0) * 16.0).astype(jnp.int32), 0, 15)
    frac = jnp.asarray(_DIV_LUT if table is None else table)[ix * 16 + iy]
    q = frac * jnp.exp2((ex - ey).astype(jnp.float32))
    q = jnp.where(ay <= 0, jnp.float32(2.0**15), q)  # saturate on div-by-0
    q = jnp.where(ax <= 0, 0.0, q)
    return sign * q
