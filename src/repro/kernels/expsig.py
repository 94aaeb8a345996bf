"""Pallas TPU kernel: the reusable EXP-σ unit (paper §4.4 -> TPU).

One kernel, two modes (the paper's shared datapath):
  mode=0  e^x   via  2^(x·log2e_hw) with the hardware constant
          log2e ≈ 1.0111₂ = 1.4375 (1 add + 1 sub + 2 shifts in the paper;
          a fused multiply here), integer part by exp2, fraction part from
          the 256-entry EXP-LUT resident in VMEM (1 KiB).
  mode=1  sigmoid via the 4-segment piecewise-linear approximation (Eq. 9)
          with dyadic slopes — pure VPU select/multiply-add, no table.

On TPU this unit is about *numerics fidelity* (the quantized model must see
the accelerator's approximation error), not speed — DESIGN.md §2-C3.
"""
from __future__ import annotations

import functools

import numpy as np
import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.approx.units import EXP_LUT_TABLE, _LOG2E_HW
from repro.kernels.common import interpret_default

_LUT = EXP_LUT_TABLE.astype(np.float32)   # numpy: no backend at import


def _kernel(x_ref, lut_ref, o_ref, *, mode: int):
    x = x_ref[...].astype(jnp.float32)
    if mode == 0:
        y = jnp.clip(x * _LOG2E_HW, -24.0, 24.0)
        u = jnp.floor(y)
        v = y - u
        idx = jnp.clip((v * 256.0).astype(jnp.int32), 0, 255)
        frac = lut_ref[...][idx]          # VMEM-resident 256-entry LUT
        o_ref[...] = (jnp.exp2(u) * frac).astype(o_ref.dtype)
    else:
        ax = jnp.abs(x)
        f = jnp.where(
            ax >= 5.0, 1.0,
            jnp.where(ax >= 2.375, 0.03125 * ax + 0.84375,
                      jnp.where(ax >= 1.0, 0.125 * ax + 0.625,
                                0.25 * ax + 0.5)))
        o_ref[...] = jnp.where(x >= 0, f, 1.0 - f).astype(o_ref.dtype)


def _call(x: jnp.ndarray, mode: int, block: int, interpret) -> jnp.ndarray:
    shape = x.shape
    xf = x.reshape(-1)
    n = xf.shape[0]
    blk = min(block, n)
    pad = (-n) % blk
    if pad:
        xf = jnp.pad(xf, (0, pad))
    out = pl.pallas_call(
        functools.partial(_kernel, mode=mode),
        grid=(xf.shape[0] // blk,),
        in_specs=[pl.BlockSpec((blk,), lambda i: (i,)),
                  pl.BlockSpec((256,), lambda i: (0,))],
        out_specs=pl.BlockSpec((blk,), lambda i: (i,)),
        out_shape=jax.ShapeDtypeStruct((xf.shape[0],), x.dtype),
        interpret=interpret_default(interpret),
    )(xf, _LUT)
    if pad:
        out = out[:n]
    return out.reshape(shape)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def exp_kernel(x: jnp.ndarray, *, block: int = 4096,
               interpret: bool | None = None) -> jnp.ndarray:
    return _call(x, 0, block, interpret)


@functools.partial(jax.jit, static_argnames=("block", "interpret"))
def sigmoid_kernel(x: jnp.ndarray, *, block: int = 4096,
                   interpret: bool | None = None) -> jnp.ndarray:
    return _call(x, 1, block, interpret)
