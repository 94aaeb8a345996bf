"""Pallas TPU kernel: fused RWKV-4 WKV scan (paper C4 -> TPU).

The paper's headline systems idea: keep the recurrent state fully on-chip
and stream the sequence through.  TPU mapping: grid (B, C/bc); each cell
owns a bc-wide channel slice whose (a, b, o) state lives in VREGs/VMEM for
the WHOLE sequence — zero HBM state round-trips between timesteps (on GPU
each step is a kernel launch reading state from HBM; that gap is the
paper's motivation §1-(1)).  k/v stream in as one VMEM-resident block.

Numerics: the official stable running-max recurrence (never overflows),
identical to repro.core.wkv.wkv4 — which is this kernel's oracle.

Serving extensions (all optional, default off — the bare call keeps the
original pure-f32 unmasked semantics):

  valid        — (B, T) per-timestep commit mask: a masked-out step still
                 computes (fixed shapes) but its state update is discarded,
                 exactly the scheduler's `where(ok, stepped, old)`.  This is
                 what lets the fused chunked-prefill path run partial prompt
                 chunks bit-identically to the per-op scan.
  carry_dtype  — round-trip the carried state through this dtype every step
                 (e.g. "bfloat16").  The per-op decode oracle stores its
                 state in the pool dtype between steps, so bit-parity with
                 it requires the on-chip carry to snap to the same grid.
  exp_table /  — the paper's EXP / DIV LUT fraction tables as explicit
  div_table      (256,) operands, switching the recurrence to the hw
                 numerics (`core.approx.exp_lut` / `div_lut`).  Kernels
                 cannot capture array constants, so the tables travel as
                 VMEM-resident inputs — the paper's on-chip LUTs.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.core.approx import div_lut, exp_lut
from repro.kernels.common import interpret_default


def _kernel(k_ref, v_ref, w_ref, u_ref, a0_ref, b0_ref, o0_ref, *refs,
            T: int, masked: bool, carry: str | None, luts: bool):
    # every operand is at least 2-D with a unit second-minor axis, so each
    # (1, bc) row is a whole TPU tile row; the math is elementwise per
    # channel, so the row shapes leave the bits of the recurrence unchanged
    refs = list(refs)
    valid_ref = refs.pop(0) if masked else None
    if luts:
        exp_t = refs.pop(0)[...].astype(jnp.float32)
        div_t = refs.pop(0)[...].astype(jnp.float32)
        exp_fn = lambda x: exp_lut(x, table=exp_t)
        div_fn = lambda x, y: div_lut(x, y, table=div_t)
    else:
        exp_fn = jnp.exp
        div_fn = lambda x, y: x / y
    y_ref, af_ref, bf_ref, of_ref = refs
    w = w_ref[...].astype(jnp.float32)                     # (1, bc)
    u = u_ref[...].astype(jnp.float32)
    snap = ((lambda x: x) if carry is None else
            (lambda x: x.astype(jnp.dtype(carry)).astype(jnp.float32)))

    def body(t, state):
        a, b, o = state
        kt = k_ref[0, pl.ds(t, 1), :].astype(jnp.float32)  # (1, bc)
        vt = v_ref[0, pl.ds(t, 1), :].astype(jnp.float32)
        # output (includes the bonus u for the current token)
        no = jnp.maximum(o, u + kt)
        A = exp_fn(o - no)
        Bf = exp_fn(u + kt - no)
        y = div_fn(A * a + Bf * vt, A * b + Bf)
        y_ref[0, pl.ds(t, 1), :] = y.astype(y_ref.dtype)
        # state update
        no2 = jnp.maximum(o - w, kt)
        A2 = exp_fn(o - w - no2)
        B2 = exp_fn(kt - no2)
        na, nb, no_ = A2 * a + B2 * vt, A2 * b + B2, no2
        if masked:
            ok = valid_ref[0, pl.ds(t, 1), :] != 0        # (1, 1)
            na = jnp.where(ok, na, a)
            nb = jnp.where(ok, nb, b)
            no_ = jnp.where(ok, no_, o)
        return (snap(na), snap(nb), snap(no_))

    ld = lambda ref: ref[0].astype(jnp.float32)           # (1, bc)
    a, b, o = jax.lax.fori_loop(
        0, T, body, (ld(a0_ref), ld(b0_ref), ld(o0_ref)))
    af_ref[0] = a
    bf_ref[0] = b
    of_ref[0] = o


@functools.partial(jax.jit,
                   static_argnames=("bc", "interpret", "carry_dtype"))
def wkv4_pallas(k: jnp.ndarray, v: jnp.ndarray, w: jnp.ndarray,
                u: jnp.ndarray, a0=None, b0=None, o0=None, *,
                valid: jnp.ndarray | None = None,
                carry_dtype: str | None = None,
                exp_table: jnp.ndarray | None = None,
                div_table: jnp.ndarray | None = None,
                bc: int = 128, interpret: bool | None = None):
    """k, v: (B, T, C); w, u: (C,) -> (y (B,T,C) f32, (a,b,o) finals (B,C)).

    Optional serving operands (see module docstring): `valid` (B, T) commit
    mask, `carry_dtype` per-step state rounding, `exp_table`/`div_table`
    hw-numerics LUTs (supply both or neither)."""
    B, T, C = k.shape
    bc = min(bc, C)
    while C % bc != 0:
        bc //= 2
    if a0 is None:
        a0 = jnp.zeros((B, C), jnp.float32)
        b0 = jnp.zeros((B, C), jnp.float32)
        o0 = jnp.full((B, C), -1e38, jnp.float32)
    if (exp_table is None) != (div_table is None):
        raise ValueError("exp_table and div_table travel together")
    grid = (B, C // bc)
    # TPU tiling: a block's last two axes must be whole tiles or whole
    # axes, so the per-row operands carry a unit axis — state (B, 1, C),
    # the per-channel vectors (1, C) and the commit mask (B, T, 1)
    seq_spec = pl.BlockSpec((1, T, bc), lambda b, c: (b, 0, c))
    vec_spec = pl.BlockSpec((1, bc), lambda b, c: (0, c))
    st_spec = pl.BlockSpec((1, 1, bc), lambda b, c: (b, 0, c))
    row = lambda s: s.reshape(B, 1, C)
    operands = [k, v, w.reshape(1, C), u.reshape(1, C),
                row(a0), row(b0), row(o0)]
    in_specs = [seq_spec, seq_spec, vec_spec, vec_spec,
                st_spec, st_spec, st_spec]
    if valid is not None:
        operands.append(valid.astype(jnp.int32).reshape(B, T, 1))
        in_specs.append(pl.BlockSpec((1, T, 1), lambda b, c: (b, 0, 0)))
    if exp_table is not None:
        tab_spec = pl.BlockSpec((256,), lambda b, c: (0,))
        operands += [exp_table, div_table]
        in_specs += [tab_spec, tab_spec]
    st_shape = jax.ShapeDtypeStruct((B, 1, C), jnp.float32)
    y, af, bf, of = pl.pallas_call(
        functools.partial(_kernel, T=T, masked=valid is not None,
                          carry=carry_dtype, luts=exp_table is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=[seq_spec, st_spec, st_spec, st_spec],
        out_shape=[jax.ShapeDtypeStruct((B, T, C), jnp.float32),
                   st_shape, st_shape, st_shape],
        interpret=interpret_default(interpret),
    )(*operands)
    af, bf, of = (s.reshape(B, C) for s in (af, bf, of))
    return y, (af, bf, of)
