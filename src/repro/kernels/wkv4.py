"""Pallas TPU kernel: fused RWKV-4 WKV scan (paper C4 -> TPU).

The paper's headline systems idea: keep the recurrent state fully on-chip
and stream the sequence through.  TPU mapping: grid (B/bB, C/bc); each cell
owns bB batch lanes x a bc-wide channel slice whose (a, b, o) state lives in
VREGs/VMEM for the WHOLE sequence — zero HBM state round-trips between
timesteps (on GPU each step is a kernel launch reading state from HBM; that
gap is the paper's motivation §1-(1)).  k/v stream in as one VMEM-resident
(bB, T, bc) block per cell.

Layout.  The recurrence is serial in time and elementwise in (batch,
channel), so one timestep of a cell is a (bB, bc) tile: batch lanes on the
8 sublanes of an f32 vreg (bB = 8, or B when B < 8; any other batch is
padded to a multiple of 8 in the wrapper and the padding dropped) and
channels on its lanes, bc up to STEP_VREGS vregs wide so that each step
issues several independent vregs to hide the EUP (exp) and divide
latency.  The loop walks time and reads timestep t as `k_ref[:, t, :]`, a
sublane-strided load that gathers row t of each lane's (T, bc) plane into
one tile, and writes y back the same way.  So the operands keep the
caller's (B, T, C) layout and no transpose runs anywhere (transposing
to (T, B, C) in the wrapper makes XLA emit standalone copies that cost
more than the gather; PERF.md).  One lane per cell, walking its own
(T, bc) rows, would fill one sublane of every vreg and leave 7/8 of the
VPU idle.  The state operands are plain (bB, bc) blocks of (B, C); w and
u are (1, bc) rows broadcast over the sublanes.

Numerics: the official stable running-max recurrence (never overflows),
identical to repro.core.wkv.wkv4 — which is this kernel's oracle.  Every
element sees the same f32 ops in the same order whatever the tile shape,
so the tiling leaves the bits unchanged.

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
from jax.experimental.pallas import tpu as pltpu

from repro.core.approx import div_lut, exp_lut
from repro.kernels.common import interpret_default

SUBLANES, LANES = 8, 128       # an f32 vreg
STEP_VREGS = 8                 # widest (bB, bc) tile a timestep spans


def _kernel(k_ref, v_ref, w_ref, u_ref, a0_ref, b0_ref, o0_ref, *refs,
            T: int, masked: bool, carry: str | None, luts: bool):
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
        a, b, o = state                                    # (bB, bc)
        kt = k_ref[:, t, :].astype(jnp.float32)
        vt = v_ref[:, t, :].astype(jnp.float32)
        # output (includes the bonus u for the current token)
        no = jnp.maximum(o, u + kt)
        A = exp_fn(o - no)
        Bf = exp_fn(u + kt - no)
        y = div_fn(A * a + Bf * vt, A * b + Bf)
        y_ref[:, t, :] = y.astype(y_ref.dtype)
        # state update
        no2 = jnp.maximum(o - w, kt)
        A2 = exp_fn(o - w - no2)
        B2 = exp_fn(kt - no2)
        na, nb, no_ = A2 * a + B2 * vt, A2 * b + B2, no2
        if masked:
            ok = valid_ref[:, t, :] != 0                   # (bB, 1)
            na = jnp.where(ok, na, a)
            nb = jnp.where(ok, nb, b)
            no_ = jnp.where(ok, no_, o)
        return (snap(na), snap(nb), snap(no_))

    ld = lambda ref: ref[...].astype(jnp.float32)
    a, b, o = jax.lax.fori_loop(
        0, T, body, (ld(a0_ref), ld(b0_ref), ld(o0_ref)))
    af_ref[...] = a
    bf_ref[...] = b
    of_ref[...] = o


def _blocks(B: int, C: int, bc: int | None = None) -> tuple[int, int, int]:
    """(bB, bc, padded B) for a (B, ., C) call: bB batch lanes on the f32
    sublanes, a channel block of at most `bc` (default STEP_VREGS vregs of
    lanes) that divides C."""
    bB = min(B, SUBLANES)
    bc = min(bc or STEP_VREGS * LANES, C)
    while C % bc != 0:
        bc //= 2
    return bB, bc, -(-B // bB) * bB


@functools.partial(jax.jit,
                   static_argnames=("bc", "interpret", "carry_dtype"))
def wkv4_pallas(k: jnp.ndarray, v: jnp.ndarray, w: jnp.ndarray,
                u: jnp.ndarray, a0=None, b0=None, o0=None, *,
                valid: jnp.ndarray | None = None,
                carry_dtype: str | None = None,
                exp_table: jnp.ndarray | None = None,
                div_table: jnp.ndarray | None = None,
                bc: int | None = None, interpret: bool | None = None):
    """k, v: (B, T, C); w, u: (C,) -> (y (B,T,C) f32, (a,b,o) finals (B,C)).

    Optional serving operands (see module docstring): `valid` (B, T) commit
    mask, `carry_dtype` per-step state rounding, `exp_table`/`div_table`
    hw-numerics LUTs (supply both or neither).  `bc` caps the channel
    block; by default `_blocks` derives it from C."""
    B, T, C = k.shape
    bB, bc, Bp = _blocks(B, C, bc)
    if a0 is None:
        a0 = jnp.zeros((B, C), jnp.float32)
        b0 = jnp.zeros((B, C), jnp.float32)
        o0 = jnp.full((B, C), -1e38, jnp.float32)
    if (exp_table is None) != (div_table is None):
        raise ValueError("exp_table and div_table travel together")
    interpret = interpret_default(interpret)
    # padded lanes compute on zeros and are dropped: every op is per lane
    pad = lambda x: jnp.pad(x, [(0, Bp - B)] + [(0, 0)] * (x.ndim - 1))
    grid = (Bp // bB, C // bc)
    seq_spec = pl.BlockSpec((bB, T, bc), lambda i, j: (i, 0, j))
    vec_spec = pl.BlockSpec((1, bc), lambda i, j: (0, j))
    st_spec = pl.BlockSpec((bB, bc), lambda i, j: (i, j))
    operands = [pad(k), pad(v), w.reshape(1, C), u.reshape(1, C),
                pad(a0), pad(b0), pad(o0)]
    in_specs = [seq_spec, seq_spec, vec_spec, vec_spec,
                st_spec, st_spec, st_spec]
    if valid is not None:
        operands.append(pad(valid.astype(jnp.int32)[..., None]))
        in_specs.append(pl.BlockSpec((bB, T, 1), lambda i, j: (i, 0, 0)))
    if exp_table is not None:
        tab_spec = pl.BlockSpec((256,), lambda i, j: (0,))
        operands += [exp_table, div_table]
        in_specs += [tab_spec, tab_spec]
    # k, v, y and the mask (lane-padded to a tile row per step), each
    # double-buffered by the pipeline, plus room for the compiler's own
    block_bytes = 4 * T * bB * (3 * bc + LANES * (valid is not None))
    st_shape = jax.ShapeDtypeStruct((Bp, C), jnp.float32)
    y, af, bf, of = pl.pallas_call(
        functools.partial(_kernel, T=T, masked=valid is not None,
                          carry=carry_dtype, luts=exp_table is not None),
        grid=grid,
        in_specs=in_specs,
        out_specs=[seq_spec, st_spec, st_spec, st_spec],
        out_shape=[jax.ShapeDtypeStruct((Bp, T, C), jnp.float32),
                   st_shape, st_shape, st_shape],
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=2 * block_bytes + (8 << 20)),
        interpret=interpret,
    )(*operands)
    return y[:B], tuple(s[:B] for s in (af, bf, of))
