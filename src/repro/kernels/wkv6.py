"""Pallas TPU kernel: chunked RWKV-6 WKV (data-dependent decay).

Grid (B, H): each cell owns one head's (N x N) state, resident in a VMEM
scratch across all chunks (the paper's on-chip state principle).  Per chunk
of C tokens the work is dense (C,N)x(N,N) and (C,C,N) contractions — MXU
food — with the exact per-pair decay tensor masked strictly-lower BEFORE the
exp, so every live exponent is <= 0: underflow-only stability (same scheme
as the ref's diagonal blocks, applied chunk-wide).

Oracle: repro.core.wkv.wkv6.wkv6_scan / wkv6_chunked.

`wkv6_seq_pallas` (below) is the SEQUENTIAL sibling used by the fused
chunked-prefill path: same grid, same on-chip (N x N) state residency, but
the recurrence advances with the exact per-step `wkv6_step` math (the
chunked form's log-space reassociation is NOT bit-identical to the step
scan, and prefill must be).  It adds the serving operands the prefill
masking semantics need: a (B, T) `valid` commit mask and a `carry_dtype`
that rounds the carried state through the pool's storage dtype every step,
exactly as the per-op decode oracle does between steps.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.wkv.wkv6 import decay_update
from repro.kernels.common import interpret_default


def _kernel(r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref,
            y_ref, sf_ref, *, T: int, C: int, N: int):
    n_chunks = T // C
    u = u_ref[0].astype(jnp.float32)[0]                   # (1,N) -> (N,)
    mask = jnp.tril(jnp.ones((C, C), jnp.float32), k=-1)  # strict lower

    def chunk_body(g, S):
        sl = (0, 0, pl.ds(g * C, C), slice(None))
        rc = r_ref[sl].astype(jnp.float32)                # (C,N)
        kc = k_ref[sl].astype(jnp.float32)
        vc = v_ref[sl].astype(jnp.float32)
        wc = w_ref[sl].astype(jnp.float32)
        logw = jnp.log(jnp.maximum(wc, 1e-38))
        L = jnp.cumsum(logw, axis=0)                      # inclusive (C,N)
        Lprev = L - logw                                  # exclusive
        # inter-chunk: exponents Lprev <= 0
        y = jnp.dot(rc * jnp.exp(Lprev), S,
                    preferred_element_type=jnp.float32)   # (C,N)
        # intra-chunk: exact pairwise decay, strictly-lower masked pre-exp
        D = Lprev[:, None, :] - L[None, :, :]             # (C,C,N)
        D = jnp.where(mask[:, :, None] > 0, D, -1e30)
        att = jnp.einsum("sn,in,sin->si", rc, kc, jnp.exp(D))
        y = y + jnp.dot(att, vc, preferred_element_type=jnp.float32)
        # bonus (current token)
        y = y + jnp.sum(rc * u[None] * kc, axis=-1, keepdims=True) * vc
        y_ref[sl] = y.astype(y_ref.dtype)
        # state update: exponents Ltot - L <= 0 and Ltot <= 0
        Ltot = L[-1:, :]                                  # (1,N)
        k_fut = kc * jnp.exp(Ltot - L)
        return jnp.exp(Ltot[0])[:, None] * S + jnp.dot(
            k_fut.T, vc, preferred_element_type=jnp.float32)

    S = jax.lax.fori_loop(0, n_chunks, chunk_body,
                          s0_ref[0, 0].astype(jnp.float32))
    sf_ref[0, 0] = S


@functools.partial(jax.jit, static_argnames=("chunk", "interpret"))
def wkv6_pallas(r, k, v, w, u, s0=None, *, chunk: int = 64,
                interpret: bool | None = None):
    """r,k,v,w: (B,T,H,N); u: (H,N) -> (y (B,T,H,N) f32, S (B,H,N,N))."""
    B, T, H, N = r.shape
    C = min(chunk, T)
    while T % C != 0:
        C //= 2
    if s0 is None:
        s0 = jnp.zeros((B, H, N, N), jnp.float32)
    # head-major layout so each grid cell reads a contiguous (T, N) strip
    tr = lambda x: jnp.transpose(x, (0, 2, 1, 3))         # (B,H,T,N)
    seq_spec = pl.BlockSpec((1, 1, T, N), lambda b, h: (b, h, 0, 0))
    # per-head bonus as (H, 1, N): a (1, N) block is then a whole axis pair
    u_spec = pl.BlockSpec((1, 1, N), lambda b, h: (h, 0, 0))
    st_spec = pl.BlockSpec((1, 1, N, N), lambda b, h: (b, h, 0, 0))
    y, sf = pl.pallas_call(
        functools.partial(_kernel, T=T, C=C, N=N),
        name="wkv6_pallas",
        grid=(B, H),
        in_specs=[seq_spec, seq_spec, seq_spec, seq_spec, u_spec, st_spec],
        out_specs=[seq_spec, st_spec],
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, N), jnp.float32),
            jax.ShapeDtypeStruct((B, H, N, N), jnp.float32),
        ],
        interpret=interpret_default(interpret),
    )(tr(r), tr(k), tr(v), tr(w), u.reshape(H, 1, N), s0)
    return jnp.transpose(y, (0, 2, 1, 3)), sf


# ---------------------------------------------------------------------------
# Sequential form: exact per-step wkv6_step math, state on-chip, masked
# ---------------------------------------------------------------------------


def _seq_kernel(live_ref, r_ref, k_ref, v_ref, w_ref, u_ref, s0_ref, *refs,
                T: int, masked: bool, carry: str | None):
    refs = list(refs)
    valid_ref = refs.pop(0) if masked else None
    y_ref, sf_ref = refs
    u = u_ref[0].astype(jnp.float32)[0]                   # (1,N) -> (N,)
    snap = ((lambda x: x) if carry is None else
            (lambda x: x.astype(jnp.dtype(carry)).astype(jnp.float32)))

    def body(t, S):
        sl = (0, 0, pl.ds(t, 1), slice(None))
        rt = r_ref[sl].astype(jnp.float32)[0]                  # (N,)
        kt = k_ref[sl].astype(jnp.float32)[0]
        vt = v_ref[sl].astype(jnp.float32)[0]
        wt = w_ref[sl].astype(jnp.float32)[0]
        # exact wkv6_step: y = r @ (S + diag(u) k⊗v); S' = diag(w) S + k⊗v
        # in `decay_update`'s rounding order
        kv = kt[:, None] * vt[None, :]                         # (N,N)
        # r as a (1, N) row: Mosaic takes a matmul of 2-D operands only
        y = jnp.dot(rt[None, :], S + u[:, None] * kv,
                    preferred_element_type=jnp.float32)[0]
        y_ref[sl] = y[None].astype(y_ref.dtype)
        S_new = decay_update(wt[:, None], S, kv)
        if masked:
            ok = valid_ref[0, pl.ds(t, 1), :][0, 0] != 0
            S_new = jnp.where(ok, S_new, S)
        return snap(S_new)

    live = live_ref[pl.program_id(0)] != 0

    @pl.when(live)
    def _walk():
        sf_ref[0, 0] = jax.lax.fori_loop(
            0, T, body, s0_ref[0, 0].astype(jnp.float32))

    # a lane with no valid step: every step would keep the state, so it
    # leaves as it came (snapped, as each masked step snaps it); nothing
    # reads its y
    @pl.when(jnp.logical_not(live))
    def _skip():
        y_ref[...] = jnp.zeros(y_ref.shape, y_ref.dtype)
        sf_ref[0, 0] = snap(s0_ref[0, 0].astype(jnp.float32))


@functools.partial(jax.jit, static_argnames=("interpret", "carry_dtype"))
def wkv6_seq_pallas(r, k, v, w, u, s0=None, *,
                    valid=None, carry_dtype: str | None = None,
                    interpret: bool | None = None):
    """Sequential WKV-6: r,k,v,w (B,T,H,N); u (H,N) -> (y (B,T,H,N) f32,
    S (B,H,N,N)).  Grid (B, H); each cell's (N, N) state stays in VMEM for
    the whole window, advanced with the exact `wkv6_step` ops so the result
    is BIT-identical to scanning the step (the prefill contract).  `valid`
    (B, T) discards masked steps' state updates; `carry_dtype` rounds the
    carry through the pool dtype every step, both as in the per-op oracle.
    A lane with no valid step is not walked (a prefill call that admits
    one request of 32 walks one lane): its state passes through and its y
    is 0."""
    B, T, H, N = r.shape
    if s0 is None:
        s0 = jnp.zeros((B, H, N, N), jnp.float32)
    # per lane, whether any step is valid: scalar-prefetched, so each
    # index map takes it as a third argument
    live = (jnp.ones((B,), jnp.int32) if valid is None else
            jnp.any(valid, axis=1).astype(jnp.int32))
    # head-major layout so each grid cell reads a contiguous (T, N) strip
    tr = lambda x: jnp.transpose(x, (0, 2, 1, 3))         # (B,H,T,N)
    seq_spec = pl.BlockSpec((1, 1, T, N), lambda b, h, _: (b, h, 0, 0))
    u_spec = pl.BlockSpec((1, 1, N), lambda b, h, _: (h, 0, 0))
    st_spec = pl.BlockSpec((1, 1, N, N), lambda b, h, _: (b, h, 0, 0))
    operands = [tr(r), tr(k), tr(v), tr(w), u.reshape(H, 1, N), s0]
    in_specs = [seq_spec, seq_spec, seq_spec, seq_spec, u_spec, st_spec]
    if valid is not None:
        operands.append(valid.astype(jnp.int32).reshape(B, T, 1))
        in_specs.append(pl.BlockSpec((1, T, 1), lambda b, h, _: (b, 0, 0)))
    y, sf = pl.pallas_call(
        functools.partial(_seq_kernel, T=T, masked=valid is not None,
                          carry=carry_dtype),
        name="wkv6_seq_pallas",
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(B, H), in_specs=in_specs,
            out_specs=[seq_spec, st_spec]),
        out_shape=[
            jax.ShapeDtypeStruct((B, H, T, N), jnp.float32),
            jax.ShapeDtypeStruct((B, H, N, N), jnp.float32),
        ],
        interpret=interpret_default(interpret),
    )(live, *operands)
    return jnp.transpose(y, (0, 2, 1, 3)), sf
