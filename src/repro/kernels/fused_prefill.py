"""Fused chunked-prefill building blocks: chunk matmuls + masked WKV scan.

Decode (PRs 2-3) collapsed the per-token step into single Pallas launches;
prefill — which gates time-to-first-token — was still a `lax.scan` of the
per-op `decode_step`: one D-wide MATVEC per prompt token, and (when
quantized) the whole Δ-PoT tree unpacked in HBM for every chunk.  This
module supplies the pieces the models' `prefill_chunk` entry points stitch
together, per the paper's computation reordering (§4.2): process a whole
prompt chunk per device program, with

  * the position-parallel work (token-shift mixes, layernorms, the r/k/v/
    receptance projections, the FFN) reshaped into (S·C, D) MATMULS over
    the chunk — MXU food instead of C matvecs (`chunk_matmul` below; the
    same tiling idea as `kernels.dpot_matmul`, here with the decode kept
    bit-exact to `core.quant.serving.unpack_leaf`), and
  * the genuinely sequential WKV recurrence running through the Pallas
    sequence kernels (`kernels.wkv4.wkv4_pallas` / `kernels.wkv6.
    wkv6_seq_pallas`), seeded from the pool state and keeping the
    per-channel state in VMEM across the chunk's timesteps, with a `valid`
    commit mask so partial chunks match the per-op scan bit-for-bit.

Packed weights flow to prefill WITHOUT `unpack_params`: the uint8 code
planes — scalar Δ-PoT W8, nibble-packed W4 (two codes per byte, half the
stream), VQ codebook indices — stream HBM->VMEM tile-by-tile and decode
inside the matching matmul kernel (`_mm_kernel` / `_mm_kernel_w4` /
`_mm_kernel_vq`), so uint8 codes are all that crosses HBM during the
whole prompt phase — the paper's bandwidth win, extended from decode to
prefill.  Bit-parity contract: `chunk_matmul(x, leaf, dt)` on a packed
leaf equals `x @ unpack_leaf(leaf).astype(dt)` exactly, because the kernel
body calls the very same `unpack_leaf` (tests/test_prefill.py).

The masking semantics live one level up (models' `block_prefill`): the
`valid` mask must be a per-slot PREFIX of the chunk (the scheduler only
emits prefix masks — a prompt chunk occupies positions [0, n)), which is
what makes the shifted-sequence token mix equal to the oracle's frozen
state carry.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.quant.serving import is_packed_leaf, leaf_plane, unpack_leaf
from repro.kernels.common import f32_dot, interpret_default


def _mm_kernel(x_ref, wq_ref, scale_ref, o_ref, *, dt):
    # decode THE SAME WAY the per-op oracle does (unpack_leaf -> bf16 ->
    # compute dtype) so the fused prefill is bit-identical, not merely close
    w = unpack_leaf({"packed": wq_ref[...],
                     "scale": scale_ref[...]}).astype(dt)
    o_ref[...] = f32_dot(x_ref[...], w)


def _mm_kernel_w4(x_ref, wq_ref, scale_ref, o_ref, *, dt):
    # W4 nibble plane: the (K/2, bn) uint8 tile re-interleaves to (K, bn)
    # inside VMEM via the SAME unpack_leaf as the per-op oracle — half the
    # HBM code bytes of the W8 kernel above, identical bits out
    w = unpack_leaf({"packed4": wq_ref[...],
                     "scale": scale_ref[...]}).astype(dt)
    o_ref[...] = f32_dot(x_ref[...], w)


def _mm_kernel_vq(x_ref, idx_ref, cb_ref, o_ref, *, dt):
    # VQ plane: uint8 indices stream per tile; the whole (C,) codebook
    # rides a constant index map and stays VMEM-resident (like the shared
    # Δ-PoT scales) — the gather decode never touches HBM-decoded weights
    w = unpack_leaf({"vq_idx": idx_ref[...],
                     "codebook": cb_ref[...]}).astype(dt)
    o_ref[...] = f32_dot(x_ref[...], w)


def _fit(block: int, dim: int) -> int:
    block = min(block, dim)
    while dim % block != 0:
        block //= 2
    return block


def _fit_lanes(block: int, dim: int) -> tuple[int, int]:
    """(block, grid extent) along a lane (last) axis.  A dim that 128 does
    not divide (the 50,277-wide vocab head) keeps a 128-multiple block and
    a ragged last block — halving to a divisor would end at unaligned
    width-1 tiles — whose out-of-range columns are never written back."""
    if dim <= block or dim % 128 == 0:
        block = _fit(block, dim)
        return block, dim // block
    return block, pl.cdiv(dim, block)


@functools.partial(jax.jit, static_argnames=("dt", "bm", "bn", "interpret"))
def dpot_chunk_matmul(x: jnp.ndarray, wq: jnp.ndarray, scale: jnp.ndarray,
                      *, dt, bm: int = 256, bn: int = 512,
                      interpret: bool | None = None) -> jnp.ndarray:
    """x: (M, K) @ packed wq: (K, N) with per-channel scale (..., N).

    Grid (M/bm, N/bn) with the FULL K per cell: the contraction is never
    split, so each output element accumulates in exactly the order the
    unfused `x @ w` does — the bit-parity requirement (`dpot_matmul`'s
    K-blocked f32 accumulator trades that for scale; prefill cannot).
    uint8 code tiles stream HBM->VMEM via the grid pipeline and decode
    on the VPU in-kernel; `dt` is the compute dtype the decoded weights
    are cast to (the oracle's `cast_params`)."""
    M, K = x.shape
    K2, N = wq.shape
    assert K == K2, (x.shape, wq.shape)
    scale = scale.reshape(1, N)
    bm, (bn, gn) = _fit(bm, M), _fit_lanes(bn, N)
    out_dt = jnp.result_type(x.dtype, jnp.dtype(dt))
    interpret = interpret_default(interpret)
    # the x, code, scale and output tiles, each double-buffered by the
    # pipeline, and room for the compiler's own: a full-K tile outgrows
    # the default scoped VMEM (16 MiB) from K = 8192 (d_ff 14,336 needs
    # 28.5 MiB)
    block_bytes = (bm * K * x.dtype.itemsize + K * bn + 4 * bn
                   + bm * bn * jnp.dtype(out_dt).itemsize)
    return pl.pallas_call(
        functools.partial(_mm_kernel, dt=jnp.dtype(dt)),
        grid=(M // bm, gn),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
            pl.BlockSpec((K, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dt),
        compiler_params=None if interpret else pltpu.CompilerParams(
            vmem_limit_bytes=2 * block_bytes + (16 << 20)),
        interpret=interpret,
    )(x, wq, scale)


@functools.partial(jax.jit, static_argnames=("dt", "bm", "bn", "interpret"))
def w4_chunk_matmul(x: jnp.ndarray, wq4: jnp.ndarray, scale: jnp.ndarray,
                    *, dt, bm: int = 256, bn: int = 512,
                    interpret: bool | None = None) -> jnp.ndarray:
    """x: (M, K) @ nibble-packed wq4: (K/2, N) with per-channel scale.

    Same grid/bit-parity contract as `dpot_chunk_matmul` — full K per
    cell, decode via `unpack_leaf` in-kernel — at HALF the streamed code
    bytes: each uint8 tile carries two contraction rows."""
    M, K = x.shape
    Kh, N = wq4.shape
    assert K == 2 * Kh, (x.shape, wq4.shape)
    scale = scale.reshape(1, N)
    bm, (bn, gn) = _fit(bm, M), _fit_lanes(bn, N)
    out_dt = jnp.result_type(x.dtype, jnp.dtype(dt))
    return pl.pallas_call(
        functools.partial(_mm_kernel_w4, dt=jnp.dtype(dt)),
        grid=(M // bm, gn),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
            pl.BlockSpec((Kh, bn), lambda i, j: (0, j)),
            pl.BlockSpec((1, bn), lambda i, j: (0, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dt),
        interpret=interpret_default(interpret),
    )(x, wq4, scale)


@functools.partial(jax.jit, static_argnames=("dt", "bm", "bn", "interpret"))
def vq_chunk_matmul(x: jnp.ndarray, idx: jnp.ndarray, codebook: jnp.ndarray,
                    *, dt, bm: int = 256, bn: int = 512,
                    interpret: bool | None = None) -> jnp.ndarray:
    """x: (M, K) @ codebook[idx: (K, N)] — VQ plane chunk matmul.

    uint8 indices stream tile-by-tile; the flat (C,) bf16 codebook rides
    a CONSTANT index map (resident across the grid, like the shared Δ-PoT
    scales).  Decode is the oracle's `unpack_leaf` gather, in-kernel."""
    M, K = x.shape
    K2, N = idx.shape
    assert K == K2, (x.shape, idx.shape)
    cb = codebook.reshape(-1)
    C = cb.shape[0]
    bm, (bn, gn) = _fit(bm, M), _fit_lanes(bn, N)
    out_dt = jnp.result_type(x.dtype, jnp.dtype(dt))
    return pl.pallas_call(
        functools.partial(_mm_kernel_vq, dt=jnp.dtype(dt)),
        grid=(M // bm, gn),
        in_specs=[
            pl.BlockSpec((bm, K), lambda i, j: (i, 0)),
            pl.BlockSpec((K, bn), lambda i, j: (0, j)),
            pl.BlockSpec((C,), lambda i, j: (0,)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((M, N), out_dt),
        interpret=interpret_default(interpret),
    )(x, idx, cb)


def chunk_matmul(x: jnp.ndarray, leaf, dt, *,
                 interpret: bool | None = None) -> jnp.ndarray:
    """`x @ leaf` over a (..., K) chunk tensor, packed-leaf aware.

    Plain leaves take the jnp matmul (already in compute dtype via
    `cast_compute` — identical to the oracle by construction).  Quantized
    plane leaves — W8 `{"packed", "scale"}`, W4 `{"packed4", "scale"}`,
    VQ `{"vq_idx", "codebook"}` — flatten the chunk to (S·C, K) and run
    the matching in-kernel-decode matmul above: bitwise
    `x @ unpack_leaf(leaf).astype(dt)` with the codes/indices, not the
    decoded bf16, crossing HBM."""
    plane = leaf_plane(leaf)
    if plane is None:
        return x @ leaf
    lead, K = x.shape[:-1], x.shape[-1]
    xf = x.reshape(-1, K)
    dt_name = jnp.dtype(dt).name
    if plane == "w4":
        out = w4_chunk_matmul(xf, leaf["packed4"], leaf["scale"],
                              dt=dt_name, interpret=interpret)
    elif plane == "vq":
        out = vq_chunk_matmul(xf, leaf["vq_idx"], leaf["codebook"],
                              dt=dt_name, interpret=interpret)
    else:
        out = dpot_chunk_matmul(xf, leaf["packed"], leaf["scale"],
                                dt=dt_name, interpret=interpret)
    return out.reshape(*lead, out.shape[-1])


def shifted_prev(seq: jnp.ndarray, first: jnp.ndarray,
                 valid: jnp.ndarray) -> jnp.ndarray:
    """Token-shift previous-value sequence under a per-slot PREFIX mask.

    seq (B, C, D) are the per-position carry candidates (h, already rounded
    to the state dtype); first (B, D) is the incoming pool carry.  Position
    t sees seq_{t-1} while t-1 is inside the valid prefix, the LAST valid
    entry once the prefix ends (the oracle's per-step masking FREEZES the
    carry there — masked-out steps still compute, from the frozen value),
    and `first` at t=0 or on lanes with no valid tokens at all.  The frozen
    tail is what keeps even the DISCARDED positions' compute bitwise equal
    to the oracle's — which matters when numerics couple lanes (rwkv4's hw
    A9 activation fake-quant takes a per-(batch, features) max: a garbage
    lane with the wrong garbage would perturb every other lane's scale)."""
    B, C = valid.shape
    nv = jnp.sum(valid.astype(jnp.int32), axis=1)
    j = jnp.minimum(jnp.arange(C)[None, :], nv[:, None]) - 1     # (B, C)
    got = jnp.take_along_axis(seq, jnp.maximum(j, 0)[..., None], axis=1)
    return jnp.where((j >= 0)[..., None], got, first[:, None])


def gather_last_valid(seq: jnp.ndarray, idx: jnp.ndarray) -> jnp.ndarray:
    """seq (B, C, ...) -> (B, ...) rows at per-slot position `idx` (B,).

    The chunk computes all C positions; the oracle's `where(ok, new, old)`
    per-step carry is recovered by selecting the LAST VALID position's
    value (callers clamp idx and fall back to the old state for all-invalid
    lanes)."""
    ix = idx.reshape((-1,) + (1,) * (seq.ndim - 1))
    return jnp.take_along_axis(seq, ix, axis=1)[:, 0]


def last_valid_select(seq: jnp.ndarray, old: jnp.ndarray,
                      n_valid: jnp.ndarray) -> jnp.ndarray:
    """Final-state helper: the last valid position of `seq`, cast to and
    falling back on `old` (the incoming pool state) for lanes whose chunk
    had no valid tokens — exactly the oracle's masked per-step carry after
    a full chunk under a prefix mask."""
    idx = jnp.maximum(n_valid - 1, 0)
    got = gather_last_valid(seq, idx).astype(old.dtype)
    anyv = (n_valid > 0).reshape((-1,) + (1,) * (old.ndim - 1))
    return jnp.where(anyv, got, old)
