"""Kernel microbenchmarks (§4 modules).

On this CPU container the Pallas kernels run in interpret mode, so
wall-times are NOT TPU times; what is meaningful here and is reported:
  * correctness deltas vs the oracle (must be ~0),
  * bytes-moved ratios (the Δ-PoT kernel moves 8-bit codes vs 16-bit
    weights: the exact HBM-traffic ratio the TPU would see),
  * oracle (XLA-compiled) wall time as a portable reference point.
"""
from __future__ import annotations

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.quant.delta_pot import (
    FORMAT_W8, dpot_quantize, dpot_pack_int8)
from repro.kernels import (dpot_matmul, fused_layernorm, wkv4_pallas,
                           wkv6_pallas)
from repro.kernels import ref as R
from benchmarks.common import emit, time_call


def run():
    rng = np.random.default_rng(0)

    # --- dpot_matmul: the serving matvec (batch 8 x 1024 -> 1024)
    M, K, N = 8, 1024, 1024
    x = jnp.asarray(rng.normal(size=(M, K)), jnp.float32)
    w = jnp.asarray(rng.normal(size=(K, N)) * 0.05, jnp.float32)
    q = dpot_quantize(w, FORMAT_W8, axis=1)
    packed, scale = dpot_pack_int8(q), q.scale[0]
    t_ref = time_call(R.dpot_matmul_ref, x, packed, scale)
    got = dpot_matmul(x, packed, scale)
    err = float(jnp.max(jnp.abs(got - R.dpot_matmul_ref(x, packed, scale))))
    bytes_fp16 = K * N * 2
    bytes_dpot = K * N * 1 + N * 4
    emit("kernels/dpot_matmul", t_ref,
         f"err={err:.1e};hbm_ratio={bytes_fp16/bytes_dpot:.2f}x")

    # --- fused layernorm
    xln = jnp.asarray(rng.normal(size=(256, 1024)), jnp.float32)
    g = jnp.ones((1024,), jnp.float32)
    b = jnp.zeros((1024,), jnp.float32)
    t_ref = time_call(R.fused_layernorm_ref, xln, g, b)
    err = float(jnp.max(jnp.abs(
        fused_layernorm(xln, g, b) - R.fused_layernorm_ref(xln, g, b))))
    # single-pass reads x once + writes once vs 2-pass (3 reads 1 write)
    emit("kernels/fused_layernorm", t_ref, f"err={err:.1e};passes=1_vs_2")

    # --- wkv4 scan
    B, T, C = 1, 256, 768
    k = jnp.asarray(rng.normal(size=(B, T, C)), jnp.float32)
    v = jnp.asarray(rng.normal(size=(B, T, C)), jnp.float32)
    wd = jnp.asarray(np.abs(rng.normal(size=(C,))) + 0.05, jnp.float32)
    u = jnp.asarray(rng.normal(size=(C,)), jnp.float32)
    t_ref = time_call(lambda *a: R.wkv4_ref(*a)[0], k, v, wd, u)
    y, _ = wkv4_pallas(k, v, wd, u)
    err = float(jnp.max(jnp.abs(y - R.wkv4_ref(k, v, wd, u)[0])))
    state_hbm_roundtrips_gpu = T * 3 * C * 4 * 2   # read+write per step
    emit("kernels/wkv4", t_ref,
         f"err={err:.1e};onchip_state_bytes_saved={state_hbm_roundtrips_gpu}")

    # --- wkv6 chunked
    B, T, H, Nd = 1, 256, 8, 64
    r6 = jnp.asarray(rng.normal(size=(B, T, H, Nd)), jnp.float32)
    k6 = jnp.asarray(rng.normal(size=(B, T, H, Nd)), jnp.float32)
    v6 = jnp.asarray(rng.normal(size=(B, T, H, Nd)), jnp.float32)
    w6 = jnp.asarray(rng.uniform(0.5, 0.999, (B, T, H, Nd)), jnp.float32)
    u6 = jnp.asarray(rng.normal(size=(H, Nd)), jnp.float32)
    t_ref = time_call(lambda *a: R.wkv6_ref(*a)[0], r6, k6, v6, w6, u6)
    y6, _ = wkv6_pallas(r6, k6, v6, w6, u6, chunk=64)
    err = float(jnp.max(jnp.abs(y6 - R.wkv6_ref(r6, k6, v6, w6, u6)[0])))
    emit("kernels/wkv6", t_ref, f"err={err:.1e};chunk=64")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run()
