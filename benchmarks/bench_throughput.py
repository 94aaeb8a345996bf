"""Fig-7 analogue: decode throughput across RWKV-4 model sizes.

Two numbers per size:
  * roofline tokens/s on the TARGET (TPU v5e): batch-1 decode is
    bandwidth-bound (arithmetic intensity ~1 FLOP/byte), so
    tokens/s = HBM_BW / bytes_per_token — reported for fp16 weights and for
    the Δ-PoT-packed weights (the paper's speedup mechanism: same ratio the
    paper gets from its on-chip + low-bit design);
  * measured CPU tokens/s for the sizes small enough to run here (169M),
    the "official implementation on commodity hardware" baseline of Fig 7.
"""
from __future__ import annotations

import time

import jax
import jax.numpy as jnp

from repro.configs.base import RWKV4_ARCHS, get_config
from repro.launch.roofline import HBM_BW
from repro.models.registry import get_model
from benchmarks.bench_resources import spec_bytes
from benchmarks.common import emit


def roofline_tokens_per_s(arch: str):
    model, b16, bq = spec_bytes(arch)
    # batch-1 decode reads every weight once per token
    return HBM_BW / b16, HBM_BW / bq


def measured_cpu_decode(arch: str, n_tokens: int = 12) -> float:
    model = get_model(arch)
    params = model.init_params(jax.random.PRNGKey(0))
    state = model.init_decode_state(1, n_tokens + 1)
    step = jax.jit(model.decode_step)
    tok = jnp.zeros((1, 1), jnp.int32)
    logits, state = step(params, state, tok, jnp.int32(0))  # compile
    jax.block_until_ready(logits)
    t0 = time.time()
    for i in range(n_tokens):
        logits, state = step(params, state, tok, jnp.int32(i + 1))
    jax.block_until_ready(logits)
    return n_tokens / (time.time() - t0)


def run():
    for arch in RWKV4_ARCHS:
        fp16_tps, q_tps = roofline_tokens_per_s(arch)
        emit(f"throughput/{arch}/roofline", 0.0,
             f"fp16_tok_s={fp16_tps:,.0f};dpot_tok_s={q_tps:,.0f};"
             f"speedup={q_tps/fp16_tps:.2f}x")
    # CPU measurement for the smallest size (the others exceed this
    # container's budget; the paper's CPU baseline is the same idea)
    tps = measured_cpu_decode("rwkv4-169m")
    emit("throughput/rwkv4-169m/cpu_measured", 1e6 / tps,
         f"tok_s={tps:.2f}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run()
