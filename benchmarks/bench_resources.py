"""Table-2 analogue: footprint accounting per model size.

The paper's Table 2 reports FPGA LUT/FF/DSP/BRAM/URAM — fabric concepts with
no TPU analogue (DESIGN.md §2).  The TPU-meaningful equivalent: HBM bytes of
the weights at fp16 vs the mixed-precision quantized packing (Δ-PoT matrices
+ W9 additive), the achieved compression (the paper's bandwidth story), and
the VMEM working set the fused kernels claim per block.
"""
from __future__ import annotations

import numpy as np
import jax

from repro.configs.base import RWKV4_ARCHS, ASSIGNED_ARCHS, get_config
from repro.models.param import P
from repro.models.registry import get_model
from repro.core.quant.policy import classify_param
from repro.core.quant.delta_pot import FORMAT_W8
from benchmarks.common import emit

VMEM_BYTES = 128 * 1024 * 1024  # v5e ~128 MiB VMEM per chip


def spec_bytes(arch: str):
    """Static byte accounting straight from the parameter spec (no
    materialization — works for the 400B config)."""
    model = get_model(arch)
    spec = model.spec()
    flat, _ = jax.tree_util.tree_flatten_with_path(
        spec, is_leaf=lambda x: isinstance(x, P))
    b_fp16 = b_quant = 0
    for path, p in flat:
        n = int(np.prod(p.shape))
        key = jax.tree_util.keystr(path)
        # classify on path + ndim without materializing the tensor
        kind = classify_param(key, type("L", (), {"ndim": len(p.shape)})())
        b_fp16 += n * 2
        if kind == "matmul":
            b_quant += n * FORMAT_W8.total_bits // 8 + 4 * p.shape[-1]
        else:
            b_quant += (n * 9 + 7) // 8 + 4
    return model, b_fp16, b_quant


def run():
    for arch in RWKV4_ARCHS + ASSIGNED_ARCHS:
        model, b16, bq = spec_bytes(arch)
        cfg = model.cfg
        d = cfg.d_model
        # fused-step VMEM working set: activations + one streamed weight tile
        # (128x512 int8) + wkv state (3 channel vectors or H*N*N)
        if cfg.rwkv_version == 6:
            state = cfg.n_heads * cfg.rwkv_head_dim ** 2 * 4
        else:
            state = 3 * d * 4
        vmem = 8 * d * 4 + 128 * 512 + state
        emit(f"resources/{arch}", 0.0,
             f"params={model.param_count()/1e6:.1f}M;"
             f"fp16_GB={b16/2**30:.3f};quant_GB={bq/2**30:.3f};"
             f"compression={b16/max(bq,1):.2f}x;"
             f"vmem_step_KB={vmem/1024:.0f};"
             f"fits_vmem={vmem < VMEM_BYTES}")


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    run()
