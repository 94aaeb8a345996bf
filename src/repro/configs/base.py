"""Model / shape configuration system.

`get_config(arch_id)` returns the exact published configuration for any of
the ten assigned architectures (plus the paper's own rwkv4-* family);
`smoke_config(arch_id)` returns a reduced same-family config for CPU smoke
tests; `SHAPES` is the assigned input-shape set.
"""
from __future__ import annotations

import dataclasses
import importlib
from typing import Optional

# ---------------------------------------------------------------------------


@dataclasses.dataclass(frozen=True)
class ShapeConfig:
    name: str
    seq_len: int
    global_batch: int
    kind: str  # "train" | "prefill" | "decode"


SHAPES: dict[str, ShapeConfig] = {
    "train_4k": ShapeConfig("train_4k", 4_096, 256, "train"),
    "prefill_32k": ShapeConfig("prefill_32k", 32_768, 32, "prefill"),
    "decode_32k": ShapeConfig("decode_32k", 32_768, 128, "decode"),
    "long_500k": ShapeConfig("long_500k", 524_288, 1, "decode"),
}

# families that run long_500k (sub-quadratic decode state)
_SUBQUADRATIC = {"ssm", "hybrid", "rwkv"}


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    name: str
    family: str            # dense | moe | ssm | hybrid | audio | vlm | rwkv
    n_layers: int
    d_model: int
    n_heads: int
    n_kv_heads: int
    d_ff: int
    vocab: int
    head_dim: Optional[int] = None          # default d_model // n_heads
    act: str = "swiglu"                     # swiglu | gelu | relu_sq
    norm: str = "rmsnorm"                   # rmsnorm | layernorm
    rope_theta: float = 10_000.0
    tie_embeddings: bool = False
    # --- MoE ---
    n_experts: int = 0
    top_k: int = 0
    moe_every: int = 1                      # MoE layer every Nth layer
    capacity_factor: float = 1.25
    # grouped per-sequence dispatch: local cumsum + scatter, bf16 payload,
    # all-to-all resharding instead of buffer all-reduce (§Perf)
    moe_grouped: bool = False
    # --- MLA (MiniCPM3 / DeepSeek-style) ---
    use_mla: bool = False
    q_lora_rank: int = 768
    kv_lora_rank: int = 256
    qk_nope_dim: int = 64
    qk_rope_dim: int = 32
    v_head_dim: int = 64
    # --- RWKV / SSM ---
    rwkv_version: int = 0                   # 4 or 6 (0 = not rwkv)
    rwkv_head_dim: int = 64                 # rwkv6 head size
    rwkv_mix_rank: int = 32                 # rwkv6 ddlerp LoRA rank
    rwkv_decay_rank: int = 64               # rwkv6 decay LoRA rank
    ssm_state: int = 64                     # mamba2 state dim
    ssm_head_dim: int = 64                  # mamba2 head (value) dim
    ssm_expand: int = 2                     # mamba2 inner = expand*d_model
    shared_attn_every: int = 0              # zamba2: shared block period
    # --- encoder-decoder (whisper) ---
    enc_layers: int = 0
    enc_frames: int = 1_500
    # --- VLM ---
    n_patches: int = 0                      # prepended patch embeddings
    # --- training ---
    remat: bool = True
    dtype: str = "bfloat16"
    optimizer: str = "adamw"                # adamw | adafactor
    # route full-sequence attention through the Pallas fused flash kernel
    # (scores never touch HBM). Off by default: the XLA path is the
    # paper-agnostic baseline the §Perf table starts from.
    use_flash_kernel: bool = False
    # dry-run instrumentation: replace attention with a zero-flop stub so
    # the roofline diff (base - stub) isolates attention's traffic/flops —
    # the measurement half of the fused-kernel projection (§Perf).
    attn_stub: bool = False
    # same instrumentation for the WKV recurrence (rwkv4): isolates the
    # recurrence's traffic for the wkv4-kernel projection (§Perf)
    wkv_stub: bool = False
    # --- serving ---
    # shard the KV-cache sequence dim over spare mesh axes (SP). Pays a
    # per-step gather; worth it when the cache dominates HBM and heads
    # cannot shard — measured per-arch in EXPERIMENTS.md §Perf.
    shard_kv_seq: bool = True

    @property
    def resolved_head_dim(self) -> int:
        return self.head_dim or self.d_model // self.n_heads

    @property
    def is_moe(self) -> bool:
        return self.n_experts > 0

    @property
    def attention_free(self) -> bool:
        return self.family == "ssm" and self.rwkv_version in (4, 6)


# ---------------------------------------------------------------------------

_ARCH_MODULES = {
    "whisper-medium": "whisper_medium",
    "moonshot-v1-16b-a3b": "moonshot_v1_16b_a3b",
    "llama4-maverick-400b-a17b": "llama4_maverick_400b_a17b",
    "smollm-135m": "smollm_135m",
    "minicpm3-4b": "minicpm3_4b",
    "minitron-4b": "minitron_4b",
    "phi3-mini-3.8b": "phi3_mini_3_8b",
    "rwkv6-7b": "rwkv6_7b",
    "zamba2-7b": "zamba2_7b",
    "internvl2-2b": "internvl2_2b",
    # the paper's own model family
    "rwkv4-169m": "rwkv4_family",
    "rwkv4-430m": "rwkv4_family",
    "rwkv4-1b5": "rwkv4_family",
    "rwkv4-3b": "rwkv4_family",
    "rwkv4-7b": "rwkv4_family",
}

ASSIGNED_ARCHS = [
    "whisper-medium", "moonshot-v1-16b-a3b", "llama4-maverick-400b-a17b",
    "smollm-135m", "minicpm3-4b", "minitron-4b", "phi3-mini-3.8b",
    "rwkv6-7b", "zamba2-7b", "internvl2-2b",
]

RWKV4_ARCHS = ["rwkv4-169m", "rwkv4-430m", "rwkv4-1b5", "rwkv4-3b",
               "rwkv4-7b"]


def list_configs() -> list[str]:
    return list(_ARCH_MODULES)


def get_config(arch_id: str) -> ModelConfig:
    if arch_id not in _ARCH_MODULES:
        raise KeyError(f"unknown arch {arch_id!r}; known: {list_configs()}")
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[arch_id]}")
    return mod.get(arch_id) if hasattr(mod, "get") else mod.CONFIG


def smoke_config(arch_id: str) -> ModelConfig:
    """Reduced same-family config for CPU smoke tests."""
    mod = importlib.import_module(f"repro.configs.{_ARCH_MODULES[arch_id]}")
    return mod.smoke(arch_id) if hasattr(mod, "smoke") else mod.SMOKE


def supported_shapes(cfg: ModelConfig) -> dict[str, str]:
    """shape name -> "ok" or a skip reason (DESIGN.md §Arch-applicability)."""
    out = {}
    for name, shape in SHAPES.items():
        if name == "long_500k" and cfg.family not in _SUBQUADRATIC:
            out[name] = ("skip: full-attention arch — 500k-token decode "
                         "needs sub-quadratic attention")
        else:
            out[name] = "ok"
    return out
