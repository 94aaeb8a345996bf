"""rwkv6-7b [ssm] — Finch, data-dependent decay, attention-free.
[arXiv:2404.05892; hf]
32L d_model=4096 (attn-free) d_ff=14336 vocab=65536
head size 64 -> 64 wkv heads; LoRA ranks at d_model 4096: 64 for the five
ddlerp token-shift mixes, 128 for the data-dependent decay (BlinkDL's code
uses 32 and 64 below 4096, the SMOKE defaults).
"""
from repro.configs.base import ModelConfig

CONFIG = ModelConfig(
    name="rwkv6-7b", family="ssm",
    n_layers=32, d_model=4096, n_heads=64, n_kv_heads=64,
    d_ff=14336, vocab=65536, norm="layernorm",
    rwkv_version=6, rwkv_head_dim=64,
    rwkv_mix_rank=64, rwkv_decay_rank=128,
)

SMOKE = ModelConfig(
    name="rwkv6-smoke", family="ssm",
    n_layers=2, d_model=64, n_heads=4, n_kv_heads=4,
    d_ff=128, vocab=256, norm="layernorm",
    rwkv_version=6, rwkv_head_dim=16,
)
