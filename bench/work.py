"""The work a step of the model needs, computed from its configuration.

Counts come from the configuration file and its stated weight plane only,
never from the program's arrays or compiled code, so the same work is
counted whatever implements it.  A decode step reads every stored weight
once (W8 codes at one byte each, the per-channel f32 scales, the other
parameters in their stored dtype), the embedding rows of its active lanes,
and the recurrent state of its active lanes, which it also writes, and it
writes one row of logits per active lane.  FLOPs are two per matmul weight
per token, the head counted only for positions that produce logits.
"""
from __future__ import annotations

import json
from pathlib import Path


_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1, "uint8": 1}


def _itemsize(dtype: str) -> int:
    return _BYTES[dtype]


def layer_matmul_weights(model: dict) -> int:
    """Matmul weights of one RWKV-4 block: r, k, v, o of the time mix
    (D x D each) and the channel mix's r (D x D), k (D x F) and v (F x D)."""
    d, f = model["d_model"], model["d_ff"]
    return 5 * d * d + 2 * d * f


def head_weights(model: dict) -> int:
    return model["d_model"] * model["vocab"]


def matmul_weights(model: dict) -> int:
    return model["n_layers"] * layer_matmul_weights(model) + head_weights(model)


def scale_count(model: dict) -> int:
    """Per-output-channel scales of the W8 plane: one per column of each
    matmul leaf, shared over the stacked layers (5 D + F), plus the head's V."""
    d, f = model["d_model"], model["d_ff"]
    return 5 * d + f + model["vocab"]


def small_param_count(model: dict) -> int:
    """Parameters outside the matmuls that a step reads whole: per block two
    LayerNorms (2 x 2D), the time mix's three mixes, decay and bonus (5D)
    and the channel mix's two mixes (2D); ln0 and ln_f (2 x 2D)."""
    d = model["d_model"]
    return model["n_layers"] * 11 * d + 4 * d


def state_elems_per_lane(model: dict) -> int:
    """att_x, ffn_x and the WKV a, b, o per layer."""
    return model["n_layers"] * 5 * model["d_model"]


def decode_step_bytes(cfg: dict, active: float) -> float:
    """Bytes a decode step over `active` lanes must move."""
    model, plane = cfg["model"], cfg["plane"]
    assert plane["matmul"] == "delta_pot_w8", plane
    other = _itemsize(plane["other"])
    weights = (matmul_weights(model) * 1 + scale_count(model) * 4
               + small_param_count(model) * other)
    lane = (model["d_model"] * other                       # embedding row
            + 2 * state_elems_per_lane(model) * _itemsize(cfg["engine"]["state_dtype"])
            + model["vocab"] * _itemsize(model["dtype"]))  # logits row
    return weights + active * lane


def token_flops(model: dict, tokens: float, logit_positions: float) -> float:
    """Matmul FLOPs of `tokens` positions through the blocks, of which
    `logit_positions` also go through the head."""
    return 2.0 * (tokens * model["n_layers"] * layer_matmul_weights(model)
                  + logit_positions * head_weights(model))


def peaks(device_kind: str, path: Path | None = None) -> dict:
    """The chip's peaks from `peaks.json`; a device missing there is an
    error, never a default."""
    path = path or Path(__file__).with_name("peaks.json")
    table = json.loads(path.read_text())["devices"]
    if device_kind not in table:
        raise KeyError(f"no peaks for device kind {device_kind!r} in {path}")
    return table[device_kind]


def roofline_seconds(flops: float, nbytes: float, peak: dict) -> float:
    """The least time the chip could take: the larger of the two bounds."""
    return max(flops / peak["bf16_flops_per_s"],
               nbytes / peak["hbm_bytes_per_s"])

