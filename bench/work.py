"""The work a step of the model needs, computed from its configuration.

The model's own counts (matmul weights per block and in the head, W8
scales, small parameters, recurrent state per lane) come from its family
module (`bench/families/`, see `bench.spec.family`); what is built from
them here is the same for every family.  Counts come from the
configuration file and its stated weight plane only, never from the
program's arrays or compiled code, so the same work is counted whatever
implements it.  A decode step reads every stored weight once (W8 codes at
one byte each, the per-channel f32 scales, the other parameters in their
stored dtype), the embedding rows of its active lanes, and the recurrent
state of its active lanes, which it also writes, and it writes one row of
logits per active lane.  A prefill call reads every stored weight once,
the embedding rows of the prompt tokens it absorbs, and the state of
every lane of the pool, which it also writes.  FLOPs are two per matmul
weight per token, the head counted only for positions that produce
logits.
"""
from __future__ import annotations

import json
from pathlib import Path


_BYTES = {"bfloat16": 2, "float16": 2, "float32": 4, "int8": 1, "uint8": 1}


def _itemsize(dtype: str) -> int:
    return _BYTES[dtype]


def matmul_weights(fam, model: dict) -> int:
    """Every matmul weight: the blocks' and the head's."""
    return (model["n_layers"] * fam.layer_matmul_weights(model)
            + fam.head_weights(model))


def weight_bytes(fam, cfg: dict) -> int:
    """Every stored weight once: W8 codes at one byte each, the
    per-channel f32 scales, the other parameters in their stored dtype."""
    model, plane = cfg["model"], cfg["plane"]
    assert plane["matmul"] == "delta_pot_w8", plane
    return (matmul_weights(fam, model) * 1 + fam.scale_count(model) * 4
            + fam.small_param_count(model) * _itemsize(plane["other"]))


def _lane_state_bytes(fam, cfg: dict) -> int:
    """One lane's recurrent state, read and written."""
    return 2 * fam.state_elems_per_lane(cfg["model"]) * _itemsize(
        cfg["engine"]["state_dtype"])


def decode_step_bytes(fam, cfg: dict, active: float) -> float:
    """Bytes a decode step over `active` lanes must move."""
    model = cfg["model"]
    lane = (model["d_model"] * _itemsize(cfg["plane"]["other"])  # embedding row
            + _lane_state_bytes(fam, cfg)
            + model["vocab"] * _itemsize(model["dtype"]))        # logits row
    return weight_bytes(fam, cfg) + active * lane


def prefill_call_bytes(fam, cfg: dict, tokens: float) -> float:
    """Bytes a prefill call absorbing `tokens` prompt tokens must move:
    every stored weight, the state of every lane of the pool, read and
    written, and the tokens' embedding rows."""
    model = cfg["model"]
    return (weight_bytes(fam, cfg)
            + cfg["engine"]["max_batch"] * _lane_state_bytes(fam, cfg)
            + tokens * model["d_model"] * _itemsize(cfg["plane"]["other"]))


def token_flops(fam, model: dict, tokens: float,
                logit_positions: float) -> float:
    """FLOPs of `tokens` positions through the blocks, of which
    `logit_positions` also go through the head: two per matmul weight,
    and the family's `layer_extra_flops` per position and layer where it
    counts work outside the matmuls."""
    flops = 2.0 * (tokens * model["n_layers"] * fam.layer_matmul_weights(model)
                   + logit_positions * fam.head_weights(model))
    extra = getattr(fam, "layer_extra_flops", None)
    if extra is not None:
        flops += tokens * model["n_layers"] * extra(model)
    return flops


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

