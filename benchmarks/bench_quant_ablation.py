"""Table-1 reproduction (structure): quantization-scheme ablation, plus
the serving weight-plane sweep (W8 / W4-nibble / VQ / proxy-mixed).

No LAMBADA offline; instead (DESIGN.md §2-C5) we train a small RWKV-4 on the
synthetic motif stream until it has real structure to lose, then evaluate
perplexity + logit-KL-vs-FP under the same five schemes the paper compares:
FP (baseline), RTN, PoT, LogQ, Proposed (Δ-PoT W9 + per-channel MSE scales).

Expected ordering (the paper's): PoT worst, RTN/LogQ middle, Proposed
closest to FP.

The plane sweep then packs the SAME trained weights under each serving
plane policy (`core.quant.PlanePolicy`):

  w8     — all tensors Δ-PoT W8 (the historical serving plane)
  w4     — all tensors W4: two sign+3-bit nibble codes per uint8, HALF the
           megakernel slab bytes
  vq     — all tensors VQ: per-tensor 256-entry k-means codebook, uint8
           indices in the slab + bf16 codebook riding the const maps
  mixed  — RWKVQuant-style proxy picks a plane per tensor
           (weight-outlier proxy; `PLANE_PROXY`)

and reports, per plane: quality vs the fp oracle (ppl / logit-KL through
the per-op unpack path), megakernel decode tokens/s at batch 8 (parity-
asserted against the per-op path first — bit-identical, so the speed
number can never come from different math), and HBM bytes/token per
decode path derived from the ACTUAL packed arrays and fused slabs
(`bench_fused_decode.hbm_bytes_per_token`).

Gates (enforced via exit status on full runs, recorded always):
  * W4 megakernel bytes/token >= 1.7x smaller than W8 at batch 8 (the
    PR's slab-traffic claim — bytes are deterministic, so this is
    enforced even though it is measured in the same run as timing);
  * W4 decode tok/s >= W8 at batch 8 (halving the stream must not slow
    decode; timing gate, full runs only).

`--json` merges a "quant_planes" section into `BENCH_decode.json`,
preserving the fused-decode sweep and speculative section already there.

Run: PYTHONPATH=src python -m benchmarks.bench_quant_ablation
     [--smoke] [--json]
"""
from __future__ import annotations

import argparse
import json
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from repro.core.quant.policy import (PLANE_PROXY, PLANE_VQ, PLANE_W4,
                                     fake_quantize_tree_with)
from repro.core.quant.schemes import SCHEMES
from repro.core.quant.serving import (pack_params, plane_fingerprint,
                                      unpack_params)
from repro.configs.base import ModelConfig
from repro.data import SyntheticLM
from repro.models.registry import Model, get_model, loss_fn
from benchmarks.common import emit, provenance, tokens_per_s, \
    write_bench_json

JSON_PATH = "BENCH_decode.json"
PLANE_POLICIES = {
    "w8": None,            # pack_params' historical all-W8 default
    "w4": PLANE_W4,
    "vq": PLANE_VQ,
    "mixed": PLANE_PROXY,
}

_ABL_CFG = ModelConfig(
    name="rwkv4-ablation", family="rwkv",
    n_layers=4, d_model=128, n_heads=1, n_kv_heads=1,
    d_ff=512, vocab=512, norm="layernorm", rwkv_version=4, remat=False,
    dtype="float32",
)


def _train(model: Model, steps: int = 240, batch: int = 16, seq: int = 64):
    ds = SyntheticLM(vocab=model.cfg.vocab, seq_len=seq, global_batch=batch,
                     seed=7)
    params = model.init_params(jax.random.PRNGKey(0))

    @jax.jit
    def step(p, batch):
        (l, _), g = jax.value_and_grad(
            lambda q: loss_fn(model, q, batch), has_aux=True)(p)
        return jax.tree_util.tree_map(lambda a, b: a - 3e-3 * b, p, g), l

    for s in range(steps):
        hb = ds.batch(s)
        batch_j = {k: jnp.asarray(v) for k, v in hb.items()}
        params, l = step(params, batch_j)
    return params, float(l)


def _eval(model: Model, params, n_batches: int = 4):
    """ppl + logits on a held-out stream (params may be an unpacked tree)."""
    ds = SyntheticLM(vocab=model.cfg.vocab, seq_len=64, global_batch=16,
                     seed=1234)   # held-out stream

    @jax.jit
    def fwd(p, batch):
        logits, _ = model.forward(p, batch)
        lp = jax.nn.log_softmax(logits.astype(jnp.float32), -1)
        nll = -jnp.take_along_axis(lp, batch["labels"][..., None],
                                   -1)[..., 0]
        return jnp.mean(nll), logits

    nlls, logits_all = [], []
    for i in range(n_batches):
        hb = ds.batch(10_000 + i)
        b = {k: jnp.asarray(v) for k, v in hb.items()}
        nll, lg = fwd(params, b)
        nlls.append(float(nll))
        logits_all.append(lg)
    return float(np.mean(nlls)), logits_all


def _kl(p_logits, q_logits):
    tot, n = 0.0, 0
    for a, b in zip(p_logits, q_logits):
        p = jax.nn.softmax(a.astype(jnp.float32), -1)
        lq = jax.nn.log_softmax(b.astype(jnp.float32), -1)
        lp = jnp.log(p + 1e-9)
        tot += float(jnp.mean(jnp.sum(p * (lp - lq), -1)))
        n += 1
    return tot / n


def _scheme_rows(model: Model, params, fp_nll, fp_logits,
                 n_batches: int) -> list:
    rows = []
    for name, fn in SCHEMES.items():
        if name == "fp":
            qparams, t_us = params, 0.0
        else:
            t1 = time.time()
            qparams = fake_quantize_tree_with(params, fn, bits=9, axis=-1)
            t_us = (time.time() - t1) * 1e6
        nll, logits = _eval(model, qparams, n_batches)
        kl = _kl(fp_logits, logits) if name != "fp" else 0.0
        ppl = float(np.exp(nll))
        emit(f"quant_ablation/{name}", t_us,
             f"ppl={ppl:.3f};dppl={ppl - np.exp(fp_nll):+.3f};kl={kl:.5f}")
        rows.append((name, ppl, kl))
    return rows


# ---------------------------------------------------------------------------
# Serving weight-plane sweep (W8 / W4 / VQ / proxy-mixed)
# ---------------------------------------------------------------------------


def _plane_sweep(model: Model, params, fp_nll, fp_logits, *, batch: int,
                 n_batches: int, iters: int, rounds: int) -> list[dict]:
    """Pack the trained weights under each plane policy; measure quality
    (per-op unpack forward), megakernel decode tok/s (parity-asserted)
    and actual bytes/token per decode path."""
    from benchmarks.bench_fused_decode import _carried, hbm_bytes_per_token
    cfg = model.cfg
    rng = np.random.default_rng(0)
    toks = jnp.asarray(rng.integers(0, cfg.vocab, (batch, 1)), jnp.int32)
    st0 = model.init_decode_state(batch, 0, jnp.bfloat16)

    records = []
    for name, policy in PLANE_POLICIES.items():
        packed = pack_params(params, policy)

        # quality through the per-op unpack path (the serving oracle)
        nll, logits = _eval(model, unpack_params(packed), n_batches)
        ppl, kl = float(np.exp(nll)), _kl(fp_logits, logits)

        # megakernel decode: parity vs per-op FIRST, then time
        mono_q = jax.jit(lambda p, s, t: model.decode_step(
            unpack_params(p), s, t, jnp.int32(0)))
        fused_mq = jax.jit(lambda p, s, t: model.decode_step_fused_model(
            p, s, t, jnp.int32(0)))
        prep = model.prepare_fused_model_params(packed)
        l_mono, _ = mono_q(packed, st0, toks)
        l_mega, _ = fused_mq(prep, st0, toks)
        assert np.array_equal(np.asarray(l_mono, np.float32),
                              np.asarray(l_mega, np.float32)), \
            f"plane {name}: megakernel != per-op oracle"

        step = _carried(lambda s, f=fused_mq, p=prep: f(p, s, toks))
        tok_s = 0.0
        for _ in range(rounds):
            step.state = st0
            tok_s = max(tok_s, tokens_per_s(step, batch, iters=iters))

        hbm = hbm_bytes_per_token(cfg, batch, packed, prep)
        records.append({
            "plane": name,
            "fingerprint": plane_fingerprint(packed),
            "batch": batch,
            "ppl": round(ppl, 4),
            "dppl_vs_fp": round(ppl - float(np.exp(fp_nll)), 4),
            "kl_vs_fp": round(kl, 6),
            "tok_s": round(tok_s, 3),
            "hbm_bytes_per_token": hbm,
        })
        emit(f"quant_planes/{name}/batch{batch}", batch * 1e6 / tok_s,
             f"ppl={ppl:.3f};kl={kl:.5f};tok_s={tok_s:.1f};"
             f"hbm_bytes_tok_model={hbm['fused_model']:.5g};"
             f"fingerprint={plane_fingerprint(packed)}")
    return records


def _plane_gates(records: list[dict]) -> dict:
    by = {r["plane"]: r for r in records}
    w8, w4 = by["w8"], by["w4"]
    return {
        "w4_hbm_bytes_vs_w8_batch8": {
            "ratio": round(w8["hbm_bytes_per_token"]["fused_model"]
                           / w4["hbm_bytes_per_token"]["fused_model"], 3),
            "target": 1.7},
        "w4_tok_s_vs_w8_batch8": {
            "ratio": round(w4["tok_s"] / max(w8["tok_s"], 1e-9), 3),
            "target": 1.0},
    }


def run(smoke: bool = False, json_out: bool = False) -> bool:
    model = get_model(_ABL_CFG)
    t0 = time.time()
    steps = 60 if smoke else 240
    n_batches = 2 if smoke else 4
    params, train_loss = _train(model, steps=steps)
    fp_nll, fp_logits = _eval(model, params, n_batches)
    _scheme_rows(model, params, fp_nll, fp_logits, n_batches)
    emit("quant_ablation/train", (time.time() - t0) * 1e6,
         f"train_loss={train_loss:.3f}")

    records = _plane_sweep(model, params, fp_nll, fp_logits, batch=8,
                           n_batches=n_batches,
                           iters=2 if smoke else 6,
                           rounds=2 if smoke else 4)
    gates = _plane_gates(records)
    ok = True
    for name, g in gates.items():
        g["pass"] = g["ratio"] >= g["target"]
        ok = ok and g["pass"]
        print(f"gate: {name} = {g['ratio']:.2f}x "
              f"(target >= {g['target']}x) -> "
              f"{'PASS' if g['pass'] else 'FAIL'}")

    if json_out:
        # merge into BENCH_decode.json: the plane rows extend the decode
        # record; the fused-decode sweep and speculative section stay
        payload = {}
        if os.path.exists(JSON_PATH):
            with open(JSON_PATH) as f:
                payload = json.load(f)
        payload["quant_planes"] = {
            "arch": _ABL_CFG.name,
            "backend": jax.default_backend(),
            "smoke": smoke,
            "batch": 8,
            "provenance": provenance(),
            "records": records,
            "gates": gates,
        }
        write_bench_json(JSON_PATH, payload)
    # the bytes gate is deterministic (actual array sizes), so it is
    # enforced even on smoke; the timing gate only fails full runs
    bytes_ok = gates["w4_hbm_bytes_vs_w8_batch8"]["pass"]
    return bytes_ok and (ok or smoke)


def main() -> int:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="short train + tiny sweep for CI; the timing "
                         "gate is reported but not enforced (the "
                         "deterministic bytes gate always is)")
    ap.add_argument("--json", action="store_true",
                    help=f"merge a quant_planes section into {JSON_PATH}")
    args = ap.parse_args()
    return 0 if run(smoke=args.smoke, json_out=args.json) else 1


if __name__ == "__main__":
    raise SystemExit(main())
