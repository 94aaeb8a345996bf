"""Self-speculative decode throughput: K-token verify windows vs plain ticks.

The serving win under test (src/repro/serving/plan.py SpeculativePath;
losslessness pinned in tests/test_speculative.py): a truncated-stack
drafter proposes K-1 tokens per lane and ONE chunk-shaped verify call
scores the whole window, so an accepted window advances a lane K tokens
for one drafter pass plus one verify pass — decode throughput scales
with the ACCEPTANCE RATE while the emitted bits stay exactly the plain
engine's (asserted before any timing).

Sweep: K in {2, 4, 8} x batch in {1, 4}, two drafter configurations:

  * ALIGNED — layers >= draft_depth have att.wo / ffn.wv zeroed, so the
    deep blocks' residual contributions vanish and the depth-1 drafter's
    argmax IS the full model's: acceptance ~= 1.0 with the full stack
    still paying its real compute.  This is the benchmark's calibrated
    upper bound — the speedup K can buy when the drafter is right.
  * NATURAL — the untouched random-init weights: whatever acceptance the
    depth-1 drafter really earns (low, for random weights), showing how
    the win decays with acceptance.

Reported per cell: decode tokens/s (steady-state decode ticks only —
prefill excluded by construction), acceptance rate, and speedup vs the
plain engine at the same batch.  Gate (enforced via exit status on full
runs, recorded always):

  * best aligned-drafter speculative config >= 1.5x plain decode
    tokens/s at batch 1.

`--json` merges a "speculative" section (records + gates) into
`BENCH_decode.json`, preserving the fused-decode sweep already there;
`--smoke` shrinks the sweep for CI, where the schema is validated but
timing gates are not enforced.

Run: PYTHONPATH=src python -m benchmarks.bench_speculative [--smoke] [--json]
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import time

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import emit, provenance, write_bench_json
from repro.models.registry import get_model
from repro.serving import ServingEngine
from repro.serving.scheduler import DECODE

ARCH = "rwkv4-169m"
KS = (2, 4, 8)
BATCHES = (1, 4)
DRAFT_DEPTH = 1
JSON_PATH = "BENCH_decode.json"
GATE_SPEEDUP = 1.5
PROMPT_LEN = 8


def aligned_params(model, params, depth: int):
    """Zero att.wo / ffn.wv for layers >= depth: those blocks' residual
    contributions become exactly zero, so the first-`depth`-layers
    drafter predicts the full model's argmax (tests/test_speculative.py
    pins acceptance_rate == 1.0 on this configuration)."""
    def zero_tail(leaf):
        z = np.asarray(leaf, np.float32).copy()
        z[depth:] = 0.0
        return jnp.asarray(z, leaf.dtype)

    blocks = dict(params["blocks"])
    blocks["att"] = {**blocks["att"], "wo": zero_tail(blocks["att"]["wo"])}
    blocks["ffn"] = {**blocks["ffn"], "wv": zero_tail(blocks["ffn"]["wv"])}
    return {**params, "blocks": blocks}


def _prompts(vocab: int, batch: int, seed: int = 7):
    r = np.random.default_rng(seed)
    return [r.integers(0, vocab, size=PROMPT_LEN).tolist()
            for _ in range(batch)]


def _engine(model, params, *, batch: int, speculative=None):
    return ServingEngine(model, params=params, max_batch=batch,
                         prefill_chunk=PROMPT_LEN, fused_prefill=True,
                         speculative=speculative, draft_depth=None if
                         speculative is None else DRAFT_DEPTH)


def _decode_rate(model, params, *, batch: int, speculative, ticks: int,
                 warm_ticks: int) -> tuple[float, float]:
    """Steady-state decode tokens/s of one engine configuration, plus the
    run's acceptance rate.  The measured window opens only after every
    lane reached DECODE phase and `warm_ticks` ticks compiled + warmed
    every program, and `max_new_tokens` is sized so no lane can retire
    inside the window — the rate is pure decode-tick throughput, the
    same quantity for speculative and plain engines."""
    k = speculative or 1
    eng = _engine(model, params, batch=batch, speculative=speculative)
    max_new = (warm_ticks + ticks + 4) * k + 2
    for p in _prompts(model.cfg.vocab, batch):
        eng.submit(p, max_new_tokens=max_new)
    while len(eng.scheduler.slots) < batch or any(
            m.phase != DECODE for m in eng.scheduler.slots.values()):
        eng.step()
    for _ in range(warm_ticks):
        eng.step()
    c0 = eng.counters.decode_tokens
    t0 = time.perf_counter()
    for _ in range(ticks):
        eng.step()
    dt = time.perf_counter() - t0
    tok_s = (eng.counters.decode_tokens - c0) / max(dt, 1e-9)
    while eng.step():
        pass
    return tok_s, eng.counters.snapshot()["acceptance_rate"]


def _assert_lossless(model, params, speculative: int):
    """The precondition that makes the numbers mean anything: the
    speculative engine emits the plain engine's exact tokens."""
    def run(spec):
        eng = _engine(model, params, batch=2, speculative=spec)
        hs = [eng.submit(p, max_new_tokens=12)
              for p in _prompts(model.cfg.vocab, 2)]
        eng.run()
        return [h.tokens for h in hs]
    assert run(speculative) == run(None), \
        "speculative decode changed the output tokens"


def run(smoke: bool = False, json_out: bool = False) -> bool:
    base = get_model(ARCH, smoke=True).cfg
    n_layers = 2 if smoke else 6
    cfg = dataclasses.replace(base, n_layers=n_layers,
                              name=f"{base.name}-L{n_layers}")
    model = get_model(cfg)
    params = model.init_params(jax.random.PRNGKey(0))
    weights = {"aligned": aligned_params(model, params, DRAFT_DEPTH),
               "natural": params}
    ks = KS[:2] if smoke else KS
    ticks = 4 if smoke else 24
    warm_ticks = 2 if smoke else 6
    for name, w in weights.items():
        _assert_lossless(model, w, max(ks))

    records: list[dict] = []
    plain = {}
    for batch in BATCHES:
        tok_s, _ = _decode_rate(model, weights["aligned"], batch=batch,
                                speculative=None, ticks=ticks,
                                warm_ticks=warm_ticks)
        plain[batch] = tok_s
        records.append({"variant": "plain", "drafter": None, "k": 1,
                        "batch": batch, "n_layers": n_layers,
                        "draft_depth": None, "acceptance_rate": None,
                        "tok_s": round(tok_s, 3), "speedup_vs_plain": 1.0})
    best_batch1 = 0.0
    for drafter, w in weights.items():
        for batch in BATCHES:
            for k in ks:
                tok_s, acc = _decode_rate(model, w, batch=batch,
                                          speculative=k, ticks=ticks,
                                          warm_ticks=warm_ticks)
                speedup = tok_s / max(plain[batch], 1e-9)
                if drafter == "aligned" and batch == 1:
                    best_batch1 = max(best_batch1, speedup)
                records.append({
                    "variant": "speculative", "drafter": drafter, "k": k,
                    "batch": batch, "n_layers": n_layers,
                    "draft_depth": DRAFT_DEPTH,
                    "acceptance_rate": round(acc, 3),
                    "tok_s": round(tok_s, 3),
                    "speedup_vs_plain": round(speedup, 3)})
                emit(f"speculative/{cfg.name}/{drafter}/K{k}/batch{batch}",
                     batch * 1e6 / max(tok_s, 1e-9),
                     f"tok_s={tok_s:.1f};acceptance={acc:.3f};"
                     f"plain_tok_s={plain[batch]:.1f};"
                     f"speedup={speedup:.2f}x")

    gates = {
        "speculative_vs_plain_batch1": {
            "speedup": round(best_batch1, 3), "target": GATE_SPEEDUP,
            "pass": best_batch1 >= GATE_SPEEDUP},
    }
    ok = True
    for name, g in gates.items():
        ok = ok and g["pass"]
        print(f"gate: {name} = {g['speedup']:.2f}x "
              f"(target >= {g['target']}x) -> "
              f"{'PASS' if g['pass'] else 'FAIL'}")

    if json_out:
        # merge into BENCH_decode.json: the speculative rows extend the
        # decode-throughput record, they do not replace the fused-decode
        # sweep already there
        payload = {}
        if os.path.exists(JSON_PATH):
            with open(JSON_PATH) as f:
                payload = json.load(f)
        payload["speculative"] = {
            "arch": cfg.name,
            "backend": jax.default_backend(),
            "smoke": smoke,
            "ks": list(ks),
            "batches": list(BATCHES),
            "draft_depth": DRAFT_DEPTH,
            "ticks": ticks,
            "provenance": provenance(),
            "records": records,
            "gates": gates,
        }
        write_bench_json(JSON_PATH, payload)
    # CI smoke pins the script + JSON schema, not shared-runner timing
    return ok or smoke


def main() -> int:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="minimal sweep for CI: K in {2,4}, few ticks; "
                         "gates reported but not enforced")
    ap.add_argument("--json", action="store_true",
                    help=f"merge speculative records into {JSON_PATH}")
    args = ap.parse_args()
    return 0 if run(smoke=args.smoke, json_out=args.json) else 1


if __name__ == "__main__":
    raise SystemExit(main())
