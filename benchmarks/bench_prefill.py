"""Fused chunked prefill vs the per-op scan-of-decode_step, across chunks.

Prefill gates time-to-first-token and dominates prompt-heavy serving
traffic.  This benchmark times the ENGINE'S OWN two prefill programs (it
constructs `ServingEngine`s and drives their compiled prefill functions
directly, so what is measured is exactly what serves):

  * PER-OP  — `fused_prefill=False`: a `lax.scan` of the masked pool-wide
    `decode_step` over the chunk.  One D-wide matvec per token per
    projection: every token re-reads the entire weight set, and with
    Δ-PoT weights the whole tree is unpacked to bf16 in HBM first.
  * FUSED   — `fused_prefill=True` (`Model.prefill_chunk` through
    `kernels/fused_prefill.py`): the chunk's token-shift / layernorm /
    projections / FFN as (S·C, D)-shaped matmuls — the weight stream is
    read ONCE per chunk, amortized over C tokens — and the WKV recurrence
    through the Pallas sequence kernels with the recurrent state resident
    on-chip across the chunk's timesteps.  Packed Δ-PoT codes decode
    inside the matmul kernels: uint8 is all that crosses HBM.

Both programs are bit-identical (asserted here before timing, and pinned
exhaustively in tests/test_prefill.py).  The sweep covers prefill chunk
sizes {16, 64, 256} x batch {1, 8} x fp/dpot_w8, reporting absorbed
prompt tokens/s and the analytic weight-stream bytes per prompt token.

Gate (enforced via exit status on full runs, recorded always):
  * fused >= 2.0x per-op at chunk 64, batch 8 (fp) — the paper's §4
    reordering claim, applied to the prompt phase.

`--json` writes BENCH_prefill.json; `--smoke` shrinks the sweep for CI,
where the schema is validated but timing gates are not enforced.

Run: PYTHONPATH=src python -m benchmarks.bench_prefill [--smoke] [--json]
"""
from __future__ import annotations

import argparse

import numpy as np
import jax
import jax.numpy as jnp

from benchmarks.common import emit, time_call, write_bench_json
from repro.models.registry import get_model
from repro.serving import ServingEngine

ARCH = "rwkv4-169m"
CHUNKS = (16, 64, 256)
BATCHES = (1, 8)
N_ITERS = 10
N_ROUNDS = 5     # interleaved best-of-rounds (see bench_fused_decode)
JSON_PATH = "BENCH_prefill.json"
GATE_CHUNK, GATE_BATCH, GATE_X = 64, 8, 2.0


def weight_stream_bytes_per_token(cfg, chunk: int, packed: bool) -> dict:
    """Analytic weight bytes crossing HBM per absorbed prompt token.

    Per-op: every scan step re-reads the full weight set (bf16; with
    packed weights the tree is unpacked first — uint8 read + bf16 write
    once per chunk, then bf16 re-read per token).  Fused: each chunk
    matmul reads its weight tile ONCE per chunk — 1/C of the stream per
    token, at 1 B/weight when packed (codes decode in-kernel)."""
    D, F, Lc, V = cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.vocab
    n_w = Lc * (5 * D * D + 2 * D * F) + 2 * V * D
    if packed:
        per_op = n_w * (1 + 2) / chunk + n_w * 2     # unpack, then re-read
        fused = n_w * 1 / chunk
    else:
        per_op = n_w * 2
        fused = n_w * 2 / chunk
    return {"per_op": per_op, "fused": fused}


def _engines(model, params, chunk: int, batch: int, quantized: bool):
    mk = lambda fused: ServingEngine(
        model, params=params, max_batch=batch, prefill_chunk=chunk,
        quantized=quantized, fused_prefill=fused)
    return mk(False), mk(True)


def _prefill_closure(engine, toks, valid, fresh):
    """State-carrying closure over the engine's compiled prefill program
    (the pool state buffer is donated per call, exactly as in serving)."""
    fn = engine.scheduler.prefill_fn

    def run():
        engine.pool.state, last = fn(engine.pool.state, toks, valid, fresh)
        return last
    return run


def bench_cell(model, params, chunk: int, batch: int, quantized: bool,
               iters: int, rounds: int, records: list) -> dict:
    cfg = model.cfg
    rng = np.random.default_rng(0)
    toks = rng.integers(0, cfg.vocab, (batch, chunk)).astype(np.int32)
    valid = np.ones((batch, chunk), bool)
    fresh = np.zeros((batch,), bool)
    per_op, fused = _engines(model, params, chunk, batch, quantized)

    # --- bit-equivalence before timing (fresh lanes, full chunk) ---------
    st1, l1 = per_op.scheduler.prefill_fn(
        per_op.pool.state, toks, valid, np.ones((batch,), bool))
    st2, l2 = fused.scheduler.prefill_fn(
        fused.pool.state, toks, valid, np.ones((batch,), bool))
    assert np.array_equal(np.asarray(l1, np.float32),
                          np.asarray(l2, np.float32))
    for a, b in zip(jax.tree_util.tree_leaves(st1),
                    jax.tree_util.tree_leaves(st2)):
        assert np.array_equal(np.asarray(a, np.float32),
                              np.asarray(b, np.float32))
    per_op.pool.state, fused.pool.state = st1, st2

    variants = {
        "per_op": _prefill_closure(per_op, toks, valid, fresh),
        "fused": _prefill_closure(fused, toks, valid, fresh),
    }
    tok_s = {name: 0.0 for name in variants}
    for _ in range(rounds):
        for name, step in variants.items():
            us = time_call(step, iters=iters)
            tok_s[name] = max(tok_s[name], batch * chunk * 1e6 / us)
    quant = "dpot_w8" if quantized else "fp"
    wbytes = weight_stream_bytes_per_token(cfg, chunk, quantized)
    for name in variants:
        records.append({
            "variant": name, "quant": quant, "batch": batch,
            "chunk": chunk, "tok_s": round(tok_s[name], 3),
            "us_per_chunk": round(batch * chunk * 1e6 / tok_s[name], 1),
            "weight_bytes_per_token": wbytes[name],
        })
    emit(f"prefill/{cfg.name}/chunk{chunk}/batch{batch}/{quant}",
         batch * chunk * 1e6 / tok_s["fused"],
         f"per_op_tok_s={tok_s['per_op']:.1f};"
         f"fused_tok_s={tok_s['fused']:.1f};"
         f"fused_vs_per_op={tok_s['fused']/tok_s['per_op']:.2f}x;"
         f"weight_bytes_tok_fused={wbytes['fused']:.3g}")
    return tok_s


def run(smoke: bool = False, json_out: bool = False) -> bool:
    model = get_model(ARCH, smoke=True)
    params = model.init_params(jax.random.PRNGKey(0))
    chunks = CHUNKS[:2] if smoke else CHUNKS
    iters = 2 if smoke else N_ITERS
    rounds = 2 if smoke else N_ROUNDS
    records: list[dict] = []
    gate_cell = {}
    for quantized in (False, True):
        for chunk in chunks:
            for batch in BATCHES:
                tok_s = bench_cell(model, params, chunk, batch, quantized,
                                   iters, rounds, records)
                if (not quantized and chunk == GATE_CHUNK
                        and batch == GATE_BATCH):
                    gate_cell = tok_s

    gates = {
        f"fused_vs_per_op_chunk{GATE_CHUNK}_batch{GATE_BATCH}": {
            "speedup": round(gate_cell["fused"] / gate_cell["per_op"], 3)
            if gate_cell else None,
            "target": GATE_X},
    }
    ok = True
    for name, g in gates.items():
        g["pass"] = g["speedup"] is not None and g["speedup"] >= g["target"]
        ok = ok and g["pass"]
        print(f"gate: {name} = {g['speedup']}x "
              f"(target >= {g['target']}x) -> "
              f"{'PASS' if g['pass'] else 'FAIL'}")

    if json_out:
        write_bench_json(JSON_PATH, {
            "bench": "prefill",
            "arch": model.cfg.name,
            "backend": jax.default_backend(),
            "smoke": smoke,
            "chunks": list(chunks),
            "batches": list(BATCHES),
            "iters": iters,
            "records": records,
            "gates": gates,
        })
    # CI smoke pins the script + JSON schema, not shared-runner timing
    return ok or smoke


def main() -> int:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="minimal sweep for CI: fewer chunks/iterations; "
                         "gates reported but not enforced")
    ap.add_argument("--json", action="store_true",
                    help=f"write {JSON_PATH} (machine-readable records)")
    args = ap.parse_args()
    return 0 if run(smoke=args.smoke, json_out=args.json) else 1


if __name__ == "__main__":
    raise SystemExit(main())
