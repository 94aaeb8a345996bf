"""Prefix cache TTFT: zipfian prefix-reuse serving, cache on vs off.

The serving win under test: an RWKV prompt prefix collapses to ONE O(1)
recurrent state, so a repeated prefix (system prompt, few-shot header,
multi-turn history) costs a state copy instead of a prefill pass —
near-zero time-to-first-token for the cached portion
(src/repro/serving/prefix_cache.py; bit-parity pinned in
tests/test_prefix_cache.py).

Workload: K shared system prompts of `PREFIX_CHUNKS` prefill chunks,
drawn zipfian (rank-weighted — a few prefixes dominate, the long tail
still misses), each request appending a short unique suffix.  Requests
run through two identical engines — prefix cache OFF then ON — and every
request's generated tokens are asserted EQUAL between the two runs
before any number is reported: the speedup must come from skipping
redundant prefill, not from changing what is served.

Reported per prefix rank: observed TTFT both ways.  Gates (enforced via
exit status on full runs, recorded always):

  * mean TTFT improves >= 5x with the cache on, and
  * the workload's prefix hit rate is >= 60% (the zipf draw actually
    exercised the cache; below that the TTFT comparison is vacuous).

`--json` writes BENCH_prefix.json; `--smoke` shrinks the workload for
CI, where the schema is validated but timing gates are not enforced.

Run: PYTHONPATH=src python -m benchmarks.bench_prefix_cache [--smoke] [--json]
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from benchmarks.common import emit, write_bench_json
from repro.models.registry import get_model
from repro.runtime.monitor import ServingCounters
from repro.serving import PrefixCacheConfig, ServingEngine

ARCH = "rwkv4-169m"
CHUNK = 16
JSON_PATH = "BENCH_prefix.json"
GATE_TTFT_X = 5.0
GATE_HIT_RATE = 0.6
ZIPF_S = 1.1                 # rank weight ~ 1/rank^s


def _workload(vocab: int, *, n_prefixes: int, prefix_chunks: int,
              n_requests: int, suffix_len: int = 4, seed: int = 0):
    """Zipfian prefix-reuse request stream: each request is (shared
    system prompt drawn by rank weight) + (unique suffix)."""
    rng = np.random.default_rng(seed)
    prefixes = [rng.integers(0, vocab,
                             size=prefix_chunks * CHUNK).tolist()
                for _ in range(n_prefixes)]
    w = 1.0 / np.arange(1, n_prefixes + 1) ** ZIPF_S
    ranks = rng.choice(n_prefixes, size=n_requests, p=w / w.sum())
    # every prefix appears at least once so the tail misses are real
    ranks[:n_prefixes] = np.arange(n_prefixes)
    return [(int(r),
             prefixes[r] + rng.integers(0, vocab, size=suffix_len).tolist())
            for r in ranks]


def _serve(model, params, workload, *, cache_slots: int,
           n_new: int = 4) -> tuple[dict, list, list, ServingEngine]:
    """Drive the request stream to completion one request at a time (the
    TTFT comparison wants each request's prefill wall time unshadowed by
    neighbors), returning per-request TTFT and tokens.  Both device
    programs AND the cache's read/write/probe paths are compiled by a
    throwaway warmup request, then the counters reset — compile time is
    not time-to-first-token."""
    cache = PrefixCacheConfig(device_slots=cache_slots, host_slots=0) \
        if cache_slots else None
    engine = ServingEngine(model, params=params, max_batch=2,
                           prefill_chunk=CHUNK, fused_prefill=True,
                           prefix_cache=cache)
    warm = [7] * (2 * CHUNK + 1)         # 2 boundaries + proper suffix
    engine.submit(warm, max_new_tokens=2)
    engine.run()
    engine.submit(warm + [9], max_new_tokens=2)   # exercises the hit path
    engine.run()
    if engine.prefix_cache is not None:
        assert engine.prefix_cache.stats["hits"] == 1, "warmup never hit"
    counters = ServingCounters()
    engine.counters = engine.scheduler.counters = counters
    if engine.prefix_cache is not None:
        engine.prefix_cache.counters = counters
    tokens, ttft = [], []
    t0 = time.perf_counter()
    for _, prompt in workload:
        h = engine.submit(prompt, max_new_tokens=n_new)
        engine.run()
        tokens.append(h.tokens)
        ttft.append(counters.ttft_s[-1])
    wall = time.perf_counter() - t0
    snap = counters.snapshot()
    snap["wall_s"] = wall
    return snap, ttft, tokens, engine


def run(smoke: bool = False, json_out: bool = False) -> bool:
    model = get_model(ARCH, smoke=True)
    params = model.init_params(jax.random.PRNGKey(0))
    n_prefixes = 2 if smoke else 4
    prefix_chunks = 4 if smoke else 32
    n_requests = 6 if smoke else 48
    workload = _workload(model.cfg.vocab, n_prefixes=n_prefixes,
                         prefix_chunks=prefix_chunks,
                         n_requests=n_requests)
    cache_slots = n_prefixes * prefix_chunks + 8

    snap_off, ttft_off, toks_off, _ = _serve(model, params, workload,
                                             cache_slots=0)
    snap_on, ttft_on, toks_on, eng = _serve(model, params, workload,
                                            cache_slots=cache_slots)
    # the non-negotiable precondition: identical tokens, request by
    # request — only then do the TTFT numbers mean anything
    assert toks_on == toks_off, "cached serving changed the output tokens"

    cache_snap = eng.prefix_cache.snapshot()
    mean_off = float(np.mean(ttft_off))
    mean_on = float(np.mean(ttft_on))
    improvement = mean_off / max(mean_on, 1e-9)
    # hit rate over the measured workload only (counters were reset after
    # warmup; cache_snap additionally counts the warmup probes)
    hit_rate = snap_on["cache_hit_rate"]
    records = []
    for rank in range(n_prefixes):
        idx = [i for i, (r, _) in enumerate(workload) if r == rank]
        records.append({
            "prefix_rank": rank,
            "requests": len(idx),
            "prompt_tokens": len(workload[idx[0]][1]),
            "mean_ttft_off_ms": round(1e3 * float(
                np.mean([ttft_off[i] for i in idx])), 3),
            "mean_ttft_on_ms": round(1e3 * float(
                np.mean([ttft_on[i] for i in idx])), 3),
        })
        emit(f"prefix_cache/{model.cfg.name}/rank{rank}",
             1e6 * float(np.mean([ttft_on[i] for i in idx])),
             f"requests={len(idx)};"
             f"ttft_off_ms={records[-1]['mean_ttft_off_ms']};"
             f"ttft_on_ms={records[-1]['mean_ttft_on_ms']}")

    gates = {
        "ttft_improvement": {
            "value": round(improvement, 3), "target": GATE_TTFT_X,
            "pass": improvement >= GATE_TTFT_X},
        "hit_rate": {
            "value": round(hit_rate, 3), "target": GATE_HIT_RATE,
            "pass": hit_rate >= GATE_HIT_RATE},
    }
    ok = True
    for name, g in gates.items():
        ok = ok and g["pass"]
        print(f"gate: {name} = {g['value']} (target >= {g['target']}) -> "
              f"{'PASS' if g['pass'] else 'FAIL'}")

    if json_out:
        write_bench_json(JSON_PATH, {
            "bench": "prefix_cache",
            "arch": model.cfg.name,
            "backend": jax.default_backend(),
            "smoke": smoke,
            "chunk": CHUNK,
            "n_prefixes": n_prefixes,
            "prefix_chunks": prefix_chunks,
            "n_requests": n_requests,
            "zipf_s": ZIPF_S,
            "tokens_identical": toks_on == toks_off,
            "mean_ttft_off_ms": round(1e3 * mean_off, 3),
            "mean_ttft_on_ms": round(1e3 * mean_on, 3),
            "cached_tokens": snap_on["cached_tokens"],
            "prefill_tokens_on": snap_on["prefill_tokens"],
            "prefill_tokens_off": snap_off["prefill_tokens"],
            "cache": cache_snap,
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
                    help="minimal workload for CI: gates reported but "
                         "not enforced")
    ap.add_argument("--json", action="store_true",
                    help=f"write {JSON_PATH} (machine-readable records)")
    args = ap.parse_args()
    return 0 if run(smoke=args.smoke, json_out=args.json) else 1


if __name__ == "__main__":
    raise SystemExit(main())
