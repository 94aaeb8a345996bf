"""SLO-aware serving under overload: bursty/zipfian load vs the SLO layer.

A seeded open-loop load generator drives the engine the way production
traffic does — arrivals do not wait for completions:

  * BURSTY arrivals: exponential inter-arrival gaps whose rate
    alternates between a 4x-burst phase and a calm phase (mean held at
    the target rate), so the admission queue actually fills.
  * ZIPFIAN prompts: each prompt = a shared prefix drawn zipf-weighted
    from a small pool (so the prefix cache sees realistic reuse) + a
    unique random suffix.
  * Mixed priority classes (~20% priority 1) so shedding and priority
    admission have work to do.

Three phases:

  1. SUSTAINABLE RATE — closed-loop run at full occupancy; its
     requests/s sets the arrival rates below.
  2. UNLOADED baseline — the same workload at 0.5x sustainable on a
     default-SLO engine (unbounded queue): p50/p99 TTFT + inter-token
     latency with the engine comfortably keeping up.
  3. 2x OVERLOAD — double the sustainable rate, bursty, against the SLO
     engine (bounded queue, shed policy, prefill budget, cache-aware
     priority admission).  The gates (written to BENCH_serving.json
     with the standard provenance stamp):

       - p99 inter-token latency <= 3x the unloaded baseline (graceful
         degradation, not latency collapse),
       - zero engine errors,
       - every non-admitted request observable (submitted == finished +
         shed + backpressured + deadline-evicted: nothing silently
         lost),
       - post-run pool/cache invariants hold (free list full, no queued
         or active lanes, `PrefixCache.check_state`, zero outstanding
         leases, traced-once program cache), and
       - finished requests' token streams bit-identical to a fresh
         unbudgeted default-SLO run of the same prompts (the SLO layer
         changes WHEN work runs, never WHAT it computes).

In this fixed-shape masked engine a prefill call costs the same however
many lanes participate, so the budget's effect here is bounding
per-tick admitted prefill work (and spreading bursts) — the deferral
counter in the output shows it engaging; on hardware where prefill cost
scales with tokens the same knob caps the jitter directly.
"""
from __future__ import annotations

import argparse
import time

import jax
import numpy as np

from benchmarks.common import emit, write_bench_json
from repro.models.registry import get_model
from repro.serving import (AdmissionPolicy, Overloaded,
                           PrefixCacheConfig, ServingEngine, ServingSLO,
                           build_plan)

ARCH = "rwkv4-169m"
CHUNK = 16
N_TOKENS = 12
N_PREFIXES = 4
ZIPF_S = 1.2


def _make_trace(n: int, rate_rps: float, vocab: int, seed: int,
                *, deadline_s: float | None = None):
    """Seeded arrival trace: [(t_arrival, prompt, priority, deadline_s)].
    Gap rate alternates every 8 arrivals between 4x the target (burst)
    and the calm rate that keeps the overall mean at `rate_rps`."""
    rng = np.random.default_rng(seed)
    ranks = np.arange(1, N_PREFIXES + 1, dtype=np.float64)
    pz = ranks ** -ZIPF_S
    pz /= pz.sum()
    prefixes = [rng.integers(0, vocab, size=CHUNK).tolist()
                for _ in range(N_PREFIXES)]
    trace, t = [], 0.0
    for k in range(n):
        burst = (k // 8) % 2 == 0
        mean_gap = (1.0 / (4.0 * rate_rps)) if burst \
            else (1.75 / rate_rps)
        t += float(rng.exponential(mean_gap))
        prefix = prefixes[int(rng.choice(N_PREFIXES, p=pz))]
        suffix = rng.integers(0, vocab,
                              size=int(rng.integers(3, 8))).tolist()
        priority = 1 if rng.random() < 0.2 else 0
        trace.append((t, prefix + suffix, priority, deadline_s))
    return trace


def _make_engine(plan, batch: int, *, slo=None, cache: bool = True):
    pc = PrefixCacheConfig(device_slots=32, host_slots=64) if cache \
        else None
    return ServingEngine(plan.model, plan=plan, max_batch=batch,
                         prefix_cache=pc, slo=slo)


def _drive(engine, trace):
    """Open-loop driver: submit each request at its trace time (wall
    clock), tick the engine in between.  Returns (handles of accepted
    requests, backpressured count, engine error count)."""
    handles, backpressured, errors = [], 0, 0
    i, t0 = 0, time.perf_counter()
    while True:
        now = time.perf_counter() - t0
        while i < len(trace) and trace[i][0] <= now:
            _, prompt, priority, deadline_s = trace[i]
            i += 1
            try:
                handles.append(engine.submit(
                    prompt, max_new_tokens=N_TOKENS,
                    priority=priority, deadline_s=deadline_s))
            except Overloaded:
                backpressured += 1
        sch = engine.scheduler
        if sch.slots or sch.queue:
            try:
                engine.step()
            except Exception:
                errors += 1
                raise
        elif i < len(trace):
            time.sleep(min(2e-3, max(trace[i][0] - now, 0.0)))
        else:
            return handles, backpressured, errors


def _phase_record(name, trace, handles, backpressured, snap):
    outcomes = [h.outcome for h in handles]
    return {
        "phase": name,
        "submitted": len(trace),
        "finished": outcomes.count("finished"),
        "shed": outcomes.count("shed"),
        "deadline_evicted": outcomes.count("deadline"),
        "backpressured": backpressured,
        "ttft_p50_ms": snap["ttft_p50_s"] * 1e3,
        "ttft_p99_ms": snap["ttft_p99_s"] * 1e3,
        "itl_p50_ms": snap["itl_p50_s"] * 1e3,
        "itl_p99_ms": snap["itl_p99_s"] * 1e3,
        "mean_active_slots": snap["mean_active_slots"],
        "mean_queue_depth": snap["mean_queue_depth"],
        "peak_queue_depth": snap["peak_queue_depth"],
        "decode_tok_s": snap["decode_tokens_per_s"],
        "budget_deferred_tokens": snap["budget_deferred_tokens"],
        "cache_hit_rate": snap["cache_hit_rate"],
    }


def _check_invariants(engine, batch: int) -> list[str]:
    """Post-run pool/cache/program invariants; returns violations."""
    bad = []
    if engine.pool.n_free != batch:
        bad.append(f"pool free list {engine.pool.n_free}/{batch}")
    if engine.scheduler.slots or engine.scheduler.queue:
        bad.append("scheduler not drained")
    if engine.prefix_cache is not None:
        try:
            engine.prefix_cache.check_state()
        except AssertionError as e:
            bad.append(f"cache check_state: {e}")
        leases = sum(e.refcount for e in
                     list(engine.prefix_cache._device.values()) +
                     list(engine.prefix_cache._host.values()))
        if leases:
            bad.append(f"{leases} outstanding leases")
    if engine.trace_counts != {"decode": 1, "prefill": 1}:
        bad.append(f"retraced: {engine.trace_counts}")
    return bad


def run(*, smoke: bool = False, json_path: str | None = None,
        devices: int | None = None):
    batch = 8
    n_unloaded = 16 if smoke else 32
    n_overload = 48 if smoke else 128
    mesh = None
    if devices is not None:
        from repro.launch.mesh import make_serving_mesh
        mesh = make_serving_mesh(devices)
    model = get_model(ARCH, smoke=True)
    params = model.init_params(jax.random.PRNGKey(0))
    # ONE plan for every engine below: all phases share the compiled
    # programs, so trace_counts staying 1 covers the whole bench
    plan = build_plan(model, params, prefill_chunk=CHUNK, mesh=mesh)
    vocab = model.cfg.vocab

    # warmup: compile both programs outside every timed phase
    eng = _make_engine(plan, batch, cache=False)
    eng.submit([1] * (CHUNK + 2), max_new_tokens=2)
    eng.run()

    # phase 1: sustainable rate (closed loop at full occupancy)
    eng = _make_engine(plan, batch, cache=False)
    closed = _make_trace(2 * batch, 1e9, vocab, seed=1)
    t0 = time.perf_counter()
    for _, prompt, _, _ in closed:
        eng.submit(prompt, max_new_tokens=N_TOKENS)
    eng.run()
    rate = len(closed) / (time.perf_counter() - t0)
    emit("serving_slo/sustainable", 1e6 / rate, f"req_s={rate:.2f}")

    # phase 2: unloaded baseline at 0.5x sustainable, default SLO
    eng_u = _make_engine(plan, batch)
    trace_u = _make_trace(n_unloaded, 0.5 * rate, vocab, seed=2)
    h_u, bp_u, err_u = _drive(eng_u, trace_u)
    snap_u = eng_u.counters.snapshot()
    rec_u = _phase_record("unloaded_0.5x", trace_u, h_u, bp_u, snap_u)
    emit("serving_slo/unloaded", snap_u["mean_itl_s"] * 1e6,
         f"itl_p99_ms={rec_u['itl_p99_ms']:.2f};"
         f"ttft_p99_ms={rec_u['ttft_p99_ms']:.2f}")

    # phase 3: 2x overload, bursty, SLO engine
    slo = ServingSLO(
        prefill_budget=2 * CHUNK,
        admission=AdmissionPolicy(max_queue=2 * batch, overload="shed",
                                  prefer_cache_hits=True, aging_ticks=16))
    eng_o = _make_engine(plan, batch, slo=slo)
    trace_o = _make_trace(n_overload, 2.0 * rate, vocab, seed=3,
                          deadline_s=None if smoke else 20.0)
    h_o, bp_o, err_o = _drive(eng_o, trace_o)
    snap_o = eng_o.counters.snapshot()
    rec_o = _phase_record("overload_2x_bursty", trace_o, h_o, bp_o,
                          snap_o)
    violations = _check_invariants(eng_o, batch)

    # accounting: every submitted request must be observable somewhere
    accounted = (rec_o["finished"] + rec_o["shed"] +
                 rec_o["deadline_evicted"] + rec_o["backpressured"])
    # bit parity: finished requests replayed on a fresh default-SLO,
    # cache-off engine must reproduce their token streams exactly
    finished = [(h.request.prompt, h.tokens) for h in h_o
                if h.outcome == "finished"]
    eng_p = _make_engine(plan, batch, cache=False)
    replays = [eng_p.submit(p, max_new_tokens=N_TOKENS)
               for p, _ in finished]
    eng_p.run()
    identical = all(rh.tokens == toks for rh, (_, toks)
                    in zip(replays, finished))

    itl_ratio = (rec_o["itl_p99_ms"] / rec_u["itl_p99_ms"]
                 if rec_u["itl_p99_ms"] > 0 else float("inf"))
    gates = {
        "p99_itl_overload_vs_unloaded": {
            "value": itl_ratio, "threshold": 3.0,
            "pass": itl_ratio <= 3.0},
        "zero_engine_errors": {
            "value": err_u + err_o, "threshold": 0,
            "pass": err_u + err_o == 0},
        "all_non_admitted_observable": {
            "value": accounted, "threshold": rec_o["submitted"],
            "pass": accounted == rec_o["submitted"]},
        "post_run_invariants": {
            "value": violations or "ok", "threshold": "ok",
            "pass": not violations},
        "admitted_streams_bit_identical": {
            "value": len(finished), "threshold": len(finished),
            "pass": identical and bool(finished)},
    }
    emit("serving_slo/overload_2x", snap_o["mean_itl_s"] * 1e6,
         f"itl_p99_ratio={itl_ratio:.2f}x;"
         f"shed={rec_o['shed']};backpressured={bp_o};"
         f"deadline={rec_o['deadline_evicted']};"
         f"finished={rec_o['finished']}/{rec_o['submitted']};"
         f"gates={'PASS' if all(g['pass'] for g in gates.values()) else 'FAIL'}")

    if json_path:
        write_bench_json(json_path, {
            "arch": ARCH,
            "batch": batch,
            "n_tokens": N_TOKENS,
            "sustainable_req_s": rate,
            "slo": {"prefill_budget": slo.prefill_budget,
                    "max_queue": slo.admission.max_queue,
                    "overload": slo.admission.overload,
                    "aging_ticks": slo.admission.aging_ticks},
            "records": [rec_u, rec_o],
            "gates": gates,
        })
    if not all(g["pass"] for g in gates.values()):
        raise SystemExit(f"serving SLO gates failed: "
                         f"{ {k: g for k, g in gates.items() if not g['pass']} }")
    return gates


if __name__ == "__main__":
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--smoke", action="store_true",
                    help="CI-sized traces (16 unloaded / 48 overload)")
    ap.add_argument("--json", action="store_true",
                    help="write BENCH_serving.json")
    ap.add_argument("--devices", type=int, default=None,
                    help="drive the engines on a data-parallel serving "
                         "mesh over N local devices (0 = all visible)")
    args = ap.parse_args()
    run(smoke=args.smoke,
        json_path="BENCH_serving.json" if args.json else None,
        devices=args.devices)
