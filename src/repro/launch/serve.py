"""Serving launcher — thin CLI over the continuous-batching engine.

Default mode drives `repro.serving.ServingEngine`: N concurrent requests
share one slotted state pool, chunked prefill interleaves with fused
batched decode, and the run ends with a telemetry snapshot (tokens/s,
TTFT, latency) from `runtime.monitor.ServingCounters`.

    PYTHONPATH=src python -m repro.launch.serve --arch rwkv4-169m --smoke \
        --tokens 64 --batch 4 [--quantized] [--prefill-chunk 16] \
        [--fused[=block|model]] [--fused-prefill] [--devices N | --mesh] \
        [--prefix-cache [--prefix-cache-slots N]] \
        [--speculative K [--draft-depth D]] \
        [--max-queue N [--overload backpressure|shed]] \
        [--prefill-budget T] [--deadline S] \
        [--snapshot-dir DIR [--snapshot-every N] [--resume] \
         [--supervise [--max-restarts K]]] [--sentinel-every N]

Every flag combination resolves to ONE `repro.serving.plan.ExecutionPlan`
(path selection + one-pass param prep + program cache + mesh placement);
the engine just drives it.  `--fused block` decodes through the per-block
fused Pallas kernel (one launch per layer); `--fused model` through the
whole-model megakernel (ONE launch per decode step, grid over layers —
see docs/kernels.md).  `--fused-prefill` absorbs prompt chunks through
the fused chunked-prefill path (chunk-shaped matmuls + the on-chip WKV
sequence kernel, packed Δ-PoT weights decoded in-kernel) instead of the
per-op scan — same bits, measured faster in benchmarks/bench_prefill.py.

`--devices N` serves data-parallel over N local devices (`--mesh` over
all of them): the slot pool and per-tick batch shard across a 1-D
("data",) mesh, weights replicate, tokens stay bit-identical to the
1-device engine (docs/serving.md §multi-device serving).  On a CPU host
spawn virtual devices first:

    XLA_FLAGS=--xla_force_host_platform_device_count=8 \
        PYTHONPATH=src python -m repro.launch.serve --arch rwkv4-169m \
        --smoke --batch 8 --devices 8

`--legacy` keeps the seed behavior — one jitted decode_step in a
single-batch host loop — and is also the reference baseline for
benchmarks/bench_serving.py.  `--hw-numerics` (rwkv4 only: LUT exp, PWL
sigmoid, LUT division) implies the legacy loop, since the hw-numerics
wrapper bypasses the registry Model contract the engine builds on.

See docs/serving.md for the engine API.
"""
from __future__ import annotations

import argparse
import time

import jax
import jax.numpy as jnp
import numpy as np

from repro.core.quant.policy import QuantPolicy, fake_quantize_tree
from repro.models.registry import get_model


def greedy_decode(model, params, state, first_token, n_tokens: int,
                  start_pos: int = 0, *, sample_temp: float = 0.0,
                  rng=None):
    """Autoregressive loop around decode_step (host loop — the seed's
    single-request serving mode, kept as the engine's reference baseline)."""
    B = first_token.shape[0]
    tok = first_token
    out = [tok]
    pos = start_pos
    step_fn = jax.jit(model.decode_step)   # traced once, reused every token
    for i in range(n_tokens):
        logits, state = step_fn(params, state, tok, jnp.int32(pos))
        last = logits[:, -1]
        if sample_temp > 0 and rng is not None:
            rng, k = jax.random.split(rng)
            tok = jax.random.categorical(k, last / sample_temp)[:, None]
        else:
            tok = jnp.argmax(last, axis=-1)[:, None].astype(jnp.int32)
        pos += 1
        out.append(tok)
    return jnp.concatenate(out, axis=1), state


def sequential_decode(model, params, prompt: list[int], n_new: int, *,
                      width: int = 1):
    """Greedy decode of one request alone: feed the prompt token-by-token
    through a jitted decode_step, then argmax-chain `n_new` tokens.  This is
    the engine's bit-identity oracle (docs/serving.md) — the example and the
    scheduler tests both compare against it.

    The request runs in lane 0 of a `width`-lane batch (the other lanes
    idle on token 0); pass the engine's `max_batch`.  Lanes never mix, but
    the compiled program's batch width is part of its numerics: XLA:CPU
    lowers a one-row bf16 matmul as a vector-matrix loop and a wider one as
    a GEMM, whose f32 accumulation orders differ, so a near-tie can round
    differently.  The oracle therefore matches the engine's width.

    BOTH phases compile with defined rounding semantics
    (`kernels.common.exact_jit`), in lockstep with the engine: the engine
    pins `xla_allow_excess_precision=False` on every token-producing
    program (prefill, decode, and the speculative verifier), and the
    oracle must round the same way or near-tie argmaxes drift."""
    from repro.kernels.common import exact_jit
    step = exact_jit(model.decode_step)
    state = model.init_decode_state(width, 0)
    feed = lambda t: jnp.zeros((width, 1), jnp.int32).at[0, 0].set(t)
    logits = None
    for t in prompt:
        logits, state = step(params, state, feed(t), jnp.int32(0))
    out = []
    for _ in range(n_new):
        tok = int(jnp.argmax(logits[0, -1]))
        out.append(tok)
        logits, state = step(params, state, feed(tok), jnp.int32(0))
    return out


def serve_legacy(arch: str, *, smoke: bool = True, batch: int = 4,
                 n_tokens: int = 32, quantized: bool = False, seed: int = 0,
                 hw_numerics: bool = False):
    """Seed serving mode: one fused batch, single host loop."""
    model = get_model(arch, smoke=smoke)
    cfg = model.cfg
    rng = jax.random.PRNGKey(seed)
    params = model.init_params(rng)
    if quantized:
        t0 = time.time()
        params = fake_quantize_tree(params, QuantPolicy())
        print(f"quantized (Δ-PoT W9/A9 policy) in {time.time()-t0:.1f}s")
    state = model.init_decode_state(batch, n_tokens + 8)
    first = jax.random.randint(rng, (batch, 1), 0, cfg.vocab)

    # rwkv4 supports the full paper numerics (LUT exp / PWL sigmoid / LUT div)
    if hw_numerics and cfg.rwkv_version == 4:
        from repro.models import rwkv4 as R4

        class HwModel:
            cfg = model.cfg

            def decode_step(self, p, s, t, pos):
                return R4.decode_step(model.cast_params(p), s, t, pos,
                                      cfg, hw=True)
        m = HwModel()
    else:
        m = model

    t0 = time.time()
    toks, state = greedy_decode(m, params, state, first, n_tokens)
    dt = time.time() - t0
    tps = batch * n_tokens / max(dt, 1e-9)
    print(f"{arch}: decoded {n_tokens} tokens x {batch} seqs in "
          f"{dt:.2f}s ({tps:,.0f} tok/s)")
    return toks


def serve(arch: str, *, smoke: bool = True, batch: int = 4,
          n_tokens: int = 32, quantized: bool = False, seed: int = 0,
          prefill_chunk: int = 16, prompt_len: int = 8,
          temperature: float = 0.0, fused: bool | str | None = False,
          fused_prefill: bool = False, devices: int | None = None,
          prefix_cache: bool = False, cache_slots: int = 64,
          cache_host_slots: int = 256, speculative: int | None = None,
          draft_depth: int | None = None, max_queue: int = 0,
          overload: str = "backpressure", prefill_budget: int = 0,
          deadline_s: float | None = None, snapshot_dir: str | None = None,
          snapshot_every: int = 8, sentinel_every: int = 0,
          resume: bool = False):
    """Continuous-batching serving: `batch` concurrent requests through the
    slotted engine; prints the telemetry snapshot and returns the handles.
    `devices` (0 = all visible) serves data-parallel over a ("data",)
    serving mesh — pool and batch sharded, weights replicated.
    `prefix_cache` enables the recurrent-state prefix cache; the demo
    workload then gives every request a shared system-prompt prefix so the
    hit path is actually exercised (docs/serving.md §prefix cache).
    `max_queue`/`overload`/`prefill_budget`/`deadline_s` configure the
    SLO layer (docs/serving.md §"SLOs and overload"); the defaults keep
    the historical unbounded/unlimited behavior.

    Crash safety (docs/operations.md): `snapshot_dir` makes the engine
    write a tick-boundary snapshot every `snapshot_every` ticks;
    `resume=True` restores the newest committed snapshot from that
    directory (falling back to a fresh start when none exists — e.g. a
    crash before the first snapshot boundary) and drives the restored
    work to completion, streams continuing bit-identically.
    `sentinel_every` turns on the NaN/Inf lane sentinels."""
    from repro.launch.mesh import make_serving_mesh
    from repro.serving import (AdmissionPolicy, Overloaded,
                               PrefixCacheConfig, ServingEngine,
                               ServingSLO, SnapshotConfig)

    if resume and snapshot_dir:
        try:
            engine = ServingEngine.restore(snapshot_dir)
        except FileNotFoundError:
            print(f"no committed snapshot under {snapshot_dir!r} — "
                  "starting fresh")
        else:
            handles = list(engine._handles.values())
            print(f"resumed {len(handles)} request(s) from "
                  f"{snapshot_dir!r} at tick "
                  f"{engine.scheduler._tick_no}")
            snap = engine.run()
            if engine.snapshot_manager is not None:
                engine.snapshot_manager.wait()
            done = sum(len(h.resumed) + len(h.tokens) for h in handles)
            print(f"{arch}: resumed run drained — {done} total tokens "
                  f"across {len(handles)} stream(s) "
                  f"(resumed + continued, bit-identical)")
            for k, v in snap.items():
                print(f"  {k}: {v:.3f}" if isinstance(v, float)
                      else f"  {k}: {v}")
            return handles

    mesh = None
    if devices is not None:
        mesh = make_serving_mesh(devices)
        print(f"serving mesh: {dict(mesh.shape)} over "
              f"{mesh.devices.size} x {mesh.devices.flat[0].device_kind}")
    cache_cfg = PrefixCacheConfig(device_slots=cache_slots,
                                  host_slots=cache_host_slots) \
        if prefix_cache else None
    slo = ServingSLO(prefill_budget=prefill_budget,
                     default_deadline_s=deadline_s,
                     admission=AdmissionPolicy(max_queue=max_queue,
                                               overload=overload))
    engine = ServingEngine(arch, smoke=smoke, max_batch=batch,
                           prefill_chunk=prefill_chunk,
                           quantized=quantized,
                           fused_decode=fused or False,
                           fused_prefill=fused_prefill, seed=seed,
                           speculative=speculative, draft_depth=draft_depth,
                           mesh=mesh, prefix_cache=cache_cfg, slo=slo,
                           snapshot=None if snapshot_dir is None else
                           SnapshotConfig(directory=snapshot_dir,
                                          every=snapshot_every),
                           sentinel_every=sentinel_every)
    cfg = engine.model.cfg
    rng = np.random.default_rng(seed)
    # with the cache on, share one "system prompt" across all requests so
    # every submission after the first resumes from a cached state; a
    # warm-up request runs to completion first, since boundary states only
    # publish when their request finishes
    shared = []
    if prefix_cache:
        shared = rng.integers(0, cfg.vocab,
                              size=max(prefill_chunk, prompt_len)).tolist()
        engine.submit(shared + [int(rng.integers(0, cfg.vocab))],
                      max_new_tokens=1)
        engine.run()
    # admission is tick-driven, so every submit lands on the queue first;
    # with --max-queue below the demo's request count the engine answers
    # with typed backpressure — report it instead of letting it unwind
    handles, rejected = [], 0
    for _ in range(batch):
        prompt = shared + \
            rng.integers(0, cfg.vocab, size=prompt_len).tolist()
        try:
            handles.append(
                engine.submit(prompt, max_new_tokens=n_tokens,
                              temperature=temperature,
                              seed=int(rng.integers(1 << 31))))
        except Overloaded as exc:
            rejected += 1
            print(f"backpressured: {exc}")
    snap = engine.run()
    if engine.snapshot_manager is not None:
        engine.snapshot_manager.wait()
    if rejected:
        print(f"{rejected}/{batch} submissions backpressured "
              f"(--max-queue {max_queue}, --overload {overload})")
    print(f"{arch}: {snap['finished']} requests x {n_tokens} tokens "
          f"({'Δ-PoT W8' if quantized else 'fp'} weights) — "
          f"{snap['decode_tokens_per_s']:,.0f} decode tok/s, "
          f"TTFT {snap['mean_ttft_s']*1e3:.0f} ms, "
          f"latency {snap['mean_latency_s']*1e3:.0f} ms")
    for k, v in snap.items():
        print(f"  {k}: {v:.3f}" if isinstance(v, float) else f"  {k}: {v}")
    if engine.prefix_cache is not None:
        print("prefix cache:")
        for k, v in engine.prefix_cache.snapshot().items():
            print(f"  {k}: {v:.3f}" if isinstance(v, float)
                  else f"  {k}: {v}")
    return handles


def supervise(argv: list[str], *, max_restarts: int = 3) -> int:
    """Restart-and-resume supervisor (docs/operations.md §supervisor):
    run the serve CLI in a child process; on ANY abnormal exit — an
    injected crash, a SIGKILL, an OOM kill — relaunch it with `--resume`
    so it restores the newest committed snapshot and continues every
    stream bit-identically.  A crash before the first snapshot boundary
    resumes as a fresh start (serve's `--resume` falls back).  Gives up
    after `max_restarts` abnormal exits and returns the child's code."""
    import subprocess
    import sys
    args = [a for a in argv if a != "--supervise"]
    for attempt in range(max_restarts + 1):
        rc = subprocess.call([sys.executable, "-m", "repro.launch.serve",
                              *args])
        if rc == 0:
            return 0
        if attempt == max_restarts:
            print(f"supervisor: giving up after {max_restarts} restarts "
                  f"(last rc={rc})")
            return rc
        print(f"supervisor: serve exited rc={rc}; restarting with "
              f"--resume ({attempt + 1}/{max_restarts})")
        if "--resume" not in args:
            args.append("--resume")
    return rc


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rwkv4-169m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--batch", type=int, default=4)
    ap.add_argument("--tokens", type=int, default=32)
    ap.add_argument("--prompt-len", type=int, default=8)
    ap.add_argument("--prefill-chunk", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0)
    ap.add_argument("--quantized", action="store_true")
    ap.add_argument("--fused", nargs="?", const="block", default=None,
                    choices=["block", "model"],
                    help="fused decode granularity: 'block' (one Pallas "
                         "launch per block; bare --fused keeps the PR 2 "
                         "meaning) or 'model' (the whole-model megakernel "
                         "— ONE launch per decode step; "
                         "kernels/fused_decode.py)")
    ap.add_argument("--fused-prefill", action="store_true",
                    help="fused chunked prefill: whole prompt chunks as "
                         "(S*C, D) matmuls + the on-chip WKV sequence "
                         "kernel, packed weights decoded in-kernel "
                         "(kernels/fused_prefill.py); bit-identical to "
                         "the per-op prefill scan")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="recurrent-state prefix cache: repeated prompt "
                         "prefixes resume from cached chunk-boundary "
                         "states instead of prefilling (bit-identical "
                         "tokens; serving/prefix_cache.py).  The demo "
                         "workload shares a system prompt across requests "
                         "so the hit path shows up in the telemetry")
    ap.add_argument("--prefix-cache-slots", type=int, default=64,
                    help="device-tier cache entries (lane states)")
    ap.add_argument("--prefix-cache-host-slots", type=int, default=256,
                    help="host spill-tier entries; 0 disables spilling")
    ap.add_argument("--speculative", type=int, default=None, metavar="K",
                    help="self-speculative decode: a truncated-stack "
                         "drafter proposes K-1 tokens per tick and one "
                         "chunk-shaped verify call scores the whole "
                         "window; the longest verifier-agreed prefix is "
                         "accepted (bit-identical tokens — K only moves "
                         "tokens/s; serving/plan.py SpeculativePath)")
    ap.add_argument("--draft-depth", type=int, default=None,
                    help="layers the speculative drafter keeps (default "
                         "half the stack)")
    ap.add_argument("--max-queue", type=int, default=0,
                    help="bounded admission queue (SLO layer): queued-"
                         "request cap, 0 = unbounded; a full queue "
                         "backpressures (typed Overloaded with retry "
                         "hints) or sheds per --overload")
    ap.add_argument("--overload", default="backpressure",
                    choices=["backpressure", "shed"],
                    help="full-queue behavior: refuse the arrival "
                         "(backpressure) or drop the lowest-priority "
                         "queued request (shed); serving/slo.py")
    ap.add_argument("--prefill-budget", type=int, default=0,
                    help="prefill chunk-tokens per tick while lanes are "
                         "decoding (0 = unlimited): caps the inter-token-"
                         "latency jitter a prefill burst can inject")
    ap.add_argument("--deadline", type=float, default=None, metavar="S",
                    help="default per-request deadline in seconds; "
                         "deadline-exceeded requests are evicted with "
                         "outcome 'deadline' (state slot freed, nothing "
                         "leaked)")
    ap.add_argument("--snapshot-dir", default=None, metavar="DIR",
                    help="crash safety: write a tick-boundary engine "
                         "snapshot into DIR every --snapshot-every ticks "
                         "(atomic commits, async writes; serving/"
                         "snapshot.py, docs/operations.md)")
    ap.add_argument("--snapshot-every", type=int, default=8,
                    help="snapshot cadence in scheduler ticks")
    ap.add_argument("--sentinel-every", type=int, default=0,
                    help="NaN/Inf lane sentinel sweep every N ticks "
                         "(0 = off): poisoned lanes are quarantined and "
                         "their requests requeued for a clean replay")
    ap.add_argument("--resume", action="store_true",
                    help="restore the newest committed snapshot from "
                         "--snapshot-dir and continue every stream "
                         "bit-identically (fresh start when none exists)")
    ap.add_argument("--supervise", action="store_true",
                    help="restart-and-resume supervisor: run serve in a "
                         "child process and relaunch it with --resume on "
                         "any abnormal exit (needs --snapshot-dir)")
    ap.add_argument("--max-restarts", type=int, default=3,
                    help="supervisor restart budget")
    ap.add_argument("--devices", type=int, default=None,
                    help="serve data-parallel over N local devices (the "
                         "slot pool and per-tick batch shard over a "
                         "('data',) mesh, weights replicate); CPU hosts "
                         "need XLA_FLAGS=--xla_force_host_platform_"
                         "device_count=N set before launch")
    ap.add_argument("--mesh", action="store_true",
                    help="shorthand for --devices over ALL visible "
                         "devices")
    ap.add_argument("--legacy", action="store_true",
                    help="seed single-loop decode instead of the engine")
    ap.add_argument("--hw-numerics", action="store_true",
                    help="paper LUT/PWL numerics (rwkv4; implies --legacy)")
    args = ap.parse_args()
    if args.supervise:
        import sys
        if not args.snapshot_dir:
            ap.error("--supervise needs --snapshot-dir (nothing to "
                     "resume from otherwise)")
        raise SystemExit(supervise(sys.argv[1:],
                                   max_restarts=args.max_restarts))
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    if args.legacy or args.hw_numerics:
        serve_legacy(args.arch, smoke=args.smoke, batch=args.batch,
                     n_tokens=args.tokens, quantized=args.quantized,
                     hw_numerics=args.hw_numerics)
    else:
        devices = 0 if args.mesh else args.devices
        serve(args.arch, smoke=args.smoke, batch=args.batch,
              n_tokens=args.tokens, quantized=args.quantized,
              prefill_chunk=args.prefill_chunk,
              prompt_len=args.prompt_len, temperature=args.temperature,
              fused=args.fused, fused_prefill=args.fused_prefill,
              devices=devices, prefix_cache=args.prefix_cache,
              cache_slots=args.prefix_cache_slots,
              cache_host_slots=args.prefix_cache_host_slots,
              speculative=args.speculative, draft_depth=args.draft_depth,
              max_queue=args.max_queue, overload=args.overload,
              prefill_budget=args.prefill_budget,
              deadline_s=args.deadline, snapshot_dir=args.snapshot_dir,
              snapshot_every=args.snapshot_every,
              sentinel_every=args.sentinel_every, resume=args.resume)


if __name__ == "__main__":
    main()
