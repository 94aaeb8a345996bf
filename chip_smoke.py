"""On-chip smoke run of the RWKV serving engine at published width.

Drives the serving engine's main path through its normal entry point
(`repro.serving.ServingEngine`) on one TPU, at the full width of RWKV-4
169M (12 layers, d_model 768, d_ff 3072, vocab 50,277), with random
weights made from `--seed`:

  (a) the default engine (per-op decode, per-op prefill): 8 requests with
      prompts of 64-256 tokens, 32 new tokens each; every request must
      finish;
  (b) the fast paths on Δ-PoT W8 weights (the decode megakernel and the
      chunked prefill, weights decoded in-kernel) against the per-op
      engine on the same W8 weights: the token streams are compared and
      the first difference is printed (reported, not required — the MXU
      and an XLA dot may round differently);
  (c) the last-position logits of the per-op and the fused programs, after
      a prompt and after one more decode step, against a float32
      reference (`decode_step` on the same W8 weights, decoded to f32,
      under `jax.default_matmul_precision("highest")`).  The fused error
      may exceed the per-op error by at most `FUSED_ERR_FACTOR`.

Every phase asserts that it ran on the TPU, that no path was demoted to
its per-op twin and that no fallback program was built.  `--chips 4` runs
only the data-parallel phase: the phase-(a) engine on a 4-device serving
mesh against the same engine on one device, streams compared.

The seconds printed are those of a smoke run, compilation included, not a
benchmark.  The last line of stdout is one JSON object:
{"ok": true, "device": {"platform": "tpu", "kind": ..., "count": N}}.
Without a TPU the script exits 2 and names the platform it found.

Run:  python chip_smoke.py [--chips 4] [--seed N]
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

ARCH = "rwkv4-169m"
BATCH = 8
NEW_TOKENS = 32
PROMPT_LENS = (64, 256)
# Both programs carry the same bf16 activations, bf16 state and decoded
# weights; they differ only in where the compiler rounds and in the order
# the MXU accumulates, which moves logits by the same bf16 rounding noise
# that makes up the per-op error itself.  An error more than twice the
# per-op one is not rounding: the kernel computes something else (a wrong
# tile, a stale state row).
FUSED_ERR_FACTOR = 2.0


def log(msg: str):
    print(f"[chip_smoke] {msg}", flush=True)


def require_tpu(count: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        print(f"chip_smoke: needs a TPU, but JAX found platform "
              f"{devs[0].platform!r} ({devs[0].device_kind}, {len(devs)} "
              "device(s)); no result", file=sys.stderr)
        raise SystemExit(2)
    if len(devs) < count:
        print(f"chip_smoke: needs {count} TPU devices, found {len(devs)}",
              file=sys.stderr)
        raise SystemExit(2)
    return devs


def make_prompts(vocab: int, seed: int) -> list[list[int]]:
    rng = np.random.default_rng(seed)
    lens = rng.integers(PROMPT_LENS[0], PROMPT_LENS[1] + 1, size=BATCH)
    return [rng.integers(0, vocab, size=int(n)).tolist() for n in lens]


def check_engine(engine, name: str):
    """The per-phase invariants: on the TPU, nothing demoted, no
    fallback program built."""
    import jax
    assert jax.devices()[0].platform == "tpu", jax.devices()
    assert engine.scheduler.demoted == frozenset(), \
        f"{name}: demoted {set(engine.scheduler.demoted)}"
    assert engine.counters.path_fallbacks == 0, \
        f"{name}: {engine.counters.degraded_events}"
    fallbacks = [k for k in engine.plan.trace_counts
                 if k.endswith("_fallback")]
    assert not fallbacks, f"{name}: fallback programs {fallbacks}"


def serve(engine, prompts, name: str) -> list[list[int]]:
    """One warm-up request (compiles the pool's prefill and decode
    programs), then all prompts; returns the greedy token streams."""
    t0 = time.perf_counter()
    engine.submit(prompts[0][:16], max_new_tokens=2)
    engine.run()
    first_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    handles = [engine.submit(p, max_new_tokens=NEW_TOKENS) for p in prompts]
    engine.run()
    wall_s = time.perf_counter() - t0
    for h in handles:
        assert h.outcome == "finished" and len(h.tokens) == NEW_TOKENS, \
            (name, h.rid, h.outcome, len(h.tokens))
    assert all(n == 1 for n in engine.trace_counts.values()), \
        f"{name}: recompiled {engine.trace_counts}"
    check_engine(engine, name)
    log(f"{name}: smoke run, not a benchmark — compile + warm-up request "
        f"{first_s:.3f} s; {len(prompts)} requests in {wall_s:.3f} s wall, "
        f"{sum(map(len, prompts))} prompt tokens, "
        f"{len(prompts) * NEW_TOKENS} generated tokens")
    return [list(h.tokens) for h in handles]


def compare_streams(a, b, name: str) -> bool:
    same = True
    for i, (x, y) in enumerate(zip(a, b)):
        if x != y:
            same = False
            first = next(j for j, (u, v) in enumerate(zip(x, y)) if u != v)
            log(f"{name}: request {i} first differs at token {first} "
                f"({x[first]} vs {y[first]})")
    log(f"{name}: token streams {'equal' if same else 'differ'}")
    return same


def program_logits(plan, prompt, next_token: int):
    """Last-position logits of a plan's own prefill program over `prompt`
    (lane 0 of the pool, other lanes idle), then of its decode program for
    `next_token`: (prefill (V,), decode (V,)) as f32 numpy."""
    chunk = plan.prefill_chunk
    state = plan.model.init_slot_state(BATCH, plan.max_len,
                                       plan.state_dtype)
    prefill, decode = plan.prefill_fn(BATCH), plan.decode_fn(BATCH)
    for c in range(0, len(prompt), chunk):
        part = prompt[c:c + chunk]
        toks = np.zeros((BATCH, chunk), np.int32)
        valid = np.zeros((BATCH, chunk), bool)
        toks[0, :len(part)], valid[0, :len(part)] = part, True
        fresh = np.zeros((BATCH,), bool)
        fresh[0] = c == 0
        state, last = prefill(state, toks, valid, fresh)
    toks = np.zeros((BATCH, 1), np.int32)
    toks[0, 0] = next_token
    mask = np.zeros((BATCH,), bool)
    mask[0] = True
    step, _ = decode(state, toks, mask)
    return (np.asarray(last[0, -1], np.float32),
            np.asarray(step[0, -1], np.float32))


def reference_logits(model, packed, prompt, next_token: int):
    """float32 reference: `decode_step` over the prompt and one more token
    on the W8 weights decoded to f32, at the highest matmul precision."""
    import dataclasses
    import jax
    import jax.numpy as jnp
    from repro.core.quant.serving import unpack_params
    from repro.models import rwkv4
    cfg = dataclasses.replace(model.cfg, dtype="float32")
    params = jax.tree_util.tree_map(lambda a: a.astype(jnp.float32),
                                    unpack_params(packed))

    def run(params, tokens):
        def body(state, t):
            logits, state = rwkv4.decode_step(params, state, t.reshape(1, 1),
                                              0, cfg)
            return state, logits[0, -1]
        state = rwkv4.init_decode_state(cfg, 1, 0, jnp.float32)
        _, rows = jax.lax.scan(body, state, tokens)
        return rows[-2], rows[-1]

    with jax.default_matmul_precision("highest"):
        pre, dec = jax.jit(run)(params, jnp.asarray(prompt + [next_token],
                                                    jnp.int32))
    return np.asarray(pre), np.asarray(dec)


def one_chip(params, model, seed: int) -> bool:
    from repro.serving import ServingEngine
    prompts = make_prompts(model.cfg.vocab, seed)
    kw = dict(smoke=False, max_batch=BATCH, params=params, seed=seed)

    # (a) default paths
    base = ServingEngine(ARCH, **kw)
    serve(base, prompts, "(a) per-op decode + per-op prefill, fp")

    # (b) fast paths on W8 against the per-op engine on the same weights
    fast = ServingEngine(ARCH, quantized=True, fused_decode="model",
                         fused_prefill=True, **kw)
    perop = ServingEngine(ARCH, quantized=True, **kw)
    fast_streams = serve(fast, prompts,
                         "(b) megakernel decode + chunked prefill, W8")
    perop_streams = serve(perop, prompts,
                          "(b) per-op decode + per-op prefill, W8")
    compare_streams(fast_streams, perop_streams,
                    "(b) fused vs per-op W8 greedy")

    # (c) logits against the float32 reference
    prompt = prompts[0]
    ref_pre, ref_dec = reference_logits(model, perop.plan.prepared.raw,
                                        prompt, prompt[-1])
    nxt = prompt[-1]
    ok = True
    errs = {}
    for name, plan in (("per-op", perop.plan), ("fused", fast.plan)):
        pre, dec = program_logits(plan, prompt, nxt)
        assert pre.shape == ref_pre.shape == (model.cfg.vocab,)
        assert np.isfinite(pre).all() and np.isfinite(dec).all(), name
        errs[name] = (float(np.abs(pre - ref_pre).max()),
                      float(np.abs(dec - ref_dec).max()))
        log(f"(c) {name} W8 program vs f32 reference: max |Δlogit| "
            f"prefill {errs[name][0]:.6f}, decode {errs[name][1]:.6f} "
            f"(logit scale: max |ref| {float(np.abs(ref_pre).max()):.4f})")
    for i, what in enumerate(("prefill", "decode")):
        limit = FUSED_ERR_FACTOR * errs["per-op"][i]
        if not errs["fused"][i] <= limit:
            log(f"(c) FAIL: fused {what} error {errs['fused'][i]:.6f} > "
                f"{FUSED_ERR_FACTOR} x per-op {errs['per-op'][i]:.6f}")
            ok = False
    for e in (base, fast, perop):
        check_engine(e, "after (c)")
    return ok


def four_chips(params, model, seed: int) -> bool:
    from repro.launch.mesh import make_serving_mesh
    from repro.serving import ServingEngine
    prompts = make_prompts(model.cfg.vocab, seed)
    kw = dict(smoke=False, max_batch=BATCH, params=params, seed=seed)
    mesh = make_serving_mesh(4)
    log(f"serving mesh {dict(mesh.shape)} over {mesh.devices.size} x "
        f"{mesh.devices.flat[0].device_kind}")
    sharded = serve(ServingEngine(ARCH, mesh=mesh, **kw), prompts,
                    "(dp4) per-op engine on a 4-device mesh")
    single = serve(ServingEngine(ARCH, **kw), prompts,
                   "(dp4) per-op engine on one device")
    compare_streams(sharded, single, "(dp4) 4-device vs 1-device greedy")
    return True


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, default=1, choices=(1, 4),
                    help="4: run only the data-parallel mesh phase")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args()
    devs = require_tpu(args.chips)

    import jax
    from repro.launch.compile_cache import use_compile_cache
    from repro.models.registry import get_model
    log(f"compile cache: {use_compile_cache()}")
    log(f"jax {jax.__version__}; {len(devs)} x {devs[0].device_kind}")

    model = get_model(ARCH, smoke=False)
    cfg = model.cfg
    log(f"{ARCH}: L{cfg.n_layers} D{cfg.d_model} d_ff {cfg.d_ff} "
        f"vocab {cfg.vocab}, weights from seed {args.seed}")
    t0 = time.perf_counter()
    params = jax.block_until_ready(
        model.init_params(jax.random.PRNGKey(args.seed)))
    log(f"weights initialised in {time.perf_counter() - t0:.3f} s")
    ok = (four_chips if args.chips == 4 else one_chip)(params, model,
                                                        args.seed)
    if not ok:
        return 1
    print(json.dumps({"ok": True, "device": {
        "platform": devs[0].platform, "kind": devs[0].device_kind,
        "count": len(jax.devices())}}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
