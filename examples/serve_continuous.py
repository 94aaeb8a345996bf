"""Continuous-batching serving demo: many requests, one state pool.

    PYTHONPATH=src python examples/serve_continuous.py --smoke

Submits several concurrent requests with different prompt lengths and
budgets, streams their tokens as the engine interleaves chunked prefill
with fused batched decode, then verifies every request's output is
bit-identical to decoding it alone with a sequential batch-1 loop (the
engine's correctness contract — see docs/serving.md).
"""
from __future__ import annotations

import argparse

import jax
import numpy as np

from repro.launch.serve import sequential_decode
from repro.models.registry import get_model
from repro.serving import ServingEngine


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="rwkv4-169m")
    ap.add_argument("--smoke", action="store_true")
    ap.add_argument("--requests", type=int, default=4)
    ap.add_argument("--max-batch", type=int, default=3,
                    help="pool slots (< requests exercises queueing)")
    ap.add_argument("--tokens", type=int, default=8)
    ap.add_argument("--quantized", action="store_true")
    ap.add_argument("--plane-policy", default=None,
                    choices=["w8", "w4", "vq", "proxy"],
                    help="per-tensor weight-plane preset (implies "
                         "--quantized); default keeps all-W8 packing")
    args = ap.parse_args()

    plane_policy = None
    if args.plane_policy is not None:
        from repro.core.quant import (PLANE_PROXY, PLANE_VQ, PLANE_W4,
                                      PLANE_W8)
        plane_policy = {"w8": PLANE_W8, "w4": PLANE_W4, "vq": PLANE_VQ,
                        "proxy": PLANE_PROXY}[args.plane_policy]
        args.quantized = True

    model = get_model(args.arch, smoke=args.smoke)
    params = model.init_params(jax.random.PRNGKey(0))
    engine = ServingEngine(model, params=params, max_batch=args.max_batch,
                           prefill_chunk=8, quantized=args.quantized,
                           plane_policy=plane_policy)

    rng = np.random.default_rng(0)
    prompts = [rng.integers(0, model.cfg.vocab,
                            size=int(rng.integers(3, 20))).tolist()
               for _ in range(args.requests)]
    handles = [engine.submit(p, max_new_tokens=args.tokens)
               for p in prompts]
    quant_label = engine.plan.cache_variant().quant
    print(f"{args.requests} requests -> {args.max_batch}-slot pool "
          f"({quant_label} weights)\n")

    # stream: drive the engine and print tokens as each request emits them
    streamed: dict[int, list[int]] = {h.rid: [] for h in handles}
    more = True
    while more:
        more = engine.step()
        for h in handles:
            for tok in h.drain():
                streamed[h.rid].append(tok)
                print(f"  req{h.rid} +{tok}", end="")
        print()
    print()

    snap = engine.counters.snapshot()
    print(f"{snap['decode_tokens']} tokens in {snap['ticks']} ticks "
          f"({snap['decode_tokens_per_s']:,.0f} tok/s, "
          f"TTFT {snap['mean_ttft_s']*1e3:.0f} ms)")

    if args.quantized:
        print("(skipping bit-identity check: the sequential reference "
              "below is fp — rerun without --quantized)")
        return
    ok = True
    for h, p in zip(handles, prompts):
        ref = sequential_decode(model, params, p, args.tokens,
                                width=args.max_batch)
        match = streamed[h.rid] == ref == h.tokens
        ok &= match
        print(f"req{h.rid}: engine == sequential decode: {match}")
    if not ok:
        raise SystemExit("outputs diverged from sequential decode")
    print("all outputs bit-identical to sequential decode ✓")


if __name__ == "__main__":
    main()
