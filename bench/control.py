"""Readings for a cell's correctness limit: the program and its control.

    python3 bench/control.py --workload <cell> --seeds 1 2 3 ... --seconds <s>

For each seed, in one process: build the cell's engine, serve its traffic
for a window, and take the same sample of finished requests a benchmark
run checks.  Then read, on those prompts and served tokens, the widest
and the mean gap by which

  * a served token's float32 reference logit lies below the reference's
    best (the program's reading, the number `correct` compares), and
  * the token that the control puts first lies below the reference's best
    (the control's reading).  The control is the same reference with every
    value that the program rounds to its bfloat16 compute or state dtype
    rounded to float8 (e4m3) instead, the precision step below.

Prints one JSON line per seed and a last line with the largest program
readings and the smallest control readings.  Benchmark runs never run this.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

CONTROL_DTYPE = "float8_e4m3fn"


def readings(cfg: dict, fam, traffic: dict, seed: int, requests) -> dict:
    """The widest and the mean gap of the program and of the control on
    one sample."""
    import numpy as np
    from bench.harness import MAX_SAMPLE
    from bench.reference import (Reference, pack_sequences,
                                 reference_length, token_gaps)
    tokens, positions = pack_sequences(requests, MAX_SAMPLE,
                                       reference_length(traffic))
    ref = Reference(cfg, fam, seed).logits_at(tokens, positions)
    ctl = Reference(cfg, fam, seed, CONTROL_DTYPE).logits_at(tokens,
                                                            positions)
    program = np.concatenate([token_gaps(r, s)
                              for r, (_, s) in zip(ref, requests)])
    control = np.concatenate([token_gaps(r, np.argmax(c, axis=1))
                              for r, c in zip(ref, ctl)])
    return {"program_max": float(program.max()),
            "program_mean": float(program.mean()),
            "control_max": float(control.max()),
            "control_mean": float(control.mean())}


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--cpu", action="store_true",
                    help="allow the CPU (tests at a tiny size)")
    ap.add_argument("--root", type=Path, default=ROOT)
    args = ap.parse_args()

    from bench import harness, spec
    cell = spec.load_cell(args.workload, args.root)
    if not args.cpu:
        harness.check_devices(cell.chips)
    harness.cache_everything()
    rows = []
    for seed in args.seeds:
        engine = harness.build_engine(cell.config, cell.family, seed)
        harness.warm(engine, cell.config)
        client = harness.serve(engine, cell.traffic, cell.config, seed,
                               args.seconds)
        requests, short = harness.sampled_requests(client, seed)
        del engine, client
        gc.collect()
        row = {"seed": seed, "requests": len(requests),
               "served": sum(len(s) for _, s in requests), "short": short,
               **readings(cell.config, cell.family, cell.traffic, seed,
                          requests)}
        rows.append(row)
        print(json.dumps(row), flush=True)
    print(json.dumps({
        "workload": args.workload,
        **{f"program_{k}": max(r[f"program_{k}"] for r in rows)
           for k in ("max", "mean")},
        **{f"control_{k}_least": min(r[f"control_{k}"] for r in rows)
           for k in ("max", "mean")},
        "seconds": time.perf_counter() - T_START}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
