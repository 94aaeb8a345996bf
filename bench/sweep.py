"""Find the highest arrival rate an open-loop cell sustains, by a sweep.

    python3 bench/sweep.py --workload <cell> --rates 8 12 16 ... --seconds <s>

In one process, with one warm engine, each rate drives the cell's mix at
that rate for a window of `seconds` (after the mix's preroll), then lets
the engine drain.  A rate is sustained when the queue of requests waiting
for a slot does not grow over the window: the mean queue over its last
quarter is no more than one request above the mean over its first
quarter.  Prints one JSON line per rate.  Benchmark runs never run this;
its result is fixed into the mix's file as `rate_per_s`.
"""
import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parent.parent
sys.path[:0] = [str(ROOT), str(ROOT / "src")]


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", type=float, nargs="+", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seed", type=int, default=1)
    args = ap.parse_args()

    from bench import harness, spec, stats
    cell = spec.load_cell(args.workload, ROOT)
    harness.check_devices(cell.chips)
    harness.cache_everything()
    engine = harness.build_engine(cell.config, cell.family, args.seed)
    harness.warm(engine, cell.config)
    for rate in args.rates:
        client = harness.serve(engine, {**cell.traffic, "rate_per_s": rate},
                               cell.config, args.seed, args.seconds)
        depth = client.queue_depth
        q = max(1, len(depth) // 4)
        first, last = sum(depth[:q]) / q, sum(depth[-q:]) / q
        e2e = harness.end_to_end(client, 0.0)
        print(json.dumps({
            "rate_per_s": rate, "sustained": last <= first + 1.0,
            "queue_first_quarter": first, "queue_last_quarter": last,
            "ticks": len(depth),
            "ttft_p95_ms": e2e.get("ttft_p95_ms"),
            "itl_p95_ms": e2e.get("itl_p95_ms"),
            "ttft_p50_ms": 1e3 * stats.nearest_rank(
                [r.first - r.due for r in client.done
                 if r.first is not None and client.open <= r.due], 0.5),
            "output_tokens_per_s": e2e["output_tokens_per_s"]}), flush=True)
        while engine.step():
            pass
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
