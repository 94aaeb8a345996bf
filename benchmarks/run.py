"""Benchmark driver — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows (benchmarks.common.emit).

  bench_quant_ablation  — Table 1 (quantization scheme ablation)
  bench_resources       — Table 2 (footprint/compression accounting)
  bench_throughput      — Fig 7   (decode throughput across sizes)
  bench_energy_proxy    — Fig 8   (energy-efficiency proxy)
  bench_kernels         — §4 modules (kernel vs oracle)
  bench_serving         — continuous-batching engine vs the seed loop
  bench_prefill         — fused chunked prefill vs the per-op scan
  bench_prefix_cache    — prefix-cache TTFT vs cache-off serving
  bench_speculative     — self-speculative decode vs plain decode ticks
  bench_serving_slo     — bursty 2x-overload load vs the SLO layer
"""
from __future__ import annotations

import sys
import traceback


def main() -> None:
    from repro.launch.compile_cache import use_compile_cache
    use_compile_cache()
    from benchmarks import (bench_energy_proxy, bench_kernels,
                            bench_prefill, bench_prefix_cache,
                            bench_quant_ablation, bench_resources,
                            bench_serving, bench_serving_slo,
                            bench_speculative, bench_throughput)
    print("name,us_per_call,derived")
    failures = 0
    for mod in (bench_resources, bench_energy_proxy, bench_throughput,
                bench_kernels, bench_quant_ablation, bench_serving,
                bench_prefill, bench_prefix_cache, bench_speculative,
                bench_serving_slo):
        try:
            mod.run()
        except Exception:
            failures += 1
            print(f"{mod.__name__},nan,ERROR", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        raise SystemExit(1)


if __name__ == '__main__':
    main()
