"""Where a traced window's device-idle time goes, phase by phase.

The serving engine opens a host span for each phase of a tick, named
`serve.*` (`repro.runtime.monitor.span`): `serve.tick`, `serve.admit`,
`serve.prefill` / `serve.decode`, and inside those `serve.inputs`,
`serve.dispatch`, `serve.fetch`, `serve.sample`, `serve.emit`; besides
`serve.gc` for a Python collection and `serve.compile` for a compile.
They land in the profiler's trace on the device's clock.  `reduce` cuts
every idle interval of the device inside the window (`trace.WINDOW_SPAN`)
exactly at the edges of the host spans over it and files each piece under
the innermost span that covers it: a program span before a harness span
(`harness.SPANS`), "outside spans" where neither covers.  The parts sum
to the idle time.  It also counts the program spans and names the longest
gaps the same way.

    python3 bench/phases.py --workload <cell> --seed <n> --seconds <s>

runs one cell's window as `bench/run.py --trace 1` does, without the
reference check, and prints the reduction as one JSON line.  Benchmark
runs never run this.
"""
import argparse
import bisect
import collections
import dataclasses
import heapq
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parent.parent
if __name__ == "__main__":
    sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench.trace import (_DEVICE, MODULES_LINE, OPS_LINE,  # noqa: E402
                         WINDOW_SPAN, _clip, _union)

PREFIX = "serve."
OUTSIDE = "outside spans"
# the spans that name what the host was doing; serve.tick, serve.prefill
# and serve.decode only hold them
PHASES = ("serve.admit", "serve.inputs", "serve.dispatch", "serve.fetch",
          "serve.sample", "serve.emit", "serve.gc", "serve.compile")
GROUPS = {"dispatch": ("serve.admit", "serve.inputs", "serve.dispatch"),
          "fetch": ("serve.fetch",),
          "consume": ("serve.sample", "serve.emit")}


@dataclasses.dataclass
class Phases:
    window_s: float
    idle_s: float                   # mean over the device planes
    ticks: int                      # serve.tick spans starting inside
    idle_by_span: dict              # innermost span -> idle s; sums
                                    # to idle_s
    spans: dict                     # program span -> [count, host s]
    idle_gaps: list                 # [[span, gap s], ...], longest first

    def per_tick_ms(self, names) -> float:
        return 1e3 * sum(self.idle_by_span.get(n, 0.0)
                         for n in names) / self.ticks

    def phase_share(self) -> float:
        """Share of the idle time under program spans that lies under a
        phase span rather than in the self time of a parent."""
        under = sum(v for k, v in self.idle_by_span.items()
                    if k.startswith(PREFIX))
        return sum(self.idle_by_span.get(n, 0.0) for n in PHASES) / under


def innermost(spans, lo, hi):
    """Cut [lo, hi] at every span edge: [(start, end, label)], labelled
    with the innermost covering span (a program span before any other,
    then the latest to start), OUTSIDE where none covers."""
    edges = sorted({lo, hi} | {x for s, e, _ in spans for x in (s, e)
                               if lo < x < hi})
    opening = sorted(spans)
    live, i, out = [], 0, []        # heap of the spans opened so far
    for a, b in zip(edges, edges[1:]):
        while i < len(opening) and opening[i][0] <= a:
            s, e, name = opening[i]
            heapq.heappush(live, (not name.startswith(PREFIX), -s, e,
                                  name))
            i += 1
        while live and live[0][2] <= a:     # ended: not over [a, b)
            heapq.heappop(live)
        out.append((a, b, live[0][3] if live else OUTSIDE))
    return out


def _label_at(segments, t):
    """The label of the segment that holds time `t`."""
    return segments[bisect.bisect_right(segments, t,
                                        key=lambda sg: sg[0]) - 1][2]


def split(idle, segments):
    """{label: ns} of the (sorted, disjoint) idle intervals, cut by the
    labelled segments, which partition the window."""
    out = collections.Counter()
    j = 0
    for s, e in idle:
        while j < len(segments) and segments[j][1] <= s:
            j += 1
        k = j
        while k < len(segments) and segments[k][0] < e:
            a, b, label = segments[k]
            out[label] += min(b, e) - max(a, s)
            k += 1
    return out


def reduce(profile, host_spans=(), n_top: int = 10) -> Phases:
    """Reduce a `jax.profiler.ProfileData` holding `serve.*` spans.
    `host_spans` names the harness spans that may label idle time where
    no program span does."""
    window, labels = None, []
    for plane in profile.planes:
        if _DEVICE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                if ev.name == WINDOW_SPAN:
                    window = (ev.start_ns, ev.end_ns)
                elif ev.name.startswith(PREFIX) or ev.name in host_spans:
                    labels.append((ev.start_ns, ev.end_ns, ev.name))
    if window is None:
        raise ValueError(f"the trace holds no {WINDOW_SPAN!r} span")
    lo, hi = window
    devices = [p for p in profile.planes if _DEVICE.match(p.name)]
    if not devices:
        raise ValueError("the trace holds no TPU device plane")
    inside = _clip(labels, lo, hi)
    segments = innermost(inside, lo, hi)
    idle_ns = collections.Counter()
    gaps = []
    for plane in devices:
        lines = {line.name: line for line in plane.lines}
        op_line = lines.get(OPS_LINE) or lines.get(MODULES_LINE)
        busy = _union([(max(ev.start_ns, lo), min(ev.end_ns, hi))
                       for ev in (op_line.events if op_line else ())
                       if ev.end_ns > lo and ev.start_ns < hi])
        edges = [lo] + [x for s, e in busy for x in (s, e)] + [hi]
        idle = [(edges[i], edges[i + 1]) for i in range(0, len(edges), 2)
                if edges[i + 1] > edges[i]]
        idle_ns.update(split(idle, segments))
        gaps += idle
    n = len(devices)
    spans = collections.defaultdict(lambda: [0, 0.0])
    for s, e, name in labels:
        if name.startswith(PREFIX) and lo <= s < hi:
            spans[name][0] += 1
            spans[name][1] += (e - s) * 1e-9
    gaps.sort(key=lambda g: g[0] - g[1])
    return Phases(
        window_s=(hi - lo) * 1e-9,
        idle_s=sum(idle_ns.values()) * 1e-9 / n,
        ticks=spans["serve.tick"][0],
        idle_by_span={k: v * 1e-9 / n for k, v in idle_ns.most_common()},
        spans=dict(spans),
        idle_gaps=[[_label_at(segments, (s + e) / 2), (e - s) * 1e-9]
                   for s, e in gaps[:n_top]])


# ---------------------------------------------------------------------------
# one traced window of a cell
# ---------------------------------------------------------------------------


def run(cell, seed: int, seconds: float) -> dict:
    import jax
    from jax.profiler import ProfileData, TraceAnnotation
    from bench import harness, spec, stats, trace

    devs = harness.check_devices(cell.chips)
    harness.log(f"{cell.name}: seed {seed}, {seconds} s, phases, "
                f"{len(devs)} x {devs[0].device_kind}, cache "
                f"{harness.cache_everything()}")
    cfg = cell.config
    engine = harness.build_engine(cfg, cell.family, seed)
    probe = harness.Probe(engine.scheduler, TraceAnnotation)
    harness.warm(engine, cfg)
    counters = engine.counters
    profile_dir = spec.ROOT / ".bench_trace"
    at_open = {}

    def on_open():
        at_open.update(probe=dict(probe.c),
                       waits=len(counters.queue_wait_s),
                       snap=counters.snapshot())
        shutil.rmtree(profile_dir, ignore_errors=True)
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(str(profile_dir), profiler_options=opts)
        at_open["window"] = TraceAnnotation(WINDOW_SPAN)
        at_open["window"].__enter__()

    client = harness.serve(engine, cell.traffic, cfg, seed, seconds,
                           TraceAnnotation, on_open)
    at_open["window"].__exit__(None, None, None)
    jax.profiler.stop_trace()
    window = client.close - client.open
    ticks = len(client.queue_depth)
    harness.log(f"window {window:.3f} s: {client.window_tokens} tokens, "
                f"{ticks} ticks")
    waits = counters.queue_wait_s[at_open["waits"]:]
    snap = counters.snapshot()
    mirror = {k: snap[k] - at_open["snap"][k]
              for k in ("decode_calls", "decode_lanes", "prefill_calls",
                        "prefill_tokens", "tick_wall_s")}
    xplanes = sorted(profile_dir.rglob("*.xplane.pb"))
    if not xplanes:
        raise harness.RunFailed(f"no trace under {profile_dir}")
    profile = ProfileData.from_file(str(xplanes[-1]))
    summary = trace.summarize(profile, harness.SPANS)
    ph = reduce(profile, harness.SPANS)
    del profile
    shutil.rmtree(profile_dir, ignore_errors=True)
    groups = {g: ph.per_tick_ms(names) for g, names in GROUPS.items()}
    groups.update({n[len(PREFIX):]: ph.per_tick_ms([n])
                   for n in ("serve.gc", "serve.compile", "serve.tick",
                             "serve.prefill", "serve.decode")})
    groups["outside serve.tick"] = ph.per_tick_ms(
        [k for k in ph.idle_by_span if not k.startswith(PREFIX)])
    return {
        "workload": cell.name, "seed": seed,
        "device": {"kind": devs[0].device_kind, "count": len(devs)},
        "window_s": window, "tokens": client.window_tokens,
        "tokens_per_s": client.window_tokens / window,
        "ticks": ticks, "ticks_per_s": ticks / window,
        "trace_window_s": ph.window_s, "busy_s": summary.busy_s,
        "idle_s": ph.idle_s, "idle_parts_sum_s": sum(
            ph.idle_by_span.values()),
        "trace_ticks": ph.ticks,
        "idle_ms_per_tick": groups,
        "phase_share": ph.phase_share(),
        "idle_by_span_s": ph.idle_by_span,
        "spans": ph.spans,
        "idle_gaps": ph.idle_gaps,
        "harness_idle_gaps": summary.idle_gaps,
        "top_ops": summary.top_ops,
        "modules": summary.modules,
        "queue_wait_p95_ms": 1e3 * stats.nearest_rank(waits, 0.95)
        if waits else None,
        "admissions": len(waits),
        "counters": mirror,
        "probe": {k: v - at_open["probe"][k] for k, v in probe.c.items()},
    }


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args()

    from bench import harness, spec
    try:
        out = run(spec.load_cell(args.workload, ROOT), args.seed,
                  args.seconds)
    except (harness.RunFailed, KeyError, FileNotFoundError) as e:
        print(f"phases: {type(e).__name__}: {e}; no result", file=sys.stderr)
        return 2
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
