"""One run of one benchmark cell through `repro.serving.ServingEngine`.

The model comes from the configuration's family module (`bench/families/`,
found by `bench.spec.family`); nothing here names a model.  Set-up loads
the program, makes the configuration's weights on the device from the
seed through the family's `program_params` (f32 values, then the
program's own packing to its weight plane and each path's preparation;
one jitted call for RWKV-4), builds the execution plan and the engine,
and warms exactly the cell's two programs: the prefill at
(max_batch, prefill_chunk) and the decode at (max_batch, 1).  The window
then drives the cell's traffic (`traffic.py`) for `seconds`, timing every
token on the host clock from the client's side.  Inside the window no
program may compile, no path may be demoted and no fallback program may
be built: any of these fails the run.

After the window, with the peak memory read and the engine freed, a
sample of the finished requests goes through the plain reference
(`reference.py`, with the family's block), and the run is correct when
no served token's reference logit lies further below the reference's
best than the configuration's limit.

With `trace` the window runs under the profiler and the run reports the
cell's per-layer metrics instead of its end-to-end ones.
"""
from __future__ import annotations

import contextlib
import dataclasses
import gc
import shutil
import sys
import time

import numpy as np

from bench import spec as specs
from bench import stats, work
from bench.spec import RunFailed
from bench.trace import WINDOW_SPAN
from bench.traffic import Mix

SPANS = ("submit", "engine.step", "idle_sleep", "decode_call",
         "prefill_call")
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
# the reference checks this many finished requests, the longest among
# them (its rows are padded to this many whatever the sample holds)
MAX_SAMPLE = 8
PREROLL_LIMIT_S = 120.0


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def check_devices(chips: int):
    """The chips the cell asks for, or RunFailed: never the CPU."""
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise RunFailed(f"needs a TPU, found {devs[0].platform!r} "
                        f"({devs[0].device_kind})")
    if len(devs) < chips:
        raise RunFailed(f"needs {chips} TPU chips, found {len(devs)}")
    return devs[:chips]


def cache_everything() -> str:
    """The program's persistent compilation cache, with every program in
    it, however quickly it compiled: the engine dispatches small eager
    operations that would otherwise compile again in every run."""
    import jax
    from repro.launch.compile_cache import use_compile_cache
    where = use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return where


# ---------------------------------------------------------------------------
# the system under test
# ---------------------------------------------------------------------------


def build_engine(cfg: dict, fam, seed: int):
    """The engine the configuration describes, with weights from `seed`
    made by its family module `fam`."""
    import jax
    import jax.numpy as jnp
    from repro.configs.base import ModelConfig
    from repro.core.quant.serving import PreparedParams, pack_params
    from repro.models.registry import get_model
    from repro.serving import ExecutionPlan, ServingEngine
    from bench.weights import root_key

    mcfg, eng = cfg["model"], cfg["engine"]
    model = get_model(ModelConfig(**mcfg))
    if cfg["plane"]["matmul"] != "delta_pot_w8":
        raise RunFailed(f"unknown weight plane {cfg['plane']}")
    made = jax.eval_shape(lambda: fam.model_params(mcfg, root_key(0)))
    want = model.abstract_params()
    if (jax.tree_util.tree_structure(made)
            != jax.tree_util.tree_structure(want)
            or [a.shape for a in jax.tree_util.tree_leaves(made)]
            != [a.shape for a in jax.tree_util.tree_leaves(want)]):
        raise RunFailed("the program's parameter tree no longer matches "
                        f"{fam.__file__}")
    decode = model.decode_paths()[cfg["paths"]["decode"]]
    prefill = model.prefill_paths()[cfg["paths"]["prefill"]]

    def pack(tree):
        # the program's packing, and each path's one-time preparation
        # (None: the packed tree)
        raw = pack_params(tree)
        return raw, *(None if d.prepare is None
                      else model.prepare_path_params(d, raw)
                      for d in (decode, prefill))

    raw, dec, pre = jax.block_until_ready(
        fam.program_params(cfg, root_key(seed), pack))
    prepared = PreparedParams(
        raw=raw, decode=raw if dec is None else dec,
        prefill=raw if pre is None else pre, quantized=True,
        decode_path=decode.name, prefill_path=prefill.name)
    plan = ExecutionPlan(model, prepared, decode, prefill,
                         prefill_chunk=eng["prefill_chunk"],
                         state_dtype=jnp.dtype(eng["state_dtype"]))
    return ServingEngine(model, plan=plan, max_batch=eng["max_batch"])


class Probe:
    """Counts at the engine's two program boundaries (and spans around
    them in a traced run): decode calls and the lanes they decoded,
    prefill calls and the prompt tokens they absorbed."""

    def __init__(self, sched, span):
        self.c = dict(decode_calls=0, decode_lanes=0, prefill_calls=0,
                      prefill_tokens=0)
        decode, prefill = sched.decode_fn, sched.prefill_fn

        def counted_decode(state, toks, mask):
            with span("decode_call"):
                out = decode(state, toks, mask)
            self.c["decode_calls"] += 1
            self.c["decode_lanes"] += int(np.count_nonzero(mask))
            return out

        def counted_prefill(state, toks, valid, fresh):
            with span("prefill_call"):
                out = prefill(state, toks, valid, fresh)
            self.c["prefill_calls"] += 1
            self.c["prefill_tokens"] += int(np.count_nonzero(valid))
            return out

        sched.decode_fn, sched.prefill_fn = counted_decode, counted_prefill


# ---------------------------------------------------------------------------
# the client side
# ---------------------------------------------------------------------------


@dataclasses.dataclass
class Req:
    item: int
    handle: object
    due: float                  # host clock
    submitted: float
    seen: int = 0
    first: float | None = None
    last: float | None = None


class Client:
    """Submits the mix's requests and times every token they get back."""

    def __init__(self, engine, mix: Mix, span):
        self.engine, self.mix, self.span = engine, mix, span
        self.live: dict[int, Req] = {}
        self.done: list[Req] = []
        self.next_item = 0
        self.open = self.close = None
        self.window_tokens = 0
        self.first_tokens = 0
        self.gaps: list[float] = []
        self.tick_wall_s = 0.0
        self.queue_depth: list[int] = []    # per tick in the window

    def submit(self, due: float):
        item = self.mix.items[self.next_item]
        with self.span("submit"):
            h = self.engine.submit(item.prompt,
                                   max_new_tokens=item.max_new_tokens)
        self.live[h.rid] = Req(self.next_item, h, due, time.perf_counter())
        self.next_item += 1

    def step(self) -> list[Req]:
        """One engine tick; returns the requests that finished in it."""
        t0 = time.perf_counter()
        with self.span("engine.step"):
            self.engine.step()
        t = time.perf_counter()
        inside = self.open is not None and self.close is None
        if inside:
            self.tick_wall_s += t - t0
            self.queue_depth.append(len(self.engine.scheduler.queue))
        finished = []
        for rid, r in list(self.live.items()):
            n = len(r.handle.tokens)
            if n > r.seen:
                if inside:
                    self.window_tokens += n - r.seen
                    if r.seen == 0:
                        self.first_tokens += 1
                    else:
                        self.gaps.append(t - r.last)
                    self.gaps += [0.0] * (n - r.seen - 1)
                if r.seen == 0:
                    r.first = t
                r.seen, r.last = n, t
            if r.handle.done:
                del self.live[rid]
                self.done.append(r)
                finished.append(r)
        return finished

    def busy(self) -> bool:
        s = self.engine.scheduler
        return bool(s.queue or s.slots)


def drive_open(client: Client, seconds: float, on_open):
    """Arrivals on the mix's schedule; the window opens `preroll_s` after
    the first one.  Idle, the loop sleeps until the next request is due."""
    items, t0 = client.mix.items, time.perf_counter()
    open_t = t0 + client.mix.preroll_s
    close_t = open_t + seconds
    while True:
        t = time.perf_counter()
        if client.open is None and t >= open_t:
            on_open()
            client.open = time.perf_counter()
            close_t = client.open + seconds
        if client.open is not None and t >= close_t:
            break
        while (client.next_item < len(items)
               and t0 + items[client.next_item].due <= t):
            client.submit(t0 + items[client.next_item].due)
        if client.busy():
            client.step()
            continue
        nxt = (t0 + items[client.next_item].due
               if client.next_item < len(items) else close_t)
        stop = min(nxt, close_t if client.open else open_t)
        with client.span("idle_sleep"):
            time.sleep(max(0.0, stop - time.perf_counter()))
    client.close = time.perf_counter()


def drive_closed(client: Client, seconds: float, max_batch: int, on_open):
    """`clients` callers, each sending its next request when its last one
    finishes; the window opens once every slot has emitted a token."""
    t0 = time.perf_counter()
    for _ in range(client.mix.clients):
        client.submit(time.perf_counter())
    close_t = None
    while True:
        for _ in client.step():
            client.submit(time.perf_counter())
        t = time.perf_counter()
        if client.open is None:
            emitting = sum(1 for r in client.live.values() if r.seen)
            if emitting >= max_batch:
                on_open()
                client.open = time.perf_counter()
                close_t = client.open + seconds
            elif t - t0 > PREROLL_LIMIT_S:
                raise RunFailed("the slots never all emitted a token")
        elif t >= close_t:
            break
    client.close = time.perf_counter()


# ---------------------------------------------------------------------------
# end-to-end metrics (host clock)
# ---------------------------------------------------------------------------


def end_to_end(client: Client, setup_s: float) -> dict:
    """Every end-to-end metric this harness can take, by name."""
    window = client.close - client.open
    out = {"setup_s": setup_s,
           "output_tokens_per_s": client.window_tokens / window}
    if client.gaps:
        out["itl_p95_ms"] = 1e3 * stats.nearest_rank(client.gaps, 0.95)
    ttft = [(r.first if r.first is not None and r.first <= client.close
             else client.close) - r.due
            for r in client.done + list(client.live.values())
            if client.open <= r.due < client.close]
    if ttft:
        out["ttft_p95_ms"] = 1e3 * stats.nearest_rank(ttft, 0.95)
    return out


def lateness(client: Client) -> list[float]:
    return [r.submitted - r.due for r in client.done + list(
        client.live.values()) if client.open <= r.due < client.close]


# ---------------------------------------------------------------------------
# correctness
# ---------------------------------------------------------------------------


def sampled_requests(client: Client, seed: int):
    """The longest finished request and MAX_SAMPLE - 1 others drawn from
    the seed: (prompt, served tokens) pairs, and the ids of those that
    served another number of tokens than they asked for."""
    done = [r for r in client.done if r.handle.outcome == "finished"]
    if not done:
        return [], []
    done.sort(key=lambda r: (len(r.handle.tokens),
                             len(client.mix.items[r.item].prompt)))
    rng = np.random.default_rng([int(seed) % 2 ** 32, 0x5A])
    picked = [done.pop()] + [done[i] for i in rng.permutation(len(done))
                             [:MAX_SAMPLE - 1]]
    items = client.mix.items
    short = [r.handle.rid for r in picked
             if len(r.handle.tokens) != items[r.item].max_new_tokens]
    return [(items[r.item].prompt, list(r.handle.tokens))
            for r in picked], short


def logit_gaps(cfg: dict, fam, traffic: dict, seed: int, requests) -> dict:
    """How far each served token's reference logit lies below the
    reference's best logit at its position: the widest and the mean gap
    over every served token of the sample."""
    from bench.reference import (Reference, pack_sequences,
                                 reference_length, token_gaps)
    tokens, positions = pack_sequences(requests, MAX_SAMPLE,
                                       reference_length(traffic))
    logits = Reference(cfg, fam, seed).logits_at(tokens, positions)
    gaps = np.concatenate([token_gaps(lg, served)
                           for lg, (_, served) in zip(logits, requests)])
    return {"max_logit_gap": float(gaps.max()),
            "mean_logit_gap": float(gaps.mean())}


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------


def no_span(name):
    return contextlib.nullcontext()


def warm(engine, cfg: dict):
    """Compile and run the cell's two programs once: a prompt one token
    longer than a chunk and two new tokens make two prefill calls (the
    second finishing the prompt) and one decode call."""
    vocab, chunk = cfg["model"]["vocab"], cfg["engine"]["prefill_chunk"]
    engine.submit([1 + i % (vocab - 1) for i in range(chunk + 1)],
                  max_new_tokens=2)
    engine.run()


def serve(engine, traffic: dict, cfg: dict, seed: int, seconds: float,
          span=no_span, on_open=lambda: None):
    """Drive `traffic` through a warm engine for a window of `seconds`."""
    mix = Mix(traffic, seed, seconds, cfg["model"]["vocab"])
    client = Client(engine, mix, span)
    if mix.loop == "open":
        drive_open(client, seconds, on_open)
    else:
        drive_closed(client, seconds, cfg["engine"]["max_batch"], on_open)
    return client


@dataclasses.dataclass
class Run:
    """What the per-layer readers see of a finished run."""
    config: dict
    family: object
    peak: dict
    counts: dict
    trace: object               # trace.Summary or None


def run_cell(cell: specs.Cell, seed: int, seconds: float, trace: bool, *,
             t_start: float, require_chip: bool = True,
             fault=None) -> dict:
    """One run; returns the result line as a dict.  `fault(engine)` may
    break the engine after it is built (the harness's own tests)."""
    import jax

    devs = check_devices(cell.chips) if require_chip else jax.devices()[:1]
    log(f"{cell.name}: seed {seed}, {seconds} s, trace {int(trace)}, "
        f"{len(devs)} x {devs[0].device_kind}, cache {cache_everything()}")
    cfg = cell.config
    # off the chip there is no peak, and nothing is read against one
    peak = work.peaks(devs[0].device_kind) if require_chip else None

    span = jax.profiler.TraceAnnotation if trace else no_span
    log(f"set-up: imports done at {time.perf_counter() - t_start:.3f} s")
    engine = build_engine(cfg, cell.family, seed)
    log(f"set-up: engine built at {time.perf_counter() - t_start:.3f} s")
    if fault is not None:
        fault(engine)
    probe = Probe(engine.scheduler, span)
    warm(engine, cfg)
    log(f"set-up: warmed at {time.perf_counter() - t_start:.3f} s")

    compiles = []
    jax.monitoring.register_event_duration_secs_listener(
        lambda event, secs, **kw: compiles.append(event)
        if event == COMPILE_EVENT else None)
    traced = {}
    profile_dir = specs.ROOT / ".bench_trace"

    def on_open():
        traced["counts"] = dict(probe.c)
        traced["compiles"] = len(compiles)
        traced["programs"] = dict(engine.plan.trace_counts)
        if trace:
            shutil.rmtree(profile_dir, ignore_errors=True)
            # host spans, but no Python function tracing: it slows the
            # host several times over
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(str(profile_dir),
                                     profiler_options=opts)
            traced["window"] = span(WINDOW_SPAN)
            traced["window"].__enter__()

    client = serve(engine, cell.traffic, cfg, seed, seconds, span, on_open)
    if trace:
        traced["window"].__exit__(None, None, None)
        jax.profiler.stop_trace()
    setup_s = client.open - t_start

    counts = {k: v - traced["counts"][k] for k, v in probe.c.items()}
    counts.update(first_tokens=client.first_tokens,
                  tick_wall_s=client.tick_wall_s)
    sched = engine.scheduler
    faults = []
    if len(compiles) != traced["compiles"]:
        faults.append(f"{len(compiles) - traced['compiles']} compiles "
                      "inside the window")
    if engine.plan.trace_counts != traced["programs"]:
        faults.append(f"programs traced inside the window: "
                      f"{traced['programs']} -> {engine.plan.trace_counts}")
    if sched.demoted:
        faults.append(f"demoted paths {sorted(sched.demoted)}")
    if any(k.endswith("_fallback") for k in engine.plan.trace_counts):
        faults.append("a fallback program was built")
    if faults:
        raise RunFailed("; ".join(faults))

    late = lateness(client)
    if late:
        log(f"generator lateness over {len(late)} requests: p50 "
            f"{1e3 * stats.nearest_rank(late, 0.5):.3f} ms, p95 "
            f"{1e3 * stats.nearest_rank(late, 0.95):.3f} ms, max "
            f"{1e3 * max(late):.3f} ms")
    log(f"window {client.close - client.open:.3f} s: "
        f"{client.window_tokens} tokens, {counts['decode_calls']} decode "
        f"and {counts['prefill_calls']} prefill calls, "
        f"{len(client.done)} requests finished in all")
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": len(devs)}
    stats_ = [d.memory_stats() or {} for d in devs]
    device["memory_peak_bytes"] = max(
        (s.get("peak_bytes_in_use", 0) for s in stats_), default=0)

    summary = None
    if trace:
        from jax.profiler import ProfileData
        from bench.trace import summarize
        xplanes = sorted(profile_dir.rglob("*.xplane.pb"))
        if not xplanes:
            raise RunFailed(f"the profiler wrote no trace under "
                            f"{profile_dir}")
        summary = summarize(ProfileData.from_file(str(xplanes[-1])), SPANS)
        shutil.rmtree(profile_dir, ignore_errors=True)
        device["busy_s"] = summary.busy_s
        device["window_s"] = summary.window_s

    run = Run(cfg, cell.family, peak, counts, summary)
    if trace:
        metrics = {}
        for m in cell.per_layer:
            value = cell.readers[m["name"]](run)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        values = end_to_end(client, setup_s)
        metrics = {m["name"]: {"value": values[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end if m["name"] in values}

    attempted = [r for r in client.done + list(client.live.values())
                 if client.open <= r.submitted < client.close]
    failed = sum(1 for r in attempted
                 if r.handle.outcome not in (None, "finished"))
    requests, short = sampled_requests(client, seed)
    if short:
        log(f"finished requests {short} served the wrong number of tokens")
    del engine, client, probe, sched, run
    gc.collect()

    t_ref = time.perf_counter()
    gaps = (logit_gaps(cfg, cell.family, cell.traffic, seed, requests)
            if requests else {})
    log(f"reference over {len(requests)} requests, "
        f"{sum(len(s) for _, s in requests)} served tokens, in "
        f"{time.perf_counter() - t_ref:.3f} s")
    # the configuration names the numbers it compares and their limits
    compared = {name: {"value": gaps.get(name, float("inf")),
                       "limit": float(limit)}
                for name, limit in cfg["correct"].items()}
    correct = (all(c["value"] <= c["limit"] for c in compared.values())
               and failed == 0 and not short)
    result = {"correct": correct, "attempted": len(attempted),
              "failed": failed, "metrics": metrics, "device": device}
    if summary is not None:
        result["breakdown"] = {"device_ops": summary.top_ops,
                               "idle_gaps": summary.idle_gaps}
    result["compared"] = compared
    for name, c in compared.items():
        log(f"compared {name} {c['value']!r} limit {c['limit']!r}")
    return result
