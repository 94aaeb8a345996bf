"""The harness end to end on the CPU, at a tiny size.

The fixture directory holds a benchmark of its own: a configuration, a
mix and a per-layer metric that exist nowhere else, found by the same
lookup `bench/run.py` uses.  Runs here skip the look for a chip; every
other step of a run is the real one, including the reference check, which
must pass on a sound engine and fail when the timed path is broken.
"""
import json
import os
import shutil
import subprocess
import sys
import time

import numpy as np
import pytest

from bench import harness, spec
from conftest import FIXTURE, ROOT

CELL = "tiny-w8.trickle"


def test_lookup_is_data_driven():
    cell = spec.load_cell(CELL, FIXTURE)
    assert cell.config["name"] == "tiny-w8" and cell.chips == 1
    assert cell.traffic["loop"] == "open"
    assert [m["name"] for m in cell.end_to_end] == ["ttft_p95_ms", "setup_s"]
    assert list(cell.readers) == ["decode_lanes.trickle"]
    run = harness.Run(cell.config, None,
                      {"decode_calls": 10, "decode_lanes": 25}, None)
    assert cell.readers["decode_lanes.trickle"](run) == 2.5
    with pytest.raises(KeyError):
        spec.load_cell("no-such.cell", FIXTURE)


def test_every_cell_of_the_benchmark_resolves():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], ROOT)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        assert all(m["moves"] in names for m in cell.per_layer)
        assert set(cell.readers) == {m["name"] for m in cell.per_layer}


def _run(args, cwd, **env):
    return subprocess.run(
        [sys.executable, "bench/run.py", *args], cwd=cwd,
        capture_output=True, text=True, timeout=300,
        env={**os.environ, "JAX_PLATFORMS": "cpu", **env})


def test_no_chip_no_result():
    out = _run(["--workload", "rwkv4-169m-w8.chat", "--seed", "1",
                "--seconds", "1", "--trace", "0"], ROOT)
    assert out.returncode == 2
    assert out.stdout == ""
    assert "needs a TPU" in out.stderr


def test_bare_directory_no_result(tmp_path):
    """A directory with only BENCHMARK.json and the benchmark's files
    holds no system to run."""
    shutil.copy(ROOT / "BENCHMARK.json", tmp_path)
    shutil.copytree(ROOT / "bench", tmp_path / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    out = _run(["--workload", "rwkv4-169m-w8.chat", "--seed", "1",
                "--seconds", "1", "--trace", "0"], tmp_path)
    assert out.returncode != 0
    assert out.stdout == ""


def _decode_mask(engine, change):
    sched = engine.scheduler
    decode = sched.decode_fn
    sched.decode_fn = lambda state, toks, mask: decode(state, toks,
                                                       change(mask))


def state_unchanged(engine, _):
    """A decode step that returns its state unchanged."""
    _decode_mask(engine, np.zeros_like)


def half_batch(engine, _):
    """Half of the batch left out: of each decode call's active lanes, the
    later half (a lone lane included) never commits its step."""
    def keep_half(mask):
        active = np.flatnonzero(mask)
        out = np.zeros_like(mask)
        out[active[:len(active) // 2]] = True
        return out
    _decode_mask(engine, keep_half)


def token_altered(engine, monkeypatch):
    """A token altered where it is produced: the sampler's choice + 1."""
    from repro.serving import scheduler
    sample = scheduler.sample_tokens
    vocab = engine.model.cfg.vocab
    monkeypatch.setattr(scheduler, "sample_tokens",
                        lambda rows, metas: (sample(rows, metas) + 1) % vocab)


@pytest.mark.parametrize("fault", [None, state_unchanged, half_batch,
                                   token_altered],
                         ids=["sound", "state_unchanged", "half_batch",
                              "token_altered"])
def test_correct_catches_faults(fault, monkeypatch):
    cell = spec.load_cell(CELL, FIXTURE)
    out = harness.run_cell(
        cell, 1, 2.0, False, t_start=time.perf_counter(), require_chip=False,
        fault=None if fault is None else (lambda e: fault(e, monkeypatch)))
    gap = out["compared"]["max_logit_gap"]
    assert list(out)[-1] == "compared"
    assert out["attempted"] > 0 and out["failed"] == 0
    assert set(out["metrics"]) == {"ttft_p95_ms", "setup_s"}
    if fault is None:
        assert out["correct"] and gap["value"] <= gap["limit"]
    else:
        assert not out["correct"] and gap["value"] > gap["limit"]


def test_control_fails_the_limit():
    """The control (matmuls in float8) on the fixture's prompts and served
    tokens reads past the configuration's limit on each of three seeds,
    while the program stays inside it."""
    out = subprocess.run(
        [sys.executable, "bench/control.py", "--workload", CELL, "--seeds",
         "1", "2", "3", "--seconds", "2", "--cpu", "--root", str(FIXTURE)],
        cwd=ROOT, capture_output=True, text=True, timeout=600,
        env={**os.environ, "JAX_PLATFORMS": "cpu"})
    assert out.returncode == 0, out.stderr[-2000:]
    rows = [json.loads(line) for line in out.stdout.splitlines()
            if line.startswith('{"seed"')]
    limit = spec.load_cell(CELL, FIXTURE).config["correct"]["max_logit_gap"]
    assert all(r["program_mean"] < r["control_mean"] for r in rows)
    assert len(rows) == 3
    assert all(r["program_max"] <= limit < r["control_max"] for r in rows)
