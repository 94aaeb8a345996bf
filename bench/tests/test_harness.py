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

from bench import harness, spec, weights, work
from conftest import FIXTURE, ROOT

CELL = "tiny-w8.trickle"


def test_lookup_is_data_driven():
    cell = spec.load_cell(CELL, FIXTURE)
    assert cell.config["name"] == "tiny-w8" and cell.chips == 1
    assert cell.traffic["loop"] == "open"
    assert [m["name"] for m in cell.end_to_end] == ["ttft_p95_ms", "setup_s"]
    assert list(cell.readers) == ["decode_lanes.trickle"]
    run = harness.Run(cell.config, cell.family, None,
                      {"decode_calls": 10, "decode_lanes": 25}, None)
    assert cell.readers["decode_lanes.trickle"](run) == 2.5
    with pytest.raises(KeyError):
        spec.load_cell("no-such.cell", FIXTURE)


# A family that only this test defines: one D x D matmul per block.
TOY_FAMILY = '''
import jax
import jax.numpy as jnp
from bench.weights import nest

MATMUL_LEAVES = (("mix", "w"),)


def layer_params(model, root, layer):
    d = model["d_model"]
    key = jax.random.fold_in(jax.random.fold_in(root, 0), layer)
    return nest([(("mix", "w"), jax.random.normal(key, (d, d)))])


def top_params(model, root):
    return {"embed": jnp.zeros((model["vocab"], model["d_model"]))}


def model_params(model, root):
    blocks = jax.vmap(lambda l: layer_params(model, root, l))(
        jnp.arange(model["n_layers"]))
    return {**top_params(model, root), "blocks": blocks}


def layer_matmul_weights(model):
    return model["d_model"] ** 2


def head_weights(model):
    return 0
'''


def _fixture_copy(tmp_path, family: dict):
    """The fixture with one more configuration, of model `family`, and
    one more cell on it; no file of the fixture is edited."""
    shutil.copytree(FIXTURE, tmp_path, dirs_exist_ok=True)
    tiny = json.loads((FIXTURE / "bench/configs/tiny-w8.json").read_text())
    conf = {**tiny, "name": "toy-w8",
            "model": {**tiny["model"], **family}}
    (tmp_path / "bench/configs/toy-w8.json").write_text(json.dumps(conf))
    bench = json.loads((tmp_path / "BENCHMARK.json").read_text())
    bench["configs"].append({**bench["configs"][0], "name": "toy-w8",
                             "file": "bench/configs/toy-w8.json"})
    bench["workloads"].append({**bench["workloads"][0],
                               "name": "toy-w8.trickle", "config": "toy-w8"})
    (tmp_path / "BENCHMARK.json").write_text(json.dumps(bench))
    return conf["model"]


def test_a_new_family_is_new_files_only(tmp_path):
    """A family module dropped into a benchmark's `families/` is found
    from the configuration that names it, builds its tree and gives the
    work counts; the cells of the families the benchmark brings resolve
    beside it."""
    model = _fixture_copy(tmp_path, {"family": "toy"})
    (tmp_path / "bench/families").mkdir()
    (tmp_path / "bench/families/toy.py").write_text(TOY_FAMILY)
    cell = spec.load_cell("toy-w8.trickle", tmp_path)
    assert cell.family.__file__ == str(tmp_path / "bench/families/toy.py")
    tree = cell.family.model_params(model, weights.root_key(1))
    assert tree["blocks"]["mix"]["w"].shape == (2, 64, 64)
    assert work.token_flops(cell.family, model, 10, 3) == 2 * 10 * 2 * 64**2
    tiny = spec.load_cell(CELL, tmp_path).family
    assert tiny is spec.load_cell(CELL, FIXTURE).family
    assert tiny.__file__ == str(ROOT / "bench/families/rwkv4.py")


def test_a_family_without_a_module_fails(tmp_path):
    _fixture_copy(tmp_path, {"family": "rwkv", "rwkv_version": 99})
    with pytest.raises(spec.RunFailed) as e:
        spec.load_cell("toy-w8.trickle", tmp_path)
    assert str(tmp_path / "bench/families/rwkv99.py") in str(e.value)
    assert harness.RunFailed is spec.RunFailed


def test_every_cell_of_the_benchmark_resolves():
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], ROOT)
        names = {m["name"] for m in cell.end_to_end}
        assert "setup_s" in names and len(names) >= 2
        assert cell.per_layer
        assert all(m["moves"] in names for m in cell.per_layer)
        assert set(cell.readers) == {m["name"] for m in cell.per_layer}


def test_every_reader_reads_a_run():
    """Each per-layer metric of each cell reads a run that holds
    something for it (a made-up window on a described v5e), and reads
    nothing from a run without a trace where it needs one."""
    from types import SimpleNamespace
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    trace = SimpleNamespace(modules={"decode": (100, 1.0),
                                     "prefill": (10, 0.6)},
                            idle_share=0.25)
    counts = dict(decode_calls=100, decode_lanes=3000, prefill_calls=10,
                  prefill_tokens=15000, first_tokens=40, tick_wall_s=2.0)
    for w in bench["workloads"]:
        cell = spec.load_cell(w["name"], ROOT)
        run = harness.Run(cell.config, cell.family,
                          work.peaks("TPU v5 lite"), counts, trace)
        for m in cell.per_layer:
            value = cell.readers[m["name"]](run)
            assert isinstance(value, float) and value > 0, m["name"]
            if m["unit"] == "%":
                assert value <= 100.0, (m["name"], value)
            if m["source"] == "device_trace":
                assert cell.readers[m["name"]](harness.Run(
                    cell.config, cell.family, run.peak, counts,
                    None)) is None, m["name"]


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


def prefill_unchanged(engine, _):
    """A prefill step that returns its state unchanged: no prompt token
    of any lane is absorbed."""
    sched = engine.scheduler
    prefill = sched.prefill_fn
    sched.prefill_fn = lambda state, toks, valid, fresh: prefill(
        state, toks, np.zeros_like(valid), fresh)


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


@pytest.mark.parametrize("fault", [None, state_unchanged, prefill_unchanged,
                                   half_batch, token_altered],
                         ids=["sound", "state_unchanged", "prefill_unchanged",
                              "half_batch", "token_altered"])
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
