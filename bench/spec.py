"""Finds a cell's configuration, model family, traffic mix and per-layer
metric readers by name, from `BENCHMARK.json` and the files beside it.

A configuration is the JSON file its entry names; its model's family is
the module `<bench>/families/<family><version>.py` (see `family`); a mix
is `<bench>/traffic/<traffic>.json`; a per-layer metric is read by
`<bench>/metrics/<metric>.py`, whose `read(run)` returns the value or
None when the run holds nothing to read.  `<bench>` is the first of the
benchmark's `paths`.  Nothing here names a cell or a model, so a new
cell, mix, metric or model family is new files and new entries only.
"""
from __future__ import annotations

import dataclasses
import functools
import importlib.util
import json
from pathlib import Path
from typing import Callable

ROOT = Path(__file__).resolve().parent.parent
# the families this benchmark brings, found when a cell's own benchmark
# directory has no module of that name
FAMILIES = Path(__file__).resolve().parent / "families"


class RunFailed(RuntimeError):
    """The run cannot give a result (no chip, no family module, a compile
    in the window, a demoted path, ...)."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    family: object              # the model's family module (`family`)
    traffic: dict
    end_to_end: list            # BENCHMARK.json entries this cell reports
    per_layer: list
    readers: dict               # per-layer metric name -> read(run)


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


@functools.cache
def _load_module(path: Path, prefix: str):
    """The module at `path`, loaded once per path (the reference's jitted
    programs take the family module as a static argument)."""
    spec = importlib.util.spec_from_file_location(
        prefix + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def load_reader(path: Path) -> Callable:
    return _load_module(path, "bench_metric_").read


def family(model: dict, home: Path = FAMILIES.parent):
    """The family module of a configuration's `model` section:
    `<family><version>.py`, the version being the model's
    `<family>_version` where it has one (`family` "rwkv" with
    `rwkv_version` 4 is `rwkv4.py`), from `<home>/families/` or else from
    this benchmark's own.  RunFailed names the path when neither has it."""
    name = model["family"]
    stem = f"{name}{model.get(f'{name}_version', '')}"
    paths = list(dict.fromkeys(
        (where / f"{stem}.py").resolve() for where in (home / "families",
                                                        FAMILIES)))
    for path in paths:
        if path.is_file():
            return _load_module(path, "bench_family_")
    raise RunFailed(f"no module for model family {name!r}: looked for "
                    + " and ".join(map(str, paths)))


def load_cell(workload: str, root: Path = ROOT) -> Cell:
    """The cell named `workload`; KeyError when there is none."""
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"no workload {workload!r}; known: {sorted(cells)}")
    w = cells[workload]
    conf = {c["name"]: c for c in bench["configs"]}[w["config"]]
    home = root / bench["paths"][0]
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if m["moves"] in e2e_names and _applies(m, workload)]
    config = json.loads((root / conf["file"]).read_text())
    return Cell(
        name=workload, chips=int(w["chips"]), config=config,
        family=family(config["model"], home),
        traffic=json.loads(
            (home / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=e2e, per_layer=per_layer,
        readers={m["name"]: load_reader(home / "metrics" / f"{m['name']}.py")
                 for m in per_layer})
