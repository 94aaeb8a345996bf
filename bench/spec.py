"""Finds a cell's configuration, traffic mix and per-layer metric readers
by name, from `BENCHMARK.json` and the files beside it.

A configuration is the JSON file its entry names; a mix is
`<bench>/traffic/<traffic>.json`; a per-layer metric is read by
`<bench>/metrics/<metric>.py`, whose `read(run)` returns the value or
None when the run holds nothing to read.  `<bench>` is the first of the
benchmark's `paths`.  Nothing here names a cell, so a new cell, mix or
metric is new files and new entries only.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable

ROOT = Path(__file__).resolve().parent.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list            # BENCHMARK.json entries this cell reports
    per_layer: list
    readers: dict               # per-layer metric name -> read(run)


def _applies(entry: dict, cell: str) -> bool:
    return "workloads" not in entry or cell in entry["workloads"]


def load_reader(path: Path) -> Callable:
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


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
    return Cell(
        name=workload, chips=int(w["chips"]),
        config=json.loads((root / conf["file"]).read_text()),
        traffic=json.loads(
            (home / "traffic" / f"{w['traffic']}.json").read_text()),
        end_to_end=e2e, per_layer=per_layer,
        readers={m["name"]: load_reader(home / "metrics" / f"{m['name']}.py")
                 for m in per_layer})
