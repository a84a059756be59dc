"""Find a cell, its configuration, its model family, its traffic and its
per-layer metrics by the names in ``BENCHMARK.json``.

Each lives in a file of its own: ``configs/<config>.json`` (as named by
the configuration's ``file``), ``chipbench/families/<family>.py`` (as
named by the configuration's ``family``: widths, work counts, the plain
reference model and how the program is built for it),
``traffic/<traffic>.json``, ``limits/<cell>.json`` (the limits of the
numbers that decide ``correct``), ``windows/<cell>.json`` (the window's
fixed number of steps) and ``metrics/<metric>.py``.  Adding a cell, a
metric or a configuration of a new family adds files and entries; no file
here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import sys
from pathlib import Path
from types import ModuleType
from typing import Callable

BENCH_DIR = Path(__file__).resolve().parents[1]
ROOT = BENCH_DIR.parents[1]


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config_name: str
    config: dict  # the configuration file
    family: ModuleType  # chipbench/families/<family>.py
    traffic_name: str
    traffic: dict  # the traffic file
    end_to_end: list[dict]  # BENCHMARK.json entries this cell reports
    per_layer: list[dict]
    limits: dict[str, float]  # the compared numbers' limits
    window: dict  # {"steps": n, "seconds": s}: n steps last about s seconds

    @property
    def dims(self):
        return self.family.dims_of(self.config)


def load_benchmark(root: Path = ROOT) -> dict:
    return json.loads((Path(root) / "BENCHMARK.json").read_text())


def _reports(entry: dict, cell: str, e2e_names: set[str] | None) -> bool:
    """Whether ``cell`` reports the metric ``entry``: the cells its
    ``workloads`` list, or else every cell (an end-to-end metric) or every
    cell that reports the end-to-end metric it moves (a per-layer one)."""
    if "workloads" in entry:
        return cell in entry["workloads"]
    return e2e_names is None or entry["moves"] in e2e_names


def find_cell(name: str, root: Path = ROOT, bench: dict | None = None) -> Cell:
    """The cell ``name`` of the benchmark at ``root``; ``KeyError`` if there
    is none."""
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    config = json.loads((Path(root) / cfg_entry["file"]).read_text())
    traffic_file = Path(root) / bench["paths"][0] / "traffic" / f"{w['traffic']}.json"
    traffic = json.loads(traffic_file.read_text())
    bench_dir = traffic_file.parents[1]
    limits = json.loads((bench_dir / "limits" / f"{name}.json").read_text())
    window = json.loads((bench_dir / "windows" / f"{name}.json").read_text())
    family = load_family(config["family"], bench_dir)
    e2e = [m for m in bench["end_to_end"] if _reports(m, name, None)]
    e2e_names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"] if _reports(m, name, e2e_names)]
    return Cell(
        name=name, chips=w["chips"], config_name=w["config"], config=config, family=family,
        traffic_name=w["traffic"], traffic=traffic,
        end_to_end=e2e, per_layer=per_layer, limits=limits, window=window,
    )


def _load(path: Path, module_name: str, missing: str) -> ModuleType:
    """The Python file ``path`` as the module ``module_name``, imported once
    a process (a module that defines dataclasses must be in
    ``sys.modules``); ``KeyError(missing)`` if there is no such file."""
    path = Path(path).resolve()
    if not path.is_file():
        raise KeyError(f"{missing} at {path}")
    module = sys.modules.get(module_name)
    if module is not None and module.__file__ == str(path):
        return module
    spec = importlib.util.spec_from_file_location(module_name, path)
    module = importlib.util.module_from_spec(spec)
    sys.modules[module_name] = module
    spec.loader.exec_module(module)
    return module


def load_family(name: str, bench_dir: Path = BENCH_DIR) -> ModuleType:
    """The model family ``name``, from ``chipbench/families/<name>.py``."""
    path = Path(bench_dir) / "chipbench" / "families" / f"{name}.py"
    return _load(path, f"chipbench_family_{name}", f"no family {name!r}")


def metric_reader(name: str, bench_dir: Path = BENCH_DIR) -> Callable[[dict], float | None]:
    """``read(run) -> value or None`` of the per-layer metric ``name``, from
    ``metrics/<name>.py``."""
    path = Path(bench_dir) / "metrics" / f"{name}.py"
    return _load(path, f"chipbench_metric_{name}", f"no reader for metric {name!r}").read
