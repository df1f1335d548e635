"""Discovery: resolve a cell of ``BENCHMARK.json`` to its files by name.

Nothing here lists a cell, a configuration, a traffic mix or a metric.
Each is a file named after its entry:

* ``configs[i].file`` — the deployment (a JSON object);
* ``gen/<config.distribution>.py`` — its seeded key generator;
* ``reference/<config.reference>.py`` — its plain reference;
* ``traffic/<cell.traffic>.json`` — the mix, read by :mod:`sortbench.drive`;
* ``e2e/<metric>.py`` and ``layers/<metric>.py`` — one reader per metric.

A later PR adds a cell by adding files and entries; no code changes.
"""

from __future__ import annotations

import importlib.util
import json
import sys
from dataclasses import dataclass
from pathlib import Path
from types import ModuleType
from typing import Any

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent


def load_module(path: Path) -> ModuleType:
    """Import one reader/generator file by path (names may hold dots)."""
    if not path.is_file():
        raise FileNotFoundError(f"sortbench: no file {path}")
    name = f"sortbench_dyn.{path.resolve()}".replace(".py", "")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod  # dataclasses look their module up by name
    spec.loader.exec_module(mod)
    return mod


@dataclass
class Metric:
    name: str
    unit: str
    reader: ModuleType  # read(run) -> float | None


@dataclass
class Cell:
    name: str
    chips: int
    config: dict[str, Any]
    traffic: dict[str, Any]
    generator: ModuleType   # generate(rng, n, dtype, params) -> np.ndarray
    reference: ModuleType   # reference(x) -> np.ndarray
    end_to_end: list[Metric]
    per_layer: list[Metric]


def load_benchmark(root: Path = ROOT) -> dict[str, Any]:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def _applies(metric: dict[str, Any], cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def resolve(name: str, bench: dict[str, Any] | None = None,
            root: Path = ROOT) -> Cell:
    """The cell ``name`` with its configuration, traffic, generator,
    reference and metric readers loaded; a missing file raises."""
    bench = bench if bench is not None else load_benchmark(root)
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"sortbench: no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    cfg_entry = {c["name"]: c for c in bench["configs"]}[w["config"]]
    with open(root / cfg_entry["file"]) as f:
        config = json.load(f)
    with open(HERE / "traffic" / f"{w['traffic']}.json") as f:
        traffic = json.load(f)

    def metrics(kind: str, subdir: str) -> list[Metric]:
        return [Metric(m["name"], m["unit"],
                       load_module(HERE / subdir / f"{m['name']}.py"))
                for m in bench[kind] if _applies(m, name)]

    return Cell(
        name=name, chips=int(w["chips"]), config=config, traffic=traffic,
        generator=load_module(HERE / "gen" / f"{config['distribution']}.py"),
        reference=load_module(HERE / "reference" / f"{config['reference']}.py"),
        end_to_end=metrics("end_to_end", "e2e"),
        per_layer=metrics("per_layer", "layers"),
    )


def peaks(device_kind: str) -> dict[str, Any]:
    """The published peaks of one chip of ``device_kind``; a device not
    in ``peaks.json`` is an error, never a default."""
    with open(HERE / "peaks.json") as f:
        table = json.load(f)["devices"]
    if device_kind not in table:
        raise KeyError(f"sortbench: no peaks for device kind {device_kind!r} "
                       f"in peaks.json (have {sorted(table)})")
    return table[device_kind]
