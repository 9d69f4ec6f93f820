"""Find a cell and everything it names, by name, from ``BENCHMARK.json``.

A cell names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``, whose ``driver`` names the
general generator in ``bench/harness/drivers/<driver>.py``).  A per-layer
metric is the reader ``bench/metrics/<metric>.py``, or else the reader its
family shares, ``bench/metrics/<family>.py`` for ``<family>.<cells>``.
Adding any of these is adding a file; nothing here changes.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
BENCH = ROOT / "bench"


class SpecError(ValueError):
    """A cell, configuration, traffic mix or reader is missing or malformed."""


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    chips: int
    config: dict
    traffic: dict
    end_to_end: list      # the cell's end-to-end metric entries
    per_layer: list       # the cell's per-layer metric entries


def _load_json(path: Path) -> dict:
    try:
        return json.loads(path.read_text())
    except FileNotFoundError:
        raise SpecError(f"missing {path.relative_to(ROOT)}") from None


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def benchmark() -> dict:
    """The parsed ``BENCHMARK.json``."""
    return _load_json(ROOT / "BENCHMARK.json")


def load_cell(name: str) -> Cell:
    """A cell of ``BENCHMARK.json`` with everything it names."""
    bm = benchmark()
    cells = {w["name"]: w for w in bm["workloads"]}
    if name not in cells:
        raise SpecError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    configs = {c["name"]: c for c in bm["configs"]}
    config = _load_json(ROOT / configs[w["config"]]["file"])
    traffic = _load_json(BENCH / "traffic" / f"{w['traffic']}.json")
    e2e = [m for m in bm["end_to_end"] if _applies(m, name)]
    reported = {m["name"] for m in e2e}
    per_layer = [m for m in bm["per_layer"]
                 if _applies(m, name) and m["moves"] in reported]
    return Cell(name=name, chips=int(w["chips"]), config=config,
                traffic=traffic, end_to_end=e2e, per_layer=per_layer)


def _load_module(path: Path, label: str):
    if not path.is_file():
        raise SpecError(f"missing {path.relative_to(ROOT)}")
    spec = importlib.util.spec_from_file_location(label, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def driver(traffic: dict):
    """The general generator module a traffic mix names."""
    name = traffic["driver"]
    if not (BENCH / "harness" / "drivers" / f"{name}.py").is_file():
        raise SpecError(f"no driver {name!r} in bench/harness/drivers")
    return importlib.import_module(f"bench.harness.drivers.{name}")


def reader(metric: str):
    """The ``read(run) -> float | None`` function of a per-layer metric:
    its own file, or its family's where it has none."""
    path = BENCH / "metrics" / f"{metric}.py"
    if not path.is_file():
        path = BENCH / "metrics" / f"{metric.split('.')[0]}.py"
    mod = _load_module(path, "bench_metric_" + metric.replace(".", "_"))
    return mod.read
