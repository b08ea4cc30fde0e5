"""What one cell is, read from ``BENCHMARK.json`` and the files it names.

Nothing here lists a cell, a configuration, a traffic mix or a metric:
each is found by its name.

- ``bench/configs/<config>.json``: the model configuration as it is run
  (``program`` holds the program's ``ModelConfig`` fields);
- ``bench/traffic/<traffic>.json``: the traffic mix; its ``driver`` names
  the loop in ``bench/drivers/<driver>.py`` that drives it;
- ``bench/limits/<workload>.json``: the limit of every number that
  decides ``correct``, with the readings it was set from;
- ``bench/metrics/<metric>.py``: the reader of one per-layer metric.
"""
from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
import pathlib

ROOT = pathlib.Path(__file__).resolve().parents[1]


def _json(path: pathlib.Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: pathlib.Path = ROOT) -> dict:
    return _json(root / "BENCHMARK.json")


@dataclasses.dataclass
class Cell:
    workload: str
    chips: int
    config: dict
    traffic: dict
    limits: dict
    end_to_end: list[dict]     # the BENCHMARK.json entries this cell reports
    per_layer: list[dict]

    @property
    def driver(self) -> str:
        return self.traffic["driver"]


def reports(metric: dict, workload: str, e2e_names: set[str]) -> bool:
    """Whether a cell reports a metric: by its ``workloads`` list, else
    (a per-layer metric) wherever the metric it ``moves`` is reported."""
    if "workloads" in metric:
        return workload in metric["workloads"]
    if "moves" in metric:
        return metric["moves"] in e2e_names
    return True


def cell(workload: str, root: pathlib.Path = ROOT) -> Cell:
    """The cell ``workload``, from the checkout at ``root``."""
    bench = benchmark(root)
    w = {x["name"]: x for x in bench["workloads"]}.get(workload)
    if w is None:
        raise KeyError(f"no workload {workload!r} in BENCHMARK.json")
    cfg = {x["name"]: x for x in bench["configs"]}[w["config"]]
    e2e = [m for m in bench["end_to_end"] if reports(m, workload, set())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if reports(m, workload, names)]
    return Cell(workload=workload, chips=w["chips"],
                config=_json(root / cfg["file"]),
                traffic=_json(root / "bench" / "traffic" / f"{w['traffic']}.json"),
                limits=_json(root / "bench" / "limits" / f"{workload}.json"),
                end_to_end=e2e, per_layer=per_layer)


def driver(name: str):
    return importlib.import_module(f"bench.drivers.{name}")


def reader(metric: str, root: pathlib.Path = ROOT):
    """The module ``bench/metrics/<metric>.py`` (names may hold dots)."""
    path = root / "bench" / "metrics" / f"{metric}.py"
    spec = importlib.util.spec_from_file_location(
        f"bench_metric_{metric.replace('.', '_').replace('-', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def peaks(device_kind: str) -> dict:
    """Published peaks of one chip; a kind not in the table is an error."""
    table = _json(ROOT / "bench" / "peaks.json")["kinds"]
    if device_kind not in table:
        raise KeyError(f"device kind {device_kind!r} has no peaks in "
                       "bench/peaks.json")
    return table[device_kind]
