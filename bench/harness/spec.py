"""``BENCHMARK.json`` and the files it names, resolved by name.

A workload names a configuration (``bench/configs/<config>.json``) and a
traffic mix (``bench/traffic/<traffic>.json``); the mix's ``path`` names
the driving path ``bench/drivers/<path>.py`` (its class ``Cell``); every
metric has a reader ``bench/metrics/<metric>.py`` with ``read(ctx)``.
Adding a cell, a driving path or a metric adds files and entries; nothing
here changes.
"""

from __future__ import annotations

import dataclasses
import importlib.util
import json
import pathlib

BENCH = pathlib.Path(__file__).resolve().parents[1]
ROOT = BENCH.parent


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    traffic: dict
    end_to_end: list       # metric entries this cell reports
    per_layer: list


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    return json.loads((root / "BENCHMARK.json").read_text())


def config_path(name: str) -> pathlib.Path:
    return BENCH / "configs" / f"{name}.json"


def traffic_path(name: str) -> pathlib.Path:
    return BENCH / "traffic" / f"{name}.json"


def metric_path(name: str) -> pathlib.Path:
    return BENCH / "metrics" / f"{name}.py"


def driver_path(name: str) -> pathlib.Path:
    return BENCH / "drivers" / f"{name}.py"


def _applies(metric: dict, cell: str, reported: set) -> bool:
    if "workloads" in metric:
        return cell in metric["workloads"]
    return metric.get("moves") is None or metric["moves"] in reported


def resolve(bench: dict, workload: str) -> Cell:
    cells = {w["name"]: w for w in bench["workloads"]}
    if workload not in cells:
        raise KeyError(f"unknown workload {workload!r}; have {sorted(cells)}")
    w = cells[workload]
    cfg_entry = next(c for c in bench["configs"] if c["name"] == w["config"])
    cfg = json.loads((ROOT / cfg_entry["file"]).read_text())
    traffic = json.loads(traffic_path(w["traffic"]).read_text())
    e2e = [m for m in bench["end_to_end"] if _applies(m, workload, set())]
    names = {m["name"] for m in e2e}
    per_layer = [m for m in bench["per_layer"]
                 if _applies(m, workload, names)]
    return Cell(name=workload, chips=w["chips"], cfg=cfg, traffic=traffic,
                end_to_end=e2e, per_layer=per_layer)


def _load(path: pathlib.Path, prefix: str):
    spec = importlib.util.spec_from_file_location(
        prefix + path.stem.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def reader(name: str):
    """The ``read(ctx)`` function of metric ``name``."""
    return _load(metric_path(name), "bench_metric_").read


def driver(name: str):
    """The ``Cell`` class of driving path ``name``."""
    return _load(driver_path(name), "bench_driver_").Cell
