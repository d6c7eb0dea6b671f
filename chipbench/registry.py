"""Finds everything a cell needs by the names in ``BENCHMARK.json``.

A configuration is ``configs/<name>.json`` (its ``reference`` key names a
module of `chipbench.reference`), a traffic mix ``traffic/<name>.json``, and
every metric, end-to-end or per-layer, a reader ``metrics/<name>.py`` with
``read(run) -> float | None``. A metric split by the cells it reports in
(``decode_step_ms.chat``, ``decode_step_ms.offline``) is read by the reader
of its quantity, ``metrics/decode_step_ms.py``, unless a reader of the
whole name is there. Adding one adds a file and an entry; no code
here changes.
"""
from __future__ import annotations

import importlib.util
import json
from dataclasses import dataclass
from pathlib import Path
from typing import Callable, List, Optional

HERE = Path(__file__).resolve().parent
ROOT = HERE.parent
BENCHMARK = ROOT / "BENCHMARK.json"


def load_benchmark(path: Path = BENCHMARK) -> dict:
    with open(path) as f:
        return json.load(f)


def _load_json(path: Path) -> dict:
    if not path.is_file():
        raise FileNotFoundError(f"no file {path} for this name")
    with open(path) as f:
        return json.load(f)


def config(name: str, bench: Optional[dict] = None) -> dict:
    """The configuration named ``name``, from the file its entry names."""
    bench = bench if bench is not None else load_benchmark()
    entry = next((c for c in bench["configs"] if c["name"] == name), None)
    if entry is None:
        raise KeyError(f"no configuration {name!r} in BENCHMARK.json")
    return _load_json(ROOT / entry["file"])


def traffic(name: str) -> dict:
    return _load_json(HERE / "traffic" / f"{name}.json")


def reader(metric: str) -> Callable:
    """``read(run)`` of the metric named ``metric``: ``metrics/<name>.py``,
    or else that of the name without its last ``.<suffix>``."""
    path = HERE / "metrics" / f"{metric}.py"
    if not path.is_file() and "." in metric:
        path = HERE / "metrics" / f"{metric.rsplit('.', 1)[0]}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no reader {path} for metric {metric!r}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{metric.replace('.', '_').replace('-', '_')}",
        path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def reference(name: str):
    """The plain reference module a configuration names."""
    return importlib.import_module(f"chipbench.reference.{name}")


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


@dataclass
class Cell:
    name: str
    config_name: str
    config: dict
    traffic_name: str
    traffic: dict
    chips: int
    end_to_end: List[dict]
    per_layer: List[dict]


def cell(name: str, bench: Optional[dict] = None) -> Cell:
    bench = bench if bench is not None else load_benchmark()
    w = next((w for w in bench["workloads"] if w["name"] == name), None)
    if w is None:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    return Cell(
        name=name, config_name=w["config"],
        config=config(w["config"], bench), traffic_name=w["traffic"],
        traffic=traffic(w["traffic"]), chips=int(w["chips"]),
        end_to_end=[m for m in bench["end_to_end"] if _applies(m, name)],
        per_layer=[m for m in bench["per_layer"] if _applies(m, name)])
