"""Finds a cell's files by name: ``BENCHMARK.json`` at the root of the
checkout, ``workloads/<cell>.json``, ``configs/<config>.json`` with
``configs/<config>.py``, and ``metrics/<metric>.py``.  A cell or a
configuration may also be named by the path of its ``.json`` file
(relative to the checkout), the configuration's module beside it."""
from __future__ import annotations

import importlib
import importlib.util
import json
import sys
from pathlib import Path
from typing import Callable, Dict, List, Optional

HERE = Path(__file__).resolve().parent
CHECKOUT = HERE.parents[1]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def benchmark(root: Path = CHECKOUT) -> dict:
    return load_json(root / "BENCHMARK.json")


def _path(name: str) -> Optional[Path]:
    """The file a name ending in ``.json`` gives, else None."""
    path = Path(name)
    if path.suffix != ".json":
        return None
    return path if path.is_absolute() else CHECKOUT / path


def workload(name: str, bench: Optional[dict] = None) -> dict:
    """A cell by its name in ``BENCHMARK.json`` (its traffic file under
    ``workloads/``), or by the path of a cell file."""
    path = _path(name)
    if path is not None:
        return load_json(path)
    bench = bench if bench is not None else benchmark()
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        raise KeyError(f"no cell {name!r} in BENCHMARK.json")
    cell = load_json(HERE / "workloads" / f"{entry['traffic']}.json")
    cell.update(name=entry["name"], config=entry["config"],
                chips=entry["chips"])
    return cell


def config(name: str) -> dict:
    return load_json(_path(name) or HERE / "configs" / f"{name}.json")


def model(name: str):
    """The configuration's plain reference module."""
    path = _path(name)
    if path is None:
        return importlib.import_module(f"fedbench.configs.{name}")
    key = f"fedbench_config:{path}"
    if key not in sys.modules:
        found = importlib.util.spec_from_file_location(
            key, path.with_suffix(".py"))
        module = importlib.util.module_from_spec(found)
        found.loader.exec_module(module)
        sys.modules[key] = module
    return sys.modules[key]


def peaks(kind: str) -> dict:
    table = load_json(HERE / "peaks.json")
    if kind not in table:
        raise KeyError(f"device kind {kind!r} is not in the peaks table")
    return table[kind]


def module_name(metric: str) -> str:
    return metric.replace(".", "__").replace("-", "_")


def reader(metric: str) -> Callable[[dict], Optional[float]]:
    mod = importlib.import_module(f"fedbench.metrics.{module_name(metric)}")
    return mod.read


def metrics_of(bench: dict, cell: str, kind: str) -> List[Dict]:
    """The ``end_to_end`` or ``per_layer`` entries that ``cell``
    reports."""
    return [m for m in bench[kind]
            if "workloads" not in m or cell in m["workloads"]]
