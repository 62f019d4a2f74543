"""Find a cell's configuration, traffic mix and metric readers by name.

``BENCHMARK.json`` names the cells and metrics. A configuration is
``configs/<name>.json``, a traffic mix ``traffic/<name>.json`` and a
metric ``metrics/<name>.py`` with a ``read(run)`` function; a new cell
or metric is a new file and a new entry, never an edit of a file that
is there.
"""

from __future__ import annotations

import importlib.util
import json
import pathlib
from typing import Callable, Dict, List, Optional

BENCH_DIR = pathlib.Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent


def load_benchmark(root: pathlib.Path = ROOT) -> dict:
    with open(root / "BENCHMARK.json") as f:
        return json.load(f)


def workload(bench: dict, name: str) -> dict:
    for w in bench["workloads"]:
        if w["name"] == name:
            return w
    raise KeyError(f"no workload {name!r} in BENCHMARK.json; have "
                   f"{[w['name'] for w in bench['workloads']]}")


def _json(kind: str, name: str, base: pathlib.Path) -> dict:
    path = base / kind / f"{name}.json"
    if not path.is_file():
        raise FileNotFoundError(f"no {kind[:-1]} file {path}")
    with open(path) as f:
        return json.load(f)


def load_config(name: str, base: pathlib.Path = BENCH_DIR) -> dict:
    return _json("configs", name, base)


def load_traffic(name: str, base: pathlib.Path = BENCH_DIR) -> dict:
    return _json("traffic", name, base)


def metric_reader(name: str, base: pathlib.Path = BENCH_DIR) -> Callable:
    """The ``read(run) -> float | None`` of ``metrics/<name>.py``."""
    path = base / "metrics" / f"{name}.py"
    if not path.is_file():
        raise FileNotFoundError(f"no metric reader {path}")
    spec = importlib.util.spec_from_file_location(
        f"chipbench_metric_{name.replace('.', '_')}", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def cell_metrics(bench: dict, cell: str, traced: bool) -> List[dict]:
    """The metrics a run of ``cell`` reports: its end-to-end metrics
    untraced, its per-layer metrics traced. A metric without a
    ``workloads`` list belongs to every cell (a per-layer one, to every
    cell that reports the end-to-end metric it moves)."""
    e2e = [m for m in bench["end_to_end"]
           if cell in m.get("workloads", [cell])]
    if not traced:
        return e2e
    names = {m["name"] for m in e2e}
    return [m for m in bench["per_layer"]
            if cell in m.get("workloads", [cell]) and m["moves"] in names]


def read_metrics(metrics: List[dict], run,
                 base: pathlib.Path = BENCH_DIR) -> Dict[str, dict]:
    """Run each metric's reader; a reader that finds nothing to read
    returns None and the metric is left out of the line."""
    out: Dict[str, dict] = {}
    for m in metrics:
        value: Optional[float] = metric_reader(m["name"], base)(run)
        if value is not None:
            out[m["name"]] = {"value": float(value), "unit": m["unit"]}
    return out
