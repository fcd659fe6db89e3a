"""Find a cell's parts by name: its entry in ``BENCHMARK.json``, its
configuration file, its mix file and the readers of its metrics.

A later PR adds a cell by adding files and entries only: a mix is
``bench/mixes/<traffic>.json``, a configuration is the ``file`` its entry
names, and a per-layer metric ``m`` is read by ``bench/metrics/<m>.py``.
A metric split by the end-to-end metric it moves (``idle_share.decode``)
is read by the reader of its stem (``bench/metrics/idle_share.py``)
unless it has a file of its own.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
from pathlib import Path
from typing import Callable, Dict, List


@dataclasses.dataclass
class Cell:
    name: str
    config_name: str
    config_file: Path
    traffic: str
    mix: Dict
    chips: int
    end_to_end: List[Dict]      # metrics this cell reports (no trace)
    per_layer: List[Dict]       # metrics this cell reports (--trace 1)


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(root: Path, name: str) -> Cell:
    bench = json.loads((root / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json "
                       f"(have {sorted(cells)})")
    w = cells[name]
    cfg = {c["name"]: c for c in bench["configs"]}[w["config"]]
    mix = json.loads((root / "bench" / "mixes" /
                      f"{w['traffic']}.json").read_text())
    return Cell(name, w["config"], root / cfg["file"], w["traffic"], mix,
                int(w["chips"]),
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)])


def reader(root: Path, metric: str) -> Callable:
    """``read`` of ``bench/metrics/<metric>.py``, or of the file of the
    name's stem before its last ``.``."""
    d = root / "bench" / "metrics"
    path = d / f"{metric}.py"
    if not path.is_file() and "." in metric:
        path = d / f"{metric.rsplit('.', 1)[0]}.py"
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read
