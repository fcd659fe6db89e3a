"""Find a cell's parts by name: its entry in ``BENCHMARK.json``, its
configuration file, its architecture, its mix file and the readers of its
metrics.

A later PR adds a cell by adding files and entries only:

* a mix is ``bench/mixes/<traffic>.json``;
* a configuration is the ``file`` its entry names;
* an architecture is named by the configuration file's ``bench`` block,
  ``"model": "<name>"``: ``bench/archs/<name>.py`` builds the weights and
  the program bundle, and ``bench/archs/<name>_reference.py`` holds the
  plain forward (what each provides: ``bench/model.py`` and
  ``bench/reference.py``). Without the key, the dense ``bench/model.py``
  and ``bench/reference.py`` serve the configuration. A named module that
  is missing is an error that names its path: there is no fallback. The
  comparison that decides ``correct`` is shared by every architecture;
* a per-layer metric ``m`` is read by ``bench/metrics/<m>.py``. A metric
  split by the end-to-end metric it moves (``idle_share.decode``) is read
  by the reader of its stem (``bench/metrics/idle_share.py``) unless it
  has a file of its own.

Architecture modules and readers are loaded by path, so that each is a
file of its own.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import re
import sys
from pathlib import Path
from types import ModuleType
from typing import Callable, Dict, List, Optional, Tuple

NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


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
    model: ModuleType           # the architecture: Arch, weights, bundle
    reference: ModuleType       # its plain forward: tail_logits


def _applies(metric: Dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def _load(path: Path, name: str) -> ModuleType:
    if not path.is_file():
        raise FileNotFoundError(f"no file {path}")
    spec = importlib.util.spec_from_file_location(name, path)
    mod = importlib.util.module_from_spec(spec)
    sys.modules[name] = mod         # dataclasses look their module up
    spec.loader.exec_module(mod)
    return mod


def arch_files(root: Path, config_file: Path) -> Optional[Tuple[Path, Path]]:
    """(architecture module, reference module) that the configuration
    file names, or None where it names none (the dense default)."""
    conf = json.loads(Path(config_file).read_text())
    name = conf["bench"].get("model")
    if name is None:
        return None
    if not isinstance(name, str) or not NAME.fullmatch(name):
        raise ValueError(f"{config_file}: bench.model {name!r} is not a "
                         f"name")
    d = root / "bench" / "archs"
    return d / f"{name}.py", d / f"{name}_reference.py"


def arch_modules(root: Path,
                 config_file: Path) -> Tuple[ModuleType, ModuleType]:
    """The architecture and reference modules of a configuration file."""
    files = arch_files(root, config_file)
    if files is None:
        from bench import model, reference
        return model, reference
    stem = "bench_arch_" + files[0].stem.replace(".", "_").replace("-", "_")
    return _load(files[0], stem), _load(files[1], stem + "_reference")


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
    model, reference = arch_modules(root, root / cfg["file"])
    return Cell(name, w["config"], root / cfg["file"], w["traffic"], mix,
                int(w["chips"]),
                [m for m in bench["end_to_end"] if _applies(m, name)],
                [m for m in bench["per_layer"] if _applies(m, name)],
                model, reference)


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
