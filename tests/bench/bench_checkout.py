"""A checkout of the benchmark in a temporary directory, with two tiny
cells added as data only: for each, a configuration file, the mix file
and ``BENCHMARK.json`` entries; for the second, its architecture too,
as the two files ``bench/archs/<name>.py`` and
``bench/archs/<name>_reference.py``. Nothing here describes a chip."""
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

# (configuration, its file in DATA, workload)
TINY = [("tiny", "tiny.json", "tiny.closed"),
        ("tiny-window", "tiny-window.json", "tiny-window.closed")]


def tiny_checkout(tmp: Path) -> Path:
    """``tmp`` as a checkout holding the real ``bench/`` and
    ``BENCHMARK.json`` plus the tiny cells ``tiny.closed`` (the dense
    architecture) and ``tiny-window.closed`` (``data/archs/tiny_window``:
    sliding-window and global layers), added by new files and entries
    only."""
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copytree(DATA / "archs", tmp / "bench" / "archs",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(DATA / "tiny-mix.json", tmp / "bench" / "mixes")
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    for config, file, cell in TINY:
        shutil.copy(DATA / file, tmp / "bench" / "configs")
        b["configs"].append({"name": config, "source": "tests/bench/data",
                             "file": f"bench/configs/{file}", "reduced": [],
                             "why": "CPU tests"})
        b["workloads"].append(
            {"name": cell, "config": config, "traffic": "tiny-mix",
             "chips": 1, "why": "CPU tests"})
        for m in b["end_to_end"] + b["per_layer"]:
            if "workloads" in m:
                m["workloads"].append(cell)
    (tmp / "BENCHMARK.json").write_text(json.dumps(b))
    return tmp
