"""A checkout of the benchmark in a temporary directory, with a tiny cell
added as data only: a configuration file, a mix file and a
``BENCHMARK.json`` entry. Nothing here describes a chip."""
import json
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
DATA = Path(__file__).resolve().parent / "data"
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))


def tiny_checkout(tmp: Path) -> Path:
    """``tmp`` as a checkout holding the real ``bench/`` and
    ``BENCHMARK.json`` plus the tiny cell ``tiny.closed``, added by new
    files and entries only."""
    shutil.copytree(ROOT / "bench", tmp / "bench",
                    ignore=shutil.ignore_patterns("__pycache__"))
    shutil.copy(DATA / "tiny.json", tmp / "bench" / "configs")
    shutil.copy(DATA / "tiny-mix.json", tmp / "bench" / "mixes")
    b = json.loads((ROOT / "BENCHMARK.json").read_text())
    b["configs"].append({"name": "tiny", "source": "tests/bench/data",
                         "file": "bench/configs/tiny.json", "reduced": [],
                         "why": "CPU tests"})
    b["workloads"].append(
        {"name": "tiny.closed", "config": "tiny", "traffic": "tiny-mix",
         "chips": 1, "why": "CPU tests"})
    for m in b["end_to_end"] + b["per_layer"]:
        if "workloads" in m:
            m["workloads"].append("tiny.closed")
    (tmp / "BENCHMARK.json").write_text(json.dumps(b))
    return tmp
