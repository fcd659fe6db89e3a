"""Run one benchmark cell on the chip this process finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The last line of standard output is the result, one JSON object; the
numbers that decided ``correct`` are the last lines of standard error.
Exits non-zero with no result when JAX finds no TPU, or fewer chips than
the cell asks for.
"""
import sys
import time

T_PROC0 = time.perf_counter()

from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness  # noqa: E402

if __name__ == "__main__":
    harness.main(sys.argv[1:], T_PROC0, ROOT)
