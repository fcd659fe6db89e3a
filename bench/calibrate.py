"""Readings that the correctness limits are set from (on the chip).

    python3 bench/calibrate.py --workload <cell> --seeds 1,2,... \
        --control-seeds 1,2,3 --seconds <s> [--out FILE]

In one process, for each seed: the cell's set-up and ``--seconds`` of its
traffic through the timed path, exactly as ``bench/run.py`` runs them,
then the run's check: the widest gap of the served tokens against the
reference over every request with tokens when the window closes; on the
control seeds also the widest gap of the tokens that the fp8 control
puts first at the same positions (the cell's reference module, as
``bench/spec.py`` resolves it). Prints one JSON line per seed.
"""
import argparse
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

from bench import harness, spec  # noqa: E402


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--control-seeds", default="")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    a = ap.parse_args()
    cell = spec.load_cell(ROOT, a.workload)
    harness.device_info(cell.chips, require_tpu=True)
    harness.enable_compile_cache(ROOT)
    ctl = {int(s) for s in a.control_seeds.split(",") if s}
    out = open(a.out, "a") if a.out else None
    for seed in [int(s) for s in a.seeds.split(",")]:
        t0 = time.perf_counter()
        b = harness.Bench(cell, seed)
        b.warm_up()
        b.start_traffic()
        b.driver.run_until(time.perf_counter() + a.seconds)
        b.release_engine()
        t1 = time.perf_counter()
        row = {"workload": a.workload, "seed": seed,
               **b.check(control=seed in ctl),
               "check_s": time.perf_counter() - t1,
               "s": time.perf_counter() - t0}
        print(json.dumps(row), flush=True)
        if out:
            out.write(json.dumps(row) + "\n")
            out.flush()
        del b


if __name__ == "__main__":
    main()
