"""Benchmark harness entry point — one section per paper table/figure.

    PYTHONPATH=src python -m benchmarks.run [--quick] [--only tableX]

Prints ``name,us_per_call,derived`` CSV blocks per section. Requires the
study artifacts (experiments/study) — run
``PYTHONPATH=src python -m repro.training.run_study`` first; falls back to
--quick-compatible behavior with a helpful error otherwise.
"""
from __future__ import annotations

import argparse
import sys
import time
import traceback
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))
sys.path.insert(0, str(Path(__file__).resolve().parents[1]))


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None)
    ap.add_argument("--suite", default=None,
                    help="run a single section by name (alias of --only), "
                         "e.g. --suite serving -> BENCH_serving.json")
    ap.add_argument("--skip-study", action="store_true",
                    help="only run benches that need no trained artifacts")
    args = ap.parse_args()
    args.only = args.only or args.suite
    from repro.launch.compile_cache import enable_compile_cache
    enable_compile_cache()

    from benchmarks import engine_bench, kernel_bench, serving_bench
    sections = [("kernels", lambda q: kernel_bench.run(q)),
                ("engine", lambda q: engine_bench.run(q)),
                ("serving", lambda q: serving_bench.run(q)),
                ("prefix", lambda q: serving_bench.run_prefix(q)),
                ("resident", lambda q: serving_bench.run_resident(q)),
                ("sla", lambda q: serving_bench.run_sla(q)),
                ("bytes", lambda q: serving_bench.run_bytes_model(q)),
                ("sharded", lambda q: serving_bench.run_sharded(q))]

    study_dir = Path(__file__).resolve().parents[1] / "experiments" / "study"
    if not args.skip_study:
        if not (study_dir / "meta.json").exists():
            print("!! study artifacts missing — run "
                  "`PYTHONPATH=src python -m repro.training.run_study` "
                  "first; running kernel section only.")
        else:
            from benchmarks import (fig2a_calibration, table1_gamma_scaling,
                                    table3_end_to_end, table5_naive_k,
                                    table6_dflash_second, table7_third_level)
            sections += [
                ("table1", lambda q: table1_gamma_scaling.run(q)),
                ("table3", lambda q: table3_end_to_end.run(
                    q, temps=(0.0,) if q else (0.0, 1.0))),
                ("table5", lambda q: table5_naive_k.run(q)),
                ("table6", lambda q: table6_dflash_second.run(q)),
                ("table7", lambda q: table7_third_level.run(q)),
                ("fig2a", lambda q: fig2a_calibration.run(q)),
            ]

    failed = []
    for name, fn in sections:
        if args.only and args.only != name:
            continue
        t0 = time.time()
        print(f"\n===== {name} =====")
        try:
            fn(args.quick)
        except Exception:  # a boundary: report, run the rest, fail at exit
            traceback.print_exc()
            print(f"SECTION FAILED: {name}")
            failed.append(name)
        print(f"===== {name} done ({time.time() - t0:.0f}s) =====")
    if failed:
        sys.exit(f"failed sections: {', '.join(failed)}")


if __name__ == "__main__":
    main()
