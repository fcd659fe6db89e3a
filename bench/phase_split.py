"""Split one cell's decode cycle by phase, on the chip this process finds.

    python3 bench/phase_split.py --workload <cell> --seed <n> --seconds <s>

Set-up and window are those of ``bench/run.py`` (the last ``trace_s``
seconds of the window traced), with the trace read by ``bench/xplane.py``
so that the program's scopes and host spans are in it, and its device
events moved onto the host's clock by the least lag causality allows
(``cycle_trace.on_host_clock``; ``device_lag_ms`` holds that lag and the
largest). The last line of standard output is one JSON object:

* ``phase_ms``: device ms per decode cycle of each ``d2sd.*`` phase, of the
  drafts together, of the verify's paged read (``kernel``), of the whole
  cycle program and of its time in no phase (``cycle_trace.phase_ms``);
* ``readings``: what the trace gives for ``draft_device_ms``,
  ``verify_device_ms``, ``cascade_read_roofline`` and ``host_gap_ms``
  (device-idle ms per cycle inside ``engine.*`` spans);
* ``idle_ms_by_engine_span``: device-idle ms per cycle by the innermost
  ``engine.*`` span, and ``idle_in_any_span`` the share of the idle time
  inside any span, the benchmark's or the program's;
* ``tokens_per_s``: of the untraced and of the traced part of the window
  (what tracing costs);
* ``host_ms_per_cycle``: the median host ms per cycle of each ``engine.*``
  span over the window, from ``ServingEngine.span_s`` (no trace needed);
* ``slowest_cycle``: its index in the window, its host ms, its host ms per
  ``engine.*`` span and whether an install fell in it.

It checks nothing: ``bench/run.py`` decides ``correct``.
"""
import sys
import time

T_PROC0 = time.perf_counter()

import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import statistics  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402

from bench import cycle_trace, harness, spec, trace_reduce, xplane  # noqa
from bench.driver import Driver  # noqa: E402
from bench.peaks import peaks  # noqa: E402


class SpanDriver(Driver):
    """The benchmark's loop, keeping the engine's host seconds per span of
    each step that ran a cycle (``spans[i]`` for ``cycles[i]``)."""

    def __init__(self, *args):
        super().__init__(*args)
        self.spans = []

    def step(self) -> None:
        acc = getattr(self.eng, "span_s", {})
        s0, n0 = dict(acc), len(self.cycles)
        super().step()
        if len(self.cycles) > n0:
            self.spans.append({k: v - s0.get(k, 0.0) for k, v in acc.items()
                               if v > s0.get(k, 0.0)})


def _rate(cyc) -> float:
    if not cyc:
        return 0.0
    return sum(sum(c.n_out) for c in cyc) / (cyc[-1].t1 - cyc[0].t0)


def split(b: harness.Bench, rec, trace, peak, t_trace: float):
    lag, lag_hi = cycle_trace.device_lag_ns(trace)
    trace = cycle_trace.on_host_clock(trace)
    summary = trace_reduce.summarize(trace)
    lo, hi = summary["lo"], summary["hi"]
    run = harness.RunRecord(b, rec, trace, summary, peak)
    d = b.driver
    c0 = rec["c0"]
    n = cycle_trace.cycles(trace, lo, hi)
    ph = cycle_trace.phase_ms(trace, lo, hi)
    idle = cycle_trace.idle_by_span(trace, lo, hi)
    idle_ns, inside_ns = cycle_trace.idle_covered_ns(trace, lo, hi)
    roof = spec.reader(ROOT, "cascade_read_roofline.decode")(run)
    cyc = d.cycles[c0:]
    spans = d.spans[c0:]
    slow = max(range(len(cyc)), key=lambda i: cyc[i].t1 - cyc[i].t0)
    names = sorted({k for s in spans for k in s})
    return {
        "cycles_traced": n,
        "device_lag_ms": [lag * 1e-6, lag_hi * 1e-6],
        "program_spans": len(trace["program_spans"]),
        "phase_ms": ph,
        "readings": {
            "draft_device_ms": ph and ph["draft"],
            "verify_device_ms": ph and ph["d2sd.verify"],
            "cascade_read_roofline": roof,
            "host_gap_ms": (sum(idle.values()) * 1e-6 / n
                            if n and trace["program_spans"] else None)},
        "idle_ms_by_engine_span": {k: v * 1e-6 / n for k, v in
                                   sorted(idle.items())} if n else {},
        "idle_ms_per_cycle": idle_ns * 1e-6 / n if n else None,
        "idle_in_any_span": inside_ns / idle_ns if idle_ns else None,
        "busy_s": summary["busy_s"], "window_s": summary["window_s"],
        "tokens_per_s": {
            "untraced": _rate([c for c in cyc if c.t0 < t_trace]),
            "traced": _rate([c for c in cyc if c.t0 >= t_trace])},
        "host_ms_per_cycle": {
            k: statistics.median(s.get(k, 0.0) for s in spans) * 1e3
            for k in names},
        "slowest_cycle": {
            "index": slow, "host_ms": (cyc[slow].t1 - cyc[slow].t0) * 1e3,
            "median_host_ms": statistics.median(
                c.t1 - c.t0 for c in cyc) * 1e3,
            "span_ms": {k: v * 1e3 for k, v in spans[slow].items()},
            "install": "engine.install" in spans[slow]},
    }


def main(argv) -> None:
    args = harness.parse(argv)
    cell = spec.load_cell(ROOT, args.workload)
    dev, _ = harness.device_info(cell.chips, require_tpu=True)
    peak = peaks(dev.device_kind)
    harness.enable_compile_cache(ROOT)
    b = harness.Bench(cell, args.seed)
    b.warm_up()
    b.driver = SpanDriver(b.eng, b.reqs, time.perf_counter())
    b.driver.step()                     # as Bench.start_traffic
    b.driver.step()
    jax.block_until_ready(b.eng.wave.state if b.eng.wave else 0)
    gc.collect()
    gc.freeze()
    harness.log(f"setup_s={time.perf_counter() - T_PROC0:.3f}")
    (ROOT / ".bench_traces").mkdir(exist_ok=True)
    tdir = tempfile.mkdtemp(prefix="split_", dir=ROOT / ".bench_traces")
    rec = b.window(args.seconds, tdir)
    gc.unfreeze()
    paths = sorted(Path(tdir).rglob("*.xplane.pb"))
    trace = xplane.load(paths[-1])
    shutil.rmtree(tdir, ignore_errors=True)
    trace_s = min(float(b.mix["trace_s"]), args.seconds)
    # Bench.window starts the trace once the window has trace_s to run
    out = split(b, rec, trace, peak, rec["t_open"] + args.seconds - trace_s)
    out.update(workload=args.workload, seed=args.seed)
    print(json.dumps(out), flush=True)


if __name__ == "__main__":
    main(sys.argv[1:])
