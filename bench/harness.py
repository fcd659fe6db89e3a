"""One run of one cell: set-up, the measured window, the metrics and the
comparison that decides ``correct``.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up (counted in ``setup_s``): draw the weights on the device from the
seed through the cell's architecture module, build the engine with the
options of the cell's mix, warm every program shape the cell's traffic
reaches (``warm_up``), then bring the closed batch to its steady state:
every request queued, the first batch installed part-way through its
outputs (``bench/traffic.py``), one cycle run. The window then runs
``--seconds`` of the same loop. With
``--trace 1`` the last ``trace_s`` seconds of the window are traced and
the run reports its per-layer metrics instead of its end-to-end ones.

When the window closes, the tokens served so far to every request (the
rows still running and those that finished) are kept, the engine is
dropped, and the plain reference runs over each of them.

The architecture is the cell's (``spec.Cell.model`` and
``spec.Cell.reference``, resolved from the configuration file by
``bench/spec.py``): ``bench/model.py`` and ``bench/reference.py`` for a
dense decoder, ``bench/archs/<name>.py`` and
``bench/archs/<name>_reference.py`` for one the configuration names.
The architecture module provides ``Arch.from_file``,
``program_configs``, ``make_weights`` and ``bundle``; the reference
module provides ``tail_logits``. The comparison that decides ``correct``
(``reference.gaps``, the limits of the configuration file and the mix's
``check_tokens_min``) is the same for every architecture.
"""
from __future__ import annotations

import argparse
import gc
import json
import shutil
import sys
import tempfile
import time
from pathlib import Path
from typing import Dict, List, Optional

import jax
import numpy as np

from bench import model, reference, spec, traffic, trace_reduce
from bench.driver import Driver, PinnedEngine
from bench.peaks import peaks

PAGE = 64
WARM_NEW = 2            # tokens a warm-up request asks for
DISTINCT = 7919         # first prompt tokens step by this (coprime to V)
WARM_UID0 = 100_000     # warm-up requests' uids start here


def log(msg: str) -> None:
    print(msg, file=sys.stderr, flush=True)


class CompileCounter:
    """Compile events (tracing, lowering, compiling or loading a program
    from the persistent cache) seen through ``jax.monitoring``."""

    def __init__(self):
        self.n = 0
        self.s = 0.0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if event.startswith("/jax/core/compile/"):
            self.s += duration
            if event.endswith("backend_compile_duration"):
                self.n += 1


class GcTimer:
    """Collections of Python's garbage collector and the seconds they
    took, through ``gc.callbacks``."""

    def __init__(self):
        self.n, self.s, self._t0 = 0, 0.0, None
        gc.callbacks.append(self._on)

    def _on(self, phase, info):
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.s += time.perf_counter() - self._t0
            self.n += 1
            self._t0 = None


def enable_compile_cache(root: Path) -> str:
    """JAX's persistent cache in ``.jax_cache/`` at the checkout's root, a
    fixed path, whatever the environment names: two checkouts share no
    compiled program. Every program is kept."""
    d = root / ".jax_cache"
    d.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(d))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return str(d)


def bucket(n: int, ladder) -> int:
    """The install bucket of a prefill of ``n`` tokens (the engine's rule)."""
    for b in ladder:
        if b >= n:
            return b
    top = ladder[-1]
    return -(-n // top) * top


def pads(lo: int, hi: int, ladder) -> List[int]:
    return sorted({bucket(n, ladder) for n in range(lo, hi + 1)})


def distinct_prompt(prompt: np.ndarray, uid: int,
                    vocab: int) -> np.ndarray:
    """Give the first prompt token a value no other uid's has, so that no
    two requests share a prefix."""
    p = prompt.copy()
    p[0] = (uid * DISTINCT + 1) % vocab
    return p


class Bench:
    """The served model, the engine and the traffic of one cell and seed."""

    def __init__(self, cell: spec.Cell, seed: int):
        self.seed, self.mix = seed, cell.mix
        arch_mod = cell.model
        self.tail_logits = cell.reference.tail_logits
        self.arch = arch_mod.Arch.from_file(cell.config_file)
        conf = json.loads(cell.config_file.read_text())
        self.limits = conf["bench"].get("limits", {})
        _, dcfg, _ = arch_mod.program_configs(self.arch)
        self.w, d1, d2 = arch_mod.make_weights(seed, self.arch, dcfg)
        self.bundle = arch_mod.bundle(self.arch, self.w, d1, d2)
        mix = self.mix
        self.ladder = tuple(mix["buckets"])
        self.eng = PinnedEngine(
            self.bundle, batch_size=mix["batch"], cache_impl="paged",
            page_size=PAGE, prefix_cache=False,
            bucket_sizes=self.ladder, pool_pages=mix["pool_pages"],
            largest=(traffic.max_prompt(mix), traffic.max_new(mix)))
        self.reqs = traffic.generate(mix, seed, self.arch.vocab,
                                     mix["requests"])
        for r in self.reqs:
            r.prompt = distinct_prompt(r.prompt, r.uid, self.arch.vocab)
        self.driver: Optional[Driver] = None
        self.checked: List = []

    # ------------------------------------------------------------ set-up --
    def _drain(self, prompts: List[np.ndarray]) -> None:
        """Serve ``prompts`` (WARM_NEW tokens each) as one wave."""
        eng = self.eng
        for p in prompts:
            eng.submit(p, WARM_NEW)
        while eng.queue or eng.wave is not None:
            if eng.wave is None:
                eng.start_wave(width=eng.batch_size)
            while eng.wave is not None and eng.step():
                pass

    def _pages(self, n: int) -> int:
        g = model.GAMMA
        return -(-(n + WARM_NEW + 2 * g + 8) // PAGE)

    def _waves(self, prompts: List[np.ndarray]) -> None:
        """Serve ``prompts``, packed into waves by batch rows and pool
        pages."""
        wave: List[np.ndarray] = []
        pages = 0
        for p in prompts:
            need = self._pages(len(p))
            if wave and (len(wave) == self.mix["batch"]
                         or pages + need > self.mix["pool_pages"]):
                self._drain(wave)
                wave, pages = [], 0
            wave.append(p)
            pages += need
        if wave:
            self._drain(wave)

    def warm_up(self) -> None:
        """Run every program the cell's traffic reaches: the decode cycle
        at the pinned geometry, and the install at each bucket that a
        prompt can fall in, the steady-state starts' longer prompts
        included. Each warm prompt is the longest the traffic can send in
        its bucket, so that no warm request needs a wider page table than
        the pinned one (a wider table is another geometry: every program
        warmed under it would compile again for the traffic)."""
        mix, v = self.mix, self.arch.vocab
        rng = np.random.default_rng([int(self.seed) & (2**64 - 1), 3])
        lo, hi = mix["prompt"]["min"], traffic.max_start_prompt(mix)
        self._waves([distinct_prompt(rng.integers(0, v, min(pad, hi),
                                                  np.int32),
                                     WARM_UID0 + i, v)
                     for i, pad in enumerate(pads(lo, hi, self.ladder))])

    def start_traffic(self) -> None:
        """Bring the closed batch to its steady state: queue every
        request, install the first ``batch`` (each part-way through its
        output, see ``bench/traffic.py``) and run one cycle."""
        d = self.driver = Driver(self.eng, self.reqs, time.perf_counter())
        d.step()                            # the wave: the first batch
        d.step()                            # its first cycle

    # ------------------------------------------------------------ window --
    def window(self, seconds: float, trace_dir: Optional[str]) -> Dict:
        """Run the window; returns the host record of it."""
        d, eng = self.driver, self.eng
        trace_s = min(float(self.mix["trace_s"]), seconds)
        t_open = time.perf_counter()
        c0 = d.open_window()
        st0 = dict(eng.stats)
        t_close = t_open + seconds
        if trace_dir is None:
            d.run_until(t_close)
        else:
            d.run_until(t_close - trace_s)
            jax.profiler.start_trace(trace_dir)
            d.run_until(t_close)
            jax.block_until_ready(eng.wave.state if eng.wave else 0)
            jax.profiler.stop_trace()
        t_end = time.perf_counter()
        return {"t_open": t_open, "t_end": t_end, "c0": c0,
                "stats0": st0, "stats1": dict(eng.stats),
                "tokens": d.tokens_since_open()}

    # ------------------------------------------------------------- check --
    def release_engine(self) -> None:
        """Keep what was served by every request that has tokens on the
        host (every running row and every finished request), then drop
        the engine and the drafters."""
        self.checked = [(t.req.prompt, s) for t, s in self.driver.served()
                        if len(s)]
        self.driver.eng = None
        self.eng = None
        self.bundle = None
        gc.collect()

    def check(self, control: bool = False) -> Dict:
        """Run the cell's plain reference over every kept request (its
        prompt and served tokens); the widest gap of the served tokens, the
        number checked and, with ``control``, the widest gap of the tokens
        the fp8 control puts first at the same positions."""
        res = [reference.gaps(self.tail_logits, self.w, self.arch, p, s,
                              t_len=reference.seq_len(len(p) + len(s)),
                              n_out=reference.out_len(len(s)),
                              control=control)
               for p, s in self.checked]
        out = {
            "widest_gap": max((float(g.max()) for g, _ in res),
                              default=np.inf),
            "tokens_checked": sum(len(g) for g, _ in res),
            "requests_checked": len(res),
            "finite": all(np.isfinite(g).all() for g, _ in res)}
        if control:
            out["control_widest_gap"] = max((float(c.max()) for _, c in res),
                                            default=np.inf)
        return out


# ------------------------------------------------------------ metrics --
def alpha(cycles) -> float:
    """Tokens committed per active row-cycle (1 = only the bonus token)."""
    rows = sum(len(c.lens) for c in cycles)
    return sum(sum(c.n_out) for c in cycles) / rows if rows else 0.0


def end_to_end(rec: Dict, setup_s: float) -> Dict:
    """The cell's end-to-end metrics from the window's host record."""
    return {"setup_s": setup_s,
            "tokens_per_s": rec["tokens"] / (rec["t_end"] - rec["t_open"])}


class RunRecord:
    """What a per-layer reader (``bench/metrics/<name>.py``) reads."""

    def __init__(self, b: Bench, rec: Dict, trace: Optional[Dict],
                 summary: Optional[Dict], peak: Dict):
        d = b.driver
        self.arch, self.peak = b.arch, peak
        self.t_open, self.t_end = rec["t_open"], rec["t_end"]
        self.window_s = self.t_end - self.t_open
        self.cycles = d.cycles[rec["c0"]:]
        self.trace, self.summary = trace, summary


def per_layer(root: Path, cell: spec.Cell, run: RunRecord) -> Dict:
    out = {}
    for m in cell.per_layer:
        v = spec.reader(root, m["name"])(run)
        if v is not None:
            out[m["name"]] = {"value": float(v), "unit": m["unit"]}
    return out


def read_trace(trace_dir: str):
    paths = sorted(Path(trace_dir).rglob("*.xplane.pb"))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return trace_reduce.load_xplane(paths[-1])


# --------------------------------------------------------------- main --
def parse(argv):
    ap = argparse.ArgumentParser(description="run one benchmark cell")
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def device_info(chips: int, require_tpu: bool):
    devs = jax.devices()
    if require_tpu and devs[0].platform != "tpu":
        raise SystemExit(f"bench: JAX found no TPU (platform "
                         f"{devs[0].platform!r}); no result")
    if len(devs) < chips:
        raise SystemExit(f"bench: the cell needs {chips} chips, JAX sees "
                         f"{len(devs)}; no result")
    return devs[0], len(devs)


def window_log(b: Bench, rec: Dict, n_compiles: int, s_compiles: float,
               n_gc: int, s_gc: float):
    """The window's working point, on stderr: cycles, tokens, compiles,
    acceptance, the mean context of an active row, installs, the host's
    time per cycle (median and longest) and its garbage collections."""
    cyc = b.driver.cycles[rec["c0"]:]
    lens = [n for c in cyc for n in c.lens]
    dts = sorted(c.t1 - c.t0 for c in cyc) or [0.0]
    log(f"window: {rec['t_end'] - rec['t_open']:.3f}s cycles={len(cyc)} "
        f"tokens={rec['tokens']} compiles_in_window={n_compiles} "
        f"compile_s_in_window={s_compiles:.3f} alpha={alpha(cyc):.4f} "
        f"mean_context={np.mean(lens) if lens else 0:.1f} installs="
        f"{rec['stats1']['installs'] - rec['stats0']['installs']} "
        f"cycle_ms_median={dts[len(dts) // 2] * 1e3:.2f} "
        f"cycle_ms_max={dts[-1] * 1e3:.2f} gc_in_window={n_gc} "
        f"gc_s_in_window={s_gc:.3f}")


def verdict(b: Bench, got: Dict):
    """(``correct``, the numbers compared, each beside its limit) of a
    check (:meth:`Bench.check`): the widest gap against the configuration
    file's limit and the tokens checked against the mix's least number;
    the same for every architecture."""
    checks = {
        "widest_gap": {"value": got["widest_gap"],
                       "limit": b.limits.get("widest_gap", 0.0)},
        "tokens_checked": {"value": got["tokens_checked"],
                           "limit": b.mix["check_tokens_min"]},
    }
    correct = bool(got["finite"]
                   and checks["widest_gap"]["value"]
                   <= checks["widest_gap"]["limit"]
                   and checks["tokens_checked"]["value"]
                   >= checks["tokens_checked"]["limit"])
    return correct, checks


def main(argv, t_proc0: float, root: Path, require_tpu: bool = True,
         fault=None) -> Dict:
    """Run one cell; prints the checks on stderr and returns the result
    line (also printed as the last line of stdout). ``fault`` (tests
    only) is called with the engine before the window, to break it."""
    args = parse(argv)
    cell = spec.load_cell(root, args.workload)
    dev, count = device_info(cell.chips, require_tpu)
    peak = peaks(dev.device_kind) if require_tpu else peaks("TPU v5 lite")
    log(f"device: {dev.platform} {dev.device_kind} x{count}; "
        f"compile cache {enable_compile_cache(root)}")
    cc, gt = CompileCounter(), GcTimer()

    b = Bench(cell, args.seed)
    b.warm_up()
    if fault is not None:
        fault(b.eng)
    n_warm = cc.n
    b.start_traffic()
    jax.block_until_ready(b.eng.wave.state if b.eng.wave else 0)
    gc.collect()
    gc.freeze()         # set-up's objects: no later collection walks them
    setup_s = time.perf_counter() - t_proc0
    n0, s0, g0, gs0 = cc.n, cc.s, gt.n, gt.s
    log(f"setup_s={setup_s:.3f} compile_s={s0:.3f} compiles={n0} "
        f"compiles_in_start_traffic={n0 - n_warm}")

    tdir = None
    if args.trace:
        (root / ".bench_traces").mkdir(exist_ok=True)
        tdir = tempfile.mkdtemp(prefix="trace_", dir=root / ".bench_traces")
    jax.config.update("jax_log_compiles", True)     # names any compile
    rec = b.window(args.seconds, tdir)
    jax.config.update("jax_log_compiles", False)
    gc.unfreeze()
    window_log(b, rec, cc.n - n0, cc.s - s0, gt.n - g0, gt.s - gs0)
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use", 0)

    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": count, "memory_peak_bytes": int(mem)}
    breakdown = None
    if args.trace:
        t0 = time.perf_counter()
        trace = read_trace(tdir)
        shutil.rmtree(tdir, ignore_errors=True)
        summary = trace_reduce.summarize(trace)
        lo, hi = summary["lo"], summary["hi"]
        progs = sorted(trace_reduce.by_name(trace["modules"], lo, hi).items(),
                       key=lambda kv: -kv[1])[:8]
        log(f"trace: read in {time.perf_counter() - t0:.3f}s; busy_s="
            f"{summary['busy_s']} window_s={summary['window_s']} idle by "
            f"span {json.dumps(summary['idle_by_span'])}; programs (s) "
            f"{json.dumps([[n, d * 1e-9] for n, d in progs])}")
        device.update(busy_s=summary["busy_s"], window_s=summary["window_s"])
        breakdown = {"device_ops": summary["device_ops"],
                     "idle_gaps": summary["idle_gaps"]}
        metrics = per_layer(root, cell, RunRecord(b, rec, trace, summary,
                                                  peak))
    else:
        e2e = end_to_end(rec, setup_s)
        metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                   for m in cell.end_to_end}
    attempted = sum(1 for t in b.driver.tracks if t.n > t.n_open)

    # ---- correctness: after the window, with the engine's state freed ----
    b.release_engine()
    t0 = time.perf_counter()
    got = b.check()
    log(f"reference: {got['requests_checked']} requests, "
        f"{got['tokens_checked']} tokens in {time.perf_counter() - t0:.3f}s")
    correct, checks = verdict(b, got)
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    result = {"correct": correct, "attempted": attempted, "failed": 0,
              "metrics": metrics, "device": device}
    if breakdown is not None:
        result["breakdown"] = breakdown
    result["checks"] = checks
    print(json.dumps(result), flush=True)
    return result
