"""Profiler trace -> device busy time, idle gaps, program and kernel time.

A trace here is three lists of events ``(name, start_ns, dur_ns)``:

* ``ops``: the device's "XLA Ops" line (every operation that ran, Pallas
  kernels included);
* ``modules``: the device's "XLA Modules" line (one event per run of a
  compiled program, named after its jitted function);
* ``spans``: the host's ``bench.*`` annotations (``bench/driver.py``),
  on the same clock.

:func:`load_xplane` reads them from the ``.xplane.pb`` that
``jax.profiler`` writes; :func:`load_json` from a compact JSON copy (the
test's recorded trace). The traced window runs from the first span's
start to the last span's end.
"""
from __future__ import annotations

import json
from pathlib import Path
from typing import Dict, Iterable, List, Sequence, Tuple

Event = Tuple[str, float, float]         # name, start_ns, dur_ns


def load_xplane(path, device: str = "/device:TPU:0") -> Dict[str, List]:
    from jax.profiler import ProfileData
    pd = ProfileData.from_file(str(path))
    out: Dict[str, List] = {"ops": [], "modules": [], "spans": []}
    found = []
    for plane in pd.planes:
        found.append(plane.name)
        if plane.name == device:
            for line in plane.lines:
                key = {"XLA Ops": "ops", "XLA Modules": "modules"}.get(
                    line.name)
                if key:
                    out[key] = [(e.name, e.start_ns, e.duration_ns)
                                for e in line.events]
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                out["spans"] += [(e.name, e.start_ns, e.duration_ns)
                                 for e in line.events
                                 if e.name.startswith("bench.")]
    if not out["ops"]:
        raise ValueError(f"no 'XLA Ops' line on {device} in {path} "
                         f"(planes: {found})")
    return out


def load_json(path) -> Dict[str, List]:
    d = json.loads(Path(path).read_text())
    return {k: [tuple(e) for e in d[k]] for k in ("ops", "modules", "spans")}


def save_json(trace: Dict[str, List], path) -> None:
    Path(path).write_text(json.dumps(
        {k: [list(e) for e in v] for k, v in trace.items()}))


def window(trace) -> Tuple[float, float]:
    spans = trace["spans"]
    if not spans:
        raise ValueError("no bench.* spans in the trace")
    return (min(s for _, s, _ in spans), max(s + d for _, s, d in spans))


def _merged(events: Iterable[Event], lo: float, hi: float):
    """Disjoint covered intervals of ``events`` clipped to [lo, hi]."""
    iv = sorted((max(s, lo), min(s + d, hi)) for _, s, d in events
                if s < hi and s + d > lo)
    out: List[List[float]] = []
    for a, b in iv:
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def busy_ns(events: Iterable[Event], lo: float, hi: float) -> float:
    """Length of the union of the events' intervals inside [lo, hi]."""
    return sum(b - a for a, b in _merged(events, lo, hi))


def idle_gaps(events: Iterable[Event], lo: float,
              hi: float) -> List[Tuple[float, float]]:
    """The intervals of [lo, hi] that no event covers."""
    gaps, t = [], lo
    for a, b in _merged(events, lo, hi):
        if a > t:
            gaps.append((t, a))
        t = max(t, b)
    if hi > t:
        gaps.append((t, hi))
    return gaps


def label(gap: Tuple[float, float], spans: Sequence[Event]) -> str:
    """The host span that overlaps the gap most ("no span" if none). A
    span nested in another wins where it covers more of the gap."""
    best, name = 0.0, "no span"
    for n, s, d in spans:
        ov = min(gap[1], s + d) - max(gap[0], s)
        if ov > best:
            best, name = ov, n
    return name


def by_name(events: Iterable[Event], lo: float = float("-inf"),
            hi: float = float("inf")) -> Dict[str, float]:
    """Total ns per event name, for events that start in [lo, hi)."""
    tot: Dict[str, float] = {}
    for n, s, d in events:
        if lo <= s < hi:
            tot[n] = tot.get(n, 0.0) + d
    return tot


def matching(events: Iterable[Event], patterns: Sequence[str],
             lo: float = float("-inf"),
             hi: float = float("inf")) -> Tuple[float, int]:
    """(total ns, count) of events whose name contains any pattern and
    that start in [lo, hi)."""
    tot, n = 0.0, 0
    for name, s, d in events:
        if lo <= s < hi and any(p in name for p in patterns):
            tot += d
            n += 1
    return tot, n


def summarize(trace, top: int = 10) -> Dict:
    """busy_s, window_s, idle_share and the breakdown of a trace."""
    lo, hi = window(trace)
    busy = busy_ns(trace["ops"], lo, hi)
    gaps = sorted(idle_gaps(trace["ops"], lo, hi),
                  key=lambda g: g[0] - g[1])
    ops = sorted(by_name(trace["ops"], lo, hi).items(),
                 key=lambda kv: -kv[1])
    idle_by: Dict[str, float] = {}
    for g in gaps:
        n = label(g, trace["spans"])
        idle_by[n] = idle_by.get(n, 0.0) + (g[1] - g[0])
    return {
        "lo": lo, "hi": hi,
        "busy_s": busy * 1e-9, "window_s": (hi - lo) * 1e-9,
        "idle_share": 1.0 - busy / (hi - lo),
        "device_ops": [[n, d * 1e-9] for n, d in ops[:top]],
        "idle_gaps": [[label(g, trace["spans"]), (g[1] - g[0]) * 1e-9]
                      for g in gaps[:top]],
        "idle_by_span": {n: d * 1e-9 for n, d in idle_by.items()},
    }
