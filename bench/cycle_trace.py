"""The decode cycle in a device trace: device time by phase, the paged
cascade read against the least time its work needs, and the device's
idle time by the engine's host spans.

The phases are the program's ``jax.named_scope``s (``PHASES``); a trace
from ``bench/xplane.py`` carries each operation's name stack under
``scopes`` and the engine's ``engine.*`` host spans under
``program_spans``. A scope's device time is the union of the intervals of
the operations inside it, never their sum: the "XLA Ops" line nests a
``while`` over the operations of its body.

The verify's read is found by its kernels' names (``pallas_call(name=...)``
becomes the instruction's name), which the benchmark's own trace holds
too, so :func:`verify_read` needs no scopes: ``cascade_read_paged`` over
the page pool of a global layer, and ``cascade_read_dense`` over the
rolling buffer of a sliding-window layer (no dense decoder has one).
"""
from __future__ import annotations

import re
from typing import Dict, Iterable, List, Optional, Sequence, Tuple

from bench import model, trace_reduce
from bench.trace_reduce import Event

PHASES = ("d2sd.draft1", "d2sd.select", "d2sd.draft2", "d2sd.verify",
          "d2sd.commit")
DRAFT = PHASES[:3]
CYCLE_PROGRAM = "decode_cycle"
KERNEL = "cascade_read_paged"
DENSE_KERNEL = "cascade_read_dense"
READ_KERNELS = (KERNEL, DENSE_KERNEL)
TREE_NODES = model.GAMMA + model.TOP_K * (model.GAMMA - 1)

# "%cascade_read_paged.13 = (f32[B,Hq,splits,T,D]{...}, ...) custom-call("
_PARTIALS = re.compile(r"%([\w-]+?)(?:\.\d+)? = \(f32\[(\d+),(\d+),\d+,"
                       r"(\d+),(\d+)\]")


def _in_scope(stack: str, scopes: Sequence[str]) -> bool:
    s = "/" + stack
    return any(f"/{sc}/" in s for sc in scopes)


def scoped(trace, scopes: Sequence[str]) -> List[Event]:
    """The operations whose name stack holds any of ``scopes``."""
    return [e for e, st in zip(trace["ops"], trace["scopes"])
            if _in_scope(st, scopes)]


def cycles(trace, lo: float, hi: float) -> int:
    """Runs of the decode-cycle program that start in [lo, hi)."""
    return trace_reduce.matching(trace["modules"], (CYCLE_PROGRAM,),
                                 lo, hi)[1]


def phase_ms(trace, lo: float, hi: float) -> Optional[Dict[str, float]]:
    """Device ms per decode cycle of each phase, of the drafts together
    (``draft``), of the verify's cascade read (``kernel``), of the
    decode-cycle program (``cycle``) and of its time in no phase
    (``unattributed``). None without scopes or cycles."""
    n = cycles(trace, lo, hi)
    if "scopes" not in trace or not n:
        return None
    per = 1e-6 / n
    out = {p: trace_reduce.busy_ns(scoped(trace, (p,)), lo, hi) * per
           for p in PHASES}
    out["draft"] = trace_reduce.busy_ns(scoped(trace, DRAFT), lo, hi) * per
    kern = [e for e, st in zip(trace["ops"], trace["scopes"])
            if _kernel(e[0]) in READ_KERNELS
            and _in_scope(st, ("d2sd.verify",))]
    out["kernel"] = trace_reduce.busy_ns(kern, lo, hi) * per
    out["cycle"] = trace_reduce.matching(
        trace["modules"], (CYCLE_PROGRAM,), lo, hi)[0] * per
    out["unattributed"] = out["cycle"] - trace_reduce.busy_ns(
        scoped(trace, PHASES), lo, hi) * per
    return out


# ---------------------------------------------------- the paged read --
def _kernel(op_name: str) -> Optional[str]:
    m = _PARTIALS.match(op_name)
    return m.group(1) if m else None


def verify_read(ops: Iterable[Event], heads: int, lo: float,
                hi: float) -> Tuple[float, int]:
    """(device ns, calls) of the tree verify's cascade read in [lo, hi):
    the calls of :data:`READ_KERNELS`, one per attention layer and cycle,
    whose partials have the target's ``heads`` and the tree's node count
    (the drafters' calls of the same kernels read their own heads for 16
    or 64 block slots)."""
    ns, n = 0.0, 0
    for name, s, d in ops:
        m = _PARTIALS.match(name)
        if (m and m.group(1) in READ_KERNELS and lo <= s < hi
                and int(m.group(3)) == heads
                and int(m.group(4)) == TREE_NODES):
            ns += d
            n += 1
    return ns, n


def read_work(arch, lens: Sequence[int]) -> Tuple[float, float]:
    """(FLOPs, HBM bytes) that the tree verify's read of the live context
    needs in one cycle, whatever implements it: for each active row of
    context ``ctx``, over the ``arch.attended(ctx)`` tokens its attention
    layers read, QK^T and PV of the tree's nodes against each token,
    4 * Hq * D * T FLOPs, and one read of its bf16 key and value,
    2 * Hkv * D * 2 bytes."""
    att = float(sum(arch.attended(n) for n in lens))
    flops = 4.0 * arch.heads * arch.head_dim * TREE_NODES * att
    nbytes = 2.0 * arch.kv_heads * arch.head_dim * 2 * att
    return flops, nbytes


def read_least_s(arch, lens: Sequence[int], peak: Dict) -> float:
    """The least time of :func:`read_work` on a chip of ``peak``: its
    FLOPs at the bf16 peak or its bytes at the HBM peak, the longer."""
    flops, nbytes = read_work(arch, lens)
    return max(flops / peak["bf16_flops"], nbytes / peak["hbm_bytes_per_s"])


# ---------------------------------------------------- host gaps -------
def _nearest_lead(host: List[float], device: List[float]) -> List[float]:
    """For each host time, its lead over the nearest device time (pairs
    are unambiguous: cycles lie far further apart than the clocks)."""
    if not device:
        return []
    return [h - min(device, key=lambda d: abs(h - d)) for h in host]


def device_lag_ns(trace) -> Tuple[float, float]:
    """Bounds on how far the trace's device clock reads behind its host
    clock. No decode-cycle run starts before the ``engine.enqueue`` that
    dispatched it starts, so the lag is at least the largest lead of an
    enqueue's start over its run's start; no ``engine.readback`` of a
    cycle's tokens ends before the run ends, so it is at most the least
    lead of a read-back's end over its run's end. (0, inf) where nothing
    pairs."""
    runs = [(s, s + d) for n, s, d in trace["modules"]
            if CYCLE_PROGRAM in n]
    ps = trace["program_spans"]
    lo = _nearest_lead([s for n, s, _ in ps if n == "engine.enqueue"],
                       [s for s, _ in runs])
    hi = _nearest_lead([s + d for n, s, d in ps if n == "engine.readback"],
                       [e for _, e in runs])
    return max([0.0] + lo), min([float("inf")] + hi)


def on_host_clock(trace) -> Dict:
    """``trace`` with its device events moved onto the host's clock by the
    least lag :func:`device_lag_ns` allows, so that a device gap lines up
    with the host spans it fell in."""
    lag = device_lag_ns(trace)[0]
    out = dict(trace)
    for k in ("ops", "modules"):
        out[k] = [(n, s + lag, d) for n, s, d in trace[k]]
    return out


def _pieces(spans: Sequence[Event]):
    """The union of ``spans`` as sorted disjoint pieces (x0, x1, name), each
    named after the covering span that started last and, of those, ends
    first: the innermost, as spans nest."""
    pts = sorted({x for _, s, d in spans for x in (s, s + d)})
    out = []
    for x0, x1 in zip(pts, pts[1:]):
        inside = [(s, -(s + d), n) for n, s, d in spans
                  if s <= x0 and s + d >= x1]
        if inside:
            out.append((x0, x1, max(inside)[2]))
    return out


def _overlaps(gaps, pieces):
    """(name, ns) of each overlap of sorted disjoint ``gaps`` with the
    sorted disjoint ``pieces``."""
    i = 0
    for a, b in gaps:
        while i < len(pieces) and pieces[i][1] <= a:
            i += 1
        j = i
        while j < len(pieces) and pieces[j][0] < b:
            x0, x1, n = pieces[j]
            yield n, min(b, x1) - max(a, x0)
            j += 1


def idle_by_span(trace, lo: float, hi: float) -> Dict[str, float]:
    """Device-idle ns in [lo, hi) per innermost ``engine.*`` span; idle
    time in no such span is left out."""
    out: Dict[str, float] = {}
    gaps = trace_reduce.idle_gaps(trace["ops"], lo, hi)
    for n, ns in _overlaps(gaps, _pieces(trace["program_spans"])):
        out[n] = out.get(n, 0.0) + ns
    return out


def idle_covered_ns(trace, lo: float, hi: float) -> Tuple[float, float]:
    """(device-idle ns in [lo, hi), the part of it inside any span, the
    benchmark's or the program's)."""
    gaps = trace_reduce.idle_gaps(trace["ops"], lo, hi)
    spans = trace["spans"] + trace.get("program_spans", [])
    return (sum(b - a for a, b in gaps),
            sum(ns for _, ns in _overlaps(gaps, _pieces(spans))))
