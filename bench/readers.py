"""Arithmetic that the per-layer readers (``bench/metrics/*.py``) share:
device time of a program in the trace, and the FLOPs of the plain target
forward for the committed tokens."""
from __future__ import annotations

from typing import Optional, Sequence

from bench import trace_reduce


def program_ms_per(run, patterns: Sequence[str]) -> Optional[float]:
    """Device ms of the programs whose module name contains a pattern, in
    the traced window, per run of them."""
    if run.trace is None:
        return None
    lo, hi = run.summary["lo"], run.summary["hi"]
    ns, n = trace_reduce.matching(run.trace["modules"], patterns, lo, hi)
    return ns * 1e-6 / n if n else None


def target_flops(run, cycles) -> float:
    """FLOPs of the plain target forward for the tokens ``cycles``
    committed: per token, 2 x the multiplied parameters
    (``Arch.matmul_params``) plus QK^T and PV over the tokens its
    attention layers read at its context (``Arch.attended``)."""
    a = run.arch
    per_tok = 2.0 * a.matmul_params()
    attn = 4.0 * a.heads * a.head_dim
    return sum(n * (per_tok + attn * a.attended(ln))
               for c in cycles for n, ln in zip(c.n_out, c.lens))
