"""Kernels: the tree verify's cascade read (the ``cascade_read_paged``
kernel, and ``cascade_read_dense`` over a sliding-window layer's rolling
buffer) as a share of its roofline, in the traced window (%).

The least time is that of the work the read of the live context needs
(``cycle_trace.read_work``: the tree's nodes against the tokens each
active row's attention layers read, ``Arch.attended``, at the contexts
``bench/driver.py`` recorded for the traced cycles), whatever implements
it. The compute bound applies in both decode cells: the read does
Hq / Hkv x 76 FLOPs per byte (608 for qwen2.5-3b, 304 for the paper's
target), over the v5e's 240 at its peaks. Nothing is read from a program
whose kernels have other names."""
from bench import cycle_trace


def read(run):
    if run.trace is None:
        return None
    a = run.arch
    ns, calls = cycle_trace.verify_read(run.trace["ops"], a.heads,
                                        run.summary["lo"], run.summary["hi"])
    if not calls:
        return None
    n = calls / a.layers        # cycles: one call per attention layer
    last = run.cycles[-max(1, round(n)):]
    least = n * sum(cycle_trace.read_least_s(a, c.lens, run.peak)
                    for c in last) / len(last)
    return 100.0 * least / (ns * 1e-9)
