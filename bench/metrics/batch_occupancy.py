"""Scheduler: active row-cycles over all row-cycles in the window (%)."""


def read(run):
    rows = sum(c.rows for c in run.cycles)
    return 100.0 * sum(len(c.lens) for c in run.cycles) / rows if rows \
        else None
