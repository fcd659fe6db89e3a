"""Cache manager: mean share of the KV page pool in use at each decode
cycle of the window (%)."""


def read(run):
    if not run.cycles:
        return None
    return 100.0 * sum(c.pool_use for c in run.cycles) / len(run.cycles)
