"""Device: 1 - (union of device operations) / traced window (%)."""


def read(run):
    if run.summary is None:
        return None
    return 100.0 * run.summary["idle_share"]
