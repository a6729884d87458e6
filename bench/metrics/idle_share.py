"""Device: 1 - (union of the intervals in which an operation ran on the
device) / the traced window, averaged over the chips the cell uses."""


def read(run):
    s = run.trace.idle_share if run.trace is not None else None
    return None if s is None else 100.0 * s
