"""Leap sweep: mean of the program's own ``tiered_sweep`` span (host clock,
synced on the sweep's ``info``) over the engine steps that swept."""


def read(run):
    s = run.sweep_samples
    return 1e3 * sum(s) / len(s) if s else None
