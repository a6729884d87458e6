"""Leap sweep: prefetched pages that attention used over prefetches issued,
from the pool counters (``tiered_stats`` plus the engine's per-slot
``counter_base``) summed over every stream after the window."""


def read(run):
    issued = run.counters.get("prefetch_issued", 0)
    return 100.0 * run.counters.get("prefetch_hits", 0) / issued if issued else None
