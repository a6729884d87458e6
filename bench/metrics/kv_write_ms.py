"""K/V write: host time of the program's own ``engine.kv_write`` spans (the
per-slot page lookup and scatter into the cold pool) and
``engine.invalidate`` spans (the hot tiers' invalidation of the pages
written), clipped to the traced window, per engine step traced. Nothing
is read, and nothing returned, from a program that opens neither span."""

SPANS = ("engine.kv_write", "engine.invalidate")


def read(run):
    tr = run.trace
    if tr is None or not tr.steps:
        return None
    spans = [(max(a, tr.lo), min(b, tr.hi)) for a, b, name in tr.host
             if name in SPANS and b > tr.lo and a < tr.hi]
    if not spans:
        return None
    return 1e-6 * sum(b - a for a, b in spans) / tr.steps
