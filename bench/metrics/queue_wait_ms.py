"""Admission: median wait from a request's entry into the admission queue
to its admission into a slot, on the request's own host-clock stamps
(``t_enqueued_ns``, ``t_admitted_ns``), over the requests due in the
window. A request still queued at the window's close counts the time it
has waited by then, as ``ttft_p50_ms`` counts one with no first token.
Nothing is returned from a program whose requests carry no such stamps."""

import statistics


def read(run):
    stamped = [r for r in run.recs if getattr(r.req, "t_enqueued_ns", -1) >= 0]
    if not stamped:
        return None
    # the stamps are time.perf_counter_ns(), the clock ``due`` is read on
    # in seconds from the harness's origin; a request is pushed at or after
    # its due time, so the least stamp-minus-due is that origin in ns
    origin_ns = min(r.req.t_enqueued_ns - 1e9 * r.due for r in stamped)
    end = run.t_open + run.window_s
    close_ns = origin_ns + 1e9 * end
    waits = []
    for r in stamped:
        if not run.t_open <= r.due <= end:
            continue
        adm = r.req.t_admitted_ns
        waits.append(1e-6 * ((adm if adm >= 0 else close_ns) - r.req.t_enqueued_ns))
    return statistics.median(waits) if waits else None
