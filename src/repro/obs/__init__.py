"""Observability: page-lifecycle tracing + unified telemetry (DESIGN.md §8).

* :mod:`repro.obs.trace`   — event schema, info-array decoders (both data
  planes), lock-step twin recorder.
* :mod:`repro.obs.export`  — Chrome trace-event (Perfetto) JSON + JSONL.
* :mod:`repro.obs.diff`    — first-divergent-event trace differ.
* :mod:`repro.obs.metrics` — counter/histogram registry, the unified
  percentile ladder, span timers on the profiler's clock.
"""

from .diff import (Divergence, assert_traces_equal, diff_report,
                   first_divergence)
from .export import (read_jsonl, read_request_jsonl, to_chrome_trace,
                     write_chrome_trace, write_jsonl, write_request_jsonl)
from .metrics import Registry, percentile_ladder
from .trace import (AGGREGATE_KINDS, DEMAND_KINDS, KINDS, REQUEST_PHASES,
                    SUMMARY_KINDS, Event, RequestPhase, TraceRecorder,
                    decode_stream_events, decode_sweep_events,
                    events_to_counts, home_of_host, summary_events)

__all__ = [
    "AGGREGATE_KINDS", "DEMAND_KINDS", "Divergence", "Event", "KINDS",
    "REQUEST_PHASES", "Registry", "RequestPhase", "SUMMARY_KINDS",
    "TraceRecorder", "assert_traces_equal",
    "decode_stream_events", "decode_sweep_events",
    "diff_report", "events_to_counts", "first_divergence", "home_of_host",
    "percentile_ladder", "read_jsonl", "read_request_jsonl",
    "summary_events", "to_chrome_trace",
    "write_chrome_trace", "write_jsonl", "write_request_jsonl",
]
