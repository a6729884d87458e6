"""Attention consumer: the least time the K/V bytes of the decoding rows'
lengths need at the chip's HBM bandwidth (``bench/work.py``), over the
device time of the attention programs in the trace, per call.

The programs, read off a v5e trace by name: ``paged_attention`` (the flat
and stacked-pool paths, reference or kernel) and ``paged_attention_hot_slots``
(the fused hot-slot kernels). The engine calls one for the tiered read and
one for the flat-pool pin each step; the count of executions in the trace
says how many calls a step makes.
"""

PROGRAMS = (r"jit_paged_attention", r"jit_paged_attention_hot_slots")


def read(run):
    if run.trace is None or run.peak is None:
        return None
    secs, calls = run.trace.program_time(PROGRAMS)
    steps = [s for s in run.steps if s.decode_lengths
             and s.t0 >= run.trace_t0 and s.t1 <= run.trace_t1]
    if secs <= 0 or not steps or calls <= 0:
        return None
    per_step = calls / len(steps)
    item = 2 if run.cell.dtype in ("bfloat16", "float16") else 4
    w, hq, hkv, dh = run.work, run.cell.hq, run.cell.hkv, run.cell.dh
    least = 0.0
    for s in steps:
        t, _ = w.roofline_seconds(w.decode_attention_flops(hq, dh, s.decode_lengths),
                                  w.kv_bytes(hkv, dh, item, s.decode_lengths), run.peak)
        least += t * per_step
    return 100.0 * least / secs
