"""Kernel dispatch layer (kernels/ops.py): the share of the window's
device fused scans that found their packed column already on the device.

The program stamps ``resident_hits`` and ``resident_lookups`` on each
``dispatch:fused_scan_topk`` profiler event (1 and 1 where the column's
device copy was there, 0 and 1 where the dispatch uploaded it); this
sums both over the window on the harness's thread from the run's trace
(``bench/span_trace.py``).  A program that stamps neither reads
nothing."""
from pathlib import Path

ROOT = Path(__file__).parents[2]
SPAN = "dispatch:fused_scan_topk"


def read(rec):
    from bench import span_trace
    planes = span_trace.for_run(rec, ROOT)
    if planes is None:
        return None
    lookups = span_trace.stat_sum(planes, SPAN, "resident_lookups")
    if not lookups:
        return None
    return span_trace.stat_sum(planes, SPAN, "resident_hits") / lookups
