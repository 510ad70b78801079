"""Kernel dispatch layer (kernels/ops.py): operand bytes uploaded to the
device in the window per query, in MB (1e6 bytes).

The program counts them in ``KernelStats.bytes_to_device`` and stamps
each increment on its ``transfer:to_device`` profiler event (the
``bytes`` stat); this reads the window's increments on the harness's
thread from the run's trace (``bench/span_trace.py``), since the
harness's counter snapshot does not carry the counter."""
from pathlib import Path

ROOT = Path(__file__).parents[2]


def read(rec):
    from bench import span_trace
    if not rec["queries"]:
        return None
    planes = span_trace.for_run(rec, ROOT)
    if planes is None:
        return None
    up = span_trace.stat_sum(planes, "transfer:to_device", "bytes")
    return up / rec["queries"] / 1e6
