"""Device: share of the traced window, in %, in which no op ran on the
chip while the host was inside a ``dispatch:<op>`` or ``transfer:*``
span (the innermost program span open on the harness's thread; see
``bench/span_trace.py``), in the cells that report query latency."""
from pathlib import Path

ROOT = Path(__file__).parents[2]


def read(rec):
    from bench import span_trace
    dev = rec["device"]
    if not dev or not dev.get("devices") or not rec["queries"]:
        return None
    planes = span_trace.for_run(rec, ROOT)
    if planes is None:
        return None
    idle = sum(s for name, s in span_trace.idle_by_span(planes)
               if name.startswith(("dispatch:", "transfer:")))
    return 100.0 * idle / dev["window_s"]
