"""The program's spans on the profiler's timeline, beside the device ops.

``trace_reduce.load`` keeps, of the host planes, only the harness's own
``bench.*`` annotations.  The program opens a profiler event for every
span it records while tracing is on, named under the prefix that
``repro.obs.trace.PROFILE_PREFIX`` defines; this module reads those too,
for the per-layer metrics that name device idle time, or bytes, by
program span.  ``load`` gives ``trace_reduce``'s plain data, with each
host line's program events under ``"spans"`` as ``(name, start_ns,
dur_ns, stats)`` (prefix removed), so ``trace_reduce.reduce`` reads the
same planes unchanged.

  idle_by_span  the window's idle time (device gaps, as ``reduce`` finds
                them), each piece named by the innermost program span
                open then on the thread that holds ``bench.window``, or
                ``(none)``
  stat_sum      one stat summed over one span's events on that thread,
                inside the window

A reader finds its run's trace with ``for_run``; a program that opens no
profiler events gives None there.
"""
from __future__ import annotations

import collections
import glob
import os
from pathlib import Path
from typing import Dict, List, Optional, Sequence, Tuple

from bench import program
from bench.trace_reduce import (OP_LINE, PREFIX, WINDOW, _union,
                                is_device_plane)

NONE = "(none)"
_loaded: Dict[Tuple[str, float], List[Dict]] = {}


def span_prefix() -> Optional[str]:
    """The program's prefix on the profiler's timeline; None for a
    program that puts no spans there."""
    program.import_program()
    from repro.obs import trace
    return getattr(trace, "PROFILE_PREFIX", None)


def load(path: str, prefix: str) -> List[Dict]:
    """``trace_reduce.load``'s planes, with every host line's program
    events under ``"spans"``."""
    from jax.profiler import ProfileData
    planes = []
    for plane in ProfileData.from_file(path).planes:
        device = is_device_plane(plane.name)
        lines = []
        for line in plane.lines:
            evs, spans = [], []
            for ev in line.events:
                name = ev.name
                if device or name.startswith(PREFIX):
                    evs.append((name, int(ev.start_ns), int(ev.duration_ns)))
                elif name.startswith(prefix):
                    spans.append((name[len(prefix):], int(ev.start_ns),
                                  int(ev.duration_ns), dict(ev.stats)))
            if evs or spans:
                lines.append({"name": line.name, "events": evs,
                              "spans": spans})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def window_line(planes: List[Dict]) -> Tuple[Dict, int, int]:
    """The host line that holds ``bench.window``, and the window."""
    for p in planes:
        if is_device_plane(p["name"]):
            continue
        for ln in p["lines"]:
            for n, s, d in ln["events"]:
                if n == WINDOW:
                    return ln, s, s + d
    raise ValueError(f"no {WINDOW!r} annotation in the trace")


def for_run(rec: Dict, root: Path) -> Optional[List[Dict]]:
    """The planes of the trace the harness reduced into ``rec["device"]``
    (the newest trace under ``<root>/.scratch/trace/`` whose window has
    that length), or None where there is none or it holds no program
    span on the window's thread."""
    dev = rec.get("device")
    prefix = span_prefix()
    if not dev or not dev.get("window_s") or prefix is None:
        return None
    paths = glob.glob(str(root / ".scratch" / "trace" / "*" / "plugins"
                          / "profile" / "*" / "*.xplane.pb"))
    for path in sorted(paths, key=os.path.getmtime, reverse=True):
        key = (path, os.path.getmtime(path))
        if key not in _loaded:
            _loaded[key] = load(path, prefix)
        planes = _loaded[key]
        try:
            line, w0, w1 = window_line(planes)
        except ValueError:
            continue
        if abs((w1 - w0) * 1e-9 - dev["window_s"]) < 1e-9:
            return planes if line.get("spans") else None
    return None


def _gaps(planes: List[Dict], w0: int, w1: int) -> List[Tuple[int, int]]:
    """Where no op ran on any device inside the window (as ``reduce``)."""
    busy = _union([(max(s, w0), min(s + d, w1)) for p in planes
                   if is_device_plane(p["name"]) for ln in p["lines"]
                   if ln["name"] == OP_LINE for _, s, d in ln["events"]
                   if min(s + d, w1) > max(s, w0)])
    gaps, cursor = [], w0
    for a, b in busy + [(w1, w1)]:
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    return gaps


def innermost(spans: Sequence[Tuple], w0: int, w1: int
              ) -> List[Tuple[int, int, str]]:
    """``[w0, w1)`` cut into pieces, each named by the innermost of the
    (nested) spans open in it, or ``(none)``."""
    clipped = sorted(((max(s, w0), min(s + d, w1), n)
                      for n, s, d, *_ in spans
                      if min(s + d, w1) > max(s, w0)),
                     key=lambda t: (t[0], -t[1]))
    pieces: List[Tuple[int, int, str]] = []
    stack: List[Tuple[int, int, str]] = []
    cur = w0

    def emit(upto: int) -> None:
        nonlocal cur
        if upto > cur:
            pieces.append((cur, upto, stack[-1][2] if stack else NONE))
            cur = upto

    for s, e, n in clipped:
        while stack and stack[-1][1] <= s:
            emit(stack[-1][1])
            stack.pop()
        emit(s)
        stack.append((s, e, n))
    while stack:
        emit(stack[-1][1])
        stack.pop()
    emit(w1)
    return pieces


def _meet(xs: Sequence[Tuple], ys: Sequence[Tuple[int, int]]
          ) -> List[Tuple[int, int, Tuple]]:
    """Where two sorted lists of disjoint intervals overlap: (lo, hi, x)
    for every piece of an x that lies in a y."""
    out = []
    j = 0
    for x in xs:
        a, b = x[0], x[1]
        while j < len(ys) and ys[j][1] <= a:
            j += 1
        k = j
        while k < len(ys) and ys[k][0] < b:
            lo, hi = max(a, ys[k][0]), min(b, ys[k][1])
            if hi > lo:
                out.append((lo, hi, x))
            k += 1
    return out


def idle_by_span(planes: List[Dict], under: Optional[str] = None
                 ) -> List[Tuple[str, float]]:
    """Idle seconds of the window by innermost program span on the
    window's thread (``(none)`` for time under none), largest first.
    With ``under``, only the idle time inside that thread's harness
    annotations whose names start with it (``bench.query.``)."""
    line, w0, w1 = window_line(planes)
    gaps = _gaps(planes, w0, w1)
    if under is not None:
        notes = _union([(s, s + d) for n, s, d in line["events"]
                        if n.startswith(under)])
        gaps = [(lo, hi) for lo, hi, _ in _meet(gaps, notes)]
    out: Dict[str, float] = collections.defaultdict(float)
    spans = line.get("spans", [])
    for lo, hi, piece in _meet(innermost(spans, w0, w1), gaps):
        out[piece[2]] += (hi - lo) * 1e-9
    return sorted(out.items(), key=lambda kv: -kv[1])


def stat_sum(planes: List[Dict], name: str, stat: str) -> float:
    """``stat`` summed over the window thread's ``name`` events that
    start inside the window."""
    line, w0, w1 = window_line(planes)
    return float(sum(st.get(stat, 0) for n, s, _, st in line.get("spans", [])
                     if n == name and w0 <= s < w1))
