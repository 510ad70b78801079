"""Reduce a JAX profiler trace (``.xplane.pb``) to the device numbers the
benchmark reports.

The trace is first read into plain data, ``[{"name": plane, "lines":
[{"name": line, "events": [(name, start_ns, dur_ns), ...]}]}]``, so the
reduction can be checked on a small recorded trace without a chip.

  busy_s      union of the intervals in which an op ran on a device, inside
              the harness's ``bench.window`` annotation, averaged over the
              devices that ran anything
  window_s    length of that annotation
  device_ops  device seconds per op name (summed over devices)
  idle_gaps   the gaps between device ops inside the window, cut at the
              harness annotations (``bench.<op>``) around the host's calls
              and named by them, or ``harness`` for time under none
"""
from __future__ import annotations

import bisect
import collections
import glob
import os
from typing import Dict, List, Optional, Sequence, Tuple

WINDOW = "bench.window"
PREFIX = "bench."
# the line of a device plane whose events are the ops that ran on it
OP_LINE = "XLA Ops"


def find_xplane(log_dir: str) -> Optional[str]:
    found = sorted(glob.glob(os.path.join(log_dir, "plugins", "profile",
                                          "*", "*.xplane.pb")))
    return found[-1] if found else None


def is_device_plane(name: str) -> bool:
    return name.startswith("/device:") and "CPU" not in name


def load(path: str) -> List[Dict]:
    """Planes of the trace as plain data: every device event, and only
    the harness's own annotations from the host planes."""
    from jax.profiler import ProfileData
    data = ProfileData.from_file(path)
    planes = []
    for plane in data.planes:
        device = is_device_plane(plane.name)
        lines = []
        for line in plane.lines:
            evs = [(ev.name, int(ev.start_ns), int(ev.duration_ns))
                   for ev in line.events
                   if device or ev.name.startswith(PREFIX)]
            if evs:
                lines.append({"name": line.name, "events": evs})
        planes.append({"name": plane.name, "lines": lines})
    return planes


def op_name(event: str) -> str:
    """An op event's HLO instruction name (``%fused_scan_topk.1``), without
    the shapes and operands the trace spells out after it."""
    return event.split(" = ", 1)[0]


def _union(intervals: Sequence[Tuple[int, int]]) -> List[Tuple[int, int]]:
    out: List[List[int]] = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return [(a, b) for a, b in out]


def reduce(planes: List[Dict], top: int = 10) -> Dict:
    host = [(n, s, s + d) for p in planes if not is_device_plane(p["name"])
            for ln in p["lines"] for n, s, d in ln["events"]]
    windows = [(s, e) for n, s, e in host if n == WINDOW]
    if not windows:
        raise ValueError(f"no {WINDOW!r} annotation in the trace")
    w0, w1 = windows[0]
    ops_time: Dict[str, float] = collections.defaultdict(float)
    busy_per_device = []
    merged: List[Tuple[int, int]] = []
    for p in planes:
        if not is_device_plane(p["name"]):
            continue
        ivs = []
        for ln in p["lines"]:
            if ln["name"] != OP_LINE:
                continue
            for n, s, d in ln["events"]:
                a, b = max(s, w0), min(s + d, w1)
                if b > a:
                    ivs.append((a, b))
                    ops_time[op_name(n)] += (b - a) * 1e-9
        if ivs:
            u = _union(ivs)
            busy_per_device.append(sum(b - a for a, b in u) * 1e-9)
            merged.extend(u)
    merged = _union(merged)
    gaps = []
    cursor = w0
    for a, b in merged + [(w1, w1)]:
        if a > cursor:
            gaps.append((cursor, a))
        cursor = max(cursor, b)
    notes = sorted((s, e, n) for n, s, e in host
                   if n != WINDOW and s < w1 and e > w0)
    starts = [s for s, _, _ in notes]
    labelled = [piece for gap in gaps
                for piece in _split(gap, notes, starts)]
    by_label: Dict[str, float] = collections.defaultdict(float)
    for name, sec in labelled:
        by_label[name] += sec
    return {
        "window_s": (w1 - w0) * 1e-9,
        "busy_s": (sum(busy_per_device) / len(busy_per_device)
                   if busy_per_device else 0.0),
        "devices": len(busy_per_device),
        "device_ops": sorted(ops_time.items(), key=lambda kv: -kv[1]),
        "idle_gaps": sorted(labelled, key=lambda kv: -kv[1])[:top],
        "idle_by_annotation": sorted(by_label.items(),
                                     key=lambda kv: -kv[1]),
    }


def _split(gap: Tuple[int, int], notes: Sequence[Tuple[int, int, str]],
           starts: Sequence[int]) -> List[Tuple[str, float]]:
    """Cut an idle gap at the harness annotations it overlaps (the op
    annotations follow one another): (annotation, seconds) pieces, with
    ``harness`` for time under none."""
    a, b = gap
    pieces = []
    cur = a
    for s, e, n in notes[max(0, bisect.bisect_right(starts, a) - 1):]:
        if s >= b:
            break
        lo, hi = max(s, cur), min(e, b)
        if hi <= lo:
            continue
        if lo > cur:
            pieces.append(("harness", (lo - cur) * 1e-9))
        pieces.append((n, (hi - lo) * 1e-9))
        cur = hi
    if b > cur:
        pieces.append(("harness", (b - cur) * 1e-9))
    return pieces
