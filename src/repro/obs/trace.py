"""Low-overhead span tracer: a contextvar-parented span tree per
execution flow, with ring-buffer retention of finished root spans.

Design constraints (ISSUE 10 tentpole):

  * tracing defaults OFF and the disabled path must stay within the
    benchmarked <=2% overhead budget — ``span()`` is one module-global
    check plus a shared no-op context manager, no allocation;
  * spans nest by contextvar, so operator spans land under their query
    span on the query thread while a background flush worker's spans
    root independently (contextvars are per-thread by construction);
  * finished ROOT spans are retained in a bounded deque (the roots it
    drops are counted in ``TRACER.dropped``) and render as an indented
    tree;
  * every live span is also a ``jax.profiler.TraceAnnotation`` named
    ``PROFILE_PREFIX + name``, so a profiler trace stamps the program's
    spans on the device ops' clock, on the thread that did the work.
    Spans hold durations only; where a span sits in time is the
    profiler's to record.

Call sites open spans with ``with span("flush") as sp:`` — the
with-statement guarantees the span closes on exceptions (machine-checked
by the ``obs/span-closed`` analysis rule).  ``sp.set(rows=...)``
attaches attributes; on the disabled path ``sp`` is the no-op singleton
and ``set`` discards everything.
"""
from __future__ import annotations

import contextlib
import contextvars
import threading
import time
from collections import deque
from typing import Any, Dict, Iterator, List, Optional

from jax.profiler import TraceAnnotation

# the one prefix of the program's span names on the profiler's timeline
PROFILE_PREFIX = "repro."


class Span:
    """One finished (or in-flight) span: name, duration, attributes,
    children."""

    __slots__ = ("name", "dur", "attrs", "children")
    live = True

    def __init__(self, name: str, attrs: Optional[Dict[str, Any]] = None):
        self.name = name
        self.dur = 0.0
        self.attrs: Dict[str, Any] = attrs if attrs is not None else {}
        self.children: List["Span"] = []

    def set(self, **attrs: Any) -> "Span":
        self.attrs.update(attrs)
        return self

    def add(self, key: str, delta: Any) -> None:
        """Accumulate a numeric attribute (kernel-launch style counts)."""
        self.attrs[key] = self.attrs.get(key, 0) + delta

    # ---------------------------------------------------------- traversal
    def walk(self) -> Iterator["Span"]:
        yield self
        for c in self.children:
            yield from c.walk()

    def tree(self, indent: int = 0) -> str:
        """Human-readable dump: name, duration, attrs, nested children."""
        pad = "  " * indent
        at = ""
        if self.attrs:
            at = " {" + ", ".join(f"{k}={v}"
                                  for k, v in sorted(self.attrs.items())) \
                + "}"
        lines = [f"{pad}{self.name} {self.dur * 1e3:.3f}ms{at}"]
        for c in self.children:
            lines.append(c.tree(indent + 1))
        return "\n".join(lines)


class _NullSpan:
    """Shared no-op span for the disabled path: ``with span(...)`` costs
    two trivial method calls and ``sp.set(...)`` discards its kwargs."""

    __slots__ = ()
    live = False
    name = ""
    dur = 0.0
    attrs: Dict[str, Any] = {}
    children: List[Span] = []

    def __enter__(self) -> "_NullSpan":
        return self

    def __exit__(self, *exc: Any) -> bool:
        return False

    def set(self, **attrs: Any) -> "_NullSpan":
        return self

    def add(self, key: str, delta: Any) -> None:
        pass


NULL_SPAN = _NullSpan()

_current: contextvars.ContextVar[Optional[Span]] = \
    contextvars.ContextVar("repro_obs_span", default=None)


class Tracer:
    """Process-wide retention of finished root spans (bounded).
    ``dropped`` counts the oldest roots pushed out of the full ring; it
    is monotonic (``clear`` leaves it)."""

    def __init__(self, maxlen: int = 256):
        self._lock = threading.Lock()
        self.roots: deque = deque(maxlen=maxlen)
        self.dropped = 0

    def retain(self, root: Span) -> None:
        with self._lock:
            if len(self.roots) == self.roots.maxlen:
                self.dropped += 1
            self.roots.append(root)

    def clear(self) -> None:
        with self._lock:
            self.roots.clear()

    def snapshot(self) -> List[Span]:
        with self._lock:
            return list(self.roots)

    def tree(self) -> str:
        return "\n".join(root.tree() for root in self.snapshot())


TRACER = Tracer()

_enabled = False


def enabled() -> bool:
    return _enabled


def set_tracing(on: bool) -> None:
    """Flip the process-wide tracing switch (default off)."""
    global _enabled
    _enabled = bool(on)


@contextlib.contextmanager
def force_tracing() -> Iterator[None]:
    """Enable tracing for a block and restore the prior state — the
    EXPLAIN ANALYZE path uses this so one query traces regardless of the
    global default."""
    global _enabled
    prev = _enabled
    _enabled = True
    try:
        yield
    finally:
        _enabled = prev


def _profile_event(name: str, attrs: Dict[str, Any]):
    """The profiler event of a live span, opened; None while no profiler
    trace is being taken (then it costs one check).  The attributes given
    at open ride on the event as its stats."""
    if not TraceAnnotation.is_enabled():
        return None
    mark = TraceAnnotation(PROFILE_PREFIX + name, **attrs)
    mark.__enter__()
    return mark


def _attach(node: Span) -> None:
    """Hand a finished span to the open parent, or retain it as a root."""
    parent = _current.get()
    if parent is None:
        TRACER.retain(node)
    else:
        parent.children.append(node)


class _Interval:
    """One interval of a span: its node is the current span and its
    profiler event is open; the interval adds to the node's duration."""

    __slots__ = ("node", "token", "mark", "t0")

    def __init__(self, node: Span):
        self.node = node

    def __enter__(self) -> Span:
        self.token = _current.set(self.node)
        self.mark = _profile_event(self.node.name, self.node.attrs)
        self.t0 = time.perf_counter()
        return self.node

    def __exit__(self, *exc: Any) -> bool:
        self.node.dur += time.perf_counter() - self.t0
        if self.mark is not None:
            self.mark.__exit__(None, None, None)
        _current.reset(self.token)
        return False


class _SpanCtx(_Interval):
    """Live context manager returned by ``span()`` when tracing is on:
    one interval, then the span joins its parent."""

    __slots__ = ()

    def __init__(self, name: str, attrs: Dict[str, Any]):
        super().__init__(Span(name, attrs))

    def __exit__(self, *exc: Any) -> bool:
        super().__exit__(*exc)
        _attach(self.node)
        return False


def span(name: str, **attrs: Any):
    """Open a span: ``with span("operator:FusedScanTopK") as sp: ...``.
    A shared no-op when tracing is disabled."""
    if not _enabled:
        return NULL_SPAN
    return _SpanCtx(name, attrs)


def current_span() -> Optional[Span]:
    """The innermost open span on this flow (None when untraced)."""
    if not _enabled:
        return None
    return _current.get()


class Drain(_Interval):
    """A span timed in pieces: a source generator that its consumer
    drains runs only inside ``next()``, so each ``with drain:`` block is
    one such window.  Each window is a real interval: it is the span's
    own profiler event, and spans opened inside it become the span's
    children.  The span joins its parent once, at ``record_span``, with
    the windows' summed duration."""

    __slots__ = ()

    def __init__(self, name: str):
        super().__init__(Span(name))


def record_span(node: Span, **attrs: Any) -> Span:
    """Attach a span timed apart (a ``Drain``'s, after its last window):
    it keeps only its duration, and joins the span open on this flow, or
    the retained roots when none is."""
    node.attrs.update(attrs)
    _attach(node)
    return node
