"""The benchmark's one window onto the program under test: the
``Database`` / ``Table`` facade of ``repro.core.api``, the query classes it
takes, and the counters and spans the per-layer metrics read.

Nothing here decides a result: it builds the store a configuration file
describes, turns the benchmark's plain query specs into the program's
query objects, and reads the program's counters.
"""
from __future__ import annotations

import dataclasses
import sys
from pathlib import Path
from typing import Any, Dict, List, Tuple

ROOT = Path(__file__).resolve().parents[1]


def import_program() -> None:
    """Put the program's package (``src/``) on the path."""
    src = str(ROOT / "src")
    if src not in sys.path:
        sys.path.insert(0, src)


def schema(cfg: Dict[str, Any]):
    from repro.core.types import Column, ColumnType, IndexKind, Schema
    kinds = {"vector": ColumnType.VECTOR, "spatial": ColumnType.SPATIAL,
             "text": ColumnType.TEXT, "scalar": ColumnType.SCALAR}
    return Schema([
        Column(c["name"], kinds[c["type"]], dim=c.get("dim", 0),
               index=IndexKind[c["index"]]) if "dim" in c else
        Column(c["name"], kinds[c["type"]], index=IndexKind[c["index"]])
        for c in cfg["schema"]])


TABLE_KEYS = ("continuous_mode", "view_budget_bytes")


def open_table(cfg: Dict[str, Any], path: str):
    """A durable ``Database`` at ``path`` with the configuration's
    ``LSMConfig`` and its optional ``table`` settings (the continuous
    engine's ``continuous_mode`` and ``view_budget_bytes``); returns
    (db, table)."""
    from repro.core.api import Database, LSMConfig
    fields = {f.name for f in dataclasses.fields(LSMConfig)}
    unknown = set(cfg["lsm"]) - fields
    if unknown:
        raise KeyError(f"LSMConfig has no {sorted(unknown)}")
    table = cfg.get("table", {})
    unknown = set(table) - set(TABLE_KEYS)
    if unknown:
        raise KeyError(f"Database takes no table setting {sorted(unknown)}")
    db = Database(schema(cfg), LSMConfig(**cfg["lsm"]), path=path, **table)
    return db, db.table()


def subscribe(table, spec, kind: str, interval_s: float):
    """Register ``spec`` as a standing query: ``"sync"`` refreshes every
    ``interval_s`` of the table's clock, ``"async"`` on every change."""
    if kind == "sync":
        return table.subscribe(to_query(spec), interval_s=interval_s)
    if kind == "async":
        return table.subscribe(to_query(spec), on_change=True)
    raise ValueError(f"unknown subscription kind {kind!r}")


def _expr(p):
    from repro.core import query as q
    op = p[0]
    if op == "and":
        return q.And(*[_expr(c) for c in p[1]])
    if op == "or":
        return q.Or(*[_expr(c) for c in p[1]])
    if op == "range":
        return q.Range(p[1], p[2], p[3])
    if op == "geo":
        return q.GeoWithin(p[1], tuple(p[2]))
    if op == "text":
        return q.TextContains(p[1], p[2])
    if op == "vrange":
        return q.VectorRange(p[1], p[2], p[3])
    raise TypeError(f"unknown predicate {p!r}")


def _rank(r):
    from repro.core import query as q
    kind, col, arg, w = r
    if kind == "vec":
        return q.VectorRank(col, arg, w)
    if kind == "spatial":
        return q.SpatialRank(col, tuple(arg), w)
    if kind == "textrank":
        return q.TextRank(col, tuple(arg), w)
    raise TypeError(f"unknown rank term {r!r}")


def to_query(spec):
    """A benchmark query spec as the program's ``HybridQuery``."""
    from repro.core import query as q
    return q.HybridQuery(
        where=None if spec["where"] is None else _expr(spec["where"]),
        ranks=[_rank(r) for r in spec["ranks"]], k=spec["k"])


def rows_of(result) -> List[Tuple[int, float]]:
    return [(int(r.pk), float(r.score)) for r in result]


def counters(table) -> Dict[str, Any]:
    """The program's counters that per-layer metrics read, as a flat
    snapshot (diffed around the window)."""
    from repro.kernels import ops
    ops.flush_registry_counters()
    return {"host_dispatches": ops.thread_stats().host_dispatches,
            "device_launches": sum(ops.launches_by_tag().values()),
            "jit_shape_misses": ops.thread_stats().shape_misses,
            "flush_s": table.store.metrics["flush_s"],
            "compact_s": table.store.metrics["compact_s"],
            "flushes": table.store.metrics["flushes"],
            "compactions": table.store.metrics["compactions"]}


def set_tracing(on: bool) -> None:
    from repro.obs import trace
    trace.set_tracing(on)


def take_spans() -> list:
    """The finished root spans since the last call (the tracer keeps
    only its newest 256, so the harness takes them after every op)."""
    from repro.obs.trace import TRACER
    roots = TRACER.snapshot()
    TRACER.clear()
    return roots
