"""Storage layer (core/lsm.py, core/flush.py): the window's inline flush
time per 1,000 rows its writes acknowledged, in ms: the store's
``flush_s`` counter (memtable to a level-0 segment with its indexes and
PQ codes) over ``rows_acked``."""


def read(rec):
    c = rec["counters"]
    rows = rec.get("rows_acked")
    if not rows or "flush_s" not in c["before"]:
        return None
    return (c["after"]["flush_s"] - c["before"]["flush_s"]) * 1e3 \
        / (rows / 1e3)
