"""Storage layer (core/lsm.py): the window's inline compaction time per
1,000 rows its writes acknowledged, in ms: the store's ``compact_s``
counter (a level's segments merged with their indexes and PQ codes) over
``rows_acked``."""


def read(rec):
    c = rec["counters"]
    rows = rec.get("rows_acked")
    if not rows or "compact_s" not in c["before"]:
        return None
    return (c["after"]["compact_s"] - c["before"]["compact_s"]) * 1e3 \
        / (rows / 1e3)
