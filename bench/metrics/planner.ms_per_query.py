"""Planner layer (core/optimizer/, called from core/executor.py and
core/shards/executor.py): self time of the program's ``planner`` spans
per query, in ms."""


def read(rec):
    total = rec["spans"]["self_s"].get("planner", 0.0)
    if not rec["queries"] or not total:
        return None
    return total / rec["queries"] * 1e3
