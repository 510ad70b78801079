"""Operators layer (core/operators.py): self time of the program's
``result:materialize`` spans per query, in ms: building each answer's
rows from its sources, filter answers whole and ranked ones cut to k.
A program without the span reads nothing."""


def read(rec):
    total = rec["spans"]["self_s"].get("result:materialize", 0.0)
    if not rec["queries"] or not total:
        return None
    return total / rec["queries"] * 1e3
