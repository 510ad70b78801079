"""Operators layer (core/operators.py, core/nra.py, core/executor.py):
self time of the program's ``operator:*`` spans per query, in ms."""


def read(rec):
    spans = rec["spans"]["self_s"]
    total = sum(s for name, s in spans.items() if name.startswith("operator:"))
    if not rec["queries"] or not total:
        return None
    return total / rec["queries"] * 1e3
