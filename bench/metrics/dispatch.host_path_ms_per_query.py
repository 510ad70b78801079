"""Kernel dispatch layer (kernels/ops.py): self time of the program's
``host_op:<op>`` spans per query, in ms: the host doing a kernel's work
in numpy below ``HOST_FLOP_CUTOFF``."""


def read(rec):
    spans = rec["spans"]["self_s"]
    total = sum(s for name, s in spans.items() if name.startswith("host_op:"))
    if not rec["queries"] or not total:
        return None
    return total / rec["queries"] * 1e3
