"""Kernel dispatch layer (kernels/ops.py): self time of the program's
``dispatch:<op>`` spans per query, in ms: the host's prep for a device
launch (padding, masks, occupancy) and the launch call, without the
transfers, which are spans of their own."""


def read(rec):
    spans = rec["spans"]["self_s"]
    total = sum(s for name, s in spans.items() if name.startswith("dispatch:"))
    if not rec["queries"] or not total:
        return None
    return total / rec["queries"] * 1e3
