"""Kernel dispatch layer (kernels/ops.py): time of the program's
``transfer:to_device`` and ``transfer:to_host`` spans per query, in ms:
the host's part of the operand uploads, and the result fetches with the
wait for the kernel that makes them."""


def read(rec):
    spans = rec["spans"]["self_s"]
    total = sum(s for name, s in spans.items() if name.startswith("transfer:"))
    if not rec["queries"] or not total:
        return None
    return total / rec["queries"] * 1e3
