"""Kernel dispatch layer (kernels/ops.py): ops that ran in host numpy
below the device cut-off (``kernels.host_dispatches``), per query."""


def read(rec):
    c = rec["counters"]
    if not rec["queries"]:
        return None
    return (c["after"]["host_dispatches"] - c["before"]["host_dispatches"]) \
        / rec["queries"]
