"""Kernel dispatch layer (kernels/ops.py): device program launches
(``ops.launches_by_tag()`` summed over tags), per query."""


def read(rec):
    c = rec["counters"]
    if not rec["queries"]:
        return None
    return (c["after"]["device_launches"] - c["before"]["device_launches"]) \
        / rec["queries"]
