"""Kernels layer (kernels/*.py): device time of every op on the device
ops line of the trace, inside the window, per query, in ms."""


def read(rec):
    dev = rec["device"]
    if not dev or not rec["queries"]:
        return None
    total = sum(s for _, s in dev["device_ops"])
    if not total:
        return None
    return total / max(1, dev["devices"]) / rec["queries"] * 1e3
