"""Device: share of the traced window in which no op ran on the chip,
in %, in the cells that report query latency."""


def read(rec):
    dev = rec["device"]
    if not dev or not dev["window_s"] or not rec["queries"]:
        return None
    return 100.0 * (1.0 - dev["busy_s"] / dev["window_s"])
