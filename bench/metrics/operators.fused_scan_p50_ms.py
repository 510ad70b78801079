"""Operators layer, fused-scan path: median latency, in ms, of the
window's queries that the fused scan serves (t6, pure vector NN, and t8,
vector NN under a time filter).  A steadier statistic beside
``query_p50_ms``, whose median falls where these templates meet t3."""
import statistics

TEMPLATES = ("t6", "t8")


def read(rec):
    lat = [s for t in TEMPLATES for s in rec["by_template"].get(t, [])]
    if not lat:
        return None
    return statistics.median(lat) * 1e3
