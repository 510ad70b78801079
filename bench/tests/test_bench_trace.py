"""The trace reduction, checked against hand-summed values."""
import pytest

from bench import trace_reduce as T

MS = 1_000_000   # ns


def planes():
    # host: the window 0-100 ms, a query 12-60 ms, a write 70-90 ms;
    # device: ops at 20-30, 25-40 (overlapping), 75-80 and 95-120 ms
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ("bench.window", 0, 100 * MS),
        ("bench.query.t6", 12 * MS, 48 * MS),
        ("bench.write", 70 * MS, 20 * MS)]}]}
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ("fused_scan", 20 * MS, 10 * MS),
            ("copy", 25 * MS, 15 * MS),
            ("fused_scan", 75 * MS, 5 * MS),
            ("bitmap", 95 * MS, 25 * MS)]},
        {"name": "XLA Modules", "events": [("jit_x", 0, 100 * MS)]}]}
    return [host, dev]


def test_busy_idle_and_op_time_by_hand():
    r = T.reduce(planes())
    assert r["window_s"] == pytest.approx(0.100)
    # union inside the window: 20-40, 75-80, 95-100 = 30 ms
    assert r["busy_s"] == pytest.approx(0.030)
    assert r["devices"] == 1
    ops = dict(r["device_ops"])
    assert ops["fused_scan"] == pytest.approx(0.015)
    assert ops["copy"] == pytest.approx(0.015)
    assert ops["bitmap"] == pytest.approx(0.005)     # clipped at 100 ms
    assert "jit_x" not in ops                        # not the ops line
    gaps = dict((n, s) for n, s in r["idle_by_annotation"])
    # gaps 0-20, 40-75 and 80-95 ms, cut at the annotations: harness
    # 0-12, 60-70, 90-95; query 12-20, 40-60; write 70-75, 80-90
    assert gaps["harness"] == pytest.approx(0.027)
    assert gaps["bench.query.t6"] == pytest.approx(0.028)
    assert gaps["bench.write"] == pytest.approx(0.015)
    assert sum(gaps.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    assert r["idle_gaps"][0] == ("bench.query.t6", pytest.approx(0.020))


def test_a_trace_without_the_window_is_refused():
    p = planes()
    p[0]["lines"][0]["events"] = p[0]["lines"][0]["events"][1:]
    with pytest.raises(ValueError):
        T.reduce(p)


def test_a_trace_recorded_on_a_v5e_by_hand():
    """Three rounds of a jitted matmul (``bench.query.f``) and a jitted
    sort (``bench.write``) traced on one TPU v5e, reduced to plain data by
    ``trace_reduce.load``.  The window annotation spans 42,597,256 ns to
    55,487,884 ns; the first round's three device ops (41.53-41.56 ms, the
    device clock runs ~1 ms behind the host's) fall before it and are
    left out.  The 18 ops inside do not overlap."""
    import json
    from pathlib import Path
    path = Path(__file__).parent / "fixtures" / "v5e_small_trace.json"
    r = T.reduce(json.loads(path.read_text()))
    assert r["window_s"] == pytest.approx(12_890_628e-9)
    sorts = 311_305 + 310_590 + 311_677
    fusions = 17_928 + 17_912
    copies = 5_042 + 5 + 5_890 + 13 + 5_210 + 5 + 5_907 + 13 + 5_063 + 6
    iotas = 875 + 870 + 873
    ops = dict(r["device_ops"])
    assert ops["%sort.6"] == pytest.approx(sorts * 1e-9)
    assert ops["%fusion"] == pytest.approx(fusions * 1e-9)
    assert ops["%iota.clone"] == pytest.approx(iotas * 1e-9)
    assert ops["%copy-done"] + ops["%copy-start"] == pytest.approx(
        copies * 1e-9)
    assert r["busy_s"] == pytest.approx((sorts + fusions + copies + iotas)
                                        * 1e-9)
    assert r["devices"] == 1
    idle = sum(s for _, s in r["idle_by_annotation"])
    assert idle == pytest.approx(r["window_s"] - r["busy_s"])
