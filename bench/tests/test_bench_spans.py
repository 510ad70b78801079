"""The program's spans on the trace: idle time named by program span,
bytes to the device read from the transfer events, and the readers of
the planner and kernel-dispatch layers."""
import json
import os
from pathlib import Path

import pytest

from bench import program, run
from bench import span_trace as S
from bench import trace_reduce as T
from bench.tests.tiny import tiny

MS = 1_000_000   # ns
NEW = ("planner.ms_per_query", "dispatch.prep_ms_per_query",
       "dispatch.host_path_ms_per_query", "dispatch.transfer_ms_per_query",
       "dispatch.mb_to_device_per_query",
       "device.idle_in_dispatch_share.query")


def planes():
    # the window's thread: the window 0-100 ms, a query 10-60, a write
    # 70-90; program spans nested query > operator > dispatch > transfers;
    # a second thread whose spans cover the whole window; device ops at
    # 25-40 and 75-80 ms
    main = {"name": "python", "events": [
        ("bench.window", 0, 100 * MS),
        ("bench.query.t6", 10 * MS, 50 * MS),
        ("bench.write", 70 * MS, 20 * MS)], "spans": [
        ("query", 12 * MS, 46 * MS, {"n": 1}),
        ("operator:FusedScanTopK", 15 * MS, 35 * MS, {}),
        ("dispatch:fused_scan_topk", 20 * MS, 25 * MS, {}),
        ("transfer:to_device", 21 * MS, 9 * MS, {"bytes": 1000}),
        ("transfer:to_host", 32 * MS, 12 * MS, {}),
        ("transfer:to_device", 120 * MS, 1 * MS, {"bytes": 7})]}
    other = {"name": "python", "events": [], "spans": [
        ("flush", 0, 100 * MS, {}),
        ("transfer:to_device", 50 * MS, 1 * MS, {"bytes": 5})]}
    host = {"name": "/host:CPU", "lines": [other, main]}
    dev = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Ops", "events": [
            ("fused_scan", 25 * MS, 15 * MS),
            ("copy", 75 * MS, 5 * MS)]}]}
    return [host, dev]


def test_idle_by_span_by_hand():
    idle = dict(S.idle_by_span(planes()))
    # gaps 0-25, 40-75, 80-100 ms; innermost spans: none 0-12, query
    # 12-15, operator 15-20, dispatch 20-21, to_device 21-30, dispatch
    # 30-32, to_host 32-44, dispatch 44-45, operator 45-50, query 50-58,
    # none 58-100; the other thread's flush is not the window's thread
    want = {S.NONE: 0.049, "query": 0.011, "operator:FusedScanTopK": 0.010,
            "dispatch:fused_scan_topk": 0.002, "transfer:to_device": 0.004,
            "transfer:to_host": 0.004}
    assert set(idle) == set(want)
    for name, sec in want.items():
        assert idle[name] == pytest.approx(sec), name
    r = T.reduce(planes())
    assert sum(idle.values()) == pytest.approx(r["window_s"] - r["busy_s"])
    # under the query annotation (10-60 ms) only
    under = dict(S.idle_by_span(planes(), under="bench.query."))
    assert under[S.NONE] == pytest.approx(0.004)
    assert under["query"] == pytest.approx(0.011)
    assert sum(under.values()) == pytest.approx(0.035)


def test_stat_sum_reads_the_window_thread_inside_the_window():
    assert S.stat_sum(planes(), "transfer:to_device", "bytes") == 1000


def test_the_recorded_v5e_trace_reduces_as_before():
    """``reduce`` reads the planes ``span_trace`` loads (program events
    under ``"spans"``) exactly as it reads ``trace_reduce``'s own; the
    recorded trace holds no program span, so all its idle time is under
    none."""
    path = Path(__file__).parent / "fixtures" / "v5e_small_trace.json"
    plain = json.loads(path.read_text())
    before = T.reduce(plain)
    with_spans = json.loads(path.read_text())
    line, w0, w1 = S.window_line(with_spans)
    line["spans"] = [("query", w0, (w1 - w0) // 2, {})]
    assert T.reduce(with_spans) == before
    idle = S.idle_by_span(plain)
    assert [n for n, _ in idle] == [S.NONE]
    assert idle[0][1] == pytest.approx(before["window_s"] - before["busy_s"])


def test_the_six_readers_are_found_by_name():
    with open(run.ROOT / "BENCHMARK.json", encoding="utf-8") as f:
        per_layer = {m["name"]: m for m in json.load(f)["per_layer"]}
    for name in NEW:
        assert per_layer[name]["workloads"] == ["tracy.read-fused"]
        assert callable(run.metric_reader(name))


def test_the_trace_readers_read_the_run_they_are_given(monkeypatch):
    monkeypatch.setattr(S, "for_run", lambda rec, root: planes())
    rec = {"queries": 2, "device": {"devices": 1, "window_s": 0.100}}
    # idle under dispatch (2 ms) and transfer spans (8 ms) of 100 ms
    assert run.metric_reader("device.idle_in_dispatch_share.query")(rec) \
        == pytest.approx(10.0)
    assert run.metric_reader("dispatch.mb_to_device_per_query")(rec) \
        == pytest.approx(1000 / 2 / 1e6)
    rec["device"]["devices"] = 0        # no device: nothing to read
    assert run.metric_reader("device.idle_in_dispatch_share.query")(rec) \
        is None


def test_a_traced_tiny_run_reads_the_new_layers(tmp_path, monkeypatch):
    """A tiny traced run on the CPU, with the host cut-off lowered so the
    fused scan takes the device path while small ops stay on the host:
    the span and counter readers read, the device reader finds no
    device.  The run's root is a copy of the checkout's links, so its
    store and trace are its own."""
    program.import_program()
    from repro.kernels import ops as kops
    os.symlink(run.ROOT / "bench", tmp_path / "bench")
    os.symlink(run.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(run.ROOT / ".jax_cache"))
    monkeypatch.setattr(kops, "HOST_FLOP_CUTOFF", 100_000)
    res = run.run_cell("tracy.read-fused", 2**33 + 9, 1.0, True,
                       root=tmp_path, require_tpu=False,
                       overrides=tiny("tracy.read-fused"))
    assert res["correct"], res["checks"]
    got = res["metrics"]
    for name in NEW[:5]:
        assert got[name]["value"] > 0, name
    assert NEW[5] not in got
    assert "operators.self_ms_per_query" in got
