"""Cells defined by data alone: a write-heavy cell timed by its ingest
rate, and a continuous-query cell whose standing subscriptions the
harness registers, advances and checks.  Tiny stores on the CPU."""
import hashlib
import json
import struct

import numpy as np
import pytest

from bench import control, run
from bench.generator import Generator
from bench.tests.tiny import TINY_CONFIG, tiny

TINY_WRITE_HEAVY = {"warmup_blocks": 2,
                    "write": {"insert": 104, "update": 0, "delete": 0}}
# the tiny cell steps its clock 0.5 s, half the SYNC interval, so that the
# SYNC refreshes fall at every other advance: a path the cell's 1 s step,
# which refreshes every subscription at every advance, does not take
TINY_CQ = {"warmup_blocks": 1, "clock_step_s": 0.5,
           "write": {"insert": 256, "update": 32, "delete": 32}}
CQ = "tracy-cq.refresh"


def cq_bench():
    """``BENCHMARK.json`` with the continuous-query cell: the committed
    entries where there are, made up here where not, as the PRs that add
    them will (the configuration, the cell, the refresh metric), and the
    cell listed under ``ingest_rows_per_s`` and ``cq_refresh_p50_ms``."""
    b = run.load_json(run.ROOT / "BENCHMARK.json")
    if not any(c["name"] == "tracy-cq" for c in b["configs"]):
        b["configs"].append({"name": "tracy-cq",
                             "file": "bench/configs/tracy.json"})
    if not any(w["name"] == CQ for w in b["workloads"]):
        b["workloads"].append({"name": CQ, "config": "tracy-cq",
                               "traffic": "cq-refresh", "chips": 1})
    if not any(m["name"] == "cq_refresh_p50_ms" for m in b["end_to_end"]):
        b["end_to_end"].append({"name": "cq_refresh_p50_ms", "unit": "ms",
                                "better": "lower", "source": "host_clock",
                                "workloads": []})
    for m in b["end_to_end"]:
        if m["name"] in ("ingest_rows_per_s", "cq_refresh_p50_ms") \
                and CQ not in m["workloads"]:
            m["workloads"] = m["workloads"] + [CQ]
    return b


def cq_metrics():
    """The end-to-end metrics the contract has the cell report."""
    return {m["name"] for m in run.find_cell(CQ, bench=cq_bench())
            ["end_to_end"]}


def cq_subscriptions():
    """The committed traffic's count of SYNC and of ASYNC subscriptions."""
    s = run.find_cell(CQ, bench=cq_bench())["traffic"]["subscriptions"]
    return s["sync"], s["async"]


def run_cq(mode, seed, seconds=2.0, traffic=None):
    cfg = dict(TINY_CONFIG, table={"continuous_mode": mode})
    traffic = dict(TINY_CQ, **(traffic or {}))
    return run.run_cell(CQ, seed, seconds, False, require_tpu=False,
                        overrides={"config": cfg, "traffic": traffic},
                        bench=cq_bench())


def run_write_heavy(seed):
    return run.run_cell("tracy.write-heavy", seed, 1.5, False,
                        require_tpu=False,
                        overrides={"config": dict(TINY_CONFIG),
                                   "traffic": TINY_WRITE_HEAVY})


def summary(err):
    """The ``bench-summary`` line a run printed on standard error."""
    for line in err.splitlines():
        if line.startswith("bench-summary "):
            return json.loads(line[len("bench-summary "):])
    raise AssertionError("no bench-summary line")


# ---------------------------------------------------------------------------
# the read cell's traffic is the parent's, draw for draw
# ---------------------------------------------------------------------------

def _feed(h, x):
    if isinstance(x, np.ndarray):
        h.update(f"a{x.dtype.str}{x.shape}".encode())
        if x.dtype == object:
            for v in x.ravel():
                _feed(h, v)
        else:
            h.update(np.ascontiguousarray(x).tobytes())
    elif isinstance(x, dict):
        h.update(b"d%d" % len(x))
        for k in sorted(x):
            _feed(h, k)
            _feed(h, x[k])
    elif isinstance(x, (list, tuple)):
        h.update(b"l%d" % len(x))
        for v in x:
            _feed(h, v)
    elif isinstance(x, (float, np.floating)):
        h.update(struct.pack("<d", float(x)))
    elif isinstance(x, (int, np.integer)):
        h.update(b"i%d" % int(x))
    elif isinstance(x, str):
        h.update(b"s" + x.encode())
    elif x is None:
        h.update(b"n")
    else:
        raise TypeError(type(x))


@pytest.mark.parametrize("seed,digest", [
    (7, "2a2d855735f88a7a0ba790fcec2104416ada94d6b50890f561ee669459e80e9d"),
    (2**31 + 11,
     "cf95626b1da285a43b165829a1ce3fcd92984b4d62067143af0e022a09b88c2d"),
])
def test_read_fused_sends_the_same_ops_as_before(seed, digest):
    """The digest of the preload and six blocks of ``tracy.read-fused``
    (tiny sizes), as the generator drew them before subscriptions and
    advances were added."""
    found = run.find_cell("tracy.read-fused")
    for part, values in tiny("tracy.read-fused").items():
        found[part].update(values)
    gen = Generator(found["traffic"], found["config"], seed)
    h = hashlib.sha256()
    for pks, batch in gen.preload():
        _feed(h, ("write", pks, batch))
    for _ in range(6):
        for op in gen.block():
            _feed(h, op)
    assert h.hexdigest() == digest
    assert gen.subscriptions() == []


# ---------------------------------------------------------------------------
# continuous queries
# ---------------------------------------------------------------------------

def test_subscriptions_take_their_templates_in_turn():
    found = run.find_cell(CQ, bench=cq_bench())
    found["config"].update(TINY_CONFIG)
    gen = Generator(found["traffic"], found["config"], 2**33 + 1)
    subs = gen.subscriptions()
    for _ in gen.preload():
        pass
    n_sync, n_async = cq_subscriptions()
    assert [k for _, k, _, _ in subs] == ["sync"] * n_sync \
        + ["async"] * n_async
    # as benchmarks/continuous_bench.py registers them: NN and region
    # queries in turn, SYNC at 1 s
    assert [t for t, _, _, _ in subs[:4]] == ["t6", "t5", "t6", "t5"]
    assert {i for _, _, i, _ in subs} == {1.0}
    kinds = [op[0] for op in gen.block() + gen.block()]
    assert kinds == ["write", "advance"] * 2
    assert gen.clock == 2.0
    # one fixed stream: another seed registers the same regions
    other = Generator(found["traffic"], found["config"], 5)
    t5 = [spec for t, _, _, spec in other.subscriptions() if t == "t5"]
    assert repr(t5) == repr([spec for t, _, _, spec in subs if t == "t5"])


def test_full_reexecution_refreshes_are_correct(capsys):
    res = run_cq("none", 2**32 + 9)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == cq_metrics() == {
        "setup_s", "ingest_rows_per_s", "cq_refresh_p50_ms"}
    n = summary(capsys.readouterr().err)["ops"]["advance"]
    # each advance logs every ASYNC subscription's latest; the SYNC ones
    # (1 s apart on a clock that steps 0.5 s) refresh at every other
    # advance, the first after the warm-up's one
    n_sync, n_async = cq_subscriptions()
    assert n >= 2
    assert res["checks"]["answers"]["value"] == n_async * n \
        + n_sync * (n // 2)


def test_a_stale_async_answer_is_not_correct(monkeypatch):
    from repro.core.api import Subscription
    fresh = Subscription.latest.fget
    first = {}

    def stale(self):
        res = fresh(self)
        return res if res is None else first.setdefault(self.rid, res)

    monkeypatch.setattr(Subscription, "latest", property(stale))
    res = run_cq("none", 2**32 + 9, traffic={"subscriptions": {
        "templates": ["t6", "t5"], "sync": 2, "async": 8,
        "interval_s": 1.0}})
    assert not res["correct"], res["checks"]
    assert res["checks"]["rows_wrong"]["value"] > 0


def test_a_views_mode_run_logs_every_refresh_and_both_checks(capsys):
    """The harness drives the cell in the views mode to its end and
    compares every refresh; whether the views answer exactly is the
    program's to show, so nothing here asks for ``correct``."""
    res = run_cq("views", 2**32 + 9)
    assert res["failed"] == 0
    assert set(res["metrics"]) == cq_metrics()
    assert {"rows_wrong", "score_gap"} <= set(res["checks"])
    n = summary(capsys.readouterr().err)["ops"]["advance"]
    n_sync, n_async = cq_subscriptions()
    assert n >= 2
    assert res["checks"]["answers"]["value"] == n_async * n \
        + n_sync * (n // 2)


def test_the_control_fails_on_the_subscription_traffic():
    """The bf16x3 control answers every refresh a run compares (6
    advances: every ASYNC subscription at each, the SYNC ones at three of
    them) and fails."""
    small = {"config": {"preload_rows": 4096, "load_batch_rows": 4096},
             "traffic": {"clock_step_s": 0.5,
                         "write": {"insert": 256, "update": 0,
                                   "delete": 0}}}
    seen = [control.read_seed(CQ, seed, 6, overrides=small,
                              bench=cq_bench())
            for seed in (11, 2**31 + 1, 2**40 + 7)]
    n_sync, n_async = cq_subscriptions()
    assert all(r["answers"] == n_async * 6 + n_sync * 3 for r in seen), seen
    assert not any(r["correct"] for r in seen), seen


# ---------------------------------------------------------------------------
# ingest
# ---------------------------------------------------------------------------

def test_write_heavy_warms_up_on_one_round_of_its_listed_templates():
    """The warm-up runs every template once; the window's first t13, in
    its 13th block, finds the store under 2^19 rows, the fused scan's
    shape that the warm-up's t13 took."""
    found = run.find_cell("tracy.write-heavy")
    cfg, traffic = found["config"], found["traffic"]
    per_block = traffic["writes_per_block"] * traffic["write"]["insert"]
    assert cfg["preload_rows"] + (traffic["warmup_blocks"] + 13) \
        * per_block <= 1 << 19
    found["config"].update(TINY_CONFIG)
    gen = Generator(traffic, found["config"], 2**35 + 3)
    for _ in gen.preload():
        pass
    rounds = [[op[1] for b in range(13) for op in gen.block()
               if op[0] == "query"] for _ in range(2)]
    assert rounds[0] == rounds[1] == [f"t{i}" for i in range(1, 14)]
    assert traffic["warmup_blocks"] == 13


def test_write_heavy_run_is_correct_and_times_its_writes(capsys):
    res = run_write_heavy(2**31 + 3)
    assert res["correct"], res["checks"]
    assert set(res["metrics"]) == {"setup_s", "ingest_rows_per_s"}
    s = summary(capsys.readouterr().err)
    w = TINY_WRITE_HEAVY["write"]
    assert s["rows_acked"] == s["ops"]["write"] * sum(w.values())
    assert res["metrics"]["ingest_rows_per_s"]["value"] == pytest.approx(
        s["rows_acked"] / s["write_s"], rel=1e-12)


def test_storage_readers_take_window_deltas_per_thousand_rows():
    rec = {"rows_acked": 2000,
           "counters": {"before": {"flush_s": 1.0, "compact_s": 0.5},
                        "after": {"flush_s": 1.3, "compact_s": 0.5}}}
    assert run.metric_reader("storage.flush_ms_per_krow")(rec) \
        == pytest.approx(150.0)
    assert run.metric_reader("storage.compact_ms_per_krow")(rec) == 0.0


def _ignore(method):
    def broken(self, pks, *rest):
        if len(pks) < 1024:          # the window's writes, not the preload
            return None
        return method(self, pks, *rest)
    return broken


def _drop_half(put):
    def broken(self, pks, batch):
        n = len(pks) // 2
        return put(self, pks[:n], {k: v[:n] for k, v in batch.items()})
    return broken


def _alter_answer(execute):
    def broken(self, query):
        rows, stats = execute(self, query)
        if rows:
            rows = list(rows)
            rows[0] = type(rows[0])(rows[0].pk + 1, rows[0].score,
                                    rows[0].values)
        return rows, stats
    return broken


@pytest.mark.parametrize("fault,attrs,make", [
    ("state left unchanged", ("put", "delete"), _ignore),
    ("half of each batch left out", ("put",), _drop_half),
    ("an answer altered where it is produced", ("execute",), _alter_answer),
])
def test_a_broken_write_path_is_not_correct(monkeypatch, fault, attrs,
                                            make):
    from repro.core.api import Table
    for attr in attrs:
        monkeypatch.setattr(Table, attr, make(getattr(Table, attr)))
    res = run_write_heavy(99)
    assert not res["correct"], (fault, res["checks"])


def test_the_control_fails_on_write_heavy_traffic():
    small = {"config": {"preload_rows": 4096, "load_batch_rows": 4096},
             "traffic": {"write": {"insert": 102, "update": 0, "delete": 0}}}
    seen = [control.read_seed("tracy.write-heavy", seed, 26, overrides=small)
            for seed in (11, 2**31 + 1, 2**40 + 7)]
    assert all(r["answers"] == 26 for r in seen)
    assert not any(r["correct"] for r in seen), seen
