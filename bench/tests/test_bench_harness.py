"""The harness finds what a cell is made of by name, refuses to run
without a chip, and, driven on the CPU with the timed path broken
underneath, reports ``correct`` false."""
import json
import os
import subprocess
import sys

import pytest

from bench import run
from bench.generator import Generator
from bench.tests.tiny import tiny

ROOT = run.ROOT


def bench_json():
    with open(ROOT / "BENCHMARK.json", encoding="utf-8") as f:
        return json.load(f)


def test_every_cell_finds_its_parts_by_name():
    b = bench_json()
    for cell in b["workloads"]:
        found = run.find_cell(cell["name"])
        assert found["config"]["name"] == cell["config"]
        assert found["traffic"]["queries_per_block"] >= 0
        names = {m["name"] for m in found["end_to_end"]}
        assert "setup_s" in names and len(names) >= 2
        assert found["per_layer"], cell["name"]
        for m in found["per_layer"]:
            assert callable(run.metric_reader(m["name"]))


def test_every_config_file_is_used_and_lists_its_cuts():
    b = bench_json()
    used = {c["config"] for c in b["workloads"]}
    for c in b["configs"]:
        assert c["name"] in used
        cfg = run.load_json(ROOT / c["file"])
        assert sorted(cfg["reduced"]) == sorted(c["reduced"])
        assert set(cfg["checks"]) == {"rows_wrong", "score_gap"}


def test_unknown_workload_is_refused():
    with pytest.raises(KeyError):
        run.find_cell("no-such-cell")


def test_metric_readers_return_nothing_without_their_records():
    rec = {"queries": 0, "by_template": {}, "window_s": 1.0,
           "counters": {"before": {}, "after": {}},
           "spans": {"self_s": {}, "count": {}}, "device": None}
    for m in bench_json()["per_layer"]:
        assert run.metric_reader(m["name"])(rec) is None, m["name"]


def test_generator_draws_the_same_ops_from_the_same_seed():
    found = run.find_cell("tracy.read-fused")
    a = Generator(found["traffic"], found["config"], 2**31 + 17)
    b = Generator(found["traffic"], found["config"], 2**31 + 17)
    for x, y in zip(a.block() + a.block(), b.block() + b.block()):
        assert x[0] == y[0]
        if x[0] == "query":
            assert x[1] == y[1] and repr(x[2]) == repr(y[2])
        else:
            assert (x[1] == y[1]).all()
    kinds = [op[0] for op in a.block()]
    assert kinds.count("query") == 9 and kinds.count("write") == 1


def test_fused_scan_median_reads_only_its_templates():
    rec = {"queries": 4, "by_template": {"t6": [0.010, 0.030], "t8": [0.020],
                                         "t3": [0.5]}}
    assert run.metric_reader("operators.fused_scan_p50_ms")(rec) \
        == pytest.approx(20.0)


def test_without_a_tpu_the_run_exits_nonzero_and_prints_no_result():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, "-m", "bench.run", "--workload", "tracy.read-fused",
         "--seed", "4294967311", "--seconds", "1", "--trace", "0"],
        cwd=ROOT, env=env, capture_output=True, text=True, timeout=240)
    assert p.returncode != 0
    assert p.stdout.strip() == ""
    assert "TPU" in p.stderr


def test_a_sound_tiny_run_is_correct():
    res = run.run_cell("tracy.read-fused", 2**33 + 5, 1.0, False,
                       require_tpu=False,
                       overrides=tiny("tracy.read-fused"))
    assert res["correct"], res["checks"]
    assert res["failed"] == 0
    assert set(res["metrics"]) == {"setup_s", "query_p50_ms",
                                   "query_p95_ms"}
    assert list(res)[-1] == "checks"
    assert res["checks"]["answers"]["value"] > 0


def _drop_half(put):
    def broken(self, pks, batch):
        n = len(pks) // 2
        return put(self, pks[:n], {k: v[:n] for k, v in batch.items()})
    return broken


def _ignore(put):
    def broken(self, pks, batch):
        if len(pks) < 1024:          # the window's writes, not the preload
            return None
        return put(self, pks, batch)
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


@pytest.mark.parametrize("fault,attr,make", [
    ("state left unchanged", "put", _ignore),
    ("half of each batch left out", "put", _drop_half),
    ("an answer altered where it is produced", "execute", _alter_answer),
])
def test_a_broken_timed_path_is_not_correct(monkeypatch, fault, attr,
                                            make):
    from repro.core.api import Table
    monkeypatch.setattr(Table, attr, make(getattr(Table, attr)))
    res = run.run_cell("tracy.read-fused", 99, 1.0, False, require_tpu=False,
                       overrides=tiny("tracy.read-fused"))
    assert not res["correct"], (fault, res["checks"])
