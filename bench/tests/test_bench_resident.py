"""The kernel-dispatch reader of the fused scan's resident column:
hits over lookups stamped on the window's ``dispatch:fused_scan_topk``
events, read by hand and from the tiny traced run."""
import os

import pytest

from bench import program, run
from bench import span_trace as S
from bench.tests.test_bench_spans import MS, planes
from bench.tests.tiny import tiny


def test_the_resident_hit_share_reads_the_dispatch_stamps(monkeypatch):
    """Hits over lookups stamped on the window's fused-scan dispatches; a
    program that stamps none (one without a device copy) reads nothing."""
    stamped = planes()
    main = stamped[0]["lines"][1]["spans"]
    main[2] = ("dispatch:fused_scan_topk", 20 * MS, 25 * MS,
               {"resident_hits": 0, "resident_lookups": 1})
    main += [("dispatch:fused_scan_topk", 60 * MS, 5 * MS,
              {"resident_hits": 1, "resident_lookups": 1}),
             ("dispatch:fused_scan_topk", 66 * MS, 2 * MS,
              {"resident_hits": 1, "resident_lookups": 1}),
             ("dispatch:fused_scan_topk", 130 * MS, 1 * MS,
              {"resident_hits": 0, "resident_lookups": 1})]
    reader = run.metric_reader("dispatch.resident_hit_share")
    rec = {"queries": 2, "device": {"devices": 1, "window_s": 0.100}}
    monkeypatch.setattr(S, "for_run", lambda rec, root: stamped)
    assert reader(rec) == pytest.approx(2 / 3)
    monkeypatch.setattr(S, "for_run", lambda rec, root: planes())
    assert reader(rec) is None


def test_a_traced_tiny_run_reads_the_resident_hit_share(tmp_path,
                                                        monkeypatch):
    """The tiny traced run with the kernels in interpret mode, so the
    fused scan takes the device path and keeps its column on the
    device: the share reads, and the run stays correct."""
    program.import_program()
    from repro.kernels import ops as kops
    os.symlink(run.ROOT / "bench", tmp_path / "bench")
    os.symlink(run.ROOT / "BENCHMARK.json", tmp_path / "BENCHMARK.json")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR",
                       str(run.ROOT / ".jax_cache"))
    monkeypatch.setattr(kops, "USE_PALLAS", True)
    res = run.run_cell("tracy.read-fused", 2**33 + 11, 1.0, True,
                       root=tmp_path, require_tpu=False,
                       overrides=tiny("tracy.read-fused"))
    assert res["correct"], res["checks"]
    share = res["metrics"]["dispatch.resident_hit_share"]["value"]
    assert 0 < share <= 1, share
