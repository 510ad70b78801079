"""Materialized views + continuous engines (paper §6, Fig. 5 semantics)."""
import numpy as np

from conftest import make_batch, tweet_schema
from repro.core import query as q
from repro.core.continuous import ContinuousEngine
from repro.core.executor import Executor
from repro.core.lsm import LSMConfig, LSMStore
from repro.core.views.selection import (build_candidates, cluster_spatial,
                                        knapsack_select)
from repro.core.views.view import SpatialRangeView, VectorNNView


def _store(rng, n=2000):
    store = LSMStore(tweet_schema(), LSMConfig(flush_rows=512))
    for i in range(0, n, 500):
        pks, batch = make_batch(rng, 500, pk_start=i)
        store.put(pks, batch)
    store.flush()
    return store


def test_spatial_view_incremental_equals_rebuild():
    rng = np.random.default_rng(0)
    store = _store(rng)
    eng = ContinuousEngine(store, mode="views", view_budget_bytes=2**22)
    decl = q.SyncQuery(q.HybridQuery(
        where=[q.GeoWithin("coordinate", (2, 2, 5, 5))]), 1.0)
    eng.register(decl)
    views = [v for v in eng.maintainer.views
             if isinstance(v, SpatialRangeView)]
    assert views
    v = views[0]
    before = set(v.rows)
    # incremental insert: a point inside and one outside
    pks, batch = make_batch(rng, 2, pk_start=50_000)
    batch["coordinate"] = np.asarray([[3.0, 3.0], [9.9, 9.9]], np.float32)
    store.put(pks, batch)
    assert 50_000 in v.rows and 50_001 not in v.rows
    # delete removes
    store.delete([50_000])
    assert 50_000 not in v.rows
    assert set(v.rows) == before


def test_view_update_moving_row_out_evicts_it():
    """An upsert whose new version leaves a view's region must drop the
    row's old version: the subscription equals a re-run of its query."""
    rng = np.random.default_rng(4)
    store = _store(rng)
    eng = ContinuousEngine(store, mode="views", view_budget_bytes=2**22)
    query = q.HybridQuery(where=[q.GeoWithin("coordinate", (2, 2, 5, 5))])
    eng.register(q.SyncQuery(query, 1.0))
    v = [v for v in eng.maintainer.views
         if isinstance(v, SpatialRangeView)][0]
    moved = sorted(v.rows)[:3]
    _, batch = make_batch(rng, len(moved), pk_start=0)
    batch["coordinate"] = np.full((len(moved), 2), 9.9, np.float32)
    store.put(moved, batch)
    assert not set(moved) & set(v.rows)
    got = sorted(r.pk for r in eng.advance(0.0)[0])
    want = sorted(r.pk for r in Executor(store).execute(query)[0])
    assert got == want and not set(moved) & set(got)


def test_vector_view_contains_true_topxk():
    rng = np.random.default_rng(1)
    store = _store(rng)
    qv = rng.normal(size=16).astype(np.float32)
    eng = ContinuousEngine(store, mode="views", view_budget_bytes=2**22)
    eng.register(q.SyncQuery(q.HybridQuery(
        ranks=[q.VectorRank("embedding", qv, 1.0)], k=10), 1.0))
    v = [v for v in eng.maintainer.views if isinstance(v, VectorNNView)][0]
    vecs = np.concatenate([s.columns["embedding"] for s in store.segments])
    pks = np.concatenate([s.pk for s in store.segments])
    d = np.sqrt(((vecs - v.center) ** 2).sum(1))
    want = set(pks[np.argsort(d)[:v.xk]].tolist())
    got = set(pk for _, pk, _ in v.cand)
    assert len(got & want) == v.xk


def test_view_results_match_exact_executor():
    rng = np.random.default_rng(2)
    store = _store(rng)
    qv = rng.normal(size=16).astype(np.float32)
    decl = q.SyncQuery(q.HybridQuery(
        ranks=[q.VectorRank("embedding", qv, 1.0)], k=10), 1.0)
    eng = ContinuousEngine(store, mode="views", view_budget_bytes=2**22)
    rid = eng.register(decl)
    res = eng.advance(0.0)[rid]
    exact, _ = Executor(store).execute(decl.query)
    assert [r.pk for r in res] == [r.pk for r in exact]


def test_view_freshness_after_writes():
    """Continuous queries must reflect new data immediately (the paper's
    data-freshness claim vs Napa-style deferred views)."""
    rng = np.random.default_rng(3)
    store = _store(rng)
    qv = rng.normal(size=16).astype(np.float32)
    decl = q.SyncQuery(q.HybridQuery(
        ranks=[q.VectorRank("embedding", qv, 1.0)], k=5), 1.0)
    eng = ContinuousEngine(store, mode="views", view_budget_bytes=2**22)
    rid = eng.register(decl)
    eng.advance(0.0)
    # insert an exact-match row: must become the new top-1 next tick
    pks, batch = make_batch(rng, 1, pk_start=77_777)
    batch["embedding"] = qv[None, :].copy()
    store.put(pks, batch)
    res = eng.advance(1.0)[rid]
    assert res[0].pk == 77_777 and res[0].score < 1e-3


def test_async_query_triggers_on_write_only():
    rng = np.random.default_rng(4)
    store = _store(rng)
    decl = q.AsyncQuery(q.HybridQuery(
        where=[q.Range("time", 0, 100)]))
    eng = ContinuousEngine(store, mode="none")
    rid = eng.register(decl)
    out = eng.advance(0.0)
    assert rid in out                      # initial run (dirty at reg)
    out = eng.advance(1.0)
    assert rid not in out                  # no data change -> no rerun
    pks, batch = make_batch(rng, 1, pk_start=88_888)
    store.put(pks, batch)
    out = eng.advance(2.0)
    assert rid in out                      # write -> rerun


def test_sync_interval_schedule():
    rng = np.random.default_rng(5)
    store = _store(rng, n=500)
    decl = q.SyncQuery(q.HybridQuery(where=[q.Range("time", 0, 10)]),
                       interval_s=10.0)
    eng = ContinuousEngine(store, mode="none")
    rid = eng.register(decl)
    runs = 0
    for t in range(0, 35, 5):
        if rid in eng.advance(float(t)):
            runs += 1
    assert runs == 4   # t=0,10,20,30


def test_knapsack_respects_budget():
    rng = np.random.default_rng(6)
    store = _store(rng)
    # disjoint rects -> one view candidate per query cluster
    queries = [q.HybridQuery(where=[q.GeoWithin(
        "coordinate", (3 * i, 3 * i, 3 * i + 2, 3 * i + 2))])
        for i in range(3)]
    cands = build_candidates(store, queries)
    assert len(cands) >= 2
    budget = sum(c.bytes_est for c in cands) / 2
    chosen = knapsack_select(cands, budget)
    assert sum(c.bytes_est for c in chosen) <= budget
    assert chosen   # picks something


def test_cluster_spatial_unions_overlaps():
    rects = [(0, 0, 2, 2), (1, 1, 3, 3), (8, 8, 9, 9)]
    clusters = cluster_spatial(rects)
    assert len(clusters) == 2
    big = max(clusters, key=lambda c: c[1])
    assert big[0] == (0, 0, 3, 3) and big[1] == 2


def test_engine_modes_speed_ordering():
    """views (ARCADE+S) <= fcache (ARCADE+F) <= none — Fig. 5's ordering."""
    import time
    rng = np.random.default_rng(7)
    qv = rng.normal(size=16).astype(np.float32)
    decls = [q.SyncQuery(q.HybridQuery(
        ranks=[q.VectorRank("embedding",
                            qv + rng.normal(size=16).astype(np.float32) * .05,
                            1.0)], k=10), 1.0) for _ in range(5)]
    times = {}
    for mode in ("none", "views"):
        # best-of-3: scheduler noise on a loaded machine dwarfs the
        # single-digit-ms advance loop; min is the robust statistic
        best = float("inf")
        for _ in range(3):
            store = _store(np.random.default_rng(7))
            eng = ContinuousEngine(store, mode=mode,
                                   view_budget_bytes=2**23)
            for d in decls:
                eng.register(d)
            t0 = time.perf_counter()
            for t in range(4):
                eng.advance(float(t))
            best = min(best, time.perf_counter() - t0)
        times[mode] = best
    assert times["views"] < times["none"]
