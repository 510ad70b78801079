"""Fused scan->top-k kernel path: parity sweeps vs the ref oracle and
end-to-end fused-vs-staged equivalence on the TRACY workload."""
import collections
import sys
import threading

import numpy as np
import pytest

from benchmarks import tracy
from conftest import make_batch, packed_column, tweet_schema
from repro.core import query as q
from repro.core import segment as seg_lib
from repro.core.executor import Executor
from repro.core.optimizer import planner as planner_lib
from repro.core.types import IndexKind
from repro.kernels import fused_scan as fs
from repro.kernels import ops as kops
from repro.kernels import ref
from repro.obs import REGISTRY

import jax.numpy as jnp


@pytest.fixture
def fused_toggle():
    prev = planner_lib.FUSED_ENABLED
    yield
    planner_lib.FUSED_ENABLED = prev


def _pad(a, mult, axis, value=0):
    pad = (-a.shape[axis]) % mult
    if pad == 0:
        return a
    widths = [(0, 0)] * a.ndim
    widths[axis] = (0, pad)
    return np.pad(a, widths, constant_values=value)


def _brute_topk(Q, X, mask, pks, k):
    """(d2, row) oracle: smallest squared-L2 per query over admitted
    rows, ties by (distance, pk)."""
    d2 = ((Q[:, None, :].astype(np.float64)
           - X[None, :, :].astype(np.float64)) ** 2).sum(-1)
    out = []
    for qi in range(len(Q)):
        dd = np.where(mask[qi], d2[qi], np.inf)
        order = np.lexsort((pks, dd))[:k]
        out.append(order[np.isfinite(dd[order])])
    return out


# ---------------------------------------------------------------------------
# kernel vs oracle parity
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("nq,n,d", [(8, 512, 16), (8, 1024, 64),
                                    (16, 512, 8)])
@pytest.mark.parametrize("mask_kind", ["full", "partial", "block_holes"])
def test_kernel_matches_ref(nq, n, d, mask_kind):
    rng = np.random.default_rng(0)
    Q = rng.normal(size=(nq, d)).astype(np.float32)
    X = rng.normal(size=(n, d)).astype(np.float32)
    if mask_kind == "full":
        mask = np.ones((nq, n), np.uint8)
    elif mask_kind == "partial":
        mask = (rng.random((nq, n)) < 0.3).astype(np.uint8)
    else:           # whole tiles masked for every query (occupancy skip)
        mask = np.ones((nq, n), np.uint8)
        mask[:, : fs.BLOCK_N] = 0
        mask[:, -fs.BLOCK_N // 2:] = 0
    pks = (np.arange(n, dtype=np.int32) * 7 + 3)
    occ = mask.reshape(nq // fs.BLOCK_Q, fs.BLOCK_Q,
                       n // fs.BLOCK_N, fs.BLOCK_N) \
        .any(axis=(1, 3)).astype(np.int32)
    kd, kp, ki = fs.fused_scan_topk(
        jnp.asarray(Q), jnp.asarray(X), jnp.asarray(mask),
        jnp.asarray(pks[None, :]), jnp.asarray(occ), interpret=True)
    rd, rp, ri = ref.fused_topk_ref(
        jnp.asarray(Q), jnp.asarray(X), jnp.asarray(mask),
        jnp.asarray(pks[None, :]), k=fs.KMAX)
    np.testing.assert_allclose(np.asarray(kd), np.asarray(rd),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_array_equal(np.asarray(ki), np.asarray(ri))
    np.testing.assert_array_equal(np.asarray(kp), np.asarray(rp))


def test_kernel_tie_break_by_pk():
    """Duplicate vectors give bitwise-equal distances: the winner must
    be the smallest pk, in both backends, regardless of row order."""
    rng = np.random.default_rng(1)
    d = 16
    base = rng.normal(size=(8, d)).astype(np.float32)
    X = np.repeat(base, 64, axis=0)                  # 512 rows, 8 classes
    perm = rng.permutation(len(X))
    X = X[perm]
    pks = rng.permutation(len(X)).astype(np.int32) * 5 + 2
    Q = base[:1] + 0.01
    Qp = _pad(Q, fs.BLOCK_Q, 0)
    mask = np.ones((len(Qp), len(X)), np.uint8)
    occ = np.ones((1, 1), np.int32)
    kd, kp, ki = fs.fused_scan_topk(
        jnp.asarray(Qp), jnp.asarray(X), jnp.asarray(mask),
        jnp.asarray(pks[None, :]), jnp.asarray(occ), interpret=True)
    kd, kp, ki = (np.asarray(a)[0] for a in (kd, kp, ki))
    # within every run of equal distances, pks must ascend
    for i in range(1, fs.KMAX):
        if kd[i] == kd[i - 1]:
            assert kp[i] > kp[i - 1]
    rd, rp, ri = ref.fused_topk_ref(
        jnp.asarray(Qp), jnp.asarray(X), jnp.asarray(mask),
        jnp.asarray(pks[None, :]), k=fs.KMAX)
    np.testing.assert_array_equal(ki, np.asarray(ri)[0])


# ---------------------------------------------------------------------------
# ops wrapper: ragged shapes, k sweep, degenerate bitmaps, backends
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("k", [1, 10, 128])
@pytest.mark.parametrize("nq,n,d", [(1, 700, 24), (5, 1400, 32),
                                    (9, 130, 8)])
def test_ops_fused_matches_bruteforce_ragged(nq, n, d, k):
    rng = np.random.default_rng(2)
    Q = rng.normal(size=(nq, d)).astype(np.float32)
    X = rng.normal(size=(n, d)).astype(np.float32)
    mask = rng.random((nq, n)) < 0.4
    mask[0, :] = False                                # all-masked query
    if nq > 1:
        mask[1, :] = True                             # full bitmap
    pks = np.arange(n, dtype=np.int64) * 3 + 11
    want = _brute_topk(Q, X, mask, pks, k)
    for up in (True, False):
        d2, rows = kops.fused_scan_topk(Q, packed_column(X, pks), mask, k,
                                        use_pallas=up)
        assert d2.shape == (nq, k) and rows.shape == (nq, k)
        for qi in range(nq):
            got = rows[qi][rows[qi] >= 0]
            np.testing.assert_array_equal(got, want[qi],
                                          err_msg=f"q{qi} pallas={up}")
            assert (rows[qi][len(want[qi]):] == -1).all()
            assert np.isinf(d2[qi][len(want[qi]):]).all()


def test_ops_fused_all_masked_segment_and_empty():
    rng = np.random.default_rng(3)
    Q = rng.normal(size=(3, 16)).astype(np.float32)
    X = rng.normal(size=(1100, 16)).astype(np.float32)
    pks = np.arange(1100, dtype=np.int64)
    # a whole "segment" range masked for every query (block compaction)
    mask = np.ones((3, 1100), bool)
    mask[:, 200:900] = False
    want = _brute_topk(Q, X, mask, pks, 10)
    col = packed_column(X, pks)
    for up in (True, False):
        _, rows = kops.fused_scan_topk(Q, col, mask, 10, use_pallas=up)
        for qi in range(3):
            np.testing.assert_array_equal(rows[qi][rows[qi] >= 0],
                                          want[qi])
    # fully empty bitmap and empty input
    _, rows = kops.fused_scan_topk(Q, col, np.zeros((3, 1100), bool), 4)
    assert (rows == -1).all()
    _, rows = kops.fused_scan_topk(
        Q, packed_column(np.zeros((0, 16), np.float32), np.zeros(0)),
        np.zeros((3, 0), bool), 4)
    assert rows.shape == (3, 4) and (rows == -1).all()


def test_ops_fused_jit_ref_path_matches_host(monkeypatch):
    """Force the jit'd ref backend (cutoff=0) against the host fast
    path: same rows selected on non-tied data."""
    rng = np.random.default_rng(4)
    Q = rng.normal(size=(4, 24)).astype(np.float32)
    X = rng.normal(size=(900, 24)).astype(np.float32)
    mask = rng.random((4, 900)) < 0.5
    pks = np.arange(900, dtype=np.int64) + 5
    col = packed_column(X, pks)
    d2_host, rows_host = kops.fused_scan_topk(Q, col, mask, 12,
                                              use_pallas=False)
    monkeypatch.setattr(kops, "HOST_FLOP_CUTOFF", 0)
    d2_jit, rows_jit = kops.fused_scan_topk(Q, col, mask, 12,
                                            use_pallas=False)
    np.testing.assert_array_equal(rows_host, rows_jit)
    np.testing.assert_allclose(d2_host, d2_jit, rtol=2e-4, atol=2e-4)


# ---------------------------------------------------------------------------
# the packed column's device copy (every device backend)
# ---------------------------------------------------------------------------

def _compacted(Q, X, mask, pks, k):
    """The fused kernel behind host-side block compaction, as the device
    path ran before its column stayed on the device: only blocks that
    some query admits are uploaded, bucketed to a power of two, and the
    kernel's rows map back through the kept blocks."""
    nq, dim = len(Q), X.shape[1]
    BQ, BN = fs.BLOCK_Q, fs.BLOCK_N
    xp = _pad(X, BN, 0)
    mp = _pad(mask.astype(np.uint8), BN, 1)
    pkp = _pad(np.asarray(pks, np.int64), BN, 0, value=int(fs.SENTINEL))
    nb = len(xp) // BN
    keep = np.nonzero(mp.reshape(nq, nb, BN).any(axis=(0, 2)))[0]
    nkeep = len(keep) * BN
    npad = kops._bucket(len(keep), floor=1) * BN
    xk = np.zeros((npad, dim), np.float32)
    mk = np.zeros((nq, npad), np.uint8)
    pkk = np.full(npad, int(fs.SENTINEL), np.int64)
    xk[:nkeep] = xp.reshape(nb, BN, dim)[keep].reshape(-1, dim)
    mk[:, :nkeep] = mp.reshape(nq, nb, BN)[:, keep].reshape(nq, -1)
    pkk[:nkeep] = pkp.reshape(nb, BN)[keep].reshape(-1)
    qp = _pad(Q, BQ, 0)
    mkq = _pad(mk, BQ, 0)
    occ = mkq.reshape(len(qp) // BQ, BQ, npad // BN, BN) \
        .any(axis=(1, 3)).astype(np.int32)
    d2, _, idx = fs.fused_scan_topk(
        *(jnp.asarray(a) for a in (qp, xk, mkq,
                                   pkk.astype(np.int32)[None, :], occ)),
        k=k, interpret=True)
    d2, idx = np.asarray(d2)[:nq, :k], np.asarray(idx)[:nq, :k]
    safe = np.minimum(idx, nkeep - 1)
    rows = keep[safe // BN] * BN + safe % BN
    return d2, np.where(idx == int(fs.SENTINEL), -1, rows)


def _mask(kind, nq, n, rng):
    if kind == "all_pass":
        return np.ones((nq, n), bool)
    if kind == "partial":
        return rng.random((nq, n)) < 0.3
    mask = rng.random((nq, n)) < 0.5
    if kind == "block_holes":           # whole blocks no query admits
        mask[:, :fs.BLOCK_N] = False
        mask[:, 2 * fs.BLOCK_N:3 * fs.BLOCK_N] = False
    else:                               # a segment no query admits
        mask[:, n // 5:4 * n // 5] = False
    return mask


@pytest.mark.parametrize("mask_kind", ["all_pass", "partial", "block_holes",
                                       "masked_segment"])
@pytest.mark.parametrize("nq,n,k", [(8, 2048, 10), (5, 1700, 128),
                                    (11, 1300, 7)])
def test_resident_path_matches_compacted_and_bruteforce(nq, n, k,
                                                        mask_kind):
    """Same kernel, same arithmetic, same (distance, pk, row) order: the
    resident column's answers are bitwise those of the compacted upload,
    and its rows those of a float64 brute force."""
    rng = np.random.default_rng(nq * n)
    Q = rng.normal(size=(nq, 24)).astype(np.float32)
    X = rng.normal(size=(n, 24)).astype(np.float32)
    pks = rng.permutation(n).astype(np.int64) * 3 + 1
    mask = _mask(mask_kind, nq, n, rng)
    d2, rows = kops.fused_scan_topk(Q, packed_column(X, pks), mask, k,
                                    use_pallas=True)
    want_d2, want_rows = _compacted(Q, X, mask, pks, k)
    np.testing.assert_array_equal(rows, want_rows)
    np.testing.assert_array_equal(d2.view(np.uint32),
                                  want_d2.view(np.uint32))
    want = _brute_topk(Q, X, mask, pks, k)
    for qi in range(nq):
        np.testing.assert_array_equal(rows[qi][rows[qi] >= 0], want[qi])


def test_second_call_uploads_only_queries_mask_and_occupancy():
    rng = np.random.default_rng(5)
    nq, n, d = 3, 1400, 128
    Q = rng.normal(size=(nq, d)).astype(np.float32)
    col = packed_column(rng.normal(size=(n, d)).astype(np.float32),
                        np.arange(n))
    mask = rng.random((nq, n)) < 0.3
    npad = 4 * fs.BLOCK_N                  # 3 blocks, bucketed to 4
    column = npad * d * 4 + npad * 4       # vectors f32, pks i32
    per_call = fs.BLOCK_Q * d * 4 + fs.BLOCK_Q * npad + 4 * 4
    kops.flush_registry_counters()      # what earlier calls left pending
    first = None
    for want_up, want_hits, want_misses in ((column + per_call, 0, 1),
                                            (per_call, 1, 0),
                                            (per_call, 1, 0)):
        REGISTRY.reset()
        d2, rows = kops.fused_scan_topk(Q, col, mask, 10, use_pallas=True)
        kops.flush_registry_counters()
        assert REGISTRY.get("kernels.bytes_to_device").value == want_up
        assert REGISTRY.get("kernels.resident_hits").value == want_hits
        assert REGISTRY.get("kernels.resident_misses").value == want_misses
        first = first or (d2, rows)
        np.testing.assert_array_equal(rows, first[1])
        np.testing.assert_array_equal(d2, first[0])


def _segments(rng, sizes):
    segs, pk0 = [], 0
    for n in sizes:
        pks, batch = make_batch(rng, n, pk_start=pk0)
        segs.append(seg_lib.Segment(tweet_schema(), np.asarray(pks),
                                    np.zeros(n, np.int64),
                                    np.zeros(n, bool), batch))
        pk0 += n
    return segs


def test_a_new_segment_set_misses_once_then_hits():
    rng = np.random.default_rng(6)
    segs = _segments(rng, [700, 600, 300])
    Q = rng.normal(size=(2, 16)).astype(np.float32)
    st = kops.thread_stats()

    def run(group):
        packed = seg_lib.pack_segments(group, "embedding")
        h0, m0 = st.resident_hits, st.resident_misses
        mask = np.ones((2, len(packed.x)), bool)
        _, rows = kops.fused_scan_topk(Q, packed, mask, 5, use_pallas=True)
        want = _brute_topk(Q, packed.x, mask, packed.pks, 5)
        for qi in range(2):
            np.testing.assert_array_equal(rows[qi], want[qi])
        return packed, (st.resident_hits - h0, st.resident_misses - m0)

    two, got = run(segs[:2])
    assert got == (0, 1)
    again, got = run(segs[:2])
    assert again is two and got == (1, 0)
    three, got = run(segs)              # a flush added a segment
    assert three is not two and got == (0, 1)
    assert run(segs)[1] == (1, 0)
    assert run(segs[:2])[1] == (1, 0)   # the older set is still cached


def test_evicting_the_pack_entry_drops_its_device_copy():
    rng = np.random.default_rng(7)
    segs = _segments(rng, [600] * (seg_lib._PACK_CACHE_CAP + 1))
    Q = rng.normal(size=(1, 16)).astype(np.float32)
    mask = np.ones((1, 600), bool)
    first = seg_lib.pack_segments(segs[:1], "embedding")
    _, want = kops.fused_scan_topk(Q, first, mask, 5, use_pallas=True)
    assert first.device is not None
    for s in segs[1:]:                  # as many newer sets as the cap
        seg_lib.pack_segments([s], "embedding")
    assert first.device is None and first.evicted
    # a dispatch still holding the evicted column answers, keeping no copy
    st = kops.thread_stats()
    m0 = st.resident_misses
    _, rows = kops.fused_scan_topk(Q, first, mask, 5, use_pallas=True)
    np.testing.assert_array_equal(rows, want)
    assert st.resident_misses == m0 + 1 and first.device is None
    assert seg_lib.pack_segments(segs[:1], "embedding") is not first


def test_concurrent_dispatches_share_one_device_copy(monkeypatch):
    """Threads dispatch over segment sets while the LRU evicts (more sets
    than its cap): every answer is the brute force's, and each column
    serves one device copy for as long as it is cached."""
    rng = np.random.default_rng(8)
    segs = _segments(rng, [520] * 8)
    groups = [segs[:2], segs[2:4], segs[:3], segs[4:6], segs[5:8], segs[6:]]
    Q = rng.normal(size=(3, 16)).astype(np.float32)
    want = []
    for g in groups:
        packed = seg_lib.pack_segments(g, "embedding")
        want.append(_brute_topk(Q, packed.x, np.ones((3, len(packed.x)),
                                                     bool), packed.pks, 5))
    served, errors = [], []
    publish = seg_lib.PackedColumn.publish_device

    def recording(self, handle):
        got = publish(self, handle)
        served.append((self, got, self.evicted))
        return got

    monkeypatch.setattr(seg_lib.PackedColumn, "publish_device", recording)

    def worker(offset):
        try:
            for i in range(24):
                gi = (i + offset) % len(groups)
                packed = seg_lib.pack_segments(groups[gi], "embedding")
                _, rows = kops.fused_scan_topk(
                    Q, packed, np.ones((3, len(packed.x)), bool), 5,
                    use_pallas=True)
                for qi in range(3):
                    np.testing.assert_array_equal(rows[qi], want[gi][qi])
        except Exception as e:          # surfaced by the main thread
            errors.append(e)

    prev = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        threads = [threading.Thread(target=worker, args=(t,))
                   for t in range(6)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=240)
        assert not any(t.is_alive() for t in threads)
    finally:
        sys.setswitchinterval(prev)
    assert not errors, errors[0]
    assert served
    copies = collections.defaultdict(set)
    for packed, handle, evicted in served:
        if not evicted:
            copies[id(packed)].add(id(handle))
    assert all(len(ids) == 1 for ids in copies.values())


@pytest.fixture(scope="module")
def rerank_stores():
    quant, qdata = tracy.build_store(tracy.TracyConfig(
        n_rows=1200, dim=32, seed=7, flush_rows=300, fanout=64, pq_m=16))
    graph, gdata = tracy.build_store(
        tracy.TracyConfig(n_rows=2400, dim=128, seed=7, flush_rows=600,
                          fanout=64),
        vector_index=IndexKind.GRAPH, quantize=False)
    return {"quantized": (quant, qdata), "graph": (graph, gdata)}


@pytest.mark.parametrize("kind", ["quantized", "graph"])
def test_reranks_share_the_resident_column(rerank_stores, kind,
                                           monkeypatch):
    """On a device backend the quantized and graph dispatches re-rank
    through the exact scan's resident column: one copy serves all three
    dispatches, and the answers equal the exact dispatch's."""
    store, data = rerank_stores[kind]
    monkeypatch.setattr(kops, "USE_PALLAS", True)
    ex = Executor(store)
    data.rng = np.random.default_rng(61)
    vecs = [data.query_vec() for _ in range(4)]
    approx = [q.HybridQuery(ranks=[q.VectorRank("embedding", v, 1.0)],
                            k=10, recall_target=0.9) for v in vecs]
    plans = [planner_lib.plan(ex.catalog, qq) for qq in approx]
    for p in plans:                     # wide enough to cover the top-k
        if kind == "quantized":
            assert p.quantized
            p.refine = 12
        else:
            assert p.graph
            p.graph_beam, p.graph_hops = int(fs.KMAX), 12
    st = kops.thread_stats()
    h0, m0 = st.resident_hits, st.resident_misses
    exact = ex.execute_many([q.HybridQuery(
        ranks=[q.VectorRank("embedding", v, 1.0)], k=10) for v in vecs])
    got = ex.execute_many(approx, plans=plans)
    assert [[(r.pk, float(r.score)) for r in rows] for rows, _ in got] == \
        [[(r.pk, float(r.score)) for r in rows] for rows, _ in exact]
    assert all(f"dispatch={kind}" in st_.plan for _, st_ in got)
    assert st.resident_misses - m0 == 1 and st.resident_hits > h0


# ---------------------------------------------------------------------------
# end-to-end: fused vs staged over the TRACY workload
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tracy_store():
    cfg = tracy.TracyConfig(n_rows=1200, dim=32, seed=7, flush_rows=300,
                            fanout=64)
    store, data = tracy.build_store(cfg)
    # live memtable rows on top of the segments (overlay must merge)
    pks, batch = data.batch(40)
    store.put(pks, batch)
    return store, data


def _run_both(ex, queries_a, queries_b):
    planner_lib.FUSED_ENABLED = True
    fused = ex.execute_many(queries_a)
    planner_lib.FUSED_ENABLED = False
    staged = ex.execute_many(queries_b)
    return fused, staged


def test_execute_many_fused_vs_staged_tracy(tracy_store, fused_toggle):
    store, data = tracy_store
    assert len(store.segments) >= 4 and store.memtable_rows > 0
    _, nn_t = tracy.make_templates(data)
    ex = Executor(store)
    any_fused = False
    for ti, tmpl in enumerate(nn_t):
        data.rng = np.random.default_rng(50 + ti)
        qa = [tmpl() for _ in range(6)]
        data.rng = np.random.default_rng(50 + ti)
        qb = [tmpl() for _ in range(6)]
        fused, staged = _run_both(ex, qa, qb)
        used = any("dispatch=fused" in st.plan for _, st in fused)
        any_fused |= used
        for (ra, sa), (rb, sb) in zip(fused, staged):
            assert [(r.pk, float(r.score)) for r in ra] == \
                [(r.pk, float(r.score)) for r in rb], f"template {ti}"
            if used:
                assert sa.kernel_launches <= sb.kernel_launches
                assert sa.bytes_to_host < sb.bytes_to_host
    assert any_fused, "no template exercised the fused path"


def test_fused_plan_explain_and_stats(tracy_store, fused_toggle):
    store, data = tracy_store
    ex = Executor(store)
    planner_lib.FUSED_ENABLED = True
    qq = q.HybridQuery(ranks=[q.VectorRank(
        "embedding", data.query_vec(), 1.0)], k=10)
    plan = planner_lib.plan_shared_scan(ex.catalog, qq)
    assert plan.fused
    text = plan.describe()
    assert "dispatch=fused" in text and "FusedScanTopK" in text
    assert "RankScore" not in text
    planner_lib.FUSED_ENABLED = False
    plan2 = planner_lib.plan_shared_scan(ex.catalog, qq)
    assert not plan2.fused and "RankScore" in plan2.describe()
    planner_lib.FUSED_ENABLED = True
    res, st = ex.execute(qq, plan)
    assert len(res) == 10
    assert st.kernel_launches >= 1 and st.bytes_to_host > 0


def test_fused_gate_requires_unique_pks(fused_toggle):
    planner_lib.FUSED_ENABLED = True
    cfg = tracy.TracyConfig(n_rows=600, dim=16, seed=3, flush_rows=200,
                            fanout=64)
    store, data = tracy.build_store(cfg)
    ex = Executor(store)
    qq = q.HybridQuery(ranks=[q.VectorRank(
        "embedding", data.query_vec(), 1.0)], k=5)
    assert planner_lib.plan_shared_scan(ex.catalog, qq).fused
    # overwrite an existing pk: visibility resolution now matters, and
    # the device-side cut would race it -> the planner must fall back
    pks, batch = data.batch(1)
    store.put([0], batch)
    store.flush()
    assert not store.unique_pks
    ex2 = Executor(store)
    plan = planner_lib.plan_shared_scan(ex2.catalog, qq)
    assert not plan.fused
    res, _ = ex2.execute(qq, plan)
    assert len({r.pk for r in res}) == len(res)       # winners, no dupes


def test_fused_gate_rank_shapes(tracy_store, fused_toggle):
    store, data = tracy_store
    ex = Executor(store)
    planner_lib.FUSED_ENABLED = True
    vec = data.query_vec()
    multi = q.HybridQuery(ranks=[q.VectorRank("embedding", vec, 0.5),
                                 q.SpatialRank("coordinate", (1., 2.), 0.2)],
                          k=5)
    assert not planner_lib.plan_shared_scan(ex.catalog, multi).fused
    big_k = q.HybridQuery(ranks=[q.VectorRank("embedding", vec, 1.0)],
                          k=fs.KMAX + 1)
    assert not planner_lib.plan_shared_scan(ex.catalog, big_k).fused
    neg_w = q.HybridQuery(ranks=[q.VectorRank("embedding", vec, -1.0)],
                          k=5)
    assert not planner_lib.plan_shared_scan(ex.catalog, neg_w).fused


def test_vector_range_squared_compare(tracy_store):
    """VectorRange masks compare squared distances (satellite): results
    must equal the sqrt formulation, including thresh <= 0."""
    from repro.core.operators import eval_predicate_rows
    store, data = tracy_store
    seg = store.segments[0]
    vecs = np.asarray(seg.columns["embedding"], np.float32)
    qv = data.query_vec()
    for thresh in (8.0, 0.0, -1.0):
        pred = q.VectorRange("embedding", qv, thresh)
        got = eval_predicate_rows({"embedding": vecs}, pred)
        want = np.sqrt(np.maximum(
            ((vecs - qv[None, :]) ** 2).sum(1), 0)) < thresh
        np.testing.assert_array_equal(got, want)
