"""Tests for the unified observability layer: span tracer, metrics
registry, slow-query log, and EXPLAIN ANALYZE (drift exactness + bitwise
result parity across every dispatch kind, unsharded and sharded).
"""
import numpy as np
import pytest

from benchmarks import tracy
from conftest import packed_column
from repro.core import query as q
from repro.core import operators as ops_lib
from repro.core.api import (Column, ColumnType, Database, IndexKind, Range,
                            Schema, VectorRank)
from repro.core.executor import Executor
from repro.core.lsm import LSMConfig
from repro.core.optimizer import planner as planner_lib
from repro.core.shards import ShardedExecutor, ShardRouter
from repro.kernels import ops as kops
from repro.obs import (REGISTRY, SLOW_LOG, TRACER, MetricsRegistry,
                       actuals_from, set_tracing, span)
from repro.obs import trace as obs_trace


@pytest.fixture(autouse=True)
def _obs_isolation():
    """Leave tracing off and the retention/slow-log buffers empty."""
    yield
    set_tracing(False)
    TRACER.clear()
    SLOW_LOG.configure(None)
    SLOW_LOG.clear()


# ---------------------------------------------------------------------------
# span tracer
# ---------------------------------------------------------------------------

def test_tracing_disabled_by_default():
    assert not obs_trace.enabled()
    before = len(TRACER.snapshot())
    sp = span("anything", rows=3)
    assert sp is obs_trace.NULL_SPAN and not sp.live
    with span("outer"):
        with span("inner") as inner:
            inner.set(rows=1)       # discarded, no error
    assert len(TRACER.snapshot()) == before
    assert obs_trace.current_span() is None


def test_span_nesting_and_retention():
    set_tracing(True)
    TRACER.clear()
    with span("flush", rows=10) as outer:
        assert outer.live and obs_trace.current_span() is outer
        with span("operator:X") as inner:
            inner.add("bytes", 64)
            inner.add("bytes", 36)
    roots = TRACER.snapshot()
    assert [r.name for r in roots] == ["flush"]
    (root,) = roots
    assert root.attrs == {"rows": 10} and root.dur >= 0.0
    assert [c.name for c in root.children] == ["operator:X"]
    assert root.children[0].attrs == {"bytes": 100}


def test_force_tracing_restores_prior_state():
    assert not obs_trace.enabled()
    with obs_trace.force_tracing():
        assert obs_trace.enabled()
        with pytest.raises(RuntimeError), obs_trace.force_tracing():
            assert obs_trace.enabled()
            raise RuntimeError("boom")
        assert obs_trace.enabled()
    assert not obs_trace.enabled()


def test_record_span_attaches_to_open_parent():
    set_tracing(True)
    TRACER.clear()
    with span("query") as sp:
        drain = obs_trace.Drain("operator:Scan")
        for _ in range(3):
            with drain as node:
                assert obs_trace.current_span() is node
                with span("host_op:l2_distances"):
                    pass
        assert obs_trace.current_span() is sp
        obs_trace.record_span(drain.node, rows=7)
    assert [c.name for c in sp.children] == ["operator:Scan"]
    child = sp.children[0]
    assert child.attrs["rows"] == 7
    assert [c.name for c in child.children] == ["host_op:l2_distances"] * 3
    assert child.dur >= sum(c.dur for c in child.children)
    assert not hasattr(child, "t0")
    # without a parent it lands in the ring buffer
    obs_trace.record_span(obs_trace.Drain("flush").node)
    assert [r.name for r in TRACER.snapshot()] == ["query", "flush"]


def test_tracer_tree_dump():
    set_tracing(True)
    TRACER.clear()
    with span("query", n=2):
        with span("operator:TopKMerge", k=5):
            pass
    dump = TRACER.tree()
    assert dump.startswith("query ") and "{n=2}" in dump
    assert "\n  operator:TopKMerge " in dump and "{k=5}" in dump


def test_tracer_counts_the_roots_it_drops():
    set_tracing(True)
    tracer = obs_trace.Tracer(maxlen=4)
    for i in range(7):
        tracer.retain(obs_trace.Span(f"r{i}"))
    assert [r.name for r in tracer.snapshot()] == ["r3", "r4", "r5", "r6"]
    assert tracer.dropped == 3
    tracer.clear()
    assert tracer.dropped == 3 and tracer.snapshot() == []


# ---------------------------------------------------------------------------
# spans on the profiler's clock
# ---------------------------------------------------------------------------

def _profiled(tmp_path, body):
    """Run ``body`` under obs tracing and a ``jax.profiler`` trace; the
    host events of the trace as {line index: [(name, start, end, stats)]}."""
    import jax
    from jax.profiler import ProfileData
    set_tracing(True)
    TRACER.clear()
    jax.profiler.start_trace(str(tmp_path))
    try:
        body()
    finally:
        jax.profiler.stop_trace()
        set_tracing(False)
    (path,) = tmp_path.glob("plugins/profile/*/*.xplane.pb")
    lines = {}
    for plane in ProfileData.from_file(str(path)).planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            evs = [(ev.name, ev.start_ns, ev.start_ns + ev.duration_ns,
                    dict(ev.stats)) for ev in line.events
                   if ev.name.startswith(("repro.", "test."))]
            if evs:
                lines[len(lines)] = evs
    return lines


def test_spans_reach_the_profiler_trace_nested_on_their_thread(tmp_path):
    import threading

    import jax

    def body():
        def elsewhere():
            with span("flush"):
                pass
        with jax.profiler.TraceAnnotation("test.outer"):
            with span("query", n=1):
                with span("operator:RankScore"):
                    with span("transfer:to_device", bytes=4096):
                        pass
            worker = threading.Thread(target=elsewhere)
            worker.start()
            worker.join()

    lines = _profiled(tmp_path, body)
    (mine,) = [evs for evs in lines.values()
               if any(n == "test.outer" for n, *_ in evs)]
    ev = {n: (s, e, st) for n, s, e, st in mine}
    assert obs_trace.PROFILE_PREFIX == "repro."
    assert set(ev) == {"test.outer", "repro.query", "repro.operator:RankScore",
                       "repro.transfer:to_device"}
    chain = ["test.outer", "repro.query", "repro.operator:RankScore",
             "repro.transfer:to_device"]
    for outer, inner in zip(chain, chain[1:]):
        assert ev[outer][0] <= ev[inner][0] <= ev[inner][1] <= ev[outer][1]
    assert ev["repro.transfer:to_device"][2] == {"bytes": 4096}
    # the worker's span is on its own thread's line
    others = [n for evs in lines.values() if evs is not mine
              for n, *_ in evs]
    assert others == ["repro.flush"]
    # the span tree agrees
    (root, flush) = sorted(TRACER.snapshot(), key=lambda r: r.name != "query")
    assert ["repro." + sp.name for sp in root.walk()] == chain[1:]
    assert flush.name == "flush"


class _Ctx:
    """The part of a ``PipelineContext`` a traced drain touches."""

    def __init__(self):
        self.stats = [ops_lib.ExecStats()]


class _Source(ops_lib.PhysicalOp):
    name = "Source"

    def _batches(self, ctx):
        for i in range(3):
            with span("host_op:range_bitmap"):
                ctx.stats[0].rows_scanned += 10
            yield i, np.ones((1, 4), bool)


def test_a_drained_source_gives_one_profiler_event_per_window(tmp_path):
    src = _Source([])

    def body():
        with span("operator:Consumer"):
            for _ in src.batches(_Ctx()):
                with span("test_consumer_work"):
                    sum(range(20000))

    lines = _profiled(tmp_path, body)
    (evs,) = lines.values()
    windows = [(s, e) for n, s, e, _ in evs if n == "repro.operator:Source"]
    work = [(s, e) for n, s, e, _ in evs
            if n == "repro.test_consumer_work"]
    inner = [(s, e) for n, s, e, _ in evs
             if n == "repro.host_op:range_bitmap"]
    assert len(windows) == 4            # three items, then exhaustion
    assert len(work) == 3 and len(inner) == 3
    for a, b in windows:
        for c, d in work:
            assert b <= c or d <= a     # no window overlaps the consumer
    for c, d in inner:                  # the source's own spans lie inside
        assert any(a <= c and d <= b for a, b in windows)
    # in memory: one span, the windows' summed time, the source's stats
    (root,) = TRACER.snapshot()
    names = [c.name for c in root.children]
    assert names == ["test_consumer_work"] * 3 + ["operator:Source"]
    node = root.children[-1]
    assert node.attrs["rows"] == 30 and node.attrs["out_rows"] == 12
    assert [c.name for c in node.children] == ["host_op:range_bitmap"] * 3
    assert node.dur <= sum(b - a for a, b in windows) * 1e-9 + 1e-3


def test_tracing_off_makes_no_span_object(tracy_ex, monkeypatch):
    ex, data = tracy_ex
    made = []
    real_init = obs_trace.Span.__init__

    def counting(self, *a, **kw):
        made.append(a[0])
        real_init(self, *a, **kw)

    monkeypatch.setattr(obs_trace.Span, "__init__", counting)
    monkeypatch.setattr(kops, "HOST_FLOP_CUTOFF", 0)   # device paths too
    data.rng = np.random.default_rng(5)
    qq = q.HybridQuery(
        where=q.Range("time", 100.0, 600.0),
        ranks=[q.VectorRank("embedding", data.query_vec(), 1.0)], k=10)
    ex.execute(qq)
    assert made == []
    set_tracing(True)
    ex.execute(qq)
    assert "query" in made and "planner" in made
    assert any(n.startswith("dispatch:") for n in made)


# ---------------------------------------------------------------------------
# kernel dispatch: spans by path, bytes to the device
# ---------------------------------------------------------------------------

def _dispatch_spans(call):
    """Run ``call`` traced; its root spans and the thread's counter
    deltas (bytes to the device, host dispatches)."""
    st = kops.thread_stats()
    up0, host0 = st.bytes_to_device, st.host_dispatches
    set_tracing(True)
    TRACER.clear()
    with span("test"):
        call()
    set_tracing(False)
    (root,) = TRACER.snapshot()
    return (root.children, st.bytes_to_device - up0,
            st.host_dispatches - host0)


def test_bytes_to_device_match_the_padded_operands():
    from repro.kernels import fused_scan as fs
    rng = np.random.default_rng(0)
    d = 128
    # l2_distances on the ref backend, above the host cut-off: the query
    # rows bucket to 8 and the vectors to a power of two >= 128
    q8 = rng.standard_normal((5, d)).astype(np.float32)
    x = rng.standard_normal((7000, d)).astype(np.float32)
    assert 5 * 7000 * d >= kops.HOST_FLOP_CUTOFF
    spans, up, host = _dispatch_spans(
        lambda: kops.l2_distances(q8, x, use_pallas=False))
    assert up == (8 * d + 8192 * d) * 4 and host == 0
    (sp,) = spans
    assert sp.name == "dispatch:l2_distances"
    assert [c.name for c in sp.children] == ["transfer:to_device",
                                             "transfer:to_host"]
    assert sp.children[0].attrs == {"bytes": up}
    # fused_scan_topk on the kernel path: 3 queries over 1,400 rows, 3
    # blocks that the column's device copy pads to 4.  The first call
    # uploads that copy (vectors, pks), then the queries, mask and
    # occupancy; the second finds the copy and uploads only those three
    BQ, BN = fs.BLOCK_Q, fs.BLOCK_N
    q3 = rng.standard_normal((3, d)).astype(np.float32)
    col = packed_column(rng.standard_normal((1400, d)).astype(np.float32),
                        np.arange(1400))
    mask = np.zeros((3, 1400), bool)
    mask[0, 5:40] = True
    mask[2, 1100:1300] = True
    rows = 4 * BN
    column = (rows * d * 4              # vectors, f32
              + rows * 4)               # pks, i32
    per_call = (BQ * d * 4              # queries (one tile), f32
                + BQ * rows             # mask, u8
                + 1 * 4 * 4)            # occupancy (tiles x blocks), i32
    for hit, uploads in ((0, [column, per_call]), (1, [per_call])):
        spans, up, host = _dispatch_spans(
            lambda: kops.fused_scan_topk(q3, col, mask, 10,
                                         use_pallas=True))
        assert up == sum(uploads) and host == 0
        (sp,) = spans
        assert sp.name == "dispatch:fused_scan_topk"
        assert sp.attrs == {"resident_hits": hit, "resident_lookups": 1}
        assert [c.attrs for c in sp.children] == \
            [{"bytes": b} for b in uploads] + [{}]
    # a host-path call uploads nothing
    spans, up, host = _dispatch_spans(
        lambda: kops.l2_distances(q3, col.x[:50], use_pallas=False))
    assert up == 0 and host == 1
    assert [s.name for s in spans] == ["host_op:l2_distances"]
    assert spans[0].children == []


@pytest.mark.parametrize("op,call", [
    ("l2_distances", lambda: kops.l2_distances(
        np.ones((2, 16), np.float32), np.ones((300, 16), np.float32),
        use_pallas=False)),
    ("range_bitmap", lambda: kops.range_bitmap(
        np.ones((300, 2), np.float32), np.array([[0, 2], [0, 2]], np.float32),
        use_pallas=False)),
    ("pq_adc_distances", lambda: kops.pq_adc_distances(
        np.ones(8, np.float32), np.zeros((300, 4), np.uint8),
        np.ones((4, 256, 2), np.float32), use_pallas=False)),
    ("fused_scan_topk", lambda: kops.fused_scan_topk(
        np.ones((1, 16), np.float32),
        packed_column(np.ones((300, 16), np.float32), np.arange(300)),
        np.ones((1, 300), bool), 4, use_pallas=False)),
])
def test_dispatch_span_names_follow_the_path(monkeypatch, op, call):
    monkeypatch.setattr(kops, "HOST_FLOP_CUTOFF", 10 ** 12)
    spans, up, host = _dispatch_spans(call)
    assert [s.name for s in spans] == [f"host_op:{op}"]
    assert host == 1 and up == 0
    monkeypatch.setattr(kops, "HOST_FLOP_CUTOFF", 0)
    spans, up, host = _dispatch_spans(call)
    assert [s.name for s in spans] == [f"dispatch:{op}"]
    assert host == 0 and up > 0
    assert [c.name for c in spans[0].children] == ["transfer:to_device",
                                                   "transfer:to_host"]


def test_merge_span_follows_the_path(monkeypatch):
    scores = np.ones((2, 3, 4), np.float32)
    ids = np.arange(24).reshape(2, 3, 4)
    spans, up, host = _dispatch_spans(
        lambda: kops.merge_topk_batch(scores, ids, 5, use_pallas=True))
    assert [s.name for s in spans] == ["dispatch:merge_topk_batch"]
    assert up == scores.nbytes + ids.size * 4 and host == 0
    # ids outside int32 take the exact host merge
    spans, up, host = _dispatch_spans(
        lambda: kops.merge_topk_batch(scores, ids + 2 ** 40, 5,
                                      use_pallas=True))
    assert [s.name for s in spans] == ["host_op:merge_topk_batch"]
    assert up == 0 and host == 1


def test_bytes_to_device_mirror_to_the_registry():
    REGISTRY.reset()
    kops.flush_registry_counters()
    REGISTRY.reset()
    before = kops.thread_stats().bytes_to_device
    kops.l2_distances(np.ones((8, 128), np.float32),
                      np.ones((4096, 128), np.float32), use_pallas=False)
    kops.flush_registry_counters()
    assert REGISTRY.get("kernels.bytes_to_device").value \
        == kops.thread_stats().bytes_to_device - before > 0


# ---------------------------------------------------------------------------
# metrics registry
# ---------------------------------------------------------------------------

def test_counter_gauge_histogram_basics():
    reg = MetricsRegistry()
    reg.inc("x.count")
    reg.inc("x.count", 4)
    reg.set_gauge("x.depth", 3.5)
    for v in (0.001, 0.002, 0.004, 0.2):
        reg.observe("x.latency_s", v)
    snap = reg.snapshot()
    assert snap["x.count"] == {"type": "counter", "value": 5}
    assert snap["x.depth"]["value"] == 3.5
    h = snap["x.latency_s"]
    assert h["count"] == 4 and h["sum"] == pytest.approx(0.207)
    # interpolated percentiles stay inside the observed range
    hist = reg.histogram("x.latency_s")
    for qq_ in (0.5, 0.95, 0.99):
        assert 0.001 <= hist.percentile(qq_) <= 0.2
    assert hist.p50 <= hist.p95 <= hist.p99


def test_registry_kind_mismatch_raises():
    reg = MetricsRegistry()
    reg.inc("a.b")
    with pytest.raises(TypeError):
        reg.observe("a.b", 0.1)


def test_prometheus_text_exposition():
    reg = MetricsRegistry()
    reg.inc("query.count", 3)
    reg.observe("query.latency_s", 0.004)
    reg.observe("query.latency_s", 0.040)
    text = reg.prometheus_text()
    lines = text.splitlines()
    assert "# TYPE repro_query_count counter" in lines
    assert "repro_query_count 3" in lines
    assert "# TYPE repro_query_latency_s histogram" in lines
    # cumulative bucket counts are monotone and end at the total
    cums = [int(ln.rsplit(" ", 1)[1]) for ln in lines
            if ln.startswith("repro_query_latency_s_bucket")]
    assert cums == sorted(cums) and cums[-1] == 2
    assert "repro_query_latency_s_count 2" in lines
    for quant in ("p50", "p95", "p99"):
        assert any(ln.startswith(f"repro_query_latency_s_{quant} ")
                   for ln in lines)


def test_kernel_counters_survive_registry_reset():
    kops._dispatched(128)
    kops.flush_registry_counters()   # publish the pending delta
    REGISTRY.reset()                 # drops metrics, bumps generation
    kops._dispatched(256)
    kops.flush_registry_counters()   # cached refs must re-resolve
    assert REGISTRY.get("kernels.launches").value == 1
    assert REGISTRY.get("kernels.bytes_to_host").value == 256


def test_kernel_counters_batch_to_registry():
    """The per-dispatch mirror is batched: deltas publish every
    REG_FLUSH_EVERY dispatches without an explicit flush call."""
    REGISTRY.reset()
    kops.flush_registry_counters()   # zero the thread's pending delta
    REGISTRY.reset()
    for _ in range(kops.REG_FLUSH_EVERY):
        kops._dispatched(4)
    assert REGISTRY.get("kernels.launches").value == kops.REG_FLUSH_EVERY
    assert (REGISTRY.get("kernels.bytes_to_host").value
            == 4 * kops.REG_FLUSH_EVERY)


def test_slow_query_log_threshold():
    SLOW_LOG.configure(0.01)
    assert not SLOW_LOG.maybe_record(0.005, "plan-fast")
    assert SLOW_LOG.maybe_record(0.02, "plan-slow", n_queries=3)
    (entry,) = SLOW_LOG.snapshot()
    assert entry["plan"] == "plan-slow" and entry["n_queries"] == 3
    assert entry["latency_s"] == 0.02 and entry["span_tree"] is None


# ---------------------------------------------------------------------------
# EXPLAIN ANALYZE: drift exactness + result parity
# ---------------------------------------------------------------------------

@pytest.fixture(scope="module")
def tracy_ex():
    cfg = tracy.TracyConfig(n_rows=1200, dim=32, seed=7, flush_rows=300,
                            fanout=64, pq_m=16)
    store, data = tracy.build_store(cfg)
    return Executor(store), data


@pytest.fixture(scope="module")
def graph_ex():
    cfg = tracy.TracyConfig(n_rows=1200, dim=32, seed=9, flush_rows=300,
                            fanout=64)
    store, data = tracy.build_store(cfg, vector_index=IndexKind.GRAPH,
                                    quantize=False)
    return Executor(store), data


def _pairs(rows):
    return [(r.pk, float(r.score)) for r in rows]


def test_analyze_drift_exact_on_tracy_templates(tracy_ex):
    """Per-query span-charged rows/bytes must equal ExecStats exactly:
    the analyze annotations are the cost model's ground truth."""
    ex, data = tracy_ex
    search, nn = tracy.make_templates(data)
    data.rng = np.random.default_rng(42)
    checked = 0
    for tmpl in search + nn:
        qq = tmpl()
        plan = planner_lib.plan(ex.catalog, qq)
        if plan.kind in ("nra", "postfilter_nn"):
            # index-walk dispatches do not itemize per-operator charges;
            # the scan shape of the same query must
            plan = planner_lib.plan_shared_scan(ex.catalog, qq)
        an = ex.explain_analyze(qq, plan=plan)
        rows = sum(a["rows"] for a in an.actuals.values())
        byts = sum(a["bytes"] for a in an.actuals.values())
        assert rows == an.stats.rows_scanned, an.text
        assert byts == an.stats.bytes_scanned, an.text
        checked += 1
    assert checked == len(search) + len(nn)


def test_analyze_annotates_every_operator(tracy_ex):
    ex, data = tracy_ex
    data.rng = np.random.default_rng(3)
    qq = q.HybridQuery(
        where=q.Range("time", 100.0, 600.0),
        ranks=[q.VectorRank("embedding", data.query_vec(), 1.0)], k=10)
    an = ex.explain_analyze(qq)
    lines = an.text.splitlines()
    assert lines[0].endswith("(analyzed)")
    ops_lines = [ln for ln in lines[1:] if "-> " in ln]
    assert ops_lines, an.text
    for ln in ops_lines:
        assert "(actual" in ln, ln
    # estimated nodes render estimated-vs-actual drift
    assert any("drift=" in ln and "drift=-" not in ln for ln in ops_lines), \
        an.text


def test_analyze_parity_all_dispatch_kinds(tracy_ex, graph_ex):
    """Analyze-mode results are bitwise-identical to plain execution on
    the exact, fused, quantized, and graph dispatches."""
    ex, data = tracy_ex
    gex, gdata = graph_ex
    data.rng = np.random.default_rng(11)
    gdata.rng = np.random.default_rng(11)
    rank = q.VectorRank("embedding", data.query_vec(), 1.0)
    base = dict(kind="full_scan_nn", ranks=[rank], k=10)
    cases = [
        (ex, planner_lib.Plan(fused=False, **base)),            # exact
        (ex, planner_lib.Plan(fused=True, **base)),             # fused
        (ex, planner_lib.Plan(fused=True, quantized=True,       # quantized
                              pq_m=16, refine=4, **base)),
        (gex, planner_lib.Plan(                                 # graph
            kind="full_scan_nn", k=10, graph=True, graph_r=16,
            graph_beam=40, graph_hops=8,
            ranks=[q.VectorRank("embedding", gdata.query_vec(), 1.0)])),
    ]
    for exec_, plan in cases:
        qq = q.HybridQuery(ranks=list(plan.ranks), k=plan.k)
        plain, _ = exec_.execute(qq, plan)
        an = exec_.explain_analyze(qq, plan=plan)
        assert _pairs(an.results) == _pairs(plain), plan.describe()
        assert "(actual" in an.text


def test_analyze_parity_sharded():
    cfg = tracy.TracyConfig(n_rows=1000, dim=16, seed=5, flush_rows=250)
    data = tracy.TracyData(cfg)
    router = ShardRouter(tracy.tweet_schema(cfg.dim, IndexKind.IVF),
                         LSMConfig(flush_rows=cfg.flush_rows),
                         n_shards=4)
    done = 0
    while done < cfg.n_rows:
        pks, batch = data.batch(250)
        router.put(pks, batch)
        done += 250
    router.flush()
    sex = ShardedExecutor(router)
    qq = q.HybridQuery(
        where=q.Range("time", 0.0, 700.0),
        ranks=[q.VectorRank("embedding", data.query_vec(), 1.0)], k=8)
    plain, _ = sex.execute(qq)
    an = sex.explain_analyze(qq)
    assert _pairs(an.results) == _pairs(plain)
    assert an.per_shard is not None and len(an.per_shard) == 4
    shard_lines = [ln for ln in an.text.splitlines() if "-> Shard [" in ln]
    assert len(shard_lines) == 4
    for ln in shard_lines:
        assert "(actual" in ln, ln


def test_analyze_leaves_tracing_off(tracy_ex):
    ex, data = tracy_ex
    data.rng = np.random.default_rng(23)
    qq = q.HybridQuery(where=q.Range("time", 0.0, 400.0), k=5)
    assert not obs_trace.enabled()
    ex.explain_analyze(qq)
    assert not obs_trace.enabled()
    # and a plain execute under the default records no spans
    before = len(TRACER.snapshot())
    ex.execute(qq)
    assert len(TRACER.snapshot()) == before


# ---------------------------------------------------------------------------
# facade: Database.metrics / metrics_text / slow_queries
# ---------------------------------------------------------------------------

def _mini_db(shards=1):
    sch = Schema([
        Column("emb", ColumnType.VECTOR, dim=8, index=IndexKind.IVF),
        Column("t", ColumnType.SCALAR, index=IndexKind.BTREE)])
    db = Database(sch, shards=shards)
    rng = np.random.default_rng(0)
    n = 600
    db.table().put(np.arange(n), {
        "emb": rng.standard_normal((n, 8)).astype(np.float32),
        "t": np.arange(n, dtype=np.float64)})
    db.table().flush()
    return db, rng


def test_database_metrics_and_prometheus():
    db, rng = _mini_db(shards=2)
    qb = (db.table().query().where(Range("t", 0, 300))
          .rank(VectorRank("emb", rng.standard_normal(8).astype(np.float32)))
          .limit(5))
    assert qb.all()
    m = db.metrics()
    assert "query.latency_s" in m["registry"]
    assert m["registry"]["query.count"]["value"] >= 1
    tbl = m["tables"]["default"]
    assert tbl["store"]["puts"] == 600
    assert sorted(tbl["shards"]) == [0, 1]
    assert sum(s["puts"] for s in tbl["shards"].values()) == 600
    assert tbl["executor"]["queries"] >= 1
    text = db.metrics_text()
    for needle in ("repro_query_latency_s_p50", "repro_query_latency_s_p95",
                   "repro_query_latency_s_p99", "repro_lsm_puts",
                   "repro_kernels_launches"):
        assert needle in text, needle


def test_database_slow_queries_and_builder_analyze():
    db, rng = _mini_db()
    SLOW_LOG.configure(0.0)          # everything is "slow"
    qb = (db.table().query().where(Range("t", 0, 300))
          .rank(VectorRank("emb", rng.standard_normal(8).astype(np.float32)))
          .limit(5))
    plain = qb.all()
    an = qb.explain(analyze=True)
    assert _pairs(an.results) == _pairs(plain)
    assert str(an) == an.text and "(analyzed)" in an.text
    entries = db.slow_queries()
    assert entries and all(e["latency_s"] >= 0.0 for e in entries)
    # the analyze run traced its query, so its entry kept the span tree
    assert any(e["span_tree"] for e in entries)
