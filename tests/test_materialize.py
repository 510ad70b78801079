"""Answer building (``core/operators.materialize``): the columnar build
returns what the per-row build returned, row for row and value for value,
and an answer's values stay as they were through later puts, deletes,
flushes and compactions."""
import numpy as np
import pytest

from conftest import make_batch, tweet_schema
from repro.core import operators as ops
from repro.core import query as q
from repro.core.executor import Executor
from repro.core.lsm import LSMConfig, LSMStore

DIM = 16


def per_row_materialize(ctx, query, c, k=None):
    """The per-row build the columnar one replaced, kept as the
    reference: one dict of the selected columns per returned row."""
    pks = np.empty(len(c.sids), np.int64)
    seg_by_id = {s.seg_id: s for s in ctx.segments}
    for sid in np.unique(c.sids):
        sel = c.sids == sid
        if sid < 0:
            pks[sel] = ctx.memtable_arrays()[0][c.rows[sel]]
        else:
            pks[sel] = seg_by_id[sid].pk[c.rows[sel]]
    order = np.lexsort((pks, c.scores))
    if k is not None:
        order = order[:k]
    select = query.select or [col.name for col in ctx.store.schema.columns]
    out = []
    for t in order:
        sid, row = int(c.sids[t]), int(c.rows[t])
        cols = ctx.memtable_arrays()[3] if sid < 0 \
            else seg_by_id[sid].columns
        out.append(ops.ResultRow(pk=int(pks[t]), score=float(c.scores[t]),
                                 values={name: cols[name][row]
                                         for name in select}))
    return out


def frozen(rows):
    """Each row's pk, score and values, copied out of the store."""
    return [(r.pk, r.score, {name: np.copy(v) if isinstance(v, np.ndarray)
                             else v for name, v in r.values.items()})
            for r in rows]


def assert_same_rows(got, want):
    assert [r.pk for r in got] == [r.pk for r in want]
    assert [r.score for r in got] == [r.score for r in want]
    for g, w in zip(got, want):
        assert isinstance(g.pk, int) and isinstance(g.score, float)
        assert list(g.values) == list(w.values)
        assert len(g.values) == len(w.values)
        for name in w.values:
            gv, wv = g.values[name], w.values[name]
            assert type(gv) is type(wv), name
            if isinstance(wv, (np.ndarray, np.generic)):
                assert gv.dtype == wv.dtype and gv.shape == wv.shape, name
                np.testing.assert_array_equal(gv, wv)
            else:
                assert gv == wv, name


@pytest.fixture
def store():
    """Three level-0 segments, then unflushed updates, deletes and new
    rows in the memtable: answers draw rows from every source."""
    rng = np.random.default_rng(17)
    st = LSMStore(tweet_schema(DIM), LSMConfig(flush_rows=400, fanout=4))
    for start in range(0, 1200, 400):
        pks, batch = make_batch(rng, 400, dim=DIM, pk_start=start)
        st.put(pks, batch)
    assert len(st.segments) == 3
    pks, batch = make_batch(rng, 60, dim=DIM, pk_start=1200)
    st.put(pks, batch)
    _, upd = make_batch(rng, 40, dim=DIM)
    st.put(list(range(100, 140)), upd)
    st.delete(list(range(500, 510)))
    assert st.memtable_rows == 110
    return st


def later_writes(st):
    """Overwrite and delete rows the answers hold, then flush and compact
    the store into one segment."""
    rng = np.random.default_rng(18)
    _, upd = make_batch(rng, 300, dim=DIM)
    st.put(list(range(0, 1200, 4)), upd)
    st.delete(list(range(1, 1200, 8)))
    st.flush()
    for start in range(2000, 2800, 400):
        pks, batch = make_batch(rng, 400, dim=DIM, pk_start=start)
        st.put(pks, batch)
    assert st.metrics["compactions"] >= 1


QV = np.random.default_rng(3).normal(size=DIM).astype(np.float32)
WHERE = q.Or(q.Range("time", 10.0, 40.0),
             q.TextContains("content", "apple"))

CASES = {
    "filter-all-columns": (q.HybridQuery(where=WHERE), False),
    "filter-select": (q.HybridQuery(where=WHERE,
                                    select=["time", "embedding"]), False),
    "ranked-k": (q.HybridQuery(ranks=[q.VectorRank("embedding", QV, 1.0)],
                               k=10), False),
    "ranked-filtered-select": (q.HybridQuery(
        where=q.Range("time", 0.0, 60.0),
        ranks=[q.SpatialRank("coordinate", (5.0, 5.0), 1.0)],
        k=50, select=["content", "coordinate"]), False),
    "empty": (q.HybridQuery(where=q.Range("time", 500.0, 600.0)), False),
    "filter-after-writes": (q.HybridQuery(where=WHERE), True),
    "ranked-after-writes": (q.HybridQuery(
        ranks=[q.VectorRank("embedding", QV, 1.0)], k=25), True),
}


@pytest.mark.parametrize("case", list(CASES))
def test_columnar_answers_equal_the_per_row_build(case, store, monkeypatch):
    query, then_write = CASES[case]
    built = []

    def both(ctx, qq, c, k=None):
        got = columnar(ctx, qq, c, k)
        built.append((got, per_row_materialize(ctx, qq, c, k)))
        return got

    columnar = ops.materialize
    monkeypatch.setattr(ops, "materialize", both)
    rows, _ = Executor(store).execute(query)
    assert len(built) == 1 and built[0][0] is rows
    assert_same_rows(rows, built[0][1])
    if case == "empty":
        assert rows == []
        return
    assert len(rows) > 1
    assert all(isinstance(r.values, ops.RowValues) for r in rows)
    if not then_write:
        return
    before = frozen(rows)
    later_writes(store)
    after = frozen(rows)
    assert [(pk, score) for pk, score, _ in after] == \
        [(pk, score) for pk, score, _ in before]
    for (_, _, b), (_, _, a) in zip(before, after):
        assert list(a) == list(b)
        for name in b:
            np.testing.assert_array_equal(a[name], b[name])
    # the writes reached rows of the answer
    written = set(range(0, 1200, 4)) | set(range(1, 1200, 8))
    assert written & {pk for pk, _, _ in before}
