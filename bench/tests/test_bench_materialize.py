"""The operators layer's answer-building metric: the self time of the
program's ``result:materialize`` spans per query, read from span trees
as the harness folds them."""
import pytest

from bench import program, run

program.import_program()
from repro.obs.trace import Span  # noqa: E402


def span(name, dur, *children):
    sp = Span(name)
    sp.dur = dur
    sp.children = list(children)
    return sp


def ranked_and_filter_queries(with_materialize):
    """Two queries: a ranked one, whose answer is built under
    ``operator:TopKMerge``, and a filter one, whose answer is built
    directly under ``query``.  Without ``with_materialize`` the trees are
    what a program without the span records."""
    if with_materialize:
        topk = span("operator:TopKMerge", 0.010,
                    span("result:materialize", 0.004))
        # a child span's time is not the build's own
        build = span("result:materialize", 0.030,
                     span("transfer:to_host", 0.010))
        filt = span("query", 0.050, span("operator:IndexProbe", 0.005),
                    build)
    else:
        topk = span("operator:TopKMerge", 0.010)
        filt = span("query", 0.050, span("operator:IndexProbe", 0.005))
    ranked = span("query", 0.040, span("operator:FusedScanTopK", 0.020),
                  topk)
    return [ranked, filt]


@pytest.mark.parametrize("with_materialize,want", [
    (True, (0.004 + 0.020) / 2 * 1e3),
    (False, None),
], ids=["spans", "parent-without-span"])
def test_materialize_ms_per_query_reads_the_spans_self_time(
        with_materialize, want):
    totals = run.SpanTotals()
    totals.add(ranked_and_filter_queries(with_materialize))
    rec = {"queries": 2, "spans": totals.totals()}
    got = run.metric_reader("operators.materialize_ms_per_query")(rec)
    if want is None:
        assert got is None
    else:
        assert got == pytest.approx(want)
