"""The copied float64 reference agrees with the program's facade on a
tiny TRACY store: filters, exact NN, updates and deletes, one refresh of
standing subscriptions; and it tells a wrong answer from a right one."""
import numpy as np
import pytest

from bench import program
from bench import reference as R
from bench.generator import Generator
from bench.run import find_cell

TEMPLATES = [f"t{i}" for i in range(1, 14)]


@pytest.fixture(scope="module")
def store(tmp_path_factory):
    found = find_cell("tracy.read-fused")
    cfg = dict(found["config"], preload_rows=3072, load_batch_rows=1024,
               lsm=dict(found["config"]["lsm"], flush_rows=1024, pq_m=8))
    traffic = dict(found["traffic"], templates=TEMPLATES,
                   write={"insert": 256, "update": 64, "delete": 64})
    program.import_program()
    gen = Generator(traffic, cfg, 2**32 + 3)
    db, table = program.open_table(cfg, str(tmp_path_factory.mktemp("db")))
    ref = R.Reference(cfg["dim"])
    for pks, batch in gen.preload():
        table.put(pks, batch)
        ref.write(pks, batch)
    table.flush()
    yield gen, table, ref
    db.close()


def answer(table, spec):
    return program.rows_of(table.execute(program.to_query(spec))[0])


def test_every_template_matches_before_and_after_writes(store):
    gen, table, ref = store
    for round_ in range(2):
        tally = R.Tally()
        for name in TEMPLATES:
            spec = gen._templates[name]()
            R.compare(ref, spec, answer(table, spec), tally, name)
        assert tally.answers == 13
        assert tally.rows_wrong == 0 and tally.score_gap < 1e-6, tally
        # inserts, overwrites of live rows and deletes, acknowledged
        _, ins, ins_b, upd, upd_b, dele = gen.write()
        table.put(ins, ins_b)
        table.put(upd, upd_b)
        table.delete(dele)
        ref.write(ins, ins_b)
        ref.write(upd, upd_b)
        ref.delete(dele)


def test_one_refresh_of_standing_queries_matches(store):
    gen, table, ref = store
    subs = []
    for i, shape in enumerate(["t2", "t4", "t5", "t6", "t8"] * 2):
        spec = gen._templates[shape]()
        q = program.to_query(spec)
        subs.append((f"{shape}.{i}", spec,
                     table.subscribe(q, interval_s=1.0) if i % 2 == 0
                     else table.subscribe(q, on_change=True)))
    table.advance(0.0)
    _, ins, ins_b, upd, upd_b, dele = gen.write()
    table.put(ins, ins_b)
    table.put(upd, upd_b)
    table.delete(dele)
    ref.write(ins, ins_b)
    ref.write(upd, upd_b)
    ref.delete(dele)
    table.advance(1.0)
    tally = R.Tally()
    for name, spec, sub in subs:
        R.compare(ref, spec, program.rows_of(sub.latest), tally, name)
    assert tally.answers == 10
    assert tally.rows_wrong == 0 and tally.score_gap < 1e-6, tally


def test_the_comparison_catches_wrong_answers(store):
    gen, table, ref = store
    nn = gen._templates["t6"]()
    good = R.top_k_answer(ref, nn)
    t = R.Tally()
    R.compare(ref, nn, good, t)
    assert t.numbers() == {"rows_wrong": 0, "score_gap": 0.0}
    t = R.Tally()       # the 11th best in place of the 10th
    R.compare(ref, nn, good[:9] + [R.top_k_answer(ref, dict(nn, k=11))[10]],
              t)
    assert t.score_gap > 1e-4
    t = R.Tally()
    R.compare(ref, nn, [(pk, s * (1 + 1e-4)) for pk, s in good], t)
    assert t.score_gap > 5e-5
    flt = gen._templates["t4"]()
    want = R.top_k_answer(ref, flt)
    t = R.Tally()
    R.compare(ref, flt, want[1:], t)
    assert t.rows_wrong == 1
    t = R.Tally()
    R.compare(ref, nn, good[:5], t)
    assert t.rows_wrong == 5
    t = R.Tally()
    R.compare(ref, nn, good[:9] + [good[0]], t)
    assert t.rows_wrong >= 1


def test_deleted_rows_leave_the_reference():
    ref = R.Reference(4, cap=2)
    batch = {"embedding": np.eye(4, dtype=np.float32)[:3],
             "coordinate": np.zeros((3, 2), np.float32),
             "content": np.asarray(["sports", "music tech", "w1"], object),
             "time": np.array([1.0, 2.0, 3.0]),
             "likes": np.array([1.0, 1.0, 1.0])}
    ref.write([0, 1, 2], batch)
    ref.delete([1])
    spec = {"where": ("range", "time", 0.0, 10.0), "ranks": [], "k": 10}
    assert [pk for pk, _ in R.top_k_answer(ref, spec)] == [0, 2]
    assert ref.count[1, ref.topics.index("tech")] == 1
