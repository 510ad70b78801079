"""The precision control, at a size a test run holds: the reference with
its distances one step below the configuration's precision must come out
not correct by the run's own verdict, and the float64 reference in the
same place must pass it."""
import numpy as np

from bench import control
from bench import reference as R
from bench.run import find_cell


def test_bf16x3_control_fails_and_full_precision_passes():
    cell = "tracy.read-fused"
    small = {"config": {"preload_rows": 4096, "load_batch_rows": 4096}}
    seen = []
    for seed in (11, 2**31 + 1, 2**40 + 7):
        low = control.read_seed(cell, seed, 3, overrides=small)
        seen.append(low)
        assert low["answers"] == 27
    assert not any(r["correct"] for r in seen), seen


def test_the_control_keeps_its_split_as_rows_are_written():
    rng = np.random.default_rng(3)
    low = control.Control(8, cap=2)
    batch = {"embedding": rng.normal(size=(5, 8)).astype(np.float32),
             "coordinate": np.zeros((5, 2), np.float32),
             "content": np.asarray(["sports"] * 5, object),
             "time": np.zeros(5), "likes": np.ones(5)}
    low.write(np.arange(5), batch)            # grows past its first cap
    q = rng.normal(size=8).astype(np.float32)
    full = np.sqrt(((batch["embedding"].astype(np.float64) - q) ** 2)
                   .sum(axis=1))
    got = low.vec_dist(q)
    assert got.shape == (5,)
    assert np.allclose(got, full, rtol=1e-4)
    assert not np.array_equal(got, full)


def test_the_reference_in_its_own_place_is_exact():
    found = find_cell("tracy.read-fused")
    from bench.generator import Generator
    found["config"].update(preload_rows=2048, load_batch_rows=2048)
    gen = Generator(found["traffic"], found["config"], 5)
    ref = R.Reference(found["config"]["dim"])
    for pks, batch in gen.preload():
        ref.write(pks, batch)
    tally = R.Tally()
    for _ in range(18):
        spec = gen.query()[1]
        R.compare(ref, spec, R.top_k_answer(ref, spec), tally)
    assert tally.numbers() == {"rows_wrong": 0, "score_gap": 0.0}
