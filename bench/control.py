"""The precision control of a cell's comparison: the float64 reference put
in the program's place, with its embedding distances computed one step
below the precision the configuration states.

The program computes its distances in float32 at ``Precision.HIGHEST``;
the step below is ``high``: three bf16 passes (``x_hi q_hi + x_hi q_lo +
x_lo q_hi``), here written out in numpy so that it means the same on
every machine.  The control answers the cell's own traffic, drawn from
each seed as a run draws it (the preload, then blocks of queries, writes
and advances of the clock), and the same verdict as a run
(``bench/reference.py``, with the configuration's limits) must find it
not correct: its readings are the upper end of each limit in the
configuration file.

    python3 -m bench.control --workload tracy.read-fused --seeds 1,2,3

Prints one JSON line per seed with ``correct`` and the numbers compared,
then a summary.  The benchmark's own runs never run it.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path
from typing import Dict, Optional

import numpy as np

ROOT = Path(__file__).resolve().parents[1]
if str(ROOT) not in sys.path:
    sys.path.insert(0, str(ROOT))

from bench import reference as ref_lib  # noqa: E402
from bench.generator import Generator  # noqa: E402
from bench.run import find_cell  # noqa: E402


def to_bf16(a: np.ndarray) -> np.ndarray:
    """Round float32 to the nearest bfloat16 (ties to even), kept as
    float32."""
    u = np.ascontiguousarray(a, np.float32).view(np.uint32)
    r = (u + np.uint32(0x7FFF) + ((u >> 16) & np.uint32(1))) \
        & np.uint32(0xFFFF0000)
    return r.view(np.float32)


class Control(ref_lib.Reference):
    """The reference with its embedding distances as a TPU computes them
    at ``Precision.HIGH``: each float32 operand split into a bf16 high part
    and a bf16 low part, the dot product summed in float32 from the three
    products that keep the high part (``x_hi q_hi + x_hi q_lo + x_lo
    q_hi``; a bf16 product is exact in float32), then ``|x|^2 - 2 x.q +
    |q|^2`` in float32.  It is written out in numpy so that it means the
    same on every machine (XLA may keep excess precision through a bf16
    round trip).  The split is kept per row, as rows are written."""

    def __init__(self, dim: int, cap: int = 1 << 16):
        super().__init__(dim, cap)
        self.vec_dist = self._vec_dist_high

    def _alloc(self, cap: int) -> None:
        n, old = self.n, getattr(self, "x_hi", None) is not None
        kept = {k: getattr(self, k) for k in ("x_hi", "x_lo", "xn32")} \
            if old else {}
        super()._alloc(cap)
        for name, shape in (("x_hi", (cap, self.dim)),
                            ("x_lo", (cap, self.dim)), ("xn32", (cap,))):
            arr = np.zeros(shape, np.float32)
            if old:
                arr[:n] = kept[name][:n]
            setattr(self, name, arr)

    def write(self, pks, batch) -> None:
        super().write(pks, batch)
        pks = np.asarray(pks, np.int64)
        x = np.asarray(batch["embedding"], np.float32)
        hi = to_bf16(x)
        self.x_hi[pks] = hi
        self.x_lo[pks] = to_bf16(x - hi)
        self.xn32[pks] = (x * x).sum(axis=1, dtype=np.float32)

    def _vec_dist_high(self, point) -> np.ndarray:
        n = self.n
        q = np.asarray(point, np.float32)
        q_hi = to_bf16(q)
        q_lo = to_bf16(q - q_hi)
        x_hi = self.x_hi[:n]
        xq = x_hi @ q_hi + x_hi @ q_lo + self.x_lo[:n] @ q_hi
        d2 = self.xn32[:n] - np.float32(2.0) * xq + np.dot(q, q)
        return np.sqrt(np.maximum(d2, 0.0)).astype(np.float64)


def read_seed(workload: str, seed: int, blocks: int,
              overrides: Optional[Dict] = None,
              bench: Optional[Dict] = None) -> Dict:
    """The control over ``blocks`` blocks of the cell's traffic from
    ``seed``, after its preload: every write applied to both sides, every
    query answered by the control and judged by the run's own verdict.
    After each advance it answers the standing subscriptions as a run
    logs them: the SYNC ones that are due (every ``interval_s`` of the
    clock, from the first advance) and every ASYNC one.  ``bench`` stands
    in for ``BENCHMARK.json``."""
    found = find_cell(workload, bench=bench)
    for part, values in (overrides or {}).items():
        found[part].update(values)
    config = found["config"]
    gen = Generator(found["traffic"], config, seed)
    ref = ref_lib.Reference(config["dim"], cap=config["preload_rows"])
    low = Control(config["dim"], cap=config["preload_rows"])
    for pks, batch in gen.preload():
        ref.write(pks, batch)
        low.write(pks, batch)
    subs = gen.subscriptions()
    due = [0.0] * len(subs)
    tally = ref_lib.Tally()
    t0 = time.perf_counter()
    for b in range(blocks):
        for op in gen.block():
            if op[0] == "query":
                ref_lib.compare(ref, op[2], ref_lib.top_k_answer(low, op[2]),
                                tally, f"control block {b} {op[1]}")
                continue
            if op[0] == "advance":
                for i, (name, kind, interval, spec) in enumerate(subs):
                    if kind == "sync":
                        if op[1] < due[i]:
                            continue
                        due[i] = op[1] + interval
                    ref_lib.compare(ref, spec,
                                    ref_lib.top_k_answer(low, spec), tally,
                                    f"control block {b} sub {name}#{i} "
                                    f"{kind}")
                continue
            _, ins, ins_b, upd, upd_b, dele = op
            for side in (ref, low):
                side.write(ins, ins_b)
                side.write(upd, upd_b)
                side.delete(dele)
    ok, checks = tally.verdict(ref_lib.limits_for(config))
    return {"seed": seed, "correct": ok,
            **{k: c["value"] for k, c in checks.items()},
            "worst": tally.worst, "seconds": time.perf_counter() - t0}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--blocks", type=int, default=70,
                    help="blocks of the cell's traffic per seed (a run's "
                         "warm-up and window hold about 70)")
    args = ap.parse_args(argv)
    rows = []
    for s in args.seeds.split(","):
        rows.append(read_seed(args.workload, int(s), args.blocks))
        print(json.dumps(rows[-1]), flush=True)
    print(json.dumps({"correct_on_any_seed": any(r["correct"] for r in rows),
                      "least": {k: min(r[k] for r in rows) for k in
                                ("rows_wrong", "score_gap")}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
