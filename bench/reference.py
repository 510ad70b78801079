"""Plain float64 reference for TRACY queries, and the comparison that
decides a run's ``correct``.

Brute force over the visible rows: the rows the harness generated, with
every acknowledged put and delete applied in order.  It reads the query
specs of ``bench/data/tracy.py`` and imports nothing of the program under
test.  Filter queries must return exactly the passing pk set; ranked
queries the ``k`` passing rows of least weighted distance, each with its
score.

``Tally`` keeps the numbers compared, each against a limit from the
configuration file:

  rows_wrong  rows missing from or extra in a filter answer, ranked rows
              that do not pass the filter or are not visible, duplicate
              rows, and a ranked answer of the wrong length (exact: 0)
  score_gap   how far a ranked answer lies from the reference, in score
              units relative to max(1, |reference score|): the worst of
              |returned score - reference score| over returned rows,
              (reference score of a returned row - k-th reference score),
              and (k-th score - score of a reference top-k row left out).
              Rounding moves it a little on every answer; a row from
              outside the top-k or a score from a coarser distance moves
              it more
"""
from __future__ import annotations

import dataclasses
from typing import Dict, Iterable, List, Sequence, Tuple

import numpy as np

from bench.data.tracy import TOPICS


class Reference:
    """The visible rows, slot = pk.  Text is parsed once into per-topic
    token counts; embeddings are kept in float64 with their norms."""

    def __init__(self, dim: int, cap: int = 1 << 16, topics=TOPICS):
        self.topics = list(topics)
        self._topic_ix = {t: i for i, t in enumerate(self.topics)}
        self.dim = dim
        self.n = 0                       # slots in use: max pk + 1
        # embedding distances to one vector, over all slots; the
        # precision control swaps in a lower-precision one
        self.vec_dist = self._vec_dist64
        self._alloc(cap)

    def _alloc(self, cap: int) -> None:
        old = getattr(self, "live", None)
        fields = {"emb": ((cap, self.dim), np.float64),
                  "xn": ((cap,), np.float64),
                  "coord": ((cap, 2), np.float32),
                  "time": ((cap,), np.float64),
                  "likes": ((cap,), np.float64),
                  "count": ((cap, len(self.topics)), np.int8),
                  "ntok": ((cap,), np.int16),
                  "live": ((cap,), bool)}
        for name, (shape, dtype) in fields.items():
            arr = np.zeros(shape, dtype)
            if old is not None:
                arr[:self.n] = getattr(self, name)[:self.n]
            setattr(self, name, arr)

    def _reserve(self, top: int) -> None:
        if top > len(self.live):
            cap = len(self.live)
            while cap < top:
                cap *= 2
            self._alloc(cap)

    def write(self, pks, batch) -> None:
        """Insert or overwrite rows ``pks`` with ``batch``."""
        pks = np.asarray(pks, np.int64)
        if not len(pks):
            return
        self._reserve(int(pks.max()) + 1)
        self.n = max(self.n, int(pks.max()) + 1)
        x = np.asarray(batch["embedding"], np.float64)
        self.emb[pks] = x
        self.xn[pks] = (x * x).sum(axis=1)
        self.coord[pks] = batch["coordinate"]
        self.time[pks] = batch["time"]
        self.likes[pks] = batch["likes"]
        counts = np.zeros((len(pks), len(self.topics)), np.int8)
        ntok = np.zeros(len(pks), np.int16)
        for r, text in enumerate(batch["content"]):
            toks = str(text).lower().split()
            ntok[r] = len(toks)
            for t in toks:
                i = self._topic_ix.get(t)
                if i is not None:
                    counts[r, i] += 1
        self.count[pks] = counts
        self.ntok[pks] = ntok
        self.live[pks] = True

    def delete(self, pks) -> None:
        self.live[np.asarray(pks, np.int64)] = False

    # ----------------------------------------------------------- answers
    def _dist(self, col: str, point) -> np.ndarray:
        """Distance of every slot to ``point`` (float64)."""
        if col == "embedding":
            return self.vec_dist(point)
        p = np.asarray(point, np.float64)
        c = self.coord[:self.n].astype(np.float64)
        return np.sqrt(((c - p) ** 2).sum(axis=1))

    def _vec_dist64(self, point) -> np.ndarray:
        n = self.n
        v = np.asarray(point, np.float64)
        d2 = self.xn[:n] - 2.0 * (self.emb[:n] @ v) + v @ v
        return np.sqrt(np.maximum(d2, 0.0))

    def pred(self, p) -> np.ndarray:
        """Bool mask over slots ``[0, n)`` (live rows only)."""
        n = self.n
        live = self.live[:n]
        op = p[0]
        if op == "and":
            m = live.copy()
            for c in p[1]:
                m &= self.pred(c)
            return m
        if op == "or":
            m = np.zeros(n, bool)
            for c in p[1]:
                m |= self.pred(c)
            return m
        if op == "range":
            v = getattr(self, p[1])[:n]
            return live & (v >= p[2]) & (v <= p[3])
        if op == "geo":
            r = np.asarray(p[2], np.float32)      # bounds in column dtype
            x, y = self.coord[:n, 0], self.coord[:n, 1]
            return live & (x >= r[0]) & (x <= r[2]) & (y >= r[1]) \
                & (y <= r[3])
        if op == "text":
            return live & (self.count[:n, self._topic_ix[p[2].lower()]] > 0)
        if op == "vrange":
            return live & (self._dist("embedding", p[2]) < p[3])
        raise TypeError(f"no reference for {p!r}")

    def scores(self, ranks) -> np.ndarray:
        """Weighted-sum rank distance of every slot (float64)."""
        total = np.zeros(self.n)
        for r in ranks:
            kind, col, arg, w = r
            if kind in ("vec", "spatial"):
                d = self._dist(col, arg)
            elif kind == "textrank":
                hits = sum(self.count[:self.n, self._topic_ix[t.lower()]]
                           .astype(np.float64) for t in arg)
                d = 1.0 / (1.0 + 10.0 * hits / (self.ntok[:self.n] + 1.0))
            else:
                raise TypeError(f"no reference for {r!r}")
            total += w * d
        return total

    def mask(self, spec) -> np.ndarray:
        return self.live[:self.n].copy() if spec["where"] is None \
            else self.pred(spec["where"])


# ---------------------------------------------------------------------------
# the comparison
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class Tally:
    answers: int = 0
    rows_wrong: int = 0
    score_gap: float = 0.0
    worst: str = ""

    def numbers(self) -> Dict[str, float]:
        return {"rows_wrong": self.rows_wrong, "score_gap": self.score_gap}

    def verdict(self, limits: Dict[str, float], min_answers: int = 1
                ) -> Tuple[bool, Dict[str, Dict[str, float]]]:
        """(correct, {name: {value, limit}}): every number within its
        limit and at least ``min_answers`` answers compared."""
        checks = {name: {"value": v, "limit": limits[name]}
                  for name, v in self.numbers().items()}
        checks["answers"] = {"value": self.answers, "limit": min_answers}
        ok = self.answers >= min_answers and all(
            c["value"] <= c["limit"] for name, c in checks.items()
            if name != "answers")
        return ok, checks


def compare(ref: Reference, spec, got: Sequence[Tuple[int, float]],
            tally: Tally, what: str = "") -> None:
    """Compare one answer, ``[(pk, score), ...]``, with the reference in
    its current state, and fold the result into ``tally``."""
    tally.answers += 1
    got_pks = np.asarray([g[0] for g in got], np.int64)
    mask = ref.mask(spec)
    before = (tally.rows_wrong, tally.score_gap)
    if not spec["ranks"]:
        want = np.flatnonzero(mask)
        tally.rows_wrong += len(np.setxor1d(want, got_pks)) \
            + len(got_pks) - len(np.unique(got_pks))
        _note_worst(tally, before, what)
        return
    rows = np.flatnonzero(mask)
    k = min(int(spec["k"]), len(rows))
    tally.rows_wrong += abs(len(got_pks) - k) \
        + len(got_pks) - len(np.unique(got_pks))
    if k == 0:
        _note_worst(tally, before, what)
        return
    full = ref.scores(spec["ranks"])
    s = full[rows]
    part = np.argpartition(s, k - 1)[:k]
    kth = float(s[part].max())
    scale = max(1.0, abs(kth))
    ok = (got_pks >= 0) & (got_pks < ref.n)
    ok[ok] &= mask[got_pks[ok]]
    tally.rows_wrong += int((~ok).sum())
    gap = 0.0
    for (pk, score), good in zip(got, ok):
        if not good:
            continue
        sref = float(full[pk])
        gap = max(gap, (sref - kth) / scale,
                  abs(float(score) - sref) / max(1.0, abs(sref)))
    missing = np.setdiff1d(rows[part], got_pks)
    if len(missing):
        gap = max(gap, float((kth - full[missing]).max()) / scale)
    tally.score_gap = max(tally.score_gap, gap)
    _note_worst(tally, before, what)


def _note_worst(tally: Tally, before, what: str) -> None:
    if (tally.rows_wrong, tally.score_gap) != before:
        tally.worst = what


# ---------------------------------------------------------------------------
# replay: the op log of a run, in order, against the reference
# ---------------------------------------------------------------------------

def replay(ref: Reference, log: Iterable, tally: Tally) -> Tally:
    """Apply a run's op log to ``ref`` in order and compare every answer
    it recorded.  Entries: ``("write", pks, batch)``, ``("delete", pks)``,
    ``("answer", spec, [(pk, score), ...], what)``."""
    for entry in log:
        kind = entry[0]
        if kind == "write":
            ref.write(entry[1], entry[2])
        elif kind == "delete":
            ref.delete(entry[1])
        elif kind == "answer":
            compare(ref, entry[1], entry[2], tally, entry[3])
        else:
            raise ValueError(f"unknown log entry {kind!r}")
    return tally


def top_k_answer(ref: Reference, spec) -> List[Tuple[int, float]]:
    """The reference's own answer to ``spec``: what the tests compare the
    program with, and what the precision control puts in its place."""
    rows = np.flatnonzero(ref.mask(spec))
    if not spec["ranks"]:
        return [(int(r), 0.0) for r in rows]
    s = ref.scores(spec["ranks"])[rows]
    k = min(int(spec["k"]), len(rows))
    order = np.lexsort((rows, s))[:k]
    return [(int(rows[i]), float(s[i])) for i in order]


def limits_for(config) -> Dict[str, float]:
    return {k: float(v) for k, v in config["checks"].items()}


def describe(checks: Dict[str, Dict[str, float]]) -> List[str]:
    return [f"check {name}: {c['value']!r} (limit {c['limit']!r})"
            for name, c in checks.items()]
