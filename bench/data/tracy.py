"""TRACY data and query templates (ARCADE paper, arXiv 2509.19757, §7.1).

A copy kept with the benchmark, so that no change to the program can move
the yardstick: a tweet table with 128-d embeddings around topic centres,
geo points, text over ten topic words and two scalars (``time``,
``likes``), and the 13 parameterised hybrid query templates.

Every random draw comes from ``numpy`` generators keyed by the run's seed
and a stream number, so the same seed gives the same rows and queries:

    centres = topic_centers(seed, dim, n_topics)
    rows    = TracyData(seed, STREAM_ROWS, centres)
    queries = TracyData(seed, STREAM_QUERIES, centres)
"""
from __future__ import annotations

from typing import Callable, Dict, Tuple

import numpy as np

TOPICS = ["sports", "music", "food", "travel", "tech", "finance",
          "weather", "movies", "health", "politics"]

STREAM_CENTERS = 0
STREAM_ROWS = 1
STREAM_QUERIES = 2
STREAM_OPS = 3


def rng_for(seed: int, stream: int) -> np.random.Generator:
    """One independent generator per (seed, stream); any non-negative
    seed, however many bits it has."""
    return np.random.default_rng([int(seed), int(stream)])


def topic_centers(seed: int, dim: int, n_topics: int) -> np.ndarray:
    return rng_for(seed, STREAM_CENTERS).normal(
        size=(n_topics, dim)).astype(np.float32)


class TracyData:
    """Rows and query parameters from one seeded stream."""

    def __init__(self, seed: int, stream: int, centers: np.ndarray):
        self.rng = rng_for(seed, stream)
        self.topic_centers = centers
        self.n_topics, self.dim = centers.shape

    def batch(self, n: int) -> Dict[str, np.ndarray]:
        """``n`` fresh rows as a columnar batch (pks are the caller's)."""
        rng = self.rng
        topics = rng.integers(0, self.n_topics, n)
        emb = (self.topic_centers[topics]
               + 0.4 * rng.normal(size=(n, self.dim))).astype(np.float32)
        pts = rng.uniform(0, 100, (n, 2)).astype(np.float32)
        second = rng.integers(0, self.n_topics, n)
        word = rng.integers(0, 50, n)
        words = [f"{TOPICS[t]} {TOPICS[s]} w{w}"
                 for t, s, w in zip(topics, second, word)]
        return {
            "embedding": emb,
            "coordinate": pts,
            "content": np.asarray(words, object),
            "time": rng.uniform(0, 1000, n),
            "likes": rng.zipf(2.0, n).astype(np.float64),
        }

    def query_vec(self) -> np.ndarray:
        t = self.rng.integers(0, self.n_topics)
        v = self.topic_centers[t] + 0.2 * self.rng.normal(size=self.dim)
        return v.astype(np.float32)

    def rect(self, side: float = 10.0) -> Tuple[float, float, float, float]:
        x, y = self.rng.uniform(0, 100 - side, 2)
        return (float(x), float(y), float(x + side), float(y + side))

    def topic(self) -> str:
        return TOPICS[self.rng.integers(0, self.n_topics)]


def make_templates(d: TracyData) -> Dict[str, Callable]:
    """The 13 TRACY templates by name.  Each call draws fresh parameters
    from ``d``'s stream and returns a plain query spec (``spec`` below):
    the reference reads it as it is, the harness turns it into the
    program's query objects."""

    def t1():   # vector range + text
        return spec(where=("and", [("vrange", "embedding", d.query_vec(), 8.0),
                                   ("text", "content", d.topic())]))

    def t2():   # scalar range + spatial region
        lo = float(d.rng.uniform(0, 900))
        return spec(where=("and", [("range", "time", lo, lo + 50),
                                   ("geo", "coordinate", d.rect(15))]))

    def t3():   # triple-modality filter
        lo = float(d.rng.uniform(0, 900))
        return spec(where=("and", [("range", "time", lo, lo + 100),
                                   ("text", "content", d.topic()),
                                   ("geo", "coordinate", d.rect(25))]))

    def t4():   # highly selective scalar
        lo = float(d.rng.uniform(0, 990))
        return spec(where=("range", "time", lo, lo + 2))

    def t5():   # popularity + region
        return spec(where=("and", [("range", "likes", 5, 1e9),
                                   ("geo", "coordinate", d.rect(20))]))

    def t6():   # pure vector NN
        return spec(ranks=[("vec", "embedding", d.query_vec(), 1.0)])

    def t7():   # vector + spatial joint ranking
        x, y = d.rng.uniform(10, 90, 2)
        return spec(ranks=[("vec", "embedding", d.query_vec(), 0.5),
                           ("spatial", "coordinate", (float(x), float(y)),
                            0.2)])

    def t8():   # vector NN with time filter
        lo = float(d.rng.uniform(0, 800))
        return spec(where=("range", "time", lo, lo + 200),
                    ranks=[("vec", "embedding", d.query_vec(), 1.0)])

    def t9():   # vector + text relevance joint ranking
        return spec(ranks=[("vec", "embedding", d.query_vec(), 1.0),
                           ("textrank", "content", (d.topic(),), 0.5)])

    def t10():  # spatial NN with text filter
        x, y = d.rng.uniform(10, 90, 2)
        return spec(where=("text", "content", d.topic()),
                    ranks=[("spatial", "coordinate", (float(x), float(y)),
                            1.0)])

    def t11():  # 3-way joint ranking with filter
        x, y = d.rng.uniform(10, 90, 2)
        lo = float(d.rng.uniform(0, 800))
        return spec(where=("range", "time", lo, lo + 400),
                    ranks=[("vec", "embedding", d.query_vec(), 0.6),
                           ("spatial", "coordinate", (float(x), float(y)),
                            0.2),
                           ("textrank", "content", (d.topic(),), 0.3)])

    def t12():  # disjunctive hybrid search: hot region OR trending topic
        lo = float(d.rng.uniform(0, 900))
        return spec(where=("or", [
            ("and", [("range", "time", lo, lo + 100),
                     ("geo", "coordinate", d.rect(20))]),
            ("text", "content", d.topic())]))

    def t13():  # disjunctive NN: recent OR keyword, ranked
        lo = float(d.rng.uniform(0, 800))
        return spec(where=("or", [("range", "time", lo, lo + 200),
                                  ("text", "content", d.topic())]),
                    ranks=[("vec", "embedding", d.query_vec(), 1.0)])

    return {f.__name__: f for f in (t1, t2, t3, t4, t5, t6, t7, t8, t9,
                                    t10, t11, t12, t13)}


def spec(where=None, ranks=(), k: int = 10) -> Dict:
    """A query as plain data.  ``where`` is None or a tree of
    ``("and"|"or", [children])``, ``("range", col, lo, hi)``,
    ``("geo", col, (x0, y0, x1, y1))``, ``("text", col, term)`` and
    ``("vrange", col, vector, radius)``; ``ranks`` lists weighted
    distances to minimise: ``("vec", col, vector, w)``,
    ``("spatial", col, (x, y), w)`` and ``("textrank", col, terms, w)``.
    Filter queries (no ranks) return every passing row; ranked queries
    the ``k`` best."""
    return {"where": where, "ranks": list(ranks), "k": int(k)}
