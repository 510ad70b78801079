"""The one traffic generator: it reads a traffic file of parameters
(``bench/traffic/<name>.json``) and a configuration, and yields the ops of
a closed loop with one client.

Traffic runs in blocks.  A block holds ``queries_per_block`` queries and
``writes_per_block`` writes in an order drawn from the seed.  Queries
take the listed templates in turn from a fresh seeded permutation each
round, so every seed sends the same mix in another order; each query
draws its parameters from the seed.  A write inserts ``write.insert`` new
rows (pks counting up), overwrites ``write.update`` live rows and deletes
``write.delete`` live rows, drawn uniformly from the rows live when the
write is made.

Ops are plain tuples:

  ("query", template, spec)
  ("write", insert_pks, insert_batch, update_pks, update_batch, delete_pks)
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Tuple

import numpy as np

from bench.data import tracy


class Generator:
    def __init__(self, traffic: Dict, config: Dict, seed: int):
        self.traffic = traffic
        self.config = config
        centers = tracy.topic_centers(seed, config["dim"],
                                      config["n_topics"])
        self.rows = tracy.TracyData(seed, tracy.STREAM_ROWS, centers)
        self.qdata = tracy.TracyData(seed, tracy.STREAM_QUERIES, centers)
        self.ops_rng = tracy.rng_for(seed, tracy.STREAM_OPS)
        self._templates = tracy.make_templates(self.qdata)
        self._names = list(traffic.get("templates", []))
        unknown = set(self._names) - set(self._templates)
        if unknown:
            raise KeyError(f"unknown templates {sorted(unknown)}")
        self._turn: List[str] = []
        self.next_pk = 0
        self.live = np.zeros(1 << 16, bool)

    # ------------------------------------------------------------- rows
    def _new_pks(self, n: int) -> np.ndarray:
        pks = np.arange(self.next_pk, self.next_pk + n, dtype=np.int64)
        self.next_pk += n
        if self.next_pk > len(self.live):
            grown = np.zeros(max(2 * len(self.live), self.next_pk), bool)
            grown[:len(self.live)] = self.live
            self.live = grown
        self.live[pks] = True
        return pks

    def preload(self) -> Iterator[Tuple[np.ndarray, Dict]]:
        """The configuration's preloaded rows, in batches."""
        n, step = self.config["preload_rows"], self.config["load_batch_rows"]
        for start in range(0, n, step):
            m = min(step, n - start)
            yield self._new_pks(m), self.rows.batch(m)

    # ------------------------------------------------------------ queries
    def query(self) -> Tuple[str, Dict]:
        if not self._turn:
            self._turn = [self._names[i] for i in
                          self.ops_rng.permutation(len(self._names))]
        name = self._turn.pop()
        return name, self._templates[name]()

    # ------------------------------------------------------------- writes
    def write(self) -> Tuple:
        w = self.traffic["write"]
        ins = self._new_pks(w["insert"])
        ins_batch = self.rows.batch(len(ins))
        n_old = w["update"] + w["delete"]
        live = np.flatnonzero(self.live[:self.next_pk - len(ins)])
        old = self.ops_rng.choice(live, n_old, replace=False) if n_old \
            else np.zeros(0, np.int64)
        upd = np.sort(old[:w["update"]])
        dele = np.sort(old[w["update"]:])
        self.live[dele] = False
        return ("write", ins, ins_batch, upd, self.rows.batch(len(upd)),
                dele)

    # ------------------------------------------------------------- blocks
    def block(self) -> List[Tuple]:
        t = self.traffic
        kinds = ["query"] * t["queries_per_block"] \
            + ["write"] * t["writes_per_block"]
        ops = []
        for i in self.ops_rng.permutation(len(kinds)):
            if kinds[i] == "query":
                ops.append(("query",) + self.query())
            else:
                ops.append(self.write())
        return ops

    def ops(self) -> Iterator[Tuple]:
        while True:
            yield from self.block()
