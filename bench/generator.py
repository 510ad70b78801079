"""The one traffic generator: it reads a traffic file of parameters
(``bench/traffic/<name>.json``) and a configuration, and yields the ops of
a closed loop with one client.

Traffic runs in blocks.  A block holds ``queries_per_block`` queries and
``writes_per_block`` writes in an order drawn from the seed.  Queries
take the listed templates in turn from a fresh seeded permutation each
round, so every seed sends the same mix in another order, or, with
``"template_order": "listed"``, in the listed order, so that every seed
spends its window on the same templates; each query draws its
parameters from the seed.  A write inserts ``write.insert`` new
rows (pks counting up), overwrites ``write.update`` live rows and deletes
``write.delete`` live rows, drawn uniformly from the rows live when the
write is made.  After a block's queries and writes come
``advances_per_block`` advances of the table's clock, each
``clock_step_s`` past the last, so that a refresh follows the writes it
must reflect.

``subscriptions`` (``{"templates", "sync", "async", "interval_s"}``)
lists the standing queries a cell registers at set-up: ``sync`` of them
refreshed every ``interval_s`` of the clock and ``async`` on every
change, taking the templates in turn, their parameters drawn from one
fixed stream apart from the queries': every seed registers the same
ranges, regions and topic offsets (the vectors still sit around the
run's own topic centres), so that the views chosen, and the work of a
refresh, do not follow the seed.  A key that a traffic file leaves out
draws nothing.

Ops are plain tuples:

  ("query", template, spec)
  ("write", insert_pks, insert_batch, update_pks, update_batch, delete_pks)
  ("advance", now)
"""
from __future__ import annotations

from typing import Dict, Iterator, List, Tuple

import numpy as np

from bench.data import tracy

# the subscriptions' own stream: registering them moves no query
STREAM_SUBSCRIPTIONS = 4


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
        order = traffic.get("template_order", "seeded")
        if order not in ("seeded", "listed"):
            raise ValueError(f"unknown template_order {order!r}")
        self._listed = order == "listed"
        self.next_pk = 0
        self.live = np.zeros(1 << 16, bool)
        self.clock = 0.0

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
            self._turn = self._names[::-1] if self._listed else [
                self._names[i] for i in
                self.ops_rng.permutation(len(self._names))]
        name = self._turn.pop()
        return name, self._templates[name]()

    def subscriptions(self) -> List[Tuple[str, str, float, Dict]]:
        """The standing queries, as (template, "sync"|"async",
        interval_s, spec): the SYNC ones first, templates in turn."""
        s = self.traffic.get("subscriptions")
        if not s:
            return []
        data = tracy.TracyData(0, STREAM_SUBSCRIPTIONS,
                               self.rows.topic_centers)
        templates = tracy.make_templates(data)
        kinds = ["sync"] * s["sync"] + ["async"] * s["async"]
        out = []
        for i, kind in enumerate(kinds):
            name = s["templates"][i % len(s["templates"])]
            out.append((name, kind, float(s["interval_s"]),
                        templates[name]()))
        return out

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
        for _ in range(t.get("advances_per_block", 0)):
            self.clock += t["clock_step_s"]
            ops.append(("advance", self.clock))
        return ops

    def ops(self) -> Iterator[Tuple]:
        while True:
            yield from self.block()
