#!/usr/bin/env python3
"""On-chip smoke test: drive the served path once on one TPU chip.

    python3 chip_smoke.py [--rows 1048576] [--seed 0]

Runs every phase in this one process (a chip belongs to one process), on
the device JAX finds, and exits non-zero without a result line when that
device is not a TPU.  Phases, each through the entry points a user
calls (the ``Database`` facade, planner, operators, ``kernels/ops.py``):

  kernels    each device op at real width against float64 numpy
  main       a TRACY store (128-d IVF embeddings, Z-order points,
             inverted text, two B-trees, PQ residence) built from --seed
             with RocksDB-sized memtables; batches of all 13 templates
  quantized  recall_target=0.95 variants of t6/t8/t13
  shards     a 4-shard facade on the one chip (cross-shard device merge)
  graph      a GRAPH-indexed store (graph dispatch)
  continuous 16 SYNC/ASYNC subscriptions over 4 ingest windows with
             updates and deletes, each followed by ``advance()``

Every answer is checked against a plain float64 numpy brute force over
the visible rows this script generated (filters: equal pk sets; exact
NN: equal top-k pks up to reference ties within ``TIE_TOL``; approximate
NN: mean recall@10 >= the target; subscriptions: equal to a re-run and
to the reference).  Each phase prints one ``smoke-phase`` line: rows,
segments, set-up and first-call compile seconds, device launches per
kernel tag, host-path dispatches and the device's peak bytes in use.
Timings are smoke timings, not benchmark numbers.  The last line is
``{"ok": true, "device": {...}}``; any failed check raises.
"""
from __future__ import annotations

import argparse
import json
import sys
import time
from pathlib import Path

import numpy as np

ROOT = Path(__file__).resolve().parent
for _p in (ROOT, ROOT / "src"):
    if str(_p) not in sys.path:
        sys.path.insert(0, str(_p))

MEMTABLE_BYTES = 64 * 2**20      # RocksDB's default write_buffer_size
# PQ geometry of the main store: one 256-level codebook per dimension
# (m = d, 128 code bytes/row, 4x fewer bytes than fp32).  TRACY's
# embeddings are isotropic noise around topic centers, so every true
# top-10 sits within a hair of its rivals: coarser codes (m=8: recall@10
# ~0.1, m=64: ~0.95) cannot meet a 0.95 target within the kernel's
# k' <= KMAX survivors.
PQ_M = 128
# every device program the served path must have launched on the chip
REQUIRED_TAGS = ("fused_scan.pallas", "ivf_scan.pallas", "bitmap.pallas",
                 "quantized_scan.pallas", "pq_adc.pallas",
                 "topk_merge_batch.pallas", "graph_search.xla")
DEVICE_TAGS = (".pallas", ".xla")     # tag suffixes of programs on the chip
# recall_target queries per approximate template: recall@10 is pooled
# over 3 x 8 queries, so one miss moves it by 1/240
APPROX_PER_TEMPLATE = 8
TIE_TOL = 1e-4                   # relative score tie band at the k-th slot
COMPILE_EVENT = "/jax/core/compile/backend_compile_duration"
CACHE_HIT_EVENT = "/jax/compilation_cache/cache_hits"


class SmokeFailure(AssertionError):
    pass


def check(cond: bool, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


# ---------------------------------------------------------------------------
# reference: float64 brute force over the rows this script generated
# ---------------------------------------------------------------------------

class Reference:
    """The visible rows, kept apart from the store: pk -> row slot.
    Text columns are parsed once into per-topic token counts."""

    def __init__(self, cap: int, dim: int, topics):
        self.topics = list(topics)
        self.emb = np.zeros((cap, dim), np.float32)
        self.coord = np.zeros((cap, 2), np.float32)
        self.time = np.zeros(cap)
        self.likes = np.zeros(cap)
        self.count = np.zeros((cap, len(self.topics)), np.int8)
        self.ntok = np.zeros(cap, np.int8)
        self.live = np.zeros(cap, bool)
        self._x64 = None

    def write(self, pks, batch) -> None:
        pks = np.asarray(pks, np.int64)
        self.emb[pks] = batch["embedding"]
        self.coord[pks] = batch["coordinate"]
        self.time[pks] = batch["time"]
        self.likes[pks] = batch["likes"]
        toks = [str(s).lower().split() for s in batch["content"]]
        self.ntok[pks] = [len(t) for t in toks]
        for ti, word in enumerate(self.topics):
            self.count[pks, ti] = [t.count(word) for t in toks]
        self.live[pks] = True
        self._x64 = None

    def delete(self, pks) -> None:
        self.live[np.asarray(pks, np.int64)] = False
        self._x64 = None

    def _vectors(self):
        if self._x64 is None:
            rows = np.nonzero(self.live)[0]
            x = self.emb[rows].astype(np.float64)
            self._x64 = (rows, x, (x * x).sum(axis=1))
        return self._x64

    def pred(self, p, q) -> np.ndarray:
        """Bool mask over all slots (live rows only) for a predicate."""
        if isinstance(p, q.And):
            m = np.ones(len(self.live), bool)
            for c in p.children:
                m &= self.pred(c, q)
            return m
        if isinstance(p, q.Or):
            m = np.zeros(len(self.live), bool)
            for c in p.children:
                m |= self.pred(c, q)
            return m
        if isinstance(p, q.Not):
            return self.live & ~self.pred(p.child, q)
        if isinstance(p, q.Range):
            v = getattr(self, p.col)
            return self.live & (v >= p.lo) & (v <= p.hi)
        if isinstance(p, q.GeoWithin):
            r = np.asarray(p.rect, np.float32)    # bounds in column dtype
            x, y = self.coord[:, 0], self.coord[:, 1]
            return self.live & (x >= r[0]) & (x <= r[2]) \
                & (y >= r[1]) & (y <= r[3])
        if isinstance(p, q.TextContains):
            return self.live & (self.count[:, self.topics.index(
                p.term.lower())] > 0)
        if isinstance(p, q.VectorRange):
            rows, x, xn = self._vectors()
            qv = p.q.astype(np.float64)
            d2 = xn - 2.0 * (x @ qv) + qv @ qv
            m = np.zeros(len(self.live), bool)
            m[rows] = np.sqrt(np.maximum(d2, 0.0)) < p.thresh
            return m
        raise TypeError(f"no reference for {p!r}")

    def scores(self, ranks, rows: np.ndarray, q) -> np.ndarray:
        """Weighted-sum rank distance of ``rows`` (float64)."""
        total = np.zeros(len(rows))
        for r in ranks:
            if isinstance(r, q.VectorRank):
                x = self.emb[rows].astype(np.float64)
                d = np.sqrt(((x - r.q.astype(np.float64)) ** 2).sum(axis=1))
            elif isinstance(r, q.SpatialRank):
                p = np.asarray(r.point, np.float64)
                d = np.sqrt(((self.coord[rows].astype(np.float64) - p) ** 2)
                            .sum(axis=1))
            elif isinstance(r, q.TextRank):
                hits = sum(self.count[rows, self.topics.index(t.lower())]
                           .astype(np.float64) for t in r.terms)
                d = 1.0 / (1.0 + 10.0 * hits / (self.ntok[rows] + 1.0))
            else:
                raise TypeError(f"no reference for {r!r}")
            total += r.weight * d
        return total

    def answer(self, query, q):
        """Filter queries: the passing pk set.  NN queries: (pks sorted by
        (score, pk), their scores) over every passing row."""
        mask = self.live if query.where is None else self.pred(query.where, q)
        rows = np.nonzero(mask)[0]
        if not query.ranks:
            return rows, None
        s = self.scores(query.ranks, rows, q)
        order = np.lexsort((rows, s))
        return rows[order], s[order]


def check_answer(ref: Reference, query, got, q, what: str) -> float:
    """Check one result list against the reference; returns recall@k for
    NN queries (1.0 for filters)."""
    pks, scores = ref.answer(query, q)
    got_pks = np.asarray([r.pk for r in got], np.int64)
    if scores is None:
        check(set(got_pks.tolist()) == set(pks.tolist()),
              f"{what}: filter pk set differs from the reference "
              f"({len(got_pks)} vs {len(pks)} rows)")
        return 1.0
    k = min(query.k, len(pks))
    want = set(pks[:k].tolist())
    recall = len(want & set(got_pks.tolist())) / max(k, 1)
    if query.recall_target is not None:
        return recall
    check(len(got_pks) == k, f"{what}: {len(got_pks)} rows, want {k}")
    if k == 0:
        return 1.0
    kth = scores[k - 1]
    tol = TIE_TOL * max(1.0, abs(kth))
    by_pk = dict(zip(pks.tolist(), scores.tolist()))
    for r in got:
        s = by_pk.get(r.pk)
        check(s is not None and s <= kth + tol,
              f"{what}: pk {r.pk} (ref score {s}) is outside the top-{k} "
              f"(k-th ref score {kth})")
        check(abs(r.score - s) <= tol,
              f"{what}: pk {r.pk} score {r.score} vs reference {s}")
    for pk in want - set(got_pks.tolist()):
        check(by_pk[pk] >= kth - tol,
              f"{what}: reference top-{k} pk {pk} missing")
    return recall


# ---------------------------------------------------------------------------
# phase accounting
# ---------------------------------------------------------------------------

class Phase:
    """One phase's accounting line: device launches per kernel tag, host
    dispatches, first-call compile seconds (XLA/Mosaic backend compiles,
    persistent-cache loads included) and set-up seconds."""

    compile_s = 0.0
    cache_hits = 0

    def __init__(self, name: str, device):
        self.name = name
        self.device = device
        self.info = {}

    @classmethod
    def listen(cls, event: str, duration: float, **_) -> None:
        if event == COMPILE_EVENT:
            cls.compile_s += duration

    @classmethod
    def count(cls, event: str, **_) -> None:
        if event == CACHE_HIT_EVENT:
            cls.cache_hits += 1

    def __enter__(self):
        from repro.kernels import ops as kops
        self.kops = kops
        self.t0 = time.perf_counter()
        self.c0 = Phase.compile_s
        self.tags0 = kops.launches_by_tag()
        self.host0 = kops.thread_stats().host_dispatches
        self.fallback0 = self.merge_fallbacks()
        return self

    @staticmethod
    def merge_fallbacks() -> int:
        """Cross-shard merges that left the device for the host merge."""
        from repro.obs import REGISTRY
        return REGISTRY.counter("kernels.merge_host_fallbacks").value

    def __exit__(self, exc_type, exc, tb):
        if exc_type is not None:
            return False
        tags = self.kops.launches_by_tag()
        launches = {t: n - self.tags0.get(t, 0) for t, n in sorted(
            tags.items()) if n != self.tags0.get(t, 0)}
        stats = self.device.memory_stats() or {}
        line = {"phase": self.name,
                **self.info,
                "wall_s": time.perf_counter() - self.t0,
                "compile_s": Phase.compile_s - self.c0,
                "device_launches": launches,
                "host_dispatches":
                    self.kops.thread_stats().host_dispatches - self.host0,
                "merge_host_fallbacks":
                    self.merge_fallbacks() - self.fallback0,
                "peak_bytes_in_use": stats.get("peak_bytes_in_use"),
                "note": "smoke timings, not benchmark numbers"}
        print("smoke-phase " + json.dumps(line), flush=True)
        bad = [t for t in launches if not t.endswith(DEVICE_TAGS)]
        check(not bad, f"{self.name}: non-device program tags {bad}")
        return False


# ---------------------------------------------------------------------------
# the smoke run
# ---------------------------------------------------------------------------

class Smoke:
    def __init__(self, args, device):
        from benchmarks import tracy
        from repro.core import query as q
        self.args = args
        self.device = device
        self.tracy = tracy
        self.q = q
        cap = args.rows + 4 * (args.window + args.window // 4) + 16
        self.ref = Reference(cap, 128, tracy.TOPICS)

    # ----------------------------------------------------------- helpers
    def config(self, schema, quantize: bool = False):
        """RocksDB-sized memtables: flush_rows so one memtable holds about
        64 MiB by the engine's own byte accounting."""
        from repro.core.lsm import LSMConfig
        from repro.core.memtable import MemTable
        data = self.tracy.TracyData(self.tracy.TracyConfig(
            seed=self.args.seed + 999))
        pks, batch = data.batch(4096)
        mt = MemTable(schema)
        mt.put_batch(pks, batch, 0)
        row_bytes = mt.approx_bytes / 4096
        return LSMConfig(flush_rows=int(MEMTABLE_BYTES // row_bytes),
                         fanout=4, pq_m=PQ_M, quantize_vectors=quantize)

    @staticmethod
    def load(table, data, n: int, ref: Reference) -> float:
        """Generate n rows from ``data``, record them in ``ref`` and ingest
        them; returns the generation seconds (ingest time is the rest of
        the phase)."""
        gen = 0.0
        done = 0
        while done < n:
            t = time.perf_counter()
            pks, batch = data.batch(min(65536, n - done))
            gen += time.perf_counter() - t
            ref.write(pks, batch)
            table.put(pks, batch)
            done += len(pks)
        table.flush()
        return gen

    @staticmethod
    def segments(table) -> int:
        store = table.store
        if hasattr(store, "shards"):
            return sum(len(s.segments) for s in store.shards)
        return len(store.segments)

    def run_checked(self, table, queries, names, what, ref=None):
        """execute_many, then check each answer; returns recalls by name,
        the EXPLAIN dispatch of each query, and the seconds spent
        executing and checking."""
        ref = ref or self.ref
        t0 = time.perf_counter()
        results = table.execute_many(queries)
        t1 = time.perf_counter()
        recalls, plans = {}, {}
        for name, query, (rows, st) in zip(names, queries, results):
            r = check_answer(ref, query, rows, self.q, f"{what}/{name}")
            recalls.setdefault(name, []).append(r)
            plans[name] = st.plan.splitlines()[0]
        return recalls, plans, {"execute_s": t1 - t0,
                                "check_s": time.perf_counter() - t1}

    def approximate(self, table, data, dispatch: str, ref, target=0.95):
        """recall_target variants of t6/t8/t13: every returned score is
        exact (the survivors are re-ranked exactly), the plan takes the
        approximate ``dispatch``, and mean recall@10 over the phase's
        queries meets the target."""
        tmpl = self.tracy.make_graph_templates(data, target) \
            * APPROX_PER_TEMPLATE
        queries = [f() for _, f in tmpl]
        recalls, plans, timing = self.run_checked(
            table, queries, [n for n, _ in tmpl], dispatch, ref)
        check(any(f"dispatch={dispatch}" in p for p in plans.values()),
              f"{dispatch}: no template took {dispatch} dispatch {plans}")
        pooled = float(np.mean([r for rs in recalls.values() for r in rs]))
        check(pooled >= target,
              f"{dispatch}: mean recall@10 {pooled} < {target}")
        return {"rows": table.n_rows, "queries": len(queries), **timing,
                "recall_at_10": pooled,
                "recall_at_10_by_template": {
                    n: float(np.mean(r)) for n, r in recalls.items()},
                "plans": plans}

    def templates(self, data):
        search, nn = self.tracy.make_templates(data)
        return [(f.__name__, f) for f in search + nn]

    # ------------------------------------------------------------ phases
    def phase_kernels(self):
        """Each device op at real width, straight through kernels/ops.py,
        against float64 numpy: the MXU distance precision, the bitmap
        filter and the PQ ADC gather."""
        from repro.kernels import ops as kops
        rng = np.random.default_rng(self.args.seed)
        with Phase("kernels", self.device) as ph:
            q = rng.normal(size=(64, 128)).astype(np.float32)
            x = (rng.normal(size=(65536, 128)) * 1.5 + 0.5).astype(np.float32)
            d2 = kops.l2_distances(q, x)
            q64, x64 = q.astype(np.float64), x.astype(np.float64)
            want = ((q64 ** 2).sum(1)[:, None] - 2.0 * q64 @ x64.T
                    + (x64 ** 2).sum(1)[None, :])
            # f32 expansion error is a few ulps of the norms (3e-7 from
            # XLA:CPU at this shape); a single bf16 MXU pass would be
            # ~1e-3, three passes (Precision.HIGH) ~1e-5
            scale = (q64 ** 2).sum(1)[:, None] + (x64 ** 2).sum(1)[None, :]
            err = float(np.max(np.abs(d2 - want) / scale))
            check(err < 4e-6, f"kernels/l2: relative error {err}")
            ph.info["l2_rel_err"] = err
            cols = rng.uniform(0, 1, (1 << 20, 4)).astype(np.float32)
            bounds = np.sort(rng.uniform(0, 1, (4, 2)), axis=1)
            got = kops.range_bitmap(cols, bounds)
            b32 = bounds.astype(np.float32)
            check(np.array_equal(got, np.all(
                (cols >= b32[:, 0]) & (cols <= b32[:, 1]), axis=1)),
                "kernels/bitmap differs from the reference")
            codes = rng.integers(0, 256, (1 << 20, 8)).astype(np.uint8)
            books = rng.normal(size=(8, 256, 16)).astype(np.float32)
            qv = rng.normal(size=128).astype(np.float32)
            adc = kops.pq_adc_distances(qv, codes, books)
            lut = ((books.astype(np.float64)
                    - qv.reshape(8, 1, 16).astype(np.float64)) ** 2).sum(2)
            want = lut[np.arange(8)[None, :], codes.astype(np.int64)].sum(1)
            err = float(np.max(np.abs(adc - want) / np.maximum(want, 1.0)))
            check(err < 1e-5, f"kernels/pq_adc: relative error {err}")
            ph.info["pq_adc_rel_err"] = err
            ph.info.update(l2_shape=[64, 65536, 128], bitmap_rows=1 << 20,
                           pq_rows=1 << 20)

    def phase_main(self):
        from repro.core.api import Database
        tr, args = self.tracy, self.args
        data = tr.TracyData(tr.TracyConfig(n_rows=args.rows, seed=args.seed))
        schema = tr.tweet_schema(128)
        with Phase("main_setup", self.device) as ph:
            t0 = time.perf_counter()
            db = Database(schema, self.config(schema, quantize=True))
            table = db.table()
            gen = self.load(table, data, args.rows, self.ref)
            ph.info.update(rows=table.n_rows, segments=self.segments(table),
                           flush_rows=table.store.cfg.flush_rows,
                           generate_s=gen,
                           setup_s=time.perf_counter() - t0)
        names = [n for n, _ in self.templates(data)]
        check(len(names) == 13, f"{len(names)} TRACY templates, want 13")
        for b in range(args.batches):
            with Phase(f"main_batch{b}", self.device) as ph:
                tmpl = self.templates(data)
                queries = [f() for _, f in tmpl]
                _, plans, timing = self.run_checked(
                    table, queries, [n for n, _ in tmpl], f"main{b}")
                ph.info.update(rows=table.n_rows, queries=len(queries),
                               **timing, plans=plans)
        with Phase("quantized", self.device) as ph:
            ph.info.update(self.approximate(table, data, "quantized", ref=None))
        return db, table, data

    def phase_shards(self):
        from repro.core.api import Database
        tr, args = self.tracy, self.args
        n = args.shard_rows
        ref = Reference(n, 128, tr.TOPICS)
        data = tr.TracyData(tr.TracyConfig(n_rows=n, seed=args.seed + 1))
        schema = tr.tweet_schema(128)
        with Phase("shards_setup", self.device) as ph:
            t0 = time.perf_counter()
            db = Database(schema, self.config(schema), shards=4)
            table = db.table()
            gen = self.load(table, data, n, ref)
            ph.info.update(rows=table.n_rows, shards=4,
                           segments=self.segments(table), generate_s=gen,
                           setup_s=time.perf_counter() - t0)
        with Phase("shards_batch", self.device) as ph:
            tmpl = self.templates(data)
            queries = [f() for _, f in tmpl]
            _, plans, timing = self.run_checked(
                table, queries, [n for n, _ in tmpl], "shards", ref)
            ph.info.update(rows=table.n_rows, queries=len(queries),
                           **timing, plans=plans)
        db.close()

    def phase_graph(self):
        from repro.core.api import Database
        from repro.core.types import IndexKind
        tr, args = self.tracy, self.args
        n = args.graph_rows
        ref = Reference(n, 128, tr.TOPICS)
        data = tr.TracyData(tr.TracyConfig(n_rows=n, seed=args.seed + 2))
        schema = tr.tweet_schema(128, IndexKind.GRAPH)
        with Phase("graph_setup", self.device) as ph:
            t0 = time.perf_counter()
            db = Database(schema, self.config(schema))
            table = db.table()
            gen = self.load(table, data, n, ref)
            ph.info.update(rows=table.n_rows, segments=self.segments(table),
                           generate_s=gen,
                           setup_s=time.perf_counter() - t0)
        with Phase("graph_batch", self.device) as ph:
            ph.info.update(self.approximate(table, data, "graph", ref=ref))
        db.close()

    def phase_continuous(self, db, table, data):
        """16 subscriptions (8 SYNC, 8 ASYNC) shaped like t2, t4 and t8;
        4 windows of inserts + updates + deletes, each followed by
        ``advance()``; every subscription must equal a re-run of its
        query and the reference."""
        q, args = self.q, self.args
        shapes = []
        for i in range(16):
            lo = float(data.rng.uniform(0, 800))
            kind = ("t2", "t4", "t8")[i % 3]
            if kind == "t2":
                query = q.HybridQuery(where=q.And(
                    q.Range("time", lo, lo + 50),
                    q.GeoWithin("coordinate", data.rect(15))))
            elif kind == "t4":
                query = q.HybridQuery(where=q.Range("time", lo, lo + 2))
            else:
                query = q.HybridQuery(
                    where=q.Range("time", lo, lo + 200),
                    ranks=[q.VectorRank("embedding", data.query_vec(), 1.0)],
                    k=10)
            sync = i % 2 == 0
            sub = table.subscribe(query, interval_s=1.0) if sync else \
                table.subscribe(query, on_change=True)
            shapes.append((f"{kind}_{'sync' if sync else 'async'}{i}",
                           query, sub))
        next_pk = args.rows
        rng = np.random.default_rng(args.seed + 3)
        for w in range(4):
            with Phase(f"continuous_window{w}", self.device) as ph:
                pks, batch = data.batch(args.window)
                pks = list(range(next_pk, next_pk + len(pks)))
                next_pk += len(pks)
                self.ref.write(pks, batch)
                table.put(pks, batch)
                live = np.nonzero(self.ref.live)[0]
                upd = np.sort(rng.choice(live, args.window // 8,
                                         replace=False))
                _, ubatch = data.batch(len(upd))
                self.ref.write(upd, ubatch)
                table.put(upd.tolist(), ubatch)
                live = np.nonzero(self.ref.live)[0]
                dele = np.sort(rng.choice(live, args.window // 8,
                                          replace=False))
                self.ref.delete(dele)
                table.delete(dele.tolist())
                out = table.advance(float(w + 1))
                ran = 0
                for name, query, sub in shapes:
                    rows = sub.latest
                    check(rows is not None, f"continuous/{name}: no result")
                    if sub.rid in out:
                        ran += 1
                    again, _ = table.execute(query)
                    key = (lambda rs: [(r.pk, r.score) for r in rs]) \
                        if query.ranks else \
                        (lambda rs: sorted(r.pk for r in rs))
                    check(key(rows) == key(again),
                          f"continuous/{name}: subscription differs from a "
                          f"re-run after window {w}: {len(rows)} vs "
                          f"{len(again)} rows")
                    check_answer(self.ref, query, rows, q,
                                 f"continuous/{name}/w{w}")
                ph.info.update(rows=table.n_rows, inserted=len(pks),
                               updated=len(upd), deleted=len(dele),
                               subscriptions=len(shapes), ran=ran,
                               segments=self.segments(table))

    def run(self):
        from repro.kernels import ops as kops
        Phase.compile_s = 0.0
        before = kops.launches_by_tag()
        self.phase_kernels()
        db, table, data = self.phase_main()
        self.phase_shards()
        self.phase_graph()
        self.phase_continuous(db, table, data)
        db.close()
        after = kops.launches_by_tag()
        launched = {t: after.get(t, 0) - before.get(t, 0)
                    for t in REQUIRED_TAGS}
        missing = [t for t, n in launched.items() if n <= 0]
        check(not missing, f"device programs never launched: {missing}")
        return launched


def parse(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--rows", type=int, default=1 << 20,
                    help="main TRACY store rows")
    ap.add_argument("--shard-rows", type=int, default=1 << 18,
                    help="rows of the 4-shard store")
    ap.add_argument("--graph-rows", type=int, default=1 << 13,
                    help="rows of the GRAPH-indexed store (cut to what its "
                         "host graph build finishes in)")
    ap.add_argument("--window", type=int, default=8192,
                    help="rows inserted per continuous ingest window")
    ap.add_argument("--batches", type=int, default=3,
                    help="execute_many batches of the 13 templates")
    ap.add_argument("--seed", type=int, default=0)
    return ap.parse_args(argv)


def main(argv=None) -> int:
    args = parse(argv)
    import jax
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: needs a TPU; JAX found {dev.platform!r}",
              file=sys.stderr)
        return 2
    from repro.launch.cache import enable_compile_cache
    cache = enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(Phase.listen)
    jax.monitoring.register_event_listener(Phase.count)
    print("smoke-start " + json.dumps(
        {"device_kind": dev.device_kind, "devices": len(jax.devices()),
         "compile_cache": cache, "rows": args.rows,
         "shard_rows": args.shard_rows, "graph_rows": args.graph_rows,
         "seed": args.seed}), flush=True)
    t0 = time.perf_counter()
    launched = Smoke(args, dev).run()
    print("smoke-total " + json.dumps(
        {"wall_s": time.perf_counter() - t0, "compile_s": Phase.compile_s,
         "compile_cache_hits": Phase.cache_hits,
         "device_launches": launched,
         "note": "smoke timings, not benchmark numbers"}), flush=True)
    print(json.dumps({"ok": True, "device": {
        "platform": dev.platform, "kind": dev.device_kind,
        "count": len(jax.devices())}}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
