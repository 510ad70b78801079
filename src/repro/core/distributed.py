"""Distributed query execution: shard_map scatter-gather over the mesh.

ARCADE's data plane at scale: segments are partitioned across the
``data`` mesh axis (each data-parallel group owns a disjoint shard of the
LSM keyspace); a vector query fans out, every shard answers a local
top-k from its own posting blocks (ivf_scan semantics), and the global
top-k is combined with an all-gather + merge — the TPU-native analog of
the paper's per-SST iterators + top-level merging iterator, one level up.

``distributed_topk`` is pure jnp and jit/shard_map-lowered, so the same
code path is exercised by tests on 1 device and by the dry-run on the
16x16 / 2x16x16 production meshes (launch/dryrun_arcade.py).

The ENGINE-integrated form of this idea — hash-partitioned LSM shards
behind the planner, visibility and fused-kernel pipeline, with the
device-side cross-shard merge — lives in ``core/shards``; this module
remains the mesh-level shard_map demo the dry-run drives.
"""
from __future__ import annotations

from typing import Tuple

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, PartitionSpec as P


def local_topk(q: jnp.ndarray, vecs: jnp.ndarray, k: int
               ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Exact local top-k: q (d,), vecs (n, d) -> (k dists, k indices).
    Distances are squared L2 (monotone for merging; sqrt at the edge).

    ``k`` may exceed the shard's row count (small shards must not break
    the scatter-gather path): ``lax.top_k`` runs at the clamped size and
    the result is padded to k with (+inf, -1) sentinel slots, which the
    global merge orders last and callers filter with ``idx >= 0``."""
    qf = q.astype(jnp.float32)
    vf = vecs.astype(jnp.float32)
    d = (jnp.sum(qf * qf) - 2.0 * (vf @ qf)
         + jnp.sum(vf * vf, axis=-1))
    n = vf.shape[0]
    kk = min(k, n)
    neg_d, idx = jax.lax.top_k(-d, kk)
    if kk < k:
        neg_d = jnp.concatenate(
            [neg_d, jnp.full((k - kk,), -jnp.inf, neg_d.dtype)])
        idx = jnp.concatenate(
            [idx, jnp.full((k - kk,), -1, idx.dtype)])
    return -neg_d, idx


def make_distributed_topk(mesh: Mesh, k: int, shard_axis: str = "data"):
    """Builds a jit'd scatter-gather top-k over ``shard_axis``.

    vecs: (n_global, d) sharded on dim 0; ids: (n_global,) matching.
    Every shard computes a local top-k, then the (tiny) per-shard results
    are all-gathered and merged — collective payload is O(shards * k),
    never O(n).
    """
    def _shardfn(q, vecs, ids):
        d, idx = local_topk(q, vecs, k)             # local candidates
        # padded slots (k > local rows) carry idx=-1: gather a -1 id so
        # they survive the merge as identifiable sentinels, never as a
        # bogus row 0 hit
        local_ids = jnp.where(idx >= 0, ids[jnp.maximum(idx, 0)], -1)
        # gather per-shard winners: (n_shards, k)
        all_d = jax.lax.all_gather(d, shard_axis)
        all_i = jax.lax.all_gather(local_ids, shard_axis)
        flat_d = all_d.reshape(-1)
        flat_i = all_i.reshape(-1)
        neg, pos = jax.lax.top_k(-flat_d, k)
        return -neg, flat_i[pos]

    fn = jax.shard_map(
        _shardfn, mesh=mesh,
        in_specs=(P(), P(shard_axis, None), P(shard_axis)),
        out_specs=(P(), P()),
        check_vma=False)
    return jax.jit(fn)


def make_distributed_hybrid_score(mesh: Mesh, k: int,
                                  shard_axis: str = "data"):
    """Weighted multi-modal scatter-gather: vector + spatial distances
    combined on-shard (Algorithm 1's scoring, dense refinement form),
    then global top-k merge."""
    def _shardfn(qv, qp, w, vecs, pts, ids, mask):
        qf = qv.astype(jnp.float32)
        vf = vecs.astype(jnp.float32)
        d_v = jnp.sqrt(jnp.maximum(
            jnp.sum(qf * qf) - 2.0 * (vf @ qf) + jnp.sum(vf * vf, -1), 0.0))
        d_s = jnp.sqrt(jnp.sum((pts.astype(jnp.float32)
                                - qp.astype(jnp.float32)) ** 2, -1))
        score = w[0] * d_v + w[1] * d_s
        score = jnp.where(mask, score, jnp.inf)
        kk = min(k, vf.shape[0])       # k may exceed the shard row count
        neg, idx = jax.lax.top_k(-score, kk)
        local_ids = ids[idx]
        if kk < k:
            neg = jnp.concatenate(
                [neg, jnp.full((k - kk,), -jnp.inf, neg.dtype)])
            local_ids = jnp.concatenate(
                [local_ids, jnp.full((k - kk,), -1, local_ids.dtype)])
        all_s = jax.lax.all_gather(-neg, shard_axis).reshape(-1)
        all_i = jax.lax.all_gather(local_ids, shard_axis).reshape(-1)
        neg2, pos = jax.lax.top_k(-all_s, k)
        return -neg2, all_i[pos]

    fn = jax.shard_map(
        _shardfn, mesh=mesh,
        in_specs=(P(), P(), P(), P(shard_axis, None), P(shard_axis, None),
                  P(shard_axis), P(shard_axis)),
        out_specs=(P(), P()),
        check_vma=False)
    return jax.jit(fn)


# ---------------------------------------------------------------------------
# host-side convenience: run a distributed query over an LSM store
# ---------------------------------------------------------------------------

def store_shards(store, n_shards: int):
    """Partition the store's rows into n_shards (by pk hash), padded to a
    common length — the layout the data axis owns in production.

    Fully vectorized: one stable argsort by shard id and a single sliced
    scatter place every row (no per-row Python loop).  RAM-resident rows
    come along via the sealed-aware ``store.memtable_arrays()`` — the
    active memtable AND memtables queued for flush — so recently-ingested
    rows are not silently dropped from the distributed scan, and
    visibility is resolved before packing: per pk only the newest-seqno
    version survives, and pks whose newest version is a tombstone are
    excluded entirely (a memtable delete shadows the flushed row)."""
    vecs, pts, ids, seqs, tombs = [], [], [], [], []
    col_v = next(c.name for c in store.schema.columns
                 if c.ctype.value == "vector")
    col_p = [c.name for c in store.schema.columns
             if c.ctype.value == "spatial"]
    for seg in store.segments:
        vecs.append(np.asarray(seg.columns[col_v], np.float32))
        if col_p:
            pts.append(np.asarray(seg.columns[col_p[0]], np.float32))
        ids.append(seg.pk)
        seqs.append(seg.seqno)
        tombs.append(seg.tombstone)
    if store.memtable_rows:
        mt_pk, mt_seq, mt_tomb, mt_cols = store.memtable_arrays()
        vecs.append(np.asarray(mt_cols[col_v], np.float32))
        if col_p:
            pts.append(np.asarray(mt_cols[col_p[0]], np.float32))
        ids.append(mt_pk)
        seqs.append(mt_seq)
        tombs.append(mt_tomb)
    if not vecs:
        raise ValueError("empty store")
    vecs = np.concatenate(vecs)
    ids = np.concatenate(ids)
    seqs = np.concatenate(seqs)
    tombs = np.concatenate(tombs)
    pts = np.concatenate(pts) if pts else np.zeros((len(ids), 2), np.float32)
    # visibility: newest seqno per pk wins; tombstone winners drop the pk
    order = np.lexsort((seqs, ids))
    run_end = np.append(ids[order][1:] != ids[order][:-1], True)
    winners = order[run_end]
    winners = winners[~tombs[winners]]
    vecs, pts, ids = vecs[winners], pts[winners], ids[winners]
    shard_of = (ids % n_shards).astype(np.int64)
    counts = np.bincount(shard_of, minlength=n_shards)
    per = int(counts.max()) if len(ids) else 1
    # slot of each row: shard base + rank within its shard, computed from
    # the stable shard sort (rows stay in store order within a shard)
    order = np.argsort(shard_of, kind="stable")
    within = np.arange(len(ids)) - np.repeat(
        np.cumsum(counts) - counts, counts)
    slots = shard_of[order] * per + within
    V = np.zeros((n_shards * per, vecs.shape[1]), np.float32)
    Pt = np.zeros((n_shards * per, 2), np.float32)
    I = np.full(n_shards * per, -1, np.int64)
    M = np.zeros(n_shards * per, bool)
    V[slots] = vecs[order]
    Pt[slots] = pts[order]
    I[slots] = ids[order]
    M[slots] = True
    return V, Pt, I, M
