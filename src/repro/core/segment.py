"""Immutable columnar segments — the SST-file analog (DESIGN.md §2).

A segment stores rows sorted by primary key in fixed-height blocks of
``BLOCK_ROWS`` (the read unit: one HBM->VMEM tile). Block handles are
(segment_id, block_id) pairs; the per-segment secondary indexes map
attribute values / centroids to block handles + in-block offsets, mirroring
the paper's "(vector, block handle) pairs" posting lists.
"""
from __future__ import annotations

import dataclasses
import io
import itertools
import os
import threading
from collections import OrderedDict
from typing import Any, Dict, Optional, Sequence, Tuple

import numpy as np

from repro.core.faults import NO_FAULTS, FaultInjector
from repro.core.quantize import QuantizedColumn
from repro.core.types import BLOCK_ROWS, ColumnType, Schema
from repro.core.wal import pack_object_array, unpack_object_array

_seg_counter = itertools.count()


def bump_seg_counter(n: int) -> None:
    """Advance the module seg-id counter to at least ``n``: freshly
    flushed segments must never collide with loaded ones, because the
    pack caches and the global index key on ``seg_id``."""
    global _seg_counter
    cur = next(_seg_counter)
    _seg_counter = itertools.count(max(cur + 1, int(n)))


@dataclasses.dataclass(frozen=True)
class BlockHandle:
    seg_id: int
    block_id: int

    def __repr__(self):
        return f"BH({self.seg_id}:{self.block_id})"


class Segment:
    """Immutable sorted run. ``indexes`` is populated by the index builders
    at flush/compaction time (the paper: vector index built in the
    background along with SST construction)."""

    def __init__(self, schema: Schema, pk: np.ndarray, seqno: np.ndarray,
                 tombstone: np.ndarray, columns: Dict[str, np.ndarray],
                 level: int = 0, seg_id: Optional[int] = None):
        order = np.argsort(pk, kind="stable")
        self.schema = schema
        self.seg_id = next(_seg_counter) if seg_id is None else seg_id
        self.level = level
        # input-row -> segment-row permutation; consumed exactly once (the
        # flush path extends the visibility index with it) then released
        self.sort_order: Optional[np.ndarray] = order
        self.pk = np.asarray(pk)[order]
        self.seqno = np.asarray(seqno)[order]
        self.tombstone = np.asarray(tombstone)[order]
        self.columns: Dict[str, np.ndarray] = {}
        for name, arr in columns.items():
            arr = np.asarray(arr)
            self.columns[name] = arr[order]
        self.n_rows = len(self.pk)
        self.indexes: Dict[str, Any] = {}
        # quantized residence tier: col name -> quantize.QuantizedColumn
        # (PQ codes in segment row order), populated at flush/compaction
        self.quantized: Dict[str, Any] = {}
        # bumped whenever derived per-segment content (quantized codes)
        # is assigned after construction: pack caches key on it, because
        # seg_id alone cannot distinguish a segment packed before its
        # codes arrived from the same segment packed after
        self.content_gen = 0
        # per-segment zone map (fence pointers) for the global index
        self.pk_min = int(self.pk[0]) if self.n_rows else 0
        self.pk_max = int(self.pk[-1]) if self.n_rows else 0

    # ---- blocks ----------------------------------------------------------
    @property
    def n_blocks(self) -> int:
        return (self.n_rows + BLOCK_ROWS - 1) // BLOCK_ROWS

    def block_rows(self, block_id: int) -> slice:
        lo = block_id * BLOCK_ROWS
        return slice(lo, min(lo + BLOCK_ROWS, self.n_rows))

    def read_block(self, col: str, block_id: int) -> np.ndarray:
        """Block-granular read — the unit the cost model charges for."""
        return self.columns[col][self.block_rows(block_id)]

    # ---- point lookups ----------------------------------------------------
    def get(self, key: int) -> Optional[int]:
        """Row index of the NEWEST version of ``key`` or None (binary
        search over sorted pk).  A segment can legally hold several
        versions of one pk — original + update ingested into the same
        memtable flush side by side — so the equal-pk run is resolved by
        max seqno, never by position."""
        i = int(np.searchsorted(self.pk, key))
        if i >= self.n_rows or self.pk[i] != key:
            return None
        j = int(np.searchsorted(self.pk, key, side="right"))
        if j - i == 1:
            return i
        return i + int(np.argmax(self.seqno[i:j]))

    def may_contain(self, key: int) -> bool:
        return self.n_rows > 0 and self.pk_min <= key <= self.pk_max

    def row(self, i: int) -> Dict[str, Any]:
        out = {"_pk": int(self.pk[i]), "_seqno": int(self.seqno[i]),
               "_tombstone": bool(self.tombstone[i])}
        for name, arr in self.columns.items():
            out[name] = arr[i]
        return out


@dataclasses.dataclass
class PackedColumn:
    """Cross-segment superbatch: one column of every visible segment
    stacked into a single matrix, with parallel row-provenance arrays —
    the unit the fused scan->top-k kernel consumes (one launch per query
    batch instead of one per segment)."""
    x: np.ndarray            # (N, d) fp32 stacked column values
    pks: np.ndarray          # (N,) int64 primary keys
    sids: np.ndarray         # (N,) int64 owning segment id per row
    rows: np.ndarray         # (N,) int64 row index inside the segment
    offsets: np.ndarray      # (n_segs + 1,) int64 segment start offsets
    # the fused scan's device copy of ``x`` and ``pks``: an opaque handle
    # that ``kernels/ops.py`` builds and reads.  An entry of
    # ``_pack_cache`` holds it exactly as long as it stays cached.
    device: Any = None
    evicted: bool = False

    def publish_device(self, handle: Any) -> Any:
        """Publish ``handle`` as this column's device copy, unless another
        thread published one first (that one wins) or the entry has left
        ``_pack_cache`` (nothing is kept).  Returns the copy to use."""
        with _pack_lock:
            if self.device is not None:
                return self.device
            if not self.evicted:
                self.device = handle
        return handle


# segments are immutable, so a packed column is valid for as long as its
# exact (col, seg_id...) combination is queried; a small LRU bounds the
# memory pinned by superbatches that outlive compaction (each entry is a
# full fp32 copy of the packed column, so the cap is deliberately tight)
_pack_cache: "OrderedDict[Tuple, PackedColumn]" = OrderedDict()
_PACK_CACHE_CAP = 4
# query threads and the background flush worker share the LRU: an
# unguarded move_to_end/popitem pair from two threads corrupts the
# OrderedDict's internal links
_pack_lock = threading.Lock()


def _publish(key: Tuple, packed: Any) -> Any:
    """Cache ``packed`` under ``key`` and return it, or the entry another
    thread published there first (so one device copy serves both).  Full,
    the LRU evicts its least-recent entries; a packed column's device
    copy goes with its entry."""
    with _pack_lock:
        raced = _pack_cache.get(key)
        if raced is not None:
            return raced
        while len(_pack_cache) >= _PACK_CACHE_CAP:
            _, old = _pack_cache.popitem(last=False)
            if isinstance(old, PackedColumn):
                old.device = None
                old.evicted = True
        _pack_cache[key] = packed
    return packed


def pack_segments(segments: Sequence[Segment], col: str) -> PackedColumn:
    """Concatenate ``col`` across ``segments`` into one superbatch."""
    key = (col,) + tuple((s.seg_id, s.content_gen) for s in segments)
    with _pack_lock:
        hit = _pack_cache.get(key)
        if hit is not None:
            _pack_cache.move_to_end(key)
            return hit
    xs = [np.asarray(s.columns[col], np.float32) for s in segments]
    ns = [s.n_rows for s in segments]
    packed = PackedColumn(
        x=np.concatenate(xs) if xs else np.zeros((0, 0), np.float32),
        pks=np.concatenate([s.pk for s in segments]),
        sids=np.concatenate([np.full(n, s.seg_id, np.int64)
                             for s, n in zip(segments, ns)]),
        rows=np.concatenate([np.arange(n, dtype=np.int64) for n in ns]),
        offsets=np.cumsum([0] + ns).astype(np.int64))
    return _publish(key, packed)


@dataclasses.dataclass
class PackedCodes:
    """Quantized sibling of ``PackedColumn``: the PQ code matrices of the
    same segments stacked in the SAME row order as ``pack_segments``, so
    a packed row id indexes both the fp32 superbatch and the code
    superbatch.  Only well-defined when every segment carries codes from
    one shared codebook set (one ``book_id``)."""
    codes: np.ndarray        # (N, m) uint8 PQ codes
    codebooks: np.ndarray    # (m, 256, dsub) fp32 shared codebooks
    book_id: int


def pack_quantized(segments: Sequence[Segment],
                   col: str) -> Optional[PackedCodes]:
    """Stack ``col``'s PQ codes across ``segments`` (row-aligned with
    ``pack_segments``).  Returns None when any segment lacks codes or the
    segments' codebooks differ — callers fall back to the exact path."""
    qcols = [s.quantized.get(col) for s in segments]
    if not qcols or any(qc is None for qc in qcols):
        return None
    book_id = qcols[0].book_id
    if any(qc.book_id != book_id for qc in qcols[1:]):
        return None
    key = ("#codes", col) + tuple((s.seg_id, s.content_gen)
                                  for s in segments)
    with _pack_lock:
        hit = _pack_cache.get(key)
        if hit is not None:
            _pack_cache.move_to_end(key)
            return hit
    packed = PackedCodes(
        codes=np.concatenate([qc.codes for qc in qcols]),
        codebooks=qcols[0].codebooks,
        book_id=book_id)
    return _publish(key, packed)


def merge_segments(schema: Schema, segments: Sequence[Segment],
                   level: int, drop_tombstones: bool,
                   return_maps: bool = False):
    """K-way merge by primary key keeping the newest seqno per key
    (size-tiered compaction). Tombstones are dropped only when compacting
    into the bottom tier (no older data can be shadowed).

    With ``return_maps`` also returns, per input segment, an int64 array
    mapping each source row to its row in the merged segment (-1 when the
    row was shadowed or tombstone-dropped) — the plumbing mergeable
    per-segment indexes need to remap their entries without a rebuild.
    """
    if not segments:
        raise ValueError("nothing to merge")
    pk = np.concatenate([s.pk for s in segments])
    seqno = np.concatenate([s.seqno for s in segments])
    tomb = np.concatenate([s.tombstone for s in segments])
    cols = {c.name: np.concatenate([s.columns[c.name] for s in segments])
            for c in schema.columns}
    # newest version per key: sort by (pk, -seqno), keep first
    order = np.lexsort((-seqno, pk))
    spk, sseq, stomb = pk[order], seqno[order], tomb[order]
    keep = np.ones(len(spk), bool)
    keep[1:] = spk[1:] != spk[:-1]
    if drop_tombstones:
        keep &= ~stomb
    cols = {k: v[order][keep] for k, v in cols.items()}
    merged = Segment(schema, spk[keep], sseq[keep], stomb[keep], cols,
                     level=level)
    if not return_maps:
        return merged
    # surviving rows are already pk-sorted (strictly increasing after the
    # dedup), so Segment's stable argsort is the identity and the merged
    # row of the i-th kept sorted position is simply its rank
    concat_to_new = np.full(len(pk), -1, np.int64)
    concat_to_new[order[keep]] = np.arange(int(keep.sum()), dtype=np.int64)
    maps, lo = [], 0
    for s in segments:
        maps.append(concat_to_new[lo:lo + s.n_rows])
        lo += s.n_rows
    return merged, maps


# ---------------------------------------------------------------------------
# persistence: one npz-style file per segment (core/manifest.py publishes
# the file names; a segment file is durable only once a manifest names it)
# ---------------------------------------------------------------------------

# loaded segments reuse their saved seg_id (the manifest references files
# by it) but must not collide in the pack caches with any same-id segment
# object from before a crash/restore in this process, so each load stamps
# a content_gen from a range live stores never use (they count from 0)
_load_gens = itertools.count(1_000_000)


def _segment_arrays(seg: Segment) -> Dict[str, np.ndarray]:
    """Flatten a segment to named arrays — no pickle anywhere: object
    columns (TEXT/BLOB) become offsets + byte blobs, indexes serialize
    through the ``to_arrays`` contract, PQ codes/codebooks go as-is."""
    arrays: Dict[str, np.ndarray] = {
        "pk": np.asarray(seg.pk, np.int64),
        "seqno": np.asarray(seg.seqno, np.int64),
        "tombstone": np.asarray(seg.tombstone, bool),
        "meta": np.asarray([seg.level, seg.seg_id], np.int64)}
    for c in seg.schema.columns:
        arr = seg.columns[c.name]
        if arr.dtype == object:
            offsets, blob = pack_object_array(arr)
            arrays[f"col.{c.name}.offsets"] = offsets
            arrays[f"col.{c.name}.blob"] = blob
        else:
            arrays[f"col.{c.name}"] = arr
    for name, qc in seg.quantized.items():
        arrays[f"pq.{name}.codes"] = qc.codes
        arrays[f"pq.{name}.codebooks"] = qc.codebooks
    for name, idx in seg.indexes.items():
        for key, val in idx.to_arrays().items():
            arrays[f"idx.{name}.{key}"] = val
    return arrays


def save_segment(seg: Segment, path: str,
                 faults: FaultInjector = NO_FAULTS) -> None:
    """Write a segment durably: serialize in memory, write temp, fsync,
    atomic rename. The file is invisible to recovery until a manifest
    publish references it, so a crash here leaves only an orphan."""
    buf = io.BytesIO()
    np.savez(buf, **_segment_arrays(seg))
    data = buf.getvalue()
    tmp = path + ".tmp"
    with open(tmp, "wb") as f:
        if faults.should_crash("flush.segment-file"):
            # simulate dying mid-write: a torn temp file lands on disk
            f.write(data[:max(1, len(data) // 2)])
            f.flush()
            faults.crash("flush.segment-file")
        f.write(data)
        f.flush()
        os.fsync(f.fileno())
    os.replace(tmp, path)
    dfd = os.open(os.path.dirname(path) or ".", os.O_RDONLY)
    try:
        os.fsync(dfd)
    finally:
        os.close(dfd)


def load_segment(schema: Schema, path: str,
                 index_factory=None) -> Segment:
    """Rebuild a segment (columns, PQ codes, all index kinds) from its
    file. Loaded PQ columns carry ``book_id=0``; the owning store remaps
    them to a fresh shared id per column so ``pack_quantized``'s
    same-book gate keeps working across loaded + new segments."""
    with np.load(path, allow_pickle=False) as z:
        arrays = {k: z[k] for k in z.files}
    level, seg_id = (int(v) for v in arrays["meta"])
    cols: Dict[str, np.ndarray] = {}
    for c in schema.columns:
        key = f"col.{c.name}"
        if key in arrays:
            cols[c.name] = arrays[key]
        else:
            cols[c.name] = unpack_object_array(
                arrays[f"{key}.offsets"], arrays[f"{key}.blob"],
                as_str=(c.ctype == ColumnType.TEXT))
    seg = Segment(schema, arrays["pk"], arrays["seqno"],
                  arrays["tombstone"].astype(bool), cols,
                  level=level, seg_id=seg_id)
    seg.sort_order = None            # visibility rebuilds from scratch
    seg.content_gen = next(_load_gens)
    for c in schema.columns:
        ck = f"pq.{c.name}.codes"
        if ck in arrays:
            seg.quantized[c.name] = QuantizedColumn(
                arrays[ck], arrays[f"pq.{c.name}.codebooks"], 0)
    if index_factory is not None:
        for c in schema.indexed_columns:
            prefix = f"idx.{c.name}."
            sub = {k[len(prefix):]: v for k, v in arrays.items()
                   if k.startswith(prefix)}
            if not sub:
                continue
            idx = index_factory(c)
            if idx is not None:
                idx.from_arrays(sub, seg, c)
                seg.indexes[c.name] = idx
    bump_seg_counter(seg_id + 1)
    return seg
