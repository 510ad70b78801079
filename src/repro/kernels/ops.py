"""Jit'd wrappers over the Pallas kernels with numpy-friendly padding.

The host-side ARCADE engine calls these for all per-segment compute:
distance scans, PQ ADC, predicate bitmaps, fused top-k scans, graph
search, cross-shard merges.  ``backend()`` is THE rule that picks what
runs, from the device JAX finds — no user switch:

  * ``pallas``    — on a TPU: every op with a Pallas kernel dispatches it
                    compiled (``interpret=False``); graph search runs its
                    jitted XLA twin (``GRAPH_PROGRAM``);
  * ``interpret`` — on the CPU with ``use_pallas`` (``REPRO_USE_PALLAS=1``
                    for the CI sweep): the same kernels in interpret mode;
  * ``ref``       — on the CPU otherwise: the jnp oracles from ref.py,
                    jit-compiled, and the host-simulated fused kernels
                    that keep CPU fused and staged results bitwise equal.

Below ``HOST_FLOP_CUTOFF`` an op runs in host numpy instead (a size
rule, in every backend but ``interpret``); those dispatches are counted
apart from device launches (``KernelStats.host_dispatches``).

Every transfer between host and device goes through ``_to_device`` and
``_to_host``.  With tracing on, each public op opens one span named by
the path it takes: ``dispatch:<op>`` on the device path (its self time
is the host's prep: padding, block compaction, masks) or ``host_op:<op>``
below the cut-off (the host doing the kernel's work); the transfers open
``transfer:to_device`` and ``transfer:to_host`` inside it.
"""
from __future__ import annotations

import collections
import dataclasses
import functools
import os
import threading
from typing import Dict, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import bitmap_filter as bf_kernel
from repro.kernels import fused_scan as fs_kernel
from repro.kernels import graph_search as gs_kernel
from repro.kernels import ivf_scan as ivf_kernel
from repro.kernels import pq_adc as pq_kernel
from repro.kernels import quantized_scan as qs_kernel
from repro.kernels import ref
from repro.kernels import topk_merge as tk_kernel
from repro.obs import REGISTRY
from repro.obs import trace as obs_trace

# CPU-only: run the Pallas kernels in interpret mode instead of the jnp
# oracles (tests flip it; the CI interpret sweep sets the env var).  On a
# TPU it has no effect — the compiled kernels are the only device path.
USE_PALLAS = os.environ.get("REPRO_USE_PALLAS", "0") == "1"

# what graph dispatch runs on a TPU: the jitted XLA twin of the beam
# search (``ref.graph_search_topk_ref``).  The Pallas kernel holds the
# whole packed store in VMEM, so it cannot serve a real store compiled.
GRAPH_PROGRAM = "xla"


@functools.lru_cache(maxsize=None)
def on_tpu() -> bool:
    return jax.default_backend() == "tpu"


def backend(use_pallas: bool = None) -> str:
    """THE backend rule (see module docstring): ``pallas`` on a TPU,
    else ``interpret`` when ``use_pallas`` (default ``USE_PALLAS``),
    else ``ref``."""
    if on_tpu():
        return "pallas"
    return "interpret" if (USE_PALLAS if use_pallas is None
                           else use_pallas) else "ref"


def graph_program(use_pallas: bool = None) -> str:
    """Which program graph dispatch runs under ``backend()`` — named in
    EXPLAIN's ``dispatch=graph(...)``."""
    mode = backend(use_pallas)
    return GRAPH_PROGRAM if mode == "pallas" else mode


# ---------------------------------------------------------------------------
# dispatch accounting
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class KernelStats:
    """Per-THREAD dispatch counters (monotonic; consumers diff
    ``stats_snapshot()`` values around a region of interest).  Thread-
    local so a background flush/compaction worker's index-build kernel
    dispatches are never attributed to the query thread it races.

    launches       — op dispatches.  The host numpy fast path under
                     ``HOST_FLOP_CUTOFF`` counts too: at production scale
                     the cutoff vanishes and every dispatch is a device
                     launch, so ratios stay machine-independent.
    host_dispatches — the subset of ``launches`` that ran in host numpy
                     (no device program); per-program device launches
                     are tallied process-wide by ``launches_by_tag()``.
    bytes_to_host  — bytes of results handed back to the host engine
                     (device->host traffic on the device path; the host
                     path's results count too).
    bytes_to_device — bytes of operands uploaded by ``_to_device``, in
                     every backend (``ref`` and ``interpret`` upload to
                     the CPU device); the host path adds none.
    shape_misses   — first sighting of a (op, bucketed shape) pair, i.e.
                     jit compile-cache misses caused by ``_bucket``-padded
                     ragged inputs (the shape-cache itself is process-
                     wide, like jax's jit cache).
    resident_hits / resident_misses — device fused scans that found their
                     packed column's device copy already built / built it
                     (``_resident``).
    """
    launches: int = 0
    bytes_to_host: int = 0
    shape_misses: int = 0
    host_dispatches: int = 0
    bytes_to_device: int = 0
    resident_hits: int = 0
    resident_misses: int = 0
    # high-water marks already published to the metrics registry; the
    # per-dispatch mirror batches (see flush_registry_counters) so the
    # hot path pays an int compare instead of a Counter lock
    reg_launches: int = 0
    reg_bytes: int = 0
    reg_misses: int = 0
    reg_host: int = 0
    reg_up: int = 0
    reg_res_hits: int = 0
    reg_res_misses: int = 0


_tls = threading.local()
_seen_shapes: set = set()
# device launches per program tag ("fused_scan.pallas", ...), all threads
_tag_launches: collections.Counter = collections.Counter()
# the jit shape cache and tag tally are process-global while the
# counters are per-thread; guard membership+insert so concurrent
# first-seens from a query thread and the flush worker don't corrupt them
_seen_lock = threading.Lock()


def thread_stats() -> KernelStats:
    """The calling thread's dispatch counters."""
    stats = getattr(_tls, "stats", None)
    if stats is None:
        stats = _tls.stats = KernelStats()
    return stats


def stats_snapshot() -> Tuple[int, int, int]:
    s = thread_stats()
    return (s.launches, s.bytes_to_host, s.shape_misses)


def launches_by_tag() -> Dict[str, int]:
    """Process-wide device launches per program tag (``<op>.<backend>``;
    host-path dispatches carry no tag and are not included)."""
    with _seen_lock:
        return dict(_tag_launches)


_reg_counters = None
_reg_generation = -1


def _registry_counters():
    """Process-wide mirrors of the per-thread counters in the metrics
    registry.  Object refs are cached (re-fetched only when
    ``REGISTRY.reset()`` bumps its generation), so the per-dispatch
    cost is an int compare; a flush makes at most seven ``Counter.inc``
    calls."""
    global _reg_counters, _reg_generation
    if _reg_counters is None or _reg_generation != REGISTRY.generation:
        _reg_generation = REGISTRY.generation
        _reg_counters = (REGISTRY.counter("kernels.launches"),
                         REGISTRY.counter("kernels.bytes_to_host"),
                         REGISTRY.counter("kernels.jit_shape_misses"),
                         REGISTRY.counter("kernels.host_dispatches"),
                         REGISTRY.counter("kernels.bytes_to_device"),
                         REGISTRY.counter("kernels.resident_hits"),
                         REGISTRY.counter("kernels.resident_misses"))
    return _reg_counters


REG_FLUSH_EVERY = 64    # dispatches between registry-mirror flushes


def flush_registry_counters() -> None:
    """Publish the calling thread's pending dispatch deltas to the
    metrics registry.  Runs every ``REG_FLUSH_EVERY`` dispatches and at
    query-batch boundaries (``Executor._observe_query``), keeping the
    registry's Counter lock off the per-dispatch path."""
    s = thread_stats()
    launches, byts, misses, host, up, hits, cold = _registry_counters()
    if s.launches != s.reg_launches:
        launches.inc(s.launches - s.reg_launches)
        s.reg_launches = s.launches
    if s.bytes_to_host != s.reg_bytes:
        byts.inc(s.bytes_to_host - s.reg_bytes)
        s.reg_bytes = s.bytes_to_host
    if s.shape_misses != s.reg_misses:
        misses.inc(s.shape_misses - s.reg_misses)
        s.reg_misses = s.shape_misses
    if s.host_dispatches != s.reg_host:
        host.inc(s.host_dispatches - s.reg_host)
        s.reg_host = s.host_dispatches
    if s.bytes_to_device != s.reg_up:
        up.inc(s.bytes_to_device - s.reg_up)
        s.reg_up = s.bytes_to_device
    if s.resident_hits != s.reg_res_hits:
        hits.inc(s.resident_hits - s.reg_res_hits)
        s.reg_res_hits = s.resident_hits
    if s.resident_misses != s.reg_res_misses:
        cold.inc(s.resident_misses - s.reg_res_misses)
        s.reg_res_misses = s.resident_misses


def _dispatched(out_bytes: int, tag: str = None, shape: Tuple = ()) -> None:
    """Record one op dispatch; with a ``tag`` it is a device launch of
    that program and also tracks the jit shape cache (host-path calls
    pass no tag — numpy has no shape cache)."""
    s = thread_stats()
    s.launches += 1
    s.bytes_to_host += int(out_bytes)
    if tag is None:
        s.host_dispatches += 1
    if s.launches - s.reg_launches >= REG_FLUSH_EVERY:
        flush_registry_counters()
    if tag is not None:
        key = (tag,) + tuple(shape)
        with _seen_lock:
            _tag_launches[tag] += 1
            fresh = key not in _seen_shapes
            if fresh:
                _seen_shapes.add(key)
        if fresh:
            s.shape_misses += 1


def _to_device(*arrays: np.ndarray) -> Tuple[jax.Array, ...]:
    """Upload host operands, one ``jnp.asarray`` each, inside one
    ``transfer:to_device`` span; their ``nbytes`` are added to
    ``KernelStats.bytes_to_device``.  The span times the host's part of
    the upload: the runtime may finish the copy after it returns, and the
    kernel waits for it (that wait lands in ``transfer:to_host``)."""
    n = sum(a.nbytes for a in arrays)
    thread_stats().bytes_to_device += n
    with obs_trace.span("transfer:to_device", bytes=n):
        return tuple(jnp.asarray(a) for a in arrays)


def _to_host(*outs: jax.Array):
    """Fetch device results, ``np.asarray`` each, inside one
    ``transfer:to_host`` span.  Dispatch is asynchronous, so the span
    includes the wait for the kernel that makes the results.  One result
    comes back bare, several as a tuple."""
    with obs_trace.span("transfer:to_host"):
        host = tuple(np.asarray(o) for o in outs)
    return host if len(host) > 1 else host[0]


def _pad_to(x: np.ndarray, mult: int, axis: int, value=0.0) -> np.ndarray:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return np.pad(x, widths, constant_values=value)


def _bucket(n: int, floor: int = 128) -> int:
    """Round up to the next power-of-two bucket (>= floor): bounds the
    number of distinct jit shapes from ragged posting lists to O(log n)."""
    b = floor
    while b < n:
        b *= 2
    return b


def _pad_bucket(x: np.ndarray, axis: int, value=0.0,
                floor: int = 128) -> np.ndarray:
    n = x.shape[axis]
    b = _bucket(n, floor)
    if b == n:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, b - n)
    return np.pad(x, widths, constant_values=value)


def _pad_codes(codes: np.ndarray, block: int,
               bucket: bool = True) -> np.ndarray:
    """THE one place PQ code matrices get padded for device dispatch:
    row pad to a ``block`` multiple (code 0 in the pad rows — masked or
    sliced off by every consumer), optionally bucket-padded to a power
    of two.  Codes stay uint8 (m bytes/row to upload; the device programs
    widen them).  Shared by both ``pq_adc_distances`` backends and the
    fused quantized scan so ``stats_snapshot()`` charges code-block
    padding identically whichever path ran."""
    cp = _pad_to(np.asarray(codes, np.uint8), block, 0)
    return _pad_bucket(cp, 0, floor=block) if bucket else cp


@functools.lru_cache(maxsize=None)
def _jit_ivf_ref():
    return jax.jit(ref.ivf_scan_ref)


@functools.lru_cache(maxsize=None)
def _jit_pq_ref():
    return jax.jit(ref.pq_adc_ref)


@functools.lru_cache(maxsize=None)
def _jit_bitmap_ref():
    return jax.jit(ref.bitmap_filter_ref)


# ---------------------------------------------------------------------------
# distance scans
# ---------------------------------------------------------------------------

# Below this many MACs the fixed device-dispatch cost dominates: run the
# op on the host (the TPU-production analog: tiny index probes stay on the
# host CPU; large posting scans go to the accelerator kernels).
HOST_FLOP_CUTOFF = 4_000_000


def _l2_host(q: np.ndarray, x: np.ndarray) -> np.ndarray:
    """Host numpy squared-L2 with BATCH-SHAPE-INDEPENDENT rounding.

    The difference form ``((x - q)**2).sum(-1)`` computes every output
    element from exactly its own (q_i, x_j) pair — numpy pairwise-sums
    the d axis per element — so a row's distance is bitwise identical
    whatever it is batched with.  The BLAS-backed ``qn - 2 q@x.T + xn``
    expansion does NOT have this property: gemm picks differently-rounded
    micro-kernels by operand shape and row position (size-1 operands hit
    a gemv/dot path; larger shapes still disagree at blocking edges), so
    the same row scored in two batch layouts could differ by ~1 ulp.
    That invariant is what makes fused-vs-staged, NRA-refinement-vs-scan
    and sharded-vs-single results bitwise comparable — and the
    difference form also never goes negative (no cancellation).  Only
    used below HOST_FLOP_CUTOFF, so the (nq, n, d) temporary is bounded
    at ~16 MB."""
    diff = x[None, :, :] - q[:, None, :]
    return (diff * diff).sum(axis=-1)


def l2_distances(q: np.ndarray, x: np.ndarray,
                 use_pallas: bool = None) -> np.ndarray:
    """Squared L2: q (nq, d), x (n, d) -> (nq, n) fp32."""
    mode = backend(use_pallas)
    q = np.asarray(q, np.float32)
    x = np.asarray(x, np.float32)
    if len(x) == 0:
        return np.zeros((len(q), 0), np.float32)
    if mode != "interpret" and q.shape[0] * x.shape[0] * x.shape[1] \
            < HOST_FLOP_CUTOFF:
        with obs_trace.span("host_op:l2_distances"):
            out = _l2_host(q, x)
            _dispatched(out.nbytes)
        return out
    with obs_trace.span("dispatch:l2_distances"):
        if mode != "ref":
            qp = _pad_to(q, ivf_kernel.BLOCK_Q, 0)
            xp = _pad_bucket(_pad_to(x, ivf_kernel.BLOCK_N, 0, value=1e30),
                             0, value=1e30, floor=ivf_kernel.BLOCK_N)
            out = _to_host(ivf_kernel.ivf_scan(
                *_to_device(qp, xp), interpret=mode == "interpret"))
            tag = f"ivf_scan.{mode}"
        else:
            qp = _pad_bucket(q, 0, floor=8)
            xp = _pad_bucket(x, 0)
            out = _to_host(_jit_ivf_ref()(*_to_device(qp, xp)))
            tag = "ivf_scan.ref"
        _dispatched(out.nbytes, tag, qp.shape + xp.shape)
    return out[:len(q), :len(x)]


def assign_nearest(x: np.ndarray, centroids: np.ndarray,
                   chunk: int = 16384) -> np.ndarray:
    """argmin over centroids per row (chunked for memory)."""
    out = np.empty(len(x), np.int64)
    for i in range(0, len(x), chunk):
        d = l2_distances(x[i:i + chunk], centroids)
        out[i:i + chunk] = np.argmin(d, axis=1)
    return out


def block_topk(q: np.ndarray, vecs: np.ndarray, k: int,
               use_pallas: bool = None) -> Tuple[np.ndarray, np.ndarray]:
    """Top-k nearest of q among vecs -> (dists sorted, indices)."""
    d = l2_distances(q[None, :], vecs, use_pallas=use_pallas)[0]
    k = min(k, len(d))
    idx = np.argpartition(d, k - 1)[:k]
    # (score, row) comparator: ties break by row index, deterministic
    # regardless of argpartition's arbitrary intra-tie order
    order = np.lexsort((idx, d[idx]))
    return d[idx][order], idx[order]


# ---------------------------------------------------------------------------
# PQ ADC
# ---------------------------------------------------------------------------

def pq_adc_distances(q: np.ndarray, codes: np.ndarray,
                     codebooks: np.ndarray,
                     use_pallas: bool = None) -> np.ndarray:
    """q (d,); codes (n, m) uint8; codebooks (m, 256, dsub) -> (n,) fp32."""
    mode = backend(use_pallas)
    if len(codes) == 0:
        return np.zeros((0,), np.float32)
    m, n_codes, dsub = codebooks.shape
    qs = q.reshape(m, dsub)
    if mode != "interpret" and codes.size < HOST_FLOP_CUTOFF:
        with obs_trace.span("host_op:pq_adc_distances"):
            lut = ((codebooks - qs[:, None, :]) ** 2).sum(axis=2)
            out = np.take_along_axis(
                lut.T, codes.astype(np.int64), axis=0).sum(axis=1) \
                .astype(np.float32)
            _dispatched(out.nbytes)
        return out
    with obs_trace.span("dispatch:pq_adc_distances"):
        # LUT: distance from q's subvector to every codeword (m, 256)
        lut = ((codebooks - qs[:, None, :]) ** 2).sum(axis=2) \
            .astype(np.float32)
        cp = _pad_codes(codes, pq_kernel.BLOCK_N)
        if mode != "ref":
            out = _to_host(pq_kernel.pq_adc(
                *_to_device(cp, lut), interpret=mode == "interpret"))
        else:
            out = _to_host(_jit_pq_ref()(*_to_device(cp, lut)))
        _dispatched(out.nbytes, f"pq_adc.{mode}", cp.shape)
    return out[:len(codes)]


# ---------------------------------------------------------------------------
# predicate bitmaps
# ---------------------------------------------------------------------------

def range_bitmap(cols: np.ndarray, bounds: np.ndarray,
                 use_pallas: bool = None) -> np.ndarray:
    """cols (n, c) fp32; bounds (c, 2) -> (n,) bool (AND of range preds)."""
    mode = backend(use_pallas)
    cols = np.asarray(cols, np.float32)
    bounds = np.asarray(bounds, np.float32)
    if len(cols) == 0:
        return np.zeros((0,), bool)
    if mode != "interpret" and cols.size < HOST_FLOP_CUTOFF:
        with obs_trace.span("host_op:range_bitmap"):
            out = np.all((cols >= bounds[:, 0][None])
                         & (cols <= bounds[:, 1][None]), axis=1)
            _dispatched(out.nbytes)
        return out
    with obs_trace.span("dispatch:range_bitmap"):
        if mode != "ref":
            cp = _pad_bucket(
                _pad_to(cols, bf_kernel.BLOCK_N, 0, value=np.inf),
                0, value=np.inf, floor=bf_kernel.BLOCK_N)
            out = _to_host(bf_kernel.bitmap_filter(
                *_to_device(cp, bounds), interpret=mode == "interpret"))
        else:
            cp = _pad_bucket(cols, 0, value=np.inf)
            out = _to_host(_jit_bitmap_ref()(*_to_device(cp, bounds)))
        _dispatched(out.nbytes, f"bitmap.{mode}", cp.shape)
    return out[:len(cols)].astype(bool, copy=False)


def rect_filter(points: np.ndarray, rect,
                use_pallas: bool = None) -> np.ndarray:
    """points (n, 2); rect (xmin, ymin, xmax, ymax) -> (n,) bool."""
    r = np.asarray(rect, np.float32)
    bounds = np.stack([[r[0], r[2]], [r[1], r[3]]])       # (2, 2)
    return range_bitmap(np.asarray(points, np.float32), bounds,
                        use_pallas=use_pallas)


# ---------------------------------------------------------------------------
# fused masked scan -> top-k (packed cross-segment path)
# ---------------------------------------------------------------------------

def fused_scan_topk(q: np.ndarray, packed, mask: np.ndarray, k: int,
                    use_pallas: bool = None
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Fused filter-aware scan -> per-query top-k over a packed column.

    q (nq, d) queries; ``packed`` the ``segment.PackedColumn`` of all
    visible segments (its ``x`` (n, d) vectors and ``pks`` (n,) primary
    keys, < 2^31: the device tie-break key); mask (nq, n) bool predicate
    bitmap.  Returns (d2 (nq, k) fp32 squared-L2 ascending, rows (nq, k)
    int64 row indices into ``packed.x``; -1 marks slots beyond the
    query's candidate count).  Ties break by (distance, pk) — the host
    merge's lexsort comparator.  The CPU ``ref`` backend SIMULATES the
    fused kernel: it reproduces the staged path's distance arithmetic at
    this size (numpy expansion below ``HOST_FLOP_CUTOFF``, the jit'd scan
    above) and the host merge's (sqrt-distance, pk) comparator exactly,
    so fused and staged results are bitwise equal backend-for-backend;
    the Pallas kernel (always, on a TPU) compares squared distances (a
    monotone transform — same rows except where f32 sqrt rounds two
    distinct squared distances together).

    ONE dispatch for the whole batch, whatever the segment or predicate
    count.  On the device backends the packed vectors and pks stay on
    the device (``_resident``): the first dispatch over a packed column
    uploads them, padded to a power-of-two count of BLOCK_N blocks so
    ragged stores hit O(log n) jit shapes, and later dispatches over the
    same column upload only the queries, the mask and a per-(query-tile,
    block) occupancy grid that lets the kernel skip tiles no query of
    the tile admits.  The ``dispatch:fused_scan_topk`` span carries
    ``resident_hits`` / ``resident_lookups`` (1 / 1 when the copy was
    found, 0 / 1 when this dispatch built it).
    """
    mode = backend(use_pallas)
    q = np.asarray(q, np.float32)
    x = np.asarray(packed.x, np.float32)
    mask = np.asarray(mask, bool)
    nq = len(q)
    k = int(min(k, fs_kernel.KMAX))
    if len(x) == 0 or k == 0 or not mask.any():
        return (np.full((nq, k), np.inf, np.float32),
                np.full((nq, k), -1, np.int64))
    if mode == "ref":
        host = q.shape[0] * x.shape[0] * x.shape[1] < HOST_FLOP_CUTOFF
        with obs_trace.span("host_op:fused_scan_topk" if host
                            else "dispatch:fused_scan_topk"):
            return _fused_scan_ref(q, x, mask, packed.pks, k, host)
    found = packed.device
    with obs_trace.span("dispatch:fused_scan_topk",
                        resident_hits=int(found is not None),
                        resident_lookups=1):
        return _fused_scan_device(q, _resident(packed, found), mask, k,
                                  mode)


def _fused_scan_ref(q, x, mask, pks, k, host: bool):
    """The ``ref`` backend's simulated fused kernel: ONE counted
    dispatch, with the exact arithmetic the staged path uses at this size
    (numpy expansion below the FLOP cutoff — ``host`` — the same jit'd
    scan kernel above it) and the host merge's (score, pk) comparator —
    so fused and staged return bitwise-equal results on matching
    backends."""
    nq = len(q)
    if host:
        d2 = _l2_host(q, x)
        shape_tag = None
    else:
        qp = _pad_bucket(q, 0, floor=8)
        xp = _pad_bucket(x, 0)
        d2 = _to_host(_jit_ivf_ref()(*_to_device(qp, xp)))[:nq, :len(x)]
        shape_tag = qp.shape + xp.shape
    s = np.where(mask, np.sqrt(np.maximum(d2, 0),
                               dtype=np.float32), np.inf)
    pks64 = np.asarray(pks, np.int64)
    out_d = np.full((nq, k), np.inf, np.float32)
    out_r = np.full((nq, k), -1, np.int64)
    for qi in range(nq):
        order = np.lexsort((pks64, s[qi]))[:k]
        order = order[np.isfinite(s[qi][order])]
        out_d[qi, :len(order)] = d2[qi][order]
        out_r[qi, :len(order)] = order
    _dispatched(out_d.nbytes + out_r.nbytes,
                None if shape_tag is None else "fused_scan.ref",
                shape_tag or ())
    return out_d, out_r


def _resident(packed, found) -> Tuple[jax.Array, jax.Array]:
    """The packed column's device copy for the fused kernel: x (N, d)
    fp32 and pks (1, N) int32, N the power-of-two count of BLOCK_N blocks
    that holds every row; pad rows are zero with ``SENTINEL`` pks (the
    mask never admits them).  ``found`` is the copy the caller read off
    the column, or None: then it is built and published, and lives as
    long as the column's ``_pack_cache`` entry
    (``segment.PackedColumn.publish_device``)."""
    s = thread_stats()
    if found is not None:
        s.resident_hits += 1
        return found
    s.resident_misses += 1
    BN = fs_kernel.BLOCK_N
    x = np.asarray(packed.x, np.float32)
    n = len(x)
    npad = _bucket(-(-n // BN), floor=1) * BN
    xp = np.zeros((npad, x.shape[1]), np.float32)
    xp[:n] = x
    pk32 = np.full((1, npad), int(fs_kernel.SENTINEL), np.int32)
    pk32[0, :n] = np.asarray(packed.pks, np.int64)
    return packed.publish_device(_to_device(xp, pk32))


def _fused_scan_device(q, resident, mask, k, mode: str):
    """The fused kernel's host prep, upload, launch and fetch over the
    column's device copy (see ``fused_scan_topk``)."""
    nq, n = mask.shape
    BQ, BN = fs_kernel.BLOCK_Q, fs_kernel.BLOCK_N
    x_dev, pk_dev = resident
    npad = x_dev.shape[0]
    qp = _pad_to(q, BQ, 0)
    # pad rows and pad queries carry mask 0: never selected
    mp = np.zeros((len(qp), npad), np.uint8)
    mp[:nq, :n] = mask
    occ = mp.reshape(len(qp) // BQ, BQ, npad // BN, BN) \
        .any(axis=(1, 3)).astype(np.int32)
    q_dev, m_dev, occ_dev = _to_device(qp, mp, occ)
    d2, _, idx = fs_kernel.fused_scan_topk(
        q_dev, x_dev, m_dev, pk_dev, occ_dev, k=k,
        interpret=mode == "interpret")
    d2, idx = _to_host(d2, idx)
    _dispatched(d2.nbytes + 2 * idx.nbytes, f"fused_scan.{mode}",
                qp.shape + tuple(x_dev.shape) + (k,))
    d2, idx = d2[:nq, :k], idx[:nq, :k]
    rows = np.where(idx == int(fs_kernel.SENTINEL), -1, idx)
    return d2, rows.astype(np.int64)


# ---------------------------------------------------------------------------
# graph beam search -> top-beam (candidate generation)
# ---------------------------------------------------------------------------

@functools.lru_cache(maxsize=None)
def _jit_graph_ref(beam: int, hops: int):
    return jax.jit(functools.partial(ref.graph_search_topk_ref,
                                     beam=beam, hops=hops))


def _graph_host(q, x, nbr, ent, mask, pks64, beam, hops):
    """Host numpy beam search: same hop/dedup/comparator structure as the
    kernel, per query.  Candidate COVERAGE can differ from the device
    paths by float ulps at the beam margin; the operator layer's exact
    re-rank normalizes scores either way."""
    nq, n = len(q), len(x)
    out_d = np.full((nq, beam), np.inf, np.float32)
    out_r = np.full((nq, beam), -1, np.int64)
    gathered = np.zeros(nq, np.int64)
    for qi in range(nq):
        qv = q[qi]
        visited = np.zeros(n, bool)
        visited[ent] = True
        diff = x[ent] - qv
        bd = (diff * diff).sum(axis=1).astype(np.float32)
        bi = ent.copy()
        adm = mask[qi][bi]
        res_d, res_i = [bd[adm]], [bi[adm]]
        order = np.lexsort((bi, pks64[bi], bd))[:beam]
        bd, bi = bd[order], bi[order]
        for _ in range(hops):
            cand = nbr[bi].ravel()
            cand = np.unique(cand[cand >= 0])
            cand = cand[~visited[cand]]
            if not len(cand):
                break
            visited[cand] = True
            diff = x[cand] - qv
            cd = (diff * diff).sum(axis=1).astype(np.float32)
            adm = mask[qi][cand]
            res_d.append(cd[adm])
            res_i.append(cand[adm])
            md = np.concatenate([bd, cd])
            mi = np.concatenate([bi, cand])
            order = np.lexsort((mi, pks64[mi], md))[:beam]
            bd, bi = md[order], mi[order]
        gathered[qi] = int(visited.sum())
        rd = np.concatenate(res_d)
        ri = np.concatenate(res_i)
        order = np.lexsort((ri, pks64[ri], rd))[:beam]
        out_d[qi, :len(order)] = rd[order]
        out_r[qi, :len(order)] = ri[order]
    _dispatched(out_d.nbytes + out_r.nbytes)
    return out_d, out_r, gathered


def graph_search_topk(q: np.ndarray, x: np.ndarray, neighbors: np.ndarray,
                      entries: np.ndarray, mask: np.ndarray,
                      pks: np.ndarray, beam: int, hops: int,
                      use_pallas: bool = None
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Graph-index candidate generation over a packed CSR superbatch.

    q (nq, d) queries; x (n, d) packed vectors; neighbors (n, R) int32
    CSR adjacency in packed row space (-1 out-degree padding); entries
    (e,) int32 seed rows (the per-segment medoids); mask (nq, n) bool
    predicate bitmap; pks (n,) primary keys.  Returns (d2 (nq, beam)
    fp32 squared-L2 ascending, rows (nq, beam) int64 packed row ids, -1
    beyond a query's candidate count, gathered (nq,) int64 count of rows
    whose vectors the walk touched — the sub-linear-access statistic).
    Ties break by (distance, pk) like every scan kernel.

    Distances are exact but coverage is approximate: callers re-rank the
    survivors through ``fused_scan_topk`` with the survivor mask, so the
    final (score, pk) results match the exact dispatch bit-for-bit
    whenever the beam covered the true top-k.

    Host-side prep pads rows to a bucketed BLOCK_N multiple (padding
    rows are unreachable: their adjacency is all -1 and no real row
    points at them) and queries to BLOCK_Q tiles.  On a TPU the walk
    runs as the jitted XLA twin (``graph_program()``), tag
    ``graph_search.xla``.
    """
    mode = backend(use_pallas)
    q = np.asarray(q, np.float32)
    x = np.asarray(x, np.float32)
    nbr = np.asarray(neighbors, np.int32)
    mask = np.asarray(mask, bool)
    pks64 = np.asarray(pks, np.int64).ravel()
    nq, n = len(q), len(x)
    beam = int(min(beam, fs_kernel.KMAX))
    hops = int(hops)
    empty = (np.full((nq, beam), np.inf, np.float32),
             np.full((nq, beam), -1, np.int64),
             np.zeros(nq, np.int64))
    ent = np.asarray(entries, np.int64).ravel()
    ent = ent[(ent >= 0) & (ent < n)]
    if n == 0 or beam == 0 or len(ent) == 0 or not mask.any():
        return empty
    work = nq * (hops * beam * nbr.shape[1] + len(ent)) * x.shape[1]
    if mode != "interpret" and work < HOST_FLOP_CUTOFF:
        with obs_trace.span("host_op:graph_search_topk"):
            return _graph_host(q, x, nbr, ent, mask, pks64, beam, hops)
    with obs_trace.span("dispatch:graph_search_topk"):
        return _graph_device(q, x, nbr, ent, mask, pks64, beam, hops,
                             mode, use_pallas)


def _graph_device(q, x, nbr, ent, mask, pks64, beam, hops, mode: str,
                  use_pallas):
    """The graph walk's host prep, upload, launch and fetch (see
    ``graph_search_topk``)."""
    nq, n = len(q), len(x)
    BQ, BN = fs_kernel.BLOCK_Q, fs_kernel.BLOCK_N
    sent = int(fs_kernel.SENTINEL)
    xp = _pad_bucket(_pad_to(x, BN, 0), 0, floor=BN)
    npad = len(xp)
    nbp = np.full((npad, nbr.shape[1]), -1, np.int32)
    nbp[:n] = nbr
    mp = np.zeros((nq, npad), np.uint8)
    mp[:, :n] = mask
    pkp = np.full(npad, sent, np.int64)
    pkp[:n] = pks64
    ep = np.full((1, _bucket(len(ent), floor=8)), sent, np.int32)
    ep[0, :len(ent)] = ent
    qp = _pad_to(q, BQ, 0)
    mq = _pad_to(mp, BQ, 0)
    pk32 = pkp.astype(np.int32)[None, :]
    operands = _to_device(qp, xp, nbp, ep, mq, pk32)
    if mode == "interpret":
        d2, _, ids, vis = gs_kernel.graph_search_topk(
            *operands, beam, hops, interpret=True)
    else:
        d2, _, ids, vis = _jit_graph_ref(beam, hops)(*operands)
    tag = f"graph_search.{graph_program(use_pallas)}"
    d2, ids, vis = (a[:nq] for a in _to_host(d2, ids, vis))
    _dispatched(d2.nbytes + ids.nbytes + vis.nbytes, tag,
                qp.shape + xp.shape + (beam, hops))
    rows = np.where(ids == sent, -1, ids).astype(np.int64)
    gathered = np.unpackbits(
        vis.view(np.uint8), axis=1).sum(axis=1).astype(np.int64)
    return d2, rows, gathered


# ---------------------------------------------------------------------------
# fused quantized (PQ ADC) scan -> top-k' (candidate generation)
# ---------------------------------------------------------------------------

def adc_lut(q: np.ndarray, codebooks: np.ndarray) -> np.ndarray:
    """Per-query ADC tables: q (nq, d); codebooks (m, 256, dsub) ->
    (nq, m, 256) fp32 with lut[q, j, c] = ||q_sub_j - codebook_j[c]||^2.
    Computed once per launch on the host (nq*m*256 floats — tiny next to
    the code matrix the device streams)."""
    nq, d = q.shape
    m, _, dsub = codebooks.shape
    qs = np.asarray(q, np.float32).reshape(nq, m, dsub)
    diff = codebooks[None, :, :, :] - qs[:, :, None, :]
    return (diff * diff).sum(axis=3).astype(np.float32)


def quantized_scan_topk(q: np.ndarray, codes: np.ndarray,
                        codebooks: np.ndarray, mask: np.ndarray,
                        pks: np.ndarray, k: int,
                        use_pallas: bool = None
                        ) -> Tuple[np.ndarray, np.ndarray]:
    """Fused PQ-ADC candidate generation over a packed code matrix.

    q (nq, d) queries; codes (n, m) uint8 packed PQ codes (row-aligned
    with the fp32 superbatch); codebooks (m, 256, dsub) shared books;
    mask (nq, n) bool; pks (n,) primary keys.  Returns (adc (nq, k) fp32
    ADC distances ascending, rows (nq, k) int64 row indices into the
    packed matrix; -1 beyond a query's candidate count).  Ties break by
    (adc, pk) so survivor sets are deterministic.

    ADC distances are approximate: callers re-rank the survivors exactly
    via ``fused_scan_topk`` with the survivor mask — which reproduces the
    exact path's per-row arithmetic bit-for-bit in both backends, so the
    final (score, pk) results match the exact dispatch whenever the
    survivors cover the true top-k.

    Host-side prep, every call: pad -> keep-block compaction ->
    power-of-two bucket -> occupancy grid over the uint8 code matrix (the
    device streams m bytes/row instead of the fused scan's 4*d).
    """
    mode = backend(use_pallas)
    q = np.asarray(q, np.float32)
    mask = np.asarray(mask, bool)
    nq = len(q)
    k = int(min(k, fs_kernel.KMAX))
    empty = (np.full((nq, k), np.inf, np.float32),
             np.full((nq, k), -1, np.int64))
    if len(codes) == 0 or k == 0 or not mask.any():
        return empty
    if mode == "ref":
        with obs_trace.span("host_op:quantized_scan_topk"):
            return _quantized_scan_ref(q, codes, codebooks, mask, pks, k)
    with obs_trace.span("dispatch:quantized_scan_topk"):
        return _quantized_scan_device(q, codes, codebooks, mask, pks, k,
                                      mode, empty)


def _quantized_scan_ref(q, codes, codebooks, mask, pks, k):
    """The ``ref`` backend's simulated fused ADC kernel: ONE counted
    (host) dispatch; same gather arithmetic and (adc, pk) comparator as
    the device kernel."""
    nq = len(q)
    lut = adc_lut(q, codebooks)                     # (nq, m, 256)
    m = codes.shape[1]
    adc = np.zeros((nq, len(codes)), np.float32)
    codes64 = codes.astype(np.int64)
    for j in range(m):
        adc += lut[:, j, :][:, codes64[:, j]]
    s = np.where(mask, adc, np.inf)
    pks64 = np.asarray(pks, np.int64)
    out_d = np.full((nq, k), np.inf, np.float32)
    out_r = np.full((nq, k), -1, np.int64)
    for qi in range(nq):
        order = np.lexsort((pks64, s[qi]))[:k]
        order = order[np.isfinite(s[qi][order])]
        out_d[qi, :len(order)] = s[qi][order]
        out_r[qi, :len(order)] = order
    _dispatched(out_d.nbytes + out_r.nbytes)
    return out_d, out_r


def _quantized_scan_device(q, codes, codebooks, mask, pks, k, mode: str,
                           empty):
    """The fused ADC kernel's host prep, upload, launch and fetch (see
    ``quantized_scan_topk``)."""
    nq = len(q)
    lut = adc_lut(q, codebooks)                     # (nq, m, 256)
    m = codes.shape[1]
    BQ, BN = fs_kernel.BLOCK_Q, fs_kernel.BLOCK_N
    cp = _pad_codes(codes, BN, bucket=False)
    mp = _pad_to(mask.astype(np.uint8), BN, 1)
    pkp = _pad_to(np.asarray(pks, np.int64), BN, 0,
                  value=int(fs_kernel.SENTINEL))
    nb = len(cp) // BN
    keep = np.nonzero(mp.reshape(nq, nb, BN).any(axis=(0, 2)))[0]
    if len(keep) == 0:
        return empty
    nb_pad = _bucket(len(keep), floor=1)
    ck = np.zeros((nb_pad * BN, m), np.uint8)
    mk = np.zeros((nq, nb_pad * BN), np.uint8)
    pkk = np.full((nb_pad * BN,), int(fs_kernel.SENTINEL), np.int64)
    ck[:len(keep) * BN] = cp.reshape(nb, BN, m)[keep].reshape(-1, m)
    mk[:, :len(keep) * BN] = \
        mp.reshape(nq, nb, BN)[:, keep].reshape(nq, -1)
    pkk[:len(keep) * BN] = pkp.reshape(nb, BN)[keep].reshape(-1)
    lutf = _pad_to(lut.reshape(nq, m * 256), BQ, 0)
    mkq = _pad_to(mk, BQ, 0)
    occ = mkq.reshape(len(lutf) // BQ, BQ, nb_pad, BN) \
        .any(axis=(1, 3)).astype(np.int32)
    pk32 = pkk.astype(np.int32)[None, :]
    adc, _, idx = qs_kernel.quantized_scan_topk(
        *_to_device(lutf, ck, mkq, pk32, occ), k=k,
        interpret=mode == "interpret")
    adc, idx = _to_host(adc, idx)
    _dispatched(adc.nbytes + 2 * idx.nbytes, f"quantized_scan.{mode}",
                lutf.shape + ck.shape + (k,))
    adc, idx = adc[:nq, :k], idx[:nq, :k]
    safe = np.minimum(idx, len(keep) * BN - 1)
    rows = keep[safe // BN] * BN + safe % BN
    rows = np.where(idx == int(fs_kernel.SENTINEL), -1, rows)
    return adc, rows.astype(np.int64)


# ---------------------------------------------------------------------------
# top-k merge
# ---------------------------------------------------------------------------

def merge_topk_batch(scores: np.ndarray, ids: np.ndarray, k: int,
                     use_pallas: bool = None
                     ) -> Tuple[np.ndarray, np.ndarray]:
    """Cross-shard top-k merge for a query batch (the sharded read path's
    combine step — kernels/topk_merge.py ``batched_topk_merge``).

    scores (nq, s, kk) fp32 and ids (nq, s, kk) int64 hold each query's s
    per-shard candidate lists; empty slots carry score=+inf (their id is
    ignored).  Returns ((nq, k) fp32, (nq, k) int64) in ascending
    (score, id) order — the host merge's ``lexsort((pk, score))``
    comparator — with id=-1 marking slots beyond a query's candidate
    count.  The device merge tie-breaks in int32 registers (the same
    bound the fused scan's pk registers impose); ids outside [0, 2^31-1)
    automatically fall back to the exact host merge instead of
    truncating (counted in ``kernels.merge_host_fallbacks``).  ONE
    dispatch for the whole batch; only the (nq, k) winners return to the
    host, never the (nq, s*kk) candidate tensor."""
    mode = backend(use_pallas)
    scores = np.asarray(scores, np.float32)
    ids64 = np.asarray(ids, np.int64)
    nq, s, kk = scores.shape
    k = int(min(k, s * kk))
    out_d = np.full((nq, k), np.inf, np.float32)
    out_i = np.full((nq, k), -1, np.int64)
    if k == 0 or nq == 0:
        return out_d, out_i
    tag = f"topk_merge_batch.{mode}"
    if mode != "ref":
        sentinel = np.iinfo(np.int32).max
        real = ids64[np.isfinite(scores)]
        if k > fs_kernel.KMAX or (len(real) and (
                int(real.min()) < 0 or int(real.max()) >= sentinel)):
            # the kernel emits one lane row (k <= KMAX) and tie-breaks on
            # int32 ids; wider k or ids outside that range would truncate
            # silently — take the exact host merge instead
            REGISTRY.inc("kernels.merge_host_fallbacks")
            tag = None
        else:
            with obs_trace.span("dispatch:merge_topk_batch"):
                idp = np.where(np.isfinite(scores), ids64,
                               sentinel).astype(np.int32)
                d, i = _to_host(*tk_kernel.batched_topk_merge(
                    *_to_device(scores, idp), k,
                    interpret=mode == "interpret"))
                i = i.astype(np.int64)
                _dispatched(d.nbytes + i.nbytes, tag, scores.shape + (k,))
            return d, np.where(np.isfinite(d), i, -1)
    # the exact host merge (the ``ref`` backend's oracle, counted as its
    # launch, or the device path's fallback, counted as host work)
    with obs_trace.span("dispatch:merge_topk_batch" if tag
                        else "host_op:merge_topk_batch"):
        flat_d = scores.reshape(nq, -1)
        flat_i = ids64.reshape(nq, -1)
        for qi in range(nq):
            order = np.lexsort((flat_i[qi], flat_d[qi]))[:k]
            order = order[np.isfinite(flat_d[qi][order])]
            out_d[qi, :len(order)] = flat_d[qi][order]
            out_i[qi, :len(order)] = flat_i[qi][order]
        _dispatched(out_d.nbytes + out_i.nbytes, tag,
                    scores.shape + (k,) if tag else ())
    return out_d, out_i
