"""Pallas TPU kernel: fused filter-aware distance scan → per-query top-k.

The staged read path launches one ``ivf_scan`` per segment per query
batch and ships the full ``(nq, n)`` distance matrix back to the host,
where numpy does the top-k cut.  This kernel fuses all three stages —
predicate masking, distance scan, top-k selection — into ONE launch over
the cross-segment *packed* superbatch (see ``ops.fused_scan_topk`` for
the host-side packing/compaction layer):

  * grid = (nq / BLOCK_Q, n / BLOCK_N); the inner (posting) dimension is
    sequential on TPU, so the output block doubles as the per-query-tile
    running top-k accumulator (the canonical revisited-block pattern);
  * per tile, squared-L2 distances use the MXU via
    ||q - v||^2 = ||q||^2 - 2 q.v + ||v||^2 at full f32 precision; the
    predicate bitmap is applied INSIDE the scan (masked lanes get +inf)
    so staged filter -> rank round trips disappear;
  * each tile merges its BLOCK_N candidates into the running (BLOCK_Q,
    KMAX) top-k with ``select_topk``: k rounds of lane-min + select under
    the lexicographic (distance, pk, row) key — the host merge's
    ``lexsort((pk, score))`` comparator.  A tile with no admitted lane at
    or below the running k-th distance skips the merge entirely, so once
    the accumulator fills most tiles cost one matmul and one compare;
  * fully-masked (query-tile, block) pairs are skipped via a per-block
    occupancy grid the host derives from zone maps + bitmaps, delivered
    by scalar prefetch — the compute predicate costs one SMEM read.

Mask layout: the wrapper packs the (nq, n) 0/1 bitmap 8 queries per
int32 word — bit b of word (t, 0, r) is query t*BLOCK_Q + b at row r —
so a tile's mask block is one lane-dense (1, BLOCK_N) int32 row that the
kernel unpacks with a sublane shift.

Only ``(nq, KMAX)`` distances + row ids + pks leave the device instead of
``(nq, n)`` distances: device->host traffic is k/n of the staged path,
and dispatches drop from O(segments x predicates) to 1 per query batch.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

BLOCK_Q = 8          # query rows per tile (sublane-aligned)
BLOCK_N = 512        # packed posting vectors per tile (lane-aligned)
KMAX = 128           # top-k capacity: one lane register row per query

# int32 sentinel for "no candidate" slots: +inf distance partners with the
# largest pk/id so sentinels sort after every real candidate
SENTINEL = np.int32(np.iinfo(np.int32).max)

# exact f32 products on the MXU (the default single bf16 pass would round
# the operands and perturb distances the exact dispatch must reproduce)
EXACT = jax.lax.Precision.HIGHEST


def pack_mask(mask: jnp.ndarray) -> jnp.ndarray:
    """(nq, n) 0/1 bitmap -> (nq/BLOCK_Q, 1, n) int32, one bit per query
    of a BLOCK_Q tile (bit b = tile row b)."""
    nq, n = mask.shape
    bits = (mask != 0).astype(jnp.int32).reshape(nq // BLOCK_Q, BLOCK_Q, n)
    shift = jnp.arange(BLOCK_Q, dtype=jnp.int32)[None, :, None]
    return jnp.sum(bits << shift, axis=1, keepdims=True)


def unpack_mask(word: jnp.ndarray) -> jnp.ndarray:
    """(1, W) packed words -> (BLOCK_Q, W) bool: row b takes bit b."""
    shift = jax.lax.broadcasted_iota(jnp.int32, (BLOCK_Q, word.shape[1]), 0)
    return ((word >> shift) & 1) != 0


def select_topk(cands, k: int, width: int = KMAX):
    """k smallest lanes, per row, across candidate tiles.

    ``cands`` is a list of (d, p, i) triples of (rows, W) arrays — f32
    key, int32 tie-break, int32 payload-and-final-tie-break.  Each of k
    rounds takes the row's lexicographic (d, p, i) minimum with three
    lane reductions, writes it to output lane r and consumes it.  Returns
    (rows, width) (d, p, i) with lanes >= k (and lanes beyond the
    candidate count) holding (+inf, SENTINEL, SENTINEL)."""
    rows = cands[0][0].shape[0]
    lane = jax.lax.broadcasted_iota(jnp.int32, (rows, width), 1)

    def lane_min(xs):
        return functools.reduce(
            jnp.minimum, [jnp.min(x, axis=1, keepdims=True) for x in xs])

    def one_round(r, carry):
        cs, od, op, oi = carry
        dmin = lane_min([d for d, _, _ in cs])
        pmin = lane_min([jnp.where(d == dmin, p, SENTINEL) for d, p, _ in cs])
        imin = lane_min([jnp.where((d == dmin) & (p == pmin), i, SENTINEL)
                         for d, p, i in cs])
        nxt = []
        for d, p, i in cs:
            hit = (d == dmin) & (p == pmin) & (i == imin)
            nxt.append((jnp.where(hit, jnp.inf, d),
                        jnp.where(hit, SENTINEL, p),
                        jnp.where(hit, SENTINEL, i)))
        at = lane == r
        return (nxt, jnp.where(at, dmin, od), jnp.where(at, pmin, op),
                jnp.where(at, imin, oi))

    init = (list(cands), jnp.full((rows, width), jnp.inf, jnp.float32),
            jnp.full((rows, width), SENTINEL, jnp.int32),
            jnp.full((rows, width), SENTINEL, jnp.int32))
    _, od, op, oi = jax.lax.fori_loop(0, k, one_round, init)
    return od, op, oi


def merge_running(out_d_ref, out_p_ref, out_i_ref, d, p, i, k: int):
    """Merge one tile's masked candidates into the running top-k held in
    the revisited output block — skipped when no admitted lane reaches
    the running k-th distance (ties included: they may win on pk)."""
    kth = out_d_ref[:, k - 1:k]
    live = (d <= kth) & (d < jnp.inf)

    @pl.when(jnp.max(live.astype(jnp.int32)) > 0)
    def _merge():
        acc = (out_d_ref[...], out_p_ref[...], out_i_ref[...])
        od, op, oi = select_topk([acc, (d, p, i)], k)
        out_d_ref[...] = od
        out_p_ref[...] = op
        out_i_ref[...] = oi


def _fused_scan_topk_kernel(occ_ref, q_ref, x_ref, mask_ref, pk_ref,
                            out_d_ref, out_p_ref, out_i_ref, *, k: int):
    """One (query-tile, posting-block) grid step.

    occ_ref:  (n_qtiles * n_blocks,) int32 scalar-prefetch occupancy grid
              — 0 when every lane of this tile is masked
    q_ref:    (BLOCK_Q, d) queries        (resident across the inner dim)
    x_ref:    (BLOCK_N, d) packed vectors
    mask_ref: (1, BLOCK_N) int32 packed predicate bitmap (bit = query)
    pk_ref:   (1, BLOCK_N) int32 primary keys (tie-break sort key)
    out_*:    (BLOCK_Q, KMAX) running top-k — same block for every j, so
              it accumulates across the sequential inner dimension
    """
    i = pl.program_id(0)
    j = pl.program_id(1)

    @pl.when(j == 0)
    def _init():
        out_d_ref[...] = jnp.full((BLOCK_Q, KMAX), jnp.inf, jnp.float32)
        out_p_ref[...] = jnp.full((BLOCK_Q, KMAX), SENTINEL, jnp.int32)
        out_i_ref[...] = jnp.full((BLOCK_Q, KMAX), SENTINEL, jnp.int32)

    @pl.when(occ_ref[i * pl.num_programs(1) + j] != 0)
    def _scan_and_merge():
        q = q_ref[...].astype(jnp.float32)
        x = x_ref[...].astype(jnp.float32)
        qn = jnp.sum(q * q, axis=1, keepdims=True)            # (BQ, 1)
        xn = jnp.sum(x * x, axis=1)[None, :]                  # (1, BN)
        # MXU matmul: (BQ, d) x (d, BN)
        dots = jax.lax.dot_general(
            q, x, (((1,), (1,)), ((), ())), precision=EXACT,
            preferred_element_type=jnp.float32)
        m = unpack_mask(mask_ref[...])
        d = jnp.where(m, qn - 2.0 * dots + xn, jnp.inf)
        ids = j * BLOCK_N + jax.lax.broadcasted_iota(
            jnp.int32, (BLOCK_Q, BLOCK_N), 1)
        ids = jnp.where(m, ids, SENTINEL)
        pks = jnp.where(m, pk_ref[...], SENTINEL)             # (BQ, BN)
        merge_running(out_d_ref, out_p_ref, out_i_ref, d, pks, ids, k)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def fused_scan_topk(q: jnp.ndarray, x: jnp.ndarray, mask: jnp.ndarray,
                    pks: jnp.ndarray, occ: jnp.ndarray, k: int = KMAX,
                    interpret: bool = False):
    """q (nq, d); x (n, d); mask (nq, n) 0/1; pks (1, n) int32;
    occ (nq/BLOCK_Q, n/BLOCK_N) int32.  All padded to tile multiples by
    ``ops.fused_scan_topk``.  Returns ((nq, KMAX) fp32 squared-L2 sorted
    ascending, (nq, KMAX) int32 pks, (nq, KMAX) int32 packed row ids);
    the first ``k`` slots are the top-k, empty slots (and every slot past
    k) hold (+inf, SENTINEL, SENTINEL)."""
    nq, d = q.shape
    n, _ = x.shape
    assert nq % BLOCK_Q == 0 and n % BLOCK_N == 0, (nq, n)
    assert 1 <= k <= KMAX, k
    grid = (nq // BLOCK_Q, n // BLOCK_N)
    out = jax.ShapeDtypeStruct((nq, KMAX), jnp.float32)
    ids = jax.ShapeDtypeStruct((nq, KMAX), jnp.int32)
    return pl.pallas_call(
        functools.partial(_fused_scan_topk_kernel, k=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((BLOCK_Q, d), lambda i, j, occ: (i, 0)),
                pl.BlockSpec((BLOCK_N, d), lambda i, j, occ: (j, 0)),
                pl.BlockSpec((pl.Squeezed(), 1, BLOCK_N),
                             lambda i, j, occ: (i, 0, j)),
                pl.BlockSpec((1, BLOCK_N), lambda i, j, occ: (0, j)),
            ],
            out_specs=[
                pl.BlockSpec((BLOCK_Q, KMAX), lambda i, j, occ: (i, 0)),
                pl.BlockSpec((BLOCK_Q, KMAX), lambda i, j, occ: (i, 0)),
                pl.BlockSpec((BLOCK_Q, KMAX), lambda i, j, occ: (i, 0)),
            ],
        ),
        out_shape=[out, ids, ids],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary")),
        interpret=interpret,
    )(occ.reshape(-1).astype(jnp.int32), q, x, pack_mask(mask), pks)
