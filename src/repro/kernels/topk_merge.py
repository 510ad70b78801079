"""Pallas TPU kernel: cross-shard top-k merge for a query batch.

ARCADE's "top-level merging iterator" (paper §4) combines per-SST results;
on TPU the scatter-gather query path merges S per-shard top-k lists with
this kernel: the (s*kk) candidates of BLOCK_Q queries form one lane-dense
(BLOCK_Q, L) VMEM tile, and ``fused_scan.select_topk`` extracts the k
winners in k rounds of lane-min + select under the (score, id, slot)
key — no host heap.  k is small (<= KMAX), so k * s * kk ops stay
negligible next to the distance scans.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_scan import BLOCK_Q, KMAX, SENTINEL, select_topk


def _batched_merge_kernel(d_ref, i_ref, out_d_ref, out_i_ref, *, k: int):
    """One query tile: (BLOCK_Q, L) candidates -> (BLOCK_Q, KMAX) winners
    in ascending (score, id) lexicographic order — ties on score break
    toward the smaller id, matching the host merge's
    ``np.lexsort((pk, score))`` comparator exactly (equal (score, id)
    pairs break by slot).  Padded slots carry id=SENTINEL and score=+inf,
    so they are only emitted once every real candidate is exhausted (the
    wrapper maps them back to "empty")."""
    d = d_ref[...]
    slot = jax.lax.broadcasted_iota(jnp.int32, d.shape, 1)
    od, oi, _ = select_topk([(d, i_ref[...], slot)], k)
    out_d_ref[...] = od
    out_i_ref[...] = oi


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def batched_topk_merge(dists: jnp.ndarray, ids: jnp.ndarray, k: int,
                       interpret: bool = False):
    """Cross-shard top-k merge for a whole query batch.

    dists (nq, s, kk) fp32, ids (nq, s, kk) int32 -> ((nq, k), (nq, k)):
    per query the k smallest candidates across all s shard lists, ordered
    by (score, id).  Pad empty slots with score=+inf and id=INT32_MAX —
    padded output slots come back as (+inf, INT32_MAX).  The wrapper
    flattens each query's lists into one row and pads rows to BLOCK_Q
    and lanes to a 128 multiple, so each grid step is one aligned tile."""
    nq, s, kk = dists.shape
    assert 1 <= k <= KMAX, k
    width = -(-(s * kk) // 128) * 128
    nqp = -(-nq // BLOCK_Q) * BLOCK_Q
    pad = ((0, nqp - nq), (0, width - s * kk))
    d = jnp.pad(dists.reshape(nq, s * kk).astype(jnp.float32), pad,
                constant_values=jnp.inf)
    i = jnp.pad(ids.reshape(nq, s * kk).astype(jnp.int32), pad,
                constant_values=SENTINEL)
    assert nqp % BLOCK_Q == 0, nqp
    grid = (nqp // BLOCK_Q,)
    od, oi = pl.pallas_call(
        functools.partial(_batched_merge_kernel, k=k),
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_Q, width), lambda t: (t, 0)),
            pl.BlockSpec((BLOCK_Q, width), lambda t: (t, 0)),
        ],
        out_specs=[
            pl.BlockSpec((BLOCK_Q, KMAX), lambda t: (t, 0)),
            pl.BlockSpec((BLOCK_Q, KMAX), lambda t: (t, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((nqp, KMAX), jnp.float32),
            jax.ShapeDtypeStruct((nqp, KMAX), jnp.int32),
        ],
        interpret=interpret,
    )(d, i)
    return od[:nq, :k], oi[:nq, :k]
