"""Pallas TPU kernel: fused PQ-ADC scan → per-query running top-k'.

Quantized sibling of ``fused_scan.py`` — same grid, same scalar-prefetch
occupancy skip, same packed mask, same revisited-output-block running
top-k, same (distance, pk, row) ``select_topk`` merge — but the posting
tile streams the PQ code matrix (m codes/row) instead of the fp32 column
(4*d bytes/row), so candidate generation reads far fewer bytes at
typical (d=128, m=8):

  * the host computes one ADC LUT per query ONCE per launch —
    ``lut[q, j, c] = || q_sub_j - codebook_j[c] ||^2`` — and the wrapper
    lays it out subquantizer-major, (m, nq, 256), so a query tile's LUTs
    ride in as one (m, BLOCK_Q, 256) block resident across the inner
    dimension;
  * the wrapper lays codes out subquantizer-major, (m, n) int32, so a
    tile's codes are one lane-dense (m, BLOCK_N) block;
  * inside the tile, per-subquantizer distances come from the one-hot
    matmul trick (``pq_adc.py``): a (256, BLOCK_N) one-hot of code row j
    contracted against the query-tile LUT slice puts the gather on the
    MXU — (BLOCK_Q, 256) x (256, BLOCK_N) per j, at full f32 precision
    so every LUT entry passes through unrounded.  A loop over j (not a
    static unroll) keeps one one-hot live, so VMEM stays flat in m;
  * the predicate bitmap masks in-kernel and the running top-k' merges
    under keys (adc, pk, row), so survivor sets are deterministic under
    ties.

ADC distances are approximations: callers keep k' = refine*k survivors
and re-rank them EXACTLY against the fp32 column through the ordinary
fused scan (``ops.fused_scan_topk``), which restores the committed
(score, pk) comparator.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.fused_scan import (BLOCK_N, BLOCK_Q, EXACT, KMAX,
                                      SENTINEL, merge_running, pack_mask,
                                      unpack_mask)


def _quantized_scan_topk_kernel(occ_ref, lut_ref, codes_ref, mask_ref,
                                pk_ref, out_d_ref, out_p_ref, out_i_ref,
                                *, k: int):
    """One (query-tile, posting-block) grid step.

    occ_ref:   (n_qtiles * n_blocks,) int32 scalar-prefetch occupancy
    lut_ref:   (m, BLOCK_Q, 256) fp32 per-query ADC LUTs (resident)
    codes_ref: (m, BLOCK_N) int32 PQ codes, subquantizer-major
    mask_ref:  (1, BLOCK_N) int32 packed predicate bitmap (bit = query)
    pk_ref:    (1, BLOCK_N) int32 primary keys (tie-break sort key)
    out_*:     (BLOCK_Q, KMAX) running top-k' accumulator
    """
    i = pl.program_id(0)
    j = pl.program_id(1)
    m = codes_ref.shape[0]

    @pl.when(j == 0)
    def _init():
        out_d_ref[...] = jnp.full((BLOCK_Q, KMAX), jnp.inf, jnp.float32)
        out_p_ref[...] = jnp.full((BLOCK_Q, KMAX), SENTINEL, jnp.int32)
        out_i_ref[...] = jnp.full((BLOCK_Q, KMAX), SENTINEL, jnp.int32)

    @pl.when(occ_ref[i * pl.num_programs(1) + j] != 0)
    def _scan_and_merge():
        codeword = jax.lax.broadcasted_iota(jnp.int32, (256, BLOCK_N), 0)

        def subquantizer(sub, acc):
            # one one-hot MXU contraction per j sums lut[q, j, codes[j, i]]
            # into the (BQ, BN) tile
            onehot = codes_ref[pl.ds(sub, 1), :] == codeword  # (256, BN)
            return acc + jax.lax.dot_general(
                lut_ref[sub], onehot.astype(jnp.float32),
                (((1,), (0,)), ((), ())), precision=EXACT,
                preferred_element_type=jnp.float32)

        acc = jax.lax.fori_loop(
            0, m, subquantizer, jnp.zeros((BLOCK_Q, BLOCK_N), jnp.float32))
        mask = unpack_mask(mask_ref[...])
        d = jnp.where(mask, acc, jnp.inf)
        ids = j * BLOCK_N + jax.lax.broadcasted_iota(
            jnp.int32, (BLOCK_Q, BLOCK_N), 1)
        ids = jnp.where(mask, ids, SENTINEL)
        pks = jnp.where(mask, pk_ref[...], SENTINEL)
        merge_running(out_d_ref, out_p_ref, out_i_ref, d, pks, ids, k)


@functools.partial(jax.jit, static_argnames=("k", "interpret"))
def quantized_scan_topk(lut: jnp.ndarray, codes: jnp.ndarray,
                        mask: jnp.ndarray, pks: jnp.ndarray,
                        occ: jnp.ndarray, k: int = KMAX,
                        interpret: bool = False):
    """lut (nq, m*256) fp32; codes (n, m) integer codes in [0, 256);
    mask (nq, n) 0/1; pks (1, n) int32; occ (nq/BLOCK_Q, n/BLOCK_N)
    int32.  All padded to tile multiples by ``ops.quantized_scan_topk``.
    Returns ((nq, KMAX) fp32 ADC distances sorted ascending, (nq, KMAX)
    int32 pks, (nq, KMAX) int32 packed row ids); the first ``k`` slots
    are the top-k', empty slots (and every slot past k) hold (+inf,
    SENTINEL, SENTINEL)."""
    nq, lut_w = lut.shape
    n, m = codes.shape
    assert lut_w == m * 256, (lut_w, m)
    assert nq % BLOCK_Q == 0 and n % BLOCK_N == 0, (nq, n)
    assert 1 <= k <= KMAX, k
    grid = (nq // BLOCK_Q, n // BLOCK_N)
    out = jax.ShapeDtypeStruct((nq, KMAX), jnp.float32)
    ids = jax.ShapeDtypeStruct((nq, KMAX), jnp.int32)
    return pl.pallas_call(
        functools.partial(_quantized_scan_topk_kernel, k=k),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=grid,
            in_specs=[
                pl.BlockSpec((m, BLOCK_Q, 256), lambda i, j, occ: (0, i, 0)),
                pl.BlockSpec((m, BLOCK_N), lambda i, j, occ: (0, j)),
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
    )(occ.reshape(-1).astype(jnp.int32),
      lut.reshape(nq, m, 256).transpose(1, 0, 2),
      codes.astype(jnp.int32).T, pack_mask(mask), pks)
