"""Pallas TPU kernel: PQ asymmetric-distance computation (ADC).

GPU PQ scan uses shared-memory LUT gathers; TPUs have no fast random
gather, so the idiomatic port is a one-hot matmul: for subquantizer j,
dist_j = lut[j] @ onehot(codes[j, :]) runs on the MXU with the (256,)
LUT row resident in VMEM (DESIGN.md hardware-adaptation table).

Layout: the wrapper transposes codes to subquantizer-major (m, n) int32
so a tile is one lane-dense (m, BLOCK_N) block, and the result is a
lane-dense (1, n) row.  Grid tiles the code dim; the (m, 256) LUT is
broadcast to every tile (tiny: m*256*4 bytes).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_scan import EXACT

BLOCK_N = 512        # codes per tile


def _pq_adc_kernel(codes_ref, lut_ref, out_ref):
    """codes: (m, BLOCK_N) int32; lut: (m, 256) fp32; out: (1, BLOCK_N)."""
    m = codes_ref.shape[0]
    codeword = jax.lax.broadcasted_iota(jnp.int32, (256, BLOCK_N), 0)
    acc = jnp.zeros((1, BLOCK_N), jnp.float32)
    for j in range(m):          # static unroll over subquantizers
        onehot = codes_ref[j:j + 1, :] == codeword            # (256, BN)
        acc = acc + jax.lax.dot_general(
            lut_ref[j:j + 1, :], onehot.astype(jnp.float32),
            (((1,), (0,)), ((), ())), precision=EXACT,
            preferred_element_type=jnp.float32)
    out_ref[...] = acc


@functools.partial(jax.jit, static_argnames=("interpret",))
def pq_adc(codes: jnp.ndarray, lut: jnp.ndarray,
           interpret: bool = False) -> jnp.ndarray:
    """codes: (n, m) integer codes in [0, 256); lut: (m, 256) fp32 ->
    (n,) fp32."""
    n, m = codes.shape
    assert n % BLOCK_N == 0, n
    grid = (n // BLOCK_N,)
    out = pl.pallas_call(
        _pq_adc_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((m, BLOCK_N), lambda i: (0, i)),
            pl.BlockSpec((m, 256), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, BLOCK_N), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.float32),
        interpret=interpret,
    )(codes.astype(jnp.int32).T, lut)
    return out[0]
