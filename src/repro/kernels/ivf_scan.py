"""Pallas TPU kernel: fused batched squared-L2 distance scan over posting
blocks — the compute hot-spot of ARCADE's vector IVF query path (paper §4:
"selectively accessing posting lists ... retrieving records via block
handles").

TPU mapping: the posting list is tiled into (BLOCK_N, d) VMEM blocks; the
query tile (BLOCK_Q, d) stays resident. Distances use the MXU via the
expansion ||q - v||^2 = ||q||^2 - 2 q.v + ||v||^2 where q.v is a
(BLOCK_Q, d) x (d, BLOCK_N) matmul at full f32 precision; norms ride the
VPU. Accumulation is fp32. Dims are padded to (8, 128) multiples by the
ops.py wrapper so MXU tiles are hardware-aligned.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels.fused_scan import EXACT

BLOCK_Q = 8          # query rows per tile (sublane-aligned)
BLOCK_N = 512        # posting vectors per tile (lane-aligned, fits VMEM)


def _ivf_scan_kernel(q_ref, v_ref, out_ref):
    """q_ref: (BLOCK_Q, d); v_ref: (BLOCK_N, d); out: (BLOCK_Q, BLOCK_N)."""
    q = q_ref[...].astype(jnp.float32)
    v = v_ref[...].astype(jnp.float32)
    qn = jnp.sum(q * q, axis=1, keepdims=True)           # (BQ, 1)
    vn = jnp.sum(v * v, axis=1)[None, :]                 # (1, BN)
    # MXU matmul: (BQ, d) x (d, BN)
    dots = jax.lax.dot_general(
        q, v, (((1,), (1,)), ((), ())), precision=EXACT,
        preferred_element_type=jnp.float32)
    out_ref[...] = qn - 2.0 * dots + vn


@functools.partial(jax.jit, static_argnames=("interpret",))
def ivf_scan(q: jnp.ndarray, vecs: jnp.ndarray,
             interpret: bool = False) -> jnp.ndarray:
    """q: (nq, d); vecs: (n, d) — both padded to tile multiples by ops.py.
    Returns (nq, n) fp32 squared-L2 distances."""
    nq, d = q.shape
    n, _ = vecs.shape
    assert nq % BLOCK_Q == 0 and n % BLOCK_N == 0, (nq, n)
    grid = (nq // BLOCK_Q, n // BLOCK_N)
    return pl.pallas_call(
        _ivf_scan_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((BLOCK_Q, d), lambda i, j: (i, 0)),
            pl.BlockSpec((BLOCK_N, d), lambda i, j: (j, 0)),
        ],
        out_specs=pl.BlockSpec((BLOCK_Q, BLOCK_N), lambda i, j: (i, j)),
        out_shape=jax.ShapeDtypeStruct((nq, n), jnp.float32),
        interpret=interpret,
    )(q, vecs)
