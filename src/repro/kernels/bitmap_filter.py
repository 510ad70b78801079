"""Pallas TPU kernel: fused multi-predicate range filter -> bitmap.

ARCADE's hybrid-search plans intersect bitmaps from several secondary
indexes (paper §5); residual predicates over scalar columns are evaluated
with this fused kernel: one pass over the column tile evaluates every
range predicate and ANDs them on the VPU — the dense-bitmap adaptation
of the paper's bitmap intersection (DESIGN.md).

Layout: the wrapper transposes columns to (c, n) so a tile is one
lane-dense (c, BLOCK_N) block; the result is a lane-dense (1, n) int32
0/1 row (TPU-friendly mask representation).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

# analysis: allow[kernel/tile-constants] mask-filter tile, deliberately
# larger than the scan-tile family (one f32 row per column, VMEM is cheap)
BLOCK_N = 1024


def _bitmap_kernel(cols_ref, bounds_ref, out_ref):
    """cols: (c, BLOCK_N) fp32; bounds: (c, 2); out: (1, BLOCK_N) int32."""
    ok = jnp.ones((1, BLOCK_N), jnp.bool_)
    for j in range(cols_ref.shape[0]):      # static unroll over predicates
        col = cols_ref[j:j + 1, :]
        ok = ok & (col >= bounds_ref[j:j + 1, 0:1]) \
            & (col <= bounds_ref[j:j + 1, 1:2])
    out_ref[...] = ok.astype(jnp.int32)


@functools.partial(jax.jit, static_argnames=("interpret",))
def bitmap_filter(cols: jnp.ndarray, bounds: jnp.ndarray,
                  interpret: bool = False) -> jnp.ndarray:
    """cols: (n, c) fp32; bounds: (c, 2) -> (n,) int32 0/1 mask."""
    n, c = cols.shape
    assert n % BLOCK_N == 0, n
    grid = (n // BLOCK_N,)
    out = pl.pallas_call(
        _bitmap_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((c, BLOCK_N), lambda i: (0, i)),
            pl.BlockSpec((c, 2), lambda i: (0, 0)),
        ],
        out_specs=pl.BlockSpec((1, BLOCK_N), lambda i: (0, i)),
        out_shape=jax.ShapeDtypeStruct((1, n), jnp.int32),
        interpret=interpret,
    )(cols.astype(jnp.float32).T, bounds.astype(jnp.float32))
    return out[0]
