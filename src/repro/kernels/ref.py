"""Pure-jnp oracles for every Pallas kernel (the correctness reference).

Each function mirrors one kernel's semantics exactly; kernel tests sweep
shapes/dtypes and assert_allclose kernel(interpret=True) vs these.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp


def ivf_scan_ref(q: jnp.ndarray, vecs: jnp.ndarray) -> jnp.ndarray:
    """Squared-L2 distances. q: (nq, d); vecs: (n, d) -> (nq, n) fp32."""
    q = q.astype(jnp.float32)
    v = vecs.astype(jnp.float32)
    qn = jnp.sum(q * q, axis=-1, keepdims=True)          # (nq, 1)
    vn = jnp.sum(v * v, axis=-1)[None, :]                # (1, n)
    # full f32 products: on a TPU the default matmul precision is one
    # bf16 pass, which would make the "exact" reference approximate
    dots = jnp.matmul(q, v.T, precision=jax.lax.Precision.HIGHEST)
    return qn - 2.0 * dots + vn


def pq_adc_ref(codes: jnp.ndarray, lut: jnp.ndarray) -> jnp.ndarray:
    """ADC: codes (n, m) uint8; lut (m, 256) fp32 -> (n,) summed distances."""
    take = jnp.take_along_axis(lut.T, codes.astype(jnp.int32), axis=0)
    # lut.T: (256, m); gather per column j at codes[:, j]
    return jnp.sum(take.astype(jnp.float32), axis=1)


def bitmap_filter_ref(cols: jnp.ndarray, bounds: jnp.ndarray) -> jnp.ndarray:
    """cols (n, c) fp32; bounds (c, 2) [lo, hi] inclusive -> (n,) bool:
    AND over all per-column range predicates (fused multi-predicate)."""
    lo = bounds[:, 0][None, :]
    hi = bounds[:, 1][None, :]
    ok = (cols >= lo) & (cols <= hi)
    return jnp.all(ok, axis=1)


def batched_topk_merge_ref(dists: jnp.ndarray, ids: jnp.ndarray, k: int):
    """Batched cross-shard merge oracle (topk_merge.batched_topk_merge).

    dists (nq, s, kk) fp32; ids (nq, s, kk) int32 -> ((nq, k), (nq, k)):
    per query the k smallest candidates across all shard lists in
    ascending (score, id) lexicographic order; padded slots carry
    (+inf, INT32_MAX) and sort last."""
    nq = dists.shape[0]
    flat_d = dists.reshape(nq, -1).astype(jnp.float32)
    flat_i = ids.reshape(nq, -1)
    sd, si = jax.lax.sort((flat_d, flat_i), dimension=1, num_keys=2)
    return sd[:, :k], si[:, :k]


def fused_topk_ref(q: jnp.ndarray, x: jnp.ndarray, mask: jnp.ndarray,
                   pks: jnp.ndarray, k: int):
    """Fused masked scan -> top-k oracle (kernels/fused_scan.py).

    q (nq, d); x (n, d); mask (nq, n); pks (1, n) int32 -> per query the k
    smallest squared-L2 distances over mask-admitted rows, ties broken by
    pk, then row id.  Returns ((nq, k) fp32, (nq, k) int32 pks, (nq, k)
    int32 row ids); empty slots hold (+inf, INT32_MAX, INT32_MAX)."""
    sentinel = jnp.int32(jnp.iinfo(jnp.int32).max)
    d = ivf_scan_ref(q, x)
    m = mask != 0
    d = jnp.where(m, d, jnp.inf)
    ids = jnp.broadcast_to(
        jax.lax.broadcasted_iota(jnp.int32, d.shape, 1), d.shape)
    ids = jnp.where(m, ids, sentinel)
    pkb = jnp.where(m, pks.astype(jnp.int32), sentinel)
    sd, sp, si = jax.lax.sort((d, pkb, ids), dimension=1, num_keys=2)
    return sd[:, :k], sp[:, :k], si[:, :k]


def quantized_topk_ref(lut: jnp.ndarray, codes: jnp.ndarray,
                       mask: jnp.ndarray, pks: jnp.ndarray, k: int):
    """Fused quantized ADC scan -> top-k' oracle (quantized_scan.py).

    lut (nq, m, 256) fp32 per-query ADC tables; codes (n, m); mask
    (nq, n); pks (1, n) int32 -> per query the k smallest ADC distances
    over mask-admitted rows, ties broken by pk, then row id.  Returns
    ((nq, k) fp32, (nq, k) int32 pks, (nq, k) int32 row ids); empty
    slots hold (+inf, INT32_MAX, INT32_MAX)."""
    sentinel = jnp.int32(jnp.iinfo(jnp.int32).max)
    # gather lut[q, j, codes[i, j]] and sum over j: (nq, m, n) -> (nq, n)
    idx = codes.astype(jnp.int32).T[None, :, :]          # (1, m, n)
    take = jnp.take_along_axis(
        lut.astype(jnp.float32), jnp.broadcast_to(
            idx, (lut.shape[0],) + idx.shape[1:]), axis=2)
    d = jnp.sum(take, axis=1)
    m = mask != 0
    d = jnp.where(m, d, jnp.inf)
    ids = jnp.broadcast_to(
        jax.lax.broadcasted_iota(jnp.int32, d.shape, 1), d.shape)
    ids = jnp.where(m, ids, sentinel)
    pkb = jnp.where(m, pks.astype(jnp.int32), sentinel)
    sd, sp, si = jax.lax.sort((d, pkb, ids), dimension=1, num_keys=2)
    return sd[:, :k], sp[:, :k], si[:, :k]


def graph_search_topk_ref(q: jnp.ndarray, x: jnp.ndarray,
                          neighbors: jnp.ndarray, entries: jnp.ndarray,
                          mask: jnp.ndarray, pks: jnp.ndarray,
                          beam: int, hops: int):
    """Batched CSR beam-search oracle (kernels/graph_search.py).

    q (nq, d); x (n, d); neighbors (n, R) int32 packed CSR, -1 padded;
    entries (1, E) int32 seed rows, SENTINEL padded; mask (nq, n); pks
    (1, n) int32.  Full-batch mirror of the kernel's hop loop: every
    operation is per-query-row independent and distances use the
    difference form, so the BLOCK_Q-tiled kernel must match bitwise.
    Returns ((nq, beam) fp32 squared-L2 ascending, (nq, beam) int32 pks,
    (nq, beam) int32 row ids, (nq, n/32) int32 visited bitmask); empty
    result slots hold (+inf, INT32_MAX, INT32_MAX)."""
    sentinel = jnp.int32(jnp.iinfo(jnp.int32).max)
    q = q.astype(jnp.float32)
    x = x.astype(jnp.float32)
    nbrs = neighbors
    m = mask != 0
    pk1 = pks[0, :]
    ent = entries[0, :]
    nq = q.shape[0]
    r_deg = nbrs.shape[1]
    nw = x.shape[0] // 32
    n_ent = ent.shape[0]
    iota_w = jax.lax.broadcasted_iota(jnp.int32, (1, 1, nw), 2)

    def dists_to(safe_ids):
        xv = jnp.take(x, safe_ids, axis=0)
        diff = xv - q[:, None, :]
        return jnp.sum(diff * diff, axis=2)

    def scatter_bits(safe_ids, live):
        bit = jnp.where(live, jnp.int32(1) << (safe_ids & 31), 0)
        hit = (safe_ids >> 5)[:, :, None] == iota_w
        return jnp.sum(jnp.where(hit, bit[:, :, None], 0), axis=1)

    def merge_topm(acc, cd, cp, ci):
        md = jnp.concatenate([acc[0], cd], axis=1)
        mp = jnp.concatenate([acc[1], cp], axis=1)
        mi = jnp.concatenate([acc[2], ci], axis=1)
        sd, sp, si = jax.lax.sort((md, mp, mi), dimension=1, num_keys=2)
        return sd[:, :beam], sp[:, :beam], si[:, :beam]

    ev = jnp.broadcast_to((ent != sentinel)[None, :], (nq, n_ent))
    esafe = jnp.broadcast_to(
        jnp.where(ent != sentinel, ent, 0)[None, :], (nq, n_ent))
    ed = jnp.where(ev, dists_to(esafe), jnp.inf)
    epk = jnp.where(ev, jnp.take(pk1, esafe), sentinel)
    eid = jnp.where(ev, esafe, sentinel)
    empty = (jnp.full((nq, beam), jnp.inf, jnp.float32),
             jnp.full((nq, beam), sentinel, jnp.int32),
             jnp.full((nq, beam), sentinel, jnp.int32))
    bd, bp, bi = merge_topm(empty, ed, epk, eid)
    epass = ev & jnp.take_along_axis(m, esafe, axis=1)
    rd, rp, ri = merge_topm(empty,
                            jnp.where(epass, ed, jnp.inf),
                            jnp.where(epass, epk, sentinel),
                            jnp.where(epass, eid, sentinel))
    vis = scatter_bits(esafe, ev)

    def hop(_, state):
        bd, bp, bi, rd, rp, ri, vis = state
        fval = bi != sentinel
        fsafe = jnp.where(fval, bi, 0)
        cand = jnp.take(nbrs, fsafe, axis=0).reshape(nq, beam * r_deg)
        cval = (cand >= 0) & jnp.repeat(fval, r_deg, axis=1)
        csafe = jnp.where(cval, cand, 0)
        words = jnp.take_along_axis(vis, csafe >> 5, axis=1)
        seen = ((words >> (csafe & 31)) & 1) != 0
        fresh = cval & ~seen
        cd = jnp.where(fresh, dists_to(csafe), jnp.inf)
        cp = jnp.where(fresh, jnp.take(pk1, csafe), sentinel)
        ci = jnp.where(fresh, csafe, sentinel)
        si_, sd_, sp_ = jax.lax.sort((ci, cd, cp), dimension=1, num_keys=1)
        dup = jnp.concatenate(
            [jnp.zeros((nq, 1), bool), si_[:, 1:] == si_[:, :-1]],
            axis=1) & (si_ != sentinel)
        uniq = (si_ != sentinel) & ~dup
        usafe = jnp.where(uniq, si_, 0)
        ud = jnp.where(uniq, sd_, jnp.inf)
        up = jnp.where(uniq, sp_, sentinel)
        ui = jnp.where(uniq, si_, sentinel)
        vis = vis | scatter_bits(usafe, uniq)
        bd, bp, bi = merge_topm((bd, bp, bi), ud, up, ui)
        admit = uniq & jnp.take_along_axis(m, usafe, axis=1)
        rd, rp, ri = merge_topm((rd, rp, ri),
                                jnp.where(admit, ud, jnp.inf),
                                jnp.where(admit, up, sentinel),
                                jnp.where(admit, ui, sentinel))
        return bd, bp, bi, rd, rp, ri, vis

    bd, bp, bi, rd, rp, ri, vis = jax.lax.fori_loop(
        0, hops, hop, (bd, bp, bi, rd, rp, ri, vis))
    return rd, rp, ri, vis


def rect_filter_ref(points: jnp.ndarray, rect: jnp.ndarray) -> jnp.ndarray:
    """points (n, 2); rect (4,) = (xmin, ymin, xmax, ymax) -> (n,) bool."""
    x, y = points[:, 0], points[:, 1]
    return (x >= rect[0]) & (x <= rect[2]) & (y >= rect[1]) & (y <= rect[3])
