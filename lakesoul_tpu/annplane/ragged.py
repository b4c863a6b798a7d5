"""Ragged query batching for multi-shard ANN scoring.

A serving micro-batch holds Q concurrent queries with DIFFERENT ``nprobe``
and different probed-cluster sets.  The rectangular resident kernels
(vector/kernels.py) score every row for every query — fine at 200k rows,
three orders of magnitude of wasted MXU work at 10M.  This module is the
Ragged-Paged-Attention answer (arxiv 2604.15464): flatten the micro-batch
into (query, cluster-tile) WORK ITEMS, run one grid over the items, and let
scalar-prefetched item tables drive the BlockSpec index maps so each grid
step DMAs exactly its cluster tile and its query row — no (rows x queries)
rectangle ever exists.

Estimator (global query frame, shared with vector/kernels.py): per row
    est = b + csq - h * csum - a * g,      g = codes_f · P(query)
where ``codes_f``/``a``/``b``/``h`` are build-time per-row constants
(:func:`fold_cluster`, one definition for 1-bit and ex-codes) and
``csq``/``csum`` are per-(query, cluster) scalars the planner computes on
the host.  Three interchangeable executors, differential-tested:

- :func:`ragged_score_pallas` — the TPU kernel (PrefetchScalarGridSpec);
- :func:`ragged_score_jnp`    — same item layout in pure jnp (interpreter
  twin for CPU differential tests);
- :func:`ragged_topk_host`    — the host production path: per-cluster
  grouped GEMMs with a vectorized ragged transpose into query-major order
  (what actually serves on CPU fallback; identical math, no item padding).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

TILE = 128  # rows per work item (a "page" in RPA terms)
# pad rows/items carry this additive constant: estimated distances become
# huge-but-finite (inf would poison a*g arithmetic), and the top-k tail
# treats anything above PAD_EST_VALID as a hole
PAD_B = np.float32(1e30)
PAD_EST_VALID = np.float32(1e29)


def _pow2(n: int, floor: int = 8) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


def ragged_arange(starts: np.ndarray, counts: np.ndarray) -> np.ndarray:
    """Concatenate ``arange(s, s + c)`` for each (s, c) pair, vectorized."""
    counts = np.asarray(counts, np.int64)
    total = int(counts.sum())
    if total == 0:
        return np.zeros(0, np.int64)
    base = np.repeat(np.asarray(starts, np.int64), counts)
    resets = np.repeat(np.cumsum(counts) - counts, counts)
    return base + (np.arange(total, dtype=np.int64) - resets)


def fold_cluster(norms, factors, code_dot_c, *, d: int, ex: bool):
    """Fold per-row RaBitQ constants into the (a, b, h) form of the ragged
    estimator.  ``ex`` selects the ex-code estimator (csum unused, h = 0);
    the 1-bit path folds the 1/sqrt(D) bit-plane normalization in."""
    norms = np.asarray(norms, np.float32)
    factors = np.asarray(factors, np.float32)
    cdc = np.asarray(code_dot_c, np.float32)
    if ex:
        a = 2.0 * norms / factors
        b = norms * norms + a * cdc
        h = np.zeros_like(a)
    else:
        root_d = np.float32(np.sqrt(d))
        hh = 2.0 * norms / (factors * root_d)
        a = 2.0 * hh
        b = norms * norms + a * cdc
        h = hh
    return a.astype(np.float32), b.astype(np.float32), h.astype(np.float32)


# --------------------------------------------------------------------------
# Pallas kernel: one grid step = one (query, cluster-tile) work item
# --------------------------------------------------------------------------


def _ragged_score_kernel(
    item_q_ref, item_tile_ref, csq_ref, csum_ref, q_ref,
    codes_ref, a_ref, b_ref, h_ref, out_ref,
):
    """codes block [TILE, d] x this item's query row [1, d] → one MXU
    matvec, fused with the affine correction into estimated sq-distances.
    All four per-item tables are scalar-prefetched into SMEM: item_q and
    item_tile are consumed by the BlockSpec index maps, csq and csum are
    read here as this step's scalars."""
    del item_q_ref, item_tile_ref
    i = pl.program_id(0)
    g = jnp.dot(codes_ref[:], q_ref[:].T, preferred_element_type=jnp.float32)[:, 0]
    out_ref[0, :] = (
        b_ref[0, :]
        + csq_ref[i]
        - h_ref[0, :] * csum_ref[i]
        - a_ref[0, :] * g
    )


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def _ragged_score_pallas_call(
    item_q, item_tile, csq, csum, q_glob, codes, a, b, h,
    *, tile: int, interpret: bool,
):
    m = item_q.shape[0]
    d = codes.shape[1]
    # Mosaic wants a block's last two dims divisible by (8, 128) or equal to
    # the array's: a one-row block of a [rows, d] array is neither, so the
    # query table and the output carry a unit middle axis and the row index
    # moves to a squeezed leading dim
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=4,
        grid=(m,),
        in_specs=[
            # this item's query row: the prefetched item table IS the index map
            pl.BlockSpec((None, 1, d), lambda i, iq, it, cq, cs: (iq[i], 0, 0)),
            # this item's cluster tile
            pl.BlockSpec((tile, d), lambda i, iq, it, cq, cs: (it[i], 0)),
            pl.BlockSpec((1, tile), lambda i, iq, it, cq, cs: (0, it[i])),
            pl.BlockSpec((1, tile), lambda i, iq, it, cq, cs: (0, it[i])),
            pl.BlockSpec((1, tile), lambda i, iq, it, cq, cs: (0, it[i])),
        ],
        out_specs=pl.BlockSpec(
            (None, 1, tile), lambda i, iq, it, cq, cs: (i, 0, 0)
        ),
    )
    out = pl.pallas_call(
        _ragged_score_kernel,
        out_shape=jax.ShapeDtypeStruct((m, 1, tile), jnp.float32),
        grid_spec=grid_spec,
        interpret=interpret,
    )(
        item_q, item_tile, csq, csum, q_glob[:, None, :],
        codes, a.reshape(1, -1), b.reshape(1, -1), h.reshape(1, -1),
    )
    return out[:, 0, :]


# the four prefetched item tables live in SMEM for the whole call: 4 x 4 B x
# 32768 items is half of the 1 MiB a v5e core has (65536 items overflow it by
# 1.1 KiB at compile time), so a larger micro-batch runs as several calls
MAX_ITEMS = 32768


def ragged_score_pallas(
    item_q, item_tile, csq, csum, q_glob, codes, a, b, h,
    *, tile: int = TILE, interpret: bool = False,
):
    """Item scores [M, tile] via the Pallas grid.  M and Q are pow2-bucketed
    so repeated micro-batches of varying raggedness reuse compiled shapes;
    pad items point at tile 0 / query 0 and are dropped here."""
    m = len(item_q)
    q_pad = _pow2(q_glob.shape[0])

    def pad1(x, n, dtype):
        x = np.asarray(x, dtype)
        return np.pad(x, [(0, n - x.shape[0])] + [(0, 0)] * (x.ndim - 1))

    q_dev = jnp.asarray(pad1(q_glob, q_pad, np.float32))
    resident = (jnp.asarray(codes), jnp.asarray(a), jnp.asarray(b), jnp.asarray(h))
    out = np.empty((m, tile), np.float32)
    for lo in range(0, m, MAX_ITEMS):
        hi = min(m, lo + MAX_ITEMS)
        m_pad = _pow2(hi - lo)
        chunk = _ragged_score_pallas_call(
            jnp.asarray(pad1(item_q[lo:hi], m_pad, np.int32)),
            jnp.asarray(pad1(item_tile[lo:hi], m_pad, np.int32)),
            jnp.asarray(pad1(csq[lo:hi], m_pad, np.float32)),
            jnp.asarray(pad1(csum[lo:hi], m_pad, np.float32)),
            q_dev, *resident,
            tile=tile, interpret=interpret,
        )
        out[lo:hi] = np.asarray(chunk)[: hi - lo]
    return out


@functools.partial(jax.jit, static_argnames=("tile",))
def _ragged_score_jnp_call(item_q, item_tile, csq, csum, q_glob, codes, a, b, h,
                           *, tile: int):
    rows = item_tile[:, None] * tile + jnp.arange(tile)[None, :]  # [M, tile]
    sub = codes[rows]                                             # [M, tile, d]
    qv = q_glob[item_q]                                           # [M, d]
    g = jnp.einsum("mtd,md->mt", sub, qv)
    return b[rows] + csq[:, None] - h[rows] * csum[:, None] - a[rows] * g


def ragged_score_jnp(item_q, item_tile, csq, csum, q_glob, codes, a, b, h,
                     *, tile: int = TILE):
    """jnp twin of the Pallas kernel (gathers materialize [M, tile, d] — a
    differential-test surface, not the host serving path)."""
    return np.asarray(
        _ragged_score_jnp_call(
            jnp.asarray(np.asarray(item_q, np.int32)),
            jnp.asarray(np.asarray(item_tile, np.int32)),
            jnp.asarray(np.asarray(csq, np.float32)),
            jnp.asarray(np.asarray(csum, np.float32)),
            jnp.asarray(np.asarray(q_glob, np.float32)),
            jnp.asarray(codes), jnp.asarray(a), jnp.asarray(b), jnp.asarray(h),
            tile=tile,
        )
    )


def plan_items(pairs_q, pairs_c, csq, csum, tile_start, tile_count):
    """Flatten (query, cluster) probe pairs into per-tile work items.
    Pairs must arrive query-major (sorted by query) so item rows stay
    query-contiguous for the top-k tail."""
    pairs_c = np.asarray(pairs_c, np.int64)
    reps = np.asarray(tile_count, np.int64)[pairs_c]
    item_q = np.repeat(np.asarray(pairs_q, np.int64), reps).astype(np.int32)
    item_tile = ragged_arange(np.asarray(tile_start, np.int64)[pairs_c], reps).astype(
        np.int32
    )
    item_csq = np.repeat(np.asarray(csq, np.float32), reps)
    item_csum = np.repeat(np.asarray(csum, np.float32), reps)
    return item_q, item_tile, item_csq, item_csum


def items_topk(est, item_q, item_tile, nq: int, s: int, *, tile: int = TILE):
    """Per-query top-``s`` over item scores: items are query-contiguous, so
    each query's candidate rows are one flat slice.  Returns
    (rows [nq, s] int64 with -1 holes, est [nq, s] f32 with +inf holes)."""
    rows = (item_tile.astype(np.int64)[:, None] * tile
            + np.arange(tile, dtype=np.int64)[None, :]).reshape(-1)
    flat = np.asarray(est, np.float32).reshape(-1)
    counts = np.bincount(item_q, minlength=nq) * tile
    offsets = np.concatenate([[0], np.cumsum(counts)])
    out_rows = np.full((nq, s), -1, np.int64)
    out_est = np.full((nq, s), np.inf, np.float32)
    for q in range(nq):
        seg = flat[offsets[q] : offsets[q + 1]]
        if not len(seg):
            continue
        s_eff = min(s, len(seg))
        if s_eff < len(seg):
            part = np.argpartition(seg, s_eff - 1)[:s_eff]
        else:
            part = np.arange(len(seg))
        vals = seg[part]
        valid = vals < PAD_EST_VALID
        out_est[q, : s_eff][valid] = vals[valid]
        out_rows[q, : s_eff][valid] = rows[offsets[q] : offsets[q + 1]][part][valid]
    return out_rows, out_est


# --------------------------------------------------------------------------
# host production path: grouped GEMMs + vectorized ragged transpose
# --------------------------------------------------------------------------


def ragged_topk_host(
    codes, a, b, h, row_start, row_count,
    pairs_q, pairs_c, csq, csum, q_glob, nq: int, s: int,
):
    """Per-query top-``s`` estimator candidates on the host.

    GEMMs group by CLUSTER (each probed cluster's codes are touched once per
    micro-batch, against the queries that probed it); results land in a
    QUERY-major flat buffer via a precomputed ragged permutation, so the
    per-query top-k is one ``argpartition`` over a contiguous slice.  Same
    math, same results as the item kernels — without tile padding."""
    pairs_q = np.asarray(pairs_q, np.int64)
    pairs_c = np.asarray(pairs_c, np.int64)
    csq = np.asarray(csq, np.float32)
    csum = np.asarray(csum, np.float32)
    row_start = np.asarray(row_start, np.int64)
    row_count = np.asarray(row_count, np.int64)
    s = min(int(s), max(1, int(row_count.sum())))
    out_rows = np.full((nq, s), -1, np.int64)
    out_est = np.full((nq, s), np.inf, np.float32)
    if not len(pairs_q):
        return out_rows, out_est

    from lakesoul_tpu import native

    if native.available():
        # the C kernel runs the whole scan + top-s in ONE GIL-released call
        # (cluster-major groups, per-query heaps) — python pays one dispatch
        # per SHARD instead of several per probed cluster, and shard passes
        # parallelize for real on the worker pool
        corder = np.argsort(pairs_c, kind="stable")
        pc = pairs_c[corder]
        uniq, grp_start = np.unique(pc, return_index=True)
        grp_off = np.append(grp_start, len(pc)).astype(np.int64)
        use_csum = bool(np.any(h)) and bool(np.any(csum))
        return native.ann_ragged_topk(
            codes, a, b, h if use_csum else None,
            row_start, row_count,
            np.ascontiguousarray(q_glob, np.float32),
            uniq.astype(np.int32), grp_off,
            np.ascontiguousarray(pairs_q[corder], np.int32),
            np.ascontiguousarray(csq[corder], np.float32),
            np.ascontiguousarray(csum[corder], np.float32) if use_csum else None,
            s,
        )

    n_pair = row_count[pairs_c]
    # destination layout: query-major, pairs in stable query order
    q_tot = np.bincount(pairs_q, weights=n_pair, minlength=nq).astype(np.int64)
    q_off = np.concatenate([[0], np.cumsum(q_tot)])
    qorder = np.argsort(pairs_q, kind="stable")
    n_sorted = n_pair[qorder]
    cum = np.cumsum(n_sorted) - n_sorted
    _, first = np.unique(pairs_q[qorder], return_index=True)
    group_of = np.searchsorted(first, np.arange(len(qorder)), side="right") - 1
    within = cum - cum[first][group_of]
    dest_start = np.empty(len(pairs_q), np.int64)
    dest_start[qorder] = q_off[np.unique(pairs_q)][group_of] + within

    use_csum = bool(np.any(h)) and bool(np.any(csum))
    total = int(q_off[-1])
    est_flat = np.empty(total, np.float32)

    # cluster-major execution order
    corder = np.argsort(pairs_c, kind="stable")
    pc, pq = pairs_c[corder], pairs_q[corder]
    pcsq, pcsum = csq[corder], csum[corder]
    uniq, grp_start = np.unique(pc, return_index=True)
    grp_end = np.append(grp_start[1:], len(pc))
    for gi in range(len(uniq)):
        c = int(uniq[gi])
        rs, n_c = int(row_start[c]), int(row_count[c])
        if n_c == 0:
            continue
        s0, s1 = int(grp_start[gi]), int(grp_end[gi])
        qs = pq[s0:s1]
        block = codes[rs : rs + n_c]
        g = block @ q_glob[qs].T  # [n_c, m] — ONE pass over the cluster
        # fuse the affine correction in place (no temporaries: the group
        # loop runs thousands of times per micro-batch); the csum term only
        # exists on 1-bit shards (ex-code planes fold h = 0)
        g *= -a[rs : rs + n_c, None]
        g += b[rs : rs + n_c, None]
        g += pcsq[s0:s1][None, :]
        if use_csum:
            g -= h[rs : rs + n_c, None] * pcsum[s0:s1][None, :]
        # land every probing query's column at its query-major destination
        # slice — plain contiguous copies; the flat candidate-row array the
        # naive transpose would also build is never materialized (candidate
        # rows are recovered below for the TOP-S survivors only)
        dest = dest_start[corder[s0:s1]]
        for j in range(s1 - s0):
            d0 = dest[j]
            est_flat[d0 : d0 + n_c] = g[:, j]

    # per-query top-s over contiguous segments, then map the surviving flat
    # positions back to shard rows: dest_start is globally ascending in
    # query-sorted pair order, so one searchsorted finds each survivor's
    # pair, and its offset inside the pair is its offset inside the cluster
    sorted_dest = dest_start[qorder]
    pair_cluster_sorted = pairs_c[qorder]
    gpos_all, q_all, s_all = [], [], []
    for q in range(nq):
        seg = est_flat[q_off[q] : q_off[q + 1]]
        if not len(seg):
            continue
        s_eff = min(s, len(seg))
        if s_eff < len(seg):
            part = np.argpartition(seg, s_eff - 1)[:s_eff]
        else:
            part = np.arange(len(seg))
        out_est[q, :s_eff] = seg[part]
        gpos_all.append(q_off[q] + part)
        q_all.append(np.full(s_eff, q, np.int64))
        s_all.append(np.arange(s_eff, dtype=np.int64))
    if gpos_all:
        gpos = np.concatenate(gpos_all)
        pair_pos = np.searchsorted(sorted_dest, gpos, side="right") - 1
        rows = (
            row_start[pair_cluster_sorted[pair_pos]]
            + (gpos - sorted_dest[pair_pos])
        )
        out_rows[np.concatenate(q_all), np.concatenate(s_all)] = rows
    return out_rows, out_est
