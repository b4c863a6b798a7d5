"""On-chip ANN scan kernels (Pallas TPU + jnp fallback).

The reference's compute-kernel layer is AVX-512 bit packing + FastScan LUTs
(rust/lakesoul-vector/src/rabitq/simd.rs, fastscan.rs).  On TPU the same
work is reshaped for the MXU/VPU:

- ``packed_scan``: uint8-packed sign codes stay packed in HBM; each grid step
  DMAs a (TILE, D/8) block into VMEM, unpacks with vectorized shift-and-mask
  (VPU), and computes the code·query dot as a (TILE, D) x (D, 1) MXU matvec,
  fused with the RaBitQ affine correction into estimated distances.
- ``bruteforce_topk``: tiled exact-L2 scan (MXU matmul) + top-k.

Both have pure-jnp fallbacks (used on CPU and for differential testing);
``pallas=`` auto-detects the platform.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lakesoul_tpu.utils import platform


# --------------------------------------------------------------------------
# packed RaBitQ scan
# --------------------------------------------------------------------------


def _packed_scan_kernel(q_ref, codes_ref, norms_ref, factors_ref, out_ref, *, d: int):
    """One tile: codes [T, d/8] uint8 → estimated squared distances [T].

    Mosaic-friendly unpack: no 3D reshapes — 8 shift-planes, each a 2D
    (T, d8) x (d8, 1) MXU matvec against the byte-strided query layout
    q_ref [8, d8] where q_ref[j, p] = q[8p + j] (bit j of byte p, MSB-first)."""
    packed = codes_ref[:].astype(jnp.int32)  # [T, d8]
    planes = jnp.concatenate(
        [((packed >> (7 - j)) & 1).astype(jnp.float32) for j in range(8)], axis=1
    )  # [T, 8*d8]: bit-plane j of byte p at column j*d8 + p
    q_flat = q_ref[:]  # [1, 8*d8] pre-laid-out on host in plane-concat order
    bq = jnp.dot(planes, q_flat.T, preferred_element_type=jnp.float32)  # [T, 1] MXU
    qsum = jnp.sum(q_flat)
    qsq = jnp.sum(q_flat * q_flat)
    dot_obar_q = (2.0 * bq[:, 0] - qsum) / jnp.sqrt(jnp.asarray(d, jnp.float32))
    norms = norms_ref[0, :]
    factors = factors_ref[0, :]
    est_rq = norms * dot_obar_q / factors
    out_ref[0, :] = norms * norms + qsq - 2.0 * est_rq


@functools.partial(jax.jit, static_argnames=("d", "tile", "interpret"))
def packed_scan_pallas(
    packed_codes, norms, factors, q_rot, *, d: int, tile: int = 512,
    interpret: bool = False,
):
    """Pallas packed-code scan over one cluster: returns estimated sq-dists
    [N].  ``interpret=True`` runs the kernel in the Pallas interpreter, which
    is how differential tests on CPU opt in per call."""
    n, d8 = packed_codes.shape
    n_pad = ((n + tile - 1) // tile) * tile
    if n_pad != n:
        packed_codes = jnp.pad(packed_codes, ((0, n_pad - n), (0, 0)))
        norms = jnp.pad(norms, (0, n_pad - n))
        factors = jnp.pad(factors, (0, n_pad - n), constant_values=1.0)
    # plane-concat query layout: q_r[0, j*d8 + p] = q[8p + j] (bit j, byte p),
    # flattened on the host so the kernel needs no shape casts
    q_pad = jnp.pad(q_rot.astype(jnp.float32), (0, d8 * 8 - q_rot.shape[0]))
    q_r = q_pad.reshape(d8, 8).T.reshape(1, d8 * 8)
    grid = (n_pad // tile,)
    out = pl.pallas_call(
        functools.partial(_packed_scan_kernel, d=d),
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
        grid=grid,
        in_specs=[
            pl.BlockSpec((1, d8 * 8), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, d8), lambda i: (i, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile), lambda i: (0, i), memory_space=pltpu.VMEM),
            pl.BlockSpec((1, tile), lambda i: (0, i), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i), memory_space=pltpu.VMEM),
        interpret=interpret,
    )(q_r, packed_codes, norms.reshape(1, -1), factors.reshape(1, -1))
    return out[0, :n]


def _pow2_bucket(n: int, floor: int = 512) -> int:
    p = floor
    while p < n:
        p *= 2
    return p


def packed_scan(
    packed_codes, norms, factors, q_rot, *, d: int, pallas: bool | None = None,
    interpret: bool = False,
):
    """Estimated sq-distances for one cluster's packed codes (auto backend).

    Cluster sizes are padded to power-of-2 buckets so repeated searches over
    many differently-sized clusters share compiled kernels instead of
    triggering a fresh XLA/Mosaic compile per shape."""
    from lakesoul_tpu.vector.rabitq import estimate_distances

    n = len(packed_codes)
    if n == 0:
        return jnp.zeros(0, jnp.float32)
    n_pad = _pow2_bucket(n)
    if n_pad != n:
        packed_codes = np.pad(np.asarray(packed_codes), ((0, n_pad - n), (0, 0)))
        norms = np.pad(np.asarray(norms), (0, n_pad - n))
        factors = np.pad(np.asarray(factors), (0, n_pad - n), constant_values=1.0)

    use_pallas = platform.on_tpu() if pallas is None else pallas
    if use_pallas:
        out = packed_scan_pallas(
            jnp.asarray(packed_codes), jnp.asarray(norms), jnp.asarray(factors),
            jnp.asarray(q_rot), d=d, interpret=interpret,
        )
    else:
        out = estimate_distances(
            jnp.asarray(packed_codes), jnp.asarray(norms), jnp.asarray(factors),
            jnp.asarray(q_rot), d=d,
        )
    # slice on the host: an eager on-device slice would compile per shape
    return np.asarray(out)[:n]


# pad sentinels shared by every padded-candidate path (fused_search host
# wrapper and the device-resident bundle): pad rows must sort last and divide
# safely
PAD_NORM = np.float32(1e9)
PAD_FACTOR = np.float32(1.0)
PAD_RAW = np.float32(1e9)


def _packed_dot_kernel(q_ref, codes_ref, out_ref):
    """bits·Q for one tile (same Mosaic-friendly plane-concat trick as the
    full scan kernel)."""
    packed = codes_ref[:].astype(jnp.int32)
    planes = jnp.concatenate(
        [((packed >> (7 - j)) & 1).astype(jnp.float32) for j in range(8)], axis=1
    )
    bq = jnp.dot(planes, q_ref[:].T, preferred_element_type=jnp.float32)
    out_ref[0, :] = bq[:, 0]


def _packed_dot_batch_kernel(q_ref, codes_ref, out_ref):
    """bits·Q for one tile against MANY queries: the unpacked plane matrix
    only ever exists per (tile, 8·d8) block in VMEM — HBM holds packed codes
    regardless of shard size."""
    packed = codes_ref[:].astype(jnp.int32)
    planes = jnp.concatenate(
        [((packed >> (7 - j)) & 1).astype(jnp.float32) for j in range(8)], axis=1
    )  # [T, 8*d8]
    out_ref[:, :] = jnp.dot(planes, q_ref[:].T, preferred_element_type=jnp.float32)


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def packed_dot_batch_pallas(packed_codes, q_rot_batch, *, tile: int = 512,
                            interpret: bool = False):
    """bits·Q over [N, d8] packed codes × [Q, d] queries → [N, Q] f32."""
    n, d8 = packed_codes.shape
    nq = q_rot_batch.shape[0]
    n_pad = ((n + tile - 1) // tile) * tile
    if n_pad != n:
        packed_codes = jnp.pad(packed_codes, ((0, n_pad - n), (0, 0)))
    q_pad = jnp.pad(
        q_rot_batch.astype(jnp.float32), ((0, 0), (0, d8 * 8 - q_rot_batch.shape[1]))
    )
    # per-query plane-concat layout: [Q, 8*d8] with q[:, j*d8 + p] = q[:, 8p+j]
    q_r = q_pad.reshape(nq, d8, 8).transpose(0, 2, 1).reshape(nq, d8 * 8)
    out = pl.pallas_call(
        _packed_dot_batch_kernel,
        out_shape=jax.ShapeDtypeStruct((n_pad, nq), jnp.float32),
        grid=(n_pad // tile,),
        in_specs=[
            pl.BlockSpec((nq, d8 * 8), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, d8), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((tile, nq), lambda i: (i, 0), memory_space=pltpu.VMEM),
        interpret=interpret,
    )(q_r, packed_codes)
    return out[:n]


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def packed_dot_pallas(packed_codes, q_rot, *, tile: int = 512,
                      interpret: bool = False):
    """bits·Q over [N, d8] packed codes → [N] f32 (Pallas TPU)."""
    n, d8 = packed_codes.shape
    n_pad = ((n + tile - 1) // tile) * tile
    if n_pad != n:
        packed_codes = jnp.pad(packed_codes, ((0, n_pad - n), (0, 0)))
    q_pad = jnp.pad(q_rot.astype(jnp.float32), (0, d8 * 8 - q_rot.shape[0]))
    q_r = q_pad.reshape(d8, 8).T.reshape(1, d8 * 8)
    out = pl.pallas_call(
        _packed_dot_kernel,
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
        grid=(n_pad // tile,),
        in_specs=[
            pl.BlockSpec((1, d8 * 8), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, d8), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i), memory_space=pltpu.VMEM),
        interpret=interpret,
    )(q_r, packed_codes)
    return out[0, :n]


@jax.jit
def _packed_dot_jnp(packed_codes, q_rot):
    from lakesoul_tpu.vector.rabitq import unpack_bits_jnp

    bits = unpack_bits_jnp(packed_codes, q_rot.shape[0])
    return bits @ q_rot


@functools.partial(jax.jit, static_argnames=("d", "s", "k", "use_pallas", "do_rerank"))
def _fused_search(codes, norms, factors, code_dot_c, csq, csum, q_glob, raw, query,
                  *, d, s, k, use_pallas, do_rerank):
    """One device call per query over the concatenated probe set.

    Estimator in the *global* query frame (rows may come from different
    clusters): with Q = P(query), xc = P(c) - Q per row's cluster,
        dist² ≈ ||r||² + ||xc||² + 2·||r||·<o_bar, xc>/factor
        <o_bar, xc> = (2·(code_dot_c - bits·Q) - csum) / √D
    so the only O(N·D) work is ONE bits·Q MXU scan; csq=||xc||², csum=Σxc
    are per-row scalars precomputed on the host.  Then top-S shortlist →
    on-device gather + exact re-rank → top-k; single [k] readback."""
    bq = (
        packed_dot_pallas(codes, q_glob)
        if use_pallas
        else _packed_dot_jnp(codes, q_glob)
    )
    dot_obar_xc = (2.0 * (code_dot_c - bq) - csum) / jnp.sqrt(jnp.asarray(d, jnp.float32))
    est = norms * norms + csq + 2.0 * norms * dot_obar_xc / factors
    if not do_rerank:
        neg, idx = jax.lax.top_k(-est, k)
        return -neg, idx
    neg_s, idx_s = jax.lax.top_k(-est, s)
    sub = raw[idx_s]  # on-device gather of shortlisted raw vectors
    q = query.astype(jnp.float32)
    exact = jnp.sum(sub * sub, axis=1) - 2.0 * (sub @ q) + jnp.sum(q * q)
    neg, order = jax.lax.top_k(-exact, k)
    return -neg, idx_s[order]


@functools.partial(jax.jit, static_argnames=("d", "s", "k", "use_pallas", "do_rerank"))
def _fused_search_resident(codes, norms, factors, code_dot_c, cluster_id, probe_mask,
                           csq_c, csum_c, q_glob, raw, query,
                           *, d, s, k, use_pallas, do_rerank):
    """Device-resident variant: the WHOLE shard stays in HBM (codes, factors,
    raw, cluster ids); per query only the rotated query and three (nlist,)
    scalar vectors travel.  Non-probed clusters are masked to +inf — on the
    MXU, scanning everything beats re-uploading per-probe concatenations
    (compute is cheaper than transfers)."""
    bq = (
        packed_dot_pallas(codes, q_glob)
        if use_pallas
        else _packed_dot_jnp(codes, q_glob)
    )
    csq = csq_c[cluster_id]
    csum = csum_c[cluster_id]
    dot_obar_xc = (2.0 * (code_dot_c - bq) - csum) / jnp.sqrt(jnp.asarray(d, jnp.float32))
    est = norms * norms + csq + 2.0 * norms * dot_obar_xc / factors
    est = jnp.where(probe_mask[cluster_id], est, jnp.inf)
    if not do_rerank:
        neg, idx = jax.lax.top_k(-est, k)
        return -neg, idx
    neg_s, idx_s = jax.lax.top_k(-est, s)
    sub = raw[idx_s]
    q = query.astype(jnp.float32)
    exact = jnp.sum(sub * sub, axis=1) - 2.0 * (sub @ q) + jnp.sum(q * q)
    exact = jnp.where(jnp.isfinite(-neg_s), exact, jnp.inf)  # masked rows stay out
    neg, order = jax.lax.top_k(-exact, k)
    return -neg, idx_s[order]


def _batched_rerank_topk(est, raw, queries, *, s: int, k: int, do_rerank: bool):
    """Shared tail of the batched resident kernels: [N, Q] estimates →
    (dists [Q, k], indices [Q, k]), with optional on-device exact re-rank."""
    est_t = est.T
    if not do_rerank:
        neg, idx = jax.lax.top_k(-est_t, k)
        return -neg, idx
    neg_s, idx_s = jax.lax.top_k(-est_t, s)
    sub = raw[idx_s]
    q32 = queries.astype(jnp.float32)
    exact = (
        jnp.sum(sub * sub, axis=-1)
        - 2.0 * jnp.einsum("qsd,qd->qs", sub, q32)
        + jnp.sum(q32 * q32, axis=-1)[:, None]
    )
    exact = jnp.where(jnp.isfinite(-neg_s), exact, jnp.inf)
    neg, order = jax.lax.top_k(-exact, k)
    return -neg, jnp.take_along_axis(idx_s, order, axis=1)


@functools.partial(jax.jit, static_argnames=("d", "s", "k", "use_pallas", "do_rerank"))
def _fused_search_resident_batch(codes, norms, factors, code_dot_c, cluster_id,
                                 probe_mask, csq_c, csum_c, q_glob, raw, queries,
                                 *, d, s, k, use_pallas, do_rerank):
    """Batched device-resident search: Q queries amortize one dispatch +
    readback.  On TPU the packed-code Pallas kernel keeps codes packed in HBM
    (plane unpack happens per tile in VMEM); the jnp fallback materializes
    the unpacked bit matrix and is only meant for CPU-sized shards."""
    if use_pallas:
        bq = packed_dot_batch_pallas(codes, q_glob)       # [N, Q]
    else:
        from lakesoul_tpu.vector.rabitq import unpack_bits_jnp

        bits = unpack_bits_jnp(codes, d)                  # [N, d]
        bq = bits @ q_glob.T                              # [N, Q] MXU
    csq = csq_c[cluster_id]                               # [N, Q]
    csum = csum_c[cluster_id]
    dot_obar_xc = (2.0 * (code_dot_c[:, None] - bq) - csum) / jnp.sqrt(
        jnp.asarray(d, jnp.float32)
    )
    est = norms[:, None] ** 2 + csq + 2.0 * norms[:, None] * dot_obar_xc / factors[:, None]
    est = jnp.where(probe_mask[cluster_id], est, jnp.inf)  # [N, Q]
    return _batched_rerank_topk(est, raw, queries, s=s, k=k, do_rerank=do_rerank)


@functools.partial(jax.jit, static_argnames=("s", "k", "do_rerank"))
def _fused_search_ex(codes, scales, norms, factors, code_dot_c, csq, q_glob, raw,
                     query, *, s, k, do_rerank):
    """Fused search over int8 ex-codes (total_bits > 1): one MXU int8 matvec
    u_hat·Q, then the global-frame estimator
        dist² ≈ ||r||² + ||xc||² + 2·||r||·(code_dot_c - u_hat·Q)/factor
    (csum is unnecessary: u_hat is a real-valued vector, not ±1 bits)."""
    g = (codes.astype(jnp.int32) @ q_glob.astype(jnp.float32)) * scales  # [N]
    est = norms * norms + csq + 2.0 * norms * (code_dot_c - g) / factors
    if not do_rerank:
        neg, idx = jax.lax.top_k(-est, k)
        return -neg, idx
    neg_s, idx_s = jax.lax.top_k(-est, s)
    sub = raw[idx_s]
    q = query.astype(jnp.float32)
    exact = jnp.sum(sub * sub, axis=1) - 2.0 * (sub @ q) + jnp.sum(q * q)
    neg, order = jax.lax.top_k(-exact, k)
    return -neg, idx_s[order]


def _pad_tail(a, n_pad: int, const=0):
    """Pad a candidate array's first axis to n_pad with a constant."""
    a = np.asarray(a)
    pad = n_pad - len(a)
    if pad <= 0:
        return a
    width = [(0, pad)] + [(0, 0)] * (a.ndim - 1)
    return np.pad(a, width, constant_values=const)


def fused_search_ex(codes, scales, norms, factors, code_dot_c, csq, q_glob, raw,
                    query, *, top_k, shortlist):
    """Host wrapper for the int8 ex-code path (pow2 padding, pad filtering
    mirrors fused_search)."""
    n = len(codes)
    n_pad = _pow2_bucket(n)
    codes = _pad_tail(codes, n_pad)
    scales = _pad_tail(scales, n_pad)
    norms = _pad_tail(norms, n_pad, PAD_NORM)
    factors = _pad_tail(factors, n_pad, PAD_FACTOR)
    code_dot_c = _pad_tail(code_dot_c, n_pad)
    csq = _pad_tail(csq, n_pad)
    if raw is not None:
        raw = _pad_tail(raw, n_pad, PAD_RAW)
    do_rerank = raw is not None
    s = min(shortlist, n_pad)
    k = min(top_k, n_pad)
    dists, idx = _fused_search_ex(
        jnp.asarray(codes),
        jnp.asarray(np.asarray(scales, np.float32)),
        jnp.asarray(np.asarray(norms, np.float32)),
        jnp.asarray(np.asarray(factors, np.float32)),
        jnp.asarray(np.asarray(code_dot_c, np.float32)),
        jnp.asarray(np.asarray(csq, np.float32)),
        jnp.asarray(q_glob, dtype=jnp.float32),
        jnp.asarray(raw) if do_rerank else jnp.zeros((1, 1), jnp.float32),
        jnp.asarray(query, dtype=jnp.float32),
        s=s, k=k, do_rerank=do_rerank,
    )
    return np.asarray(dists), np.asarray(idx)


@functools.partial(jax.jit, static_argnames=("s", "k", "do_rerank"))
def _fused_search_resident_ex_batch(codes, scales, norms, factors, code_dot_c,
                                    cluster_id, probe_mask, csq_c, q_glob, raw,
                                    queries, *, s, k, do_rerank):
    """Device-resident batched search over int8 ex-codes: codes are already
    MXU-native, so u_hat·Q is one (N, d) x (d, Q) int8×f32 matmul — no unpack
    stage at all."""
    g = (codes.astype(jnp.int32) @ q_glob.T.astype(jnp.float32)) * scales[:, None]  # [N, Q]
    csq = csq_c[cluster_id]  # [N, Q]
    est = (
        norms[:, None] ** 2
        + csq
        + 2.0 * norms[:, None] * (code_dot_c[:, None] - g) / factors[:, None]
    )
    est = jnp.where(probe_mask[cluster_id], est, jnp.inf)
    return _batched_rerank_topk(est, raw, queries, s=s, k=k, do_rerank=do_rerank)


def fused_search(codes, norms, factors, code_dot_c, csq, csum, q_glob, raw, query,
                 *, d, top_k, shortlist, pallas: bool | None = None):
    """Host wrapper: pow2-pad candidate arrays, run the fused kernel, return
    (dists, global indices) as numpy — indices >= the true candidate count
    are pad rows the caller must drop."""
    n = len(codes)
    n_pad = _pow2_bucket(n)
    codes = _pad_tail(codes, n_pad)
    # pad rows get a huge norm → huge estimated distance → never selected
    norms = _pad_tail(norms, n_pad, PAD_NORM)
    factors = _pad_tail(factors, n_pad, PAD_FACTOR)
    code_dot_c = _pad_tail(code_dot_c, n_pad)
    csq = _pad_tail(csq, n_pad)
    csum = _pad_tail(csum, n_pad)
    if raw is not None:
        raw = _pad_tail(raw, n_pad, PAD_RAW)
    do_rerank = raw is not None
    s = min(shortlist, n_pad)
    k = min(top_k, n_pad)
    use_pallas = platform.on_tpu() if pallas is None else pallas
    dists, idx = _fused_search(
        jnp.asarray(codes),
        jnp.asarray(np.asarray(norms, np.float32)),
        jnp.asarray(np.asarray(factors, np.float32)),
        jnp.asarray(np.asarray(code_dot_c, np.float32)),
        jnp.asarray(np.asarray(csq, np.float32)),
        jnp.asarray(np.asarray(csum, np.float32)),
        jnp.asarray(q_glob, dtype=jnp.float32),
        jnp.asarray(raw) if do_rerank else jnp.zeros((1, 1), jnp.float32),
        jnp.asarray(query, dtype=jnp.float32),
        d=d, s=s, k=k, use_pallas=use_pallas, do_rerank=do_rerank,
    )
    return np.asarray(dists), np.asarray(idx)


# --------------------------------------------------------------------------
# brute-force exact scan + top-k
# --------------------------------------------------------------------------


def _bruteforce_kernel(q_ref, x_ref, out_ref):
    x = x_ref[:]  # [T, D]
    q = q_ref[:]  # [1, D]
    dots = jnp.dot(x, q.T, preferred_element_type=jnp.float32)[:, 0]
    x_sq = jnp.sum(x.astype(jnp.float32) * x.astype(jnp.float32), axis=1)
    q_sq = jnp.sum(q * q)
    out_ref[0, :] = x_sq - 2.0 * dots + q_sq


@functools.partial(jax.jit, static_argnames=("tile", "interpret"))
def bruteforce_distances_pallas(vectors, query, *, tile: int = 512,
                                interpret: bool = False):
    n, d = vectors.shape
    n_pad = ((n + tile - 1) // tile) * tile
    if n_pad != n:
        vectors = jnp.pad(vectors, ((0, n_pad - n), (0, 0)))
    q2 = query.reshape(1, -1).astype(jnp.float32)
    out = pl.pallas_call(
        _bruteforce_kernel,
        out_shape=jax.ShapeDtypeStruct((1, n_pad), jnp.float32),
        grid=(n_pad // tile,),
        in_specs=[
            pl.BlockSpec((1, d), lambda i: (0, 0), memory_space=pltpu.VMEM),
            pl.BlockSpec((tile, d), lambda i: (i, 0), memory_space=pltpu.VMEM),
        ],
        out_specs=pl.BlockSpec((1, tile), lambda i: (0, i), memory_space=pltpu.VMEM),
        interpret=interpret,
    )(q2, vectors)
    return out[0, :n]


@jax.jit
def _bruteforce_jnp(vectors, query):
    v = vectors.astype(jnp.float32)
    q = query.astype(jnp.float32)
    return jnp.sum(v * v, axis=1) - 2.0 * (v @ q) + jnp.sum(q * q)


def bruteforce_topk(vectors, query, k: int, *, pallas: bool | None = None):
    """Exact L2 top-k over [N, D] vectors: returns (dists [k], indices [k]).
    N is padded to a power-of-2 bucket (pad rows at +inf distance) to keep
    the compiled-shape count logarithmic."""
    use_pallas = platform.on_tpu() if pallas is None else pallas
    n = len(vectors)
    k = min(k, n)
    n_pad = _pow2_bucket(n, floor=max(512, k))
    v = np.asarray(vectors, dtype=np.float32)
    if n_pad != n:
        v = np.pad(v, ((0, n_pad - n), (0, 0)), constant_values=np.float32(1e18))
    v = jnp.asarray(v)
    q = jnp.asarray(query)
    if use_pallas:
        return _topk_pallas(v, q, k=k)
    return _topk_jnp(v, q, k=k)


@functools.partial(jax.jit, static_argnames=("k",))
def _topk_pallas(v, q, *, k: int):
    dists = bruteforce_distances_pallas(v, q)
    neg, idx = jax.lax.top_k(-dists, k)
    return -neg, idx


@functools.partial(jax.jit, static_argnames=("k",))
def _topk_jnp(v, q, *, k: int):
    dists = _bruteforce_jnp(v, q)
    neg, idx = jax.lax.top_k(-dists, k)
    return -neg, idx
