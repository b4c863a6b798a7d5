"""IVF + RaBitQ ANN index.

Capability parity with IvfRabitqIndex (rust/lakesoul-vector/src/rabitq/ivf/
mod.rs: train:90, train_from_batches:257, search:1131, search_filtered:1149,
batch_search:1169, insert_batch:1901), redesigned around TPU kernels: cluster
scans are MXU matvecs over packed codes (lakesoul_tpu.vector.kernels), train
is JAX k-means on-device.

Incremental inserts append to per-cluster *delta* arrays, mirroring the
reference's base + delta segments; ``merge_deltas()`` folds them in."""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np

from lakesoul_tpu.errors import VectorIndexError
from lakesoul_tpu.vector.config import VectorIndexConfig
from lakesoul_tpu.vector.kmeans import kmeans
from lakesoul_tpu.vector.rabitq import RabitqQuantizer


def _finalize_topk(ids: np.ndarray, dists: np.ndarray, idx: np.ndarray, top_k: int):
    """Drop pad rows from a fused-search result and cut to top_k."""
    valid = (idx < len(ids)) & np.isfinite(dists)
    idx, dists = idx[valid], dists[valid]
    k = min(top_k, len(ids))
    return ids[idx[:k]], dists[:k]


@dataclass(frozen=True)
class SearchParams:
    """reference: SearchParams{top_k, nprobe} (ivf/mod.rs:29).

    ``rerank_depth`` sizes the estimator shortlist handed to the exact
    re-rank (None → 4·top_k).  With raw vectors kept, recall is bounded only
    by probe coverage and this depth, so deeper re-rank trades QPS for
    recall without touching the quantizer."""

    top_k: int = 10
    nprobe: int = 8
    rerank_depth: int | None = None

    def shortlist(self) -> int:
        s = self.rerank_depth if self.rerank_depth is not None else self.top_k * 4
        return max(s, self.top_k)


@dataclass
class _Cluster:
    codes: np.ndarray  # 1-bit: [n, padded/8] uint8 packed; ex: [n, padded] int8
    norms: np.ndarray  # [n] f32
    factors: np.ndarray  # [n] f32
    ids: np.ndarray  # [n] u64 row ids
    code_dot_c: np.ndarray | None = None  # [n] f32: u_hat · P(centroid)
    raw: np.ndarray | None = None  # [n, dim] f32 (kept for exact re-rank)
    scales: np.ndarray | None = None  # [n] f32, ex-codes only (u_hat = codes*scales)


class IvfRabitqIndex:
    def __init__(self, config: VectorIndexConfig):
        self.config = config
        self.quantizer = RabitqQuantizer(
            config.dim, rotator=config.rotator, seed=config.seed
        )
        self.centroids: np.ndarray | None = None  # [nlist, dim]
        self._centroids_rot: np.ndarray | None = None  # cache of P(centroids)
        self.clusters: list[_Cluster] = []
        self.deltas: list[list[_Cluster]] = []
        self.keep_raw = True
        self._device_cache_enabled = False
        self._device_bundle = None

    # ------------------------------------------------------------------ train
    @classmethod
    def train(
        cls,
        vectors: np.ndarray,
        ids: np.ndarray,
        config: VectorIndexConfig,
        *,
        keep_raw: bool = True,
        kmeans_iters: int = 10,
    ) -> "IvfRabitqIndex":
        vectors = np.ascontiguousarray(vectors, dtype=np.float32)
        ids = np.asarray(ids, dtype=np.uint64)
        if vectors.ndim != 2 or vectors.shape[1] != config.dim:
            raise VectorIndexError(
                f"expected [N, {config.dim}] vectors, got {vectors.shape}"
            )
        if len(ids) != len(vectors):
            raise VectorIndexError("ids/vectors length mismatch")
        index = cls(config)
        index.keep_raw = keep_raw
        nlist = min(config.nlist, max(1, len(vectors)))
        centroids, assign = kmeans(
            vectors, nlist, iters=kmeans_iters, seed=config.seed
        )
        index.centroids = centroids
        index.clusters = [
            index._make_cluster(vectors[assign == c], ids[assign == c], centroids[c])
            for c in range(nlist)
        ]
        index.deltas = [[] for _ in range(nlist)]
        return index

    @classmethod
    def train_from_batches(cls, batches, config: VectorIndexConfig, **kw) -> "IvfRabitqIndex":
        """batches: iterable of (vectors [n, dim], ids [n])."""
        vs, ds = [], []
        for v, i in batches:
            vs.append(np.asarray(v, dtype=np.float32))
            ds.append(np.asarray(i, dtype=np.uint64))
        if not vs:
            raise VectorIndexError("no vectors to train on")
        return cls.train(np.concatenate(vs), np.concatenate(ds), config, **kw)

    @property
    def _ex_bits(self) -> bool:
        return self.config.total_bits > 1

    def _make_cluster(self, vectors, ids, centroid) -> _Cluster:
        if len(vectors) == 0:
            if self._ex_bits:
                dt = np.int8 if self.config.total_bits <= 8 else np.int16
                codes0 = np.zeros((0, self.quantizer.padded_dim), dt)
            else:
                codes0 = np.zeros((0, self.quantizer.padded_dim // 8), np.uint8)
            return _Cluster(
                codes=codes0,
                norms=np.zeros(0, np.float32),
                factors=np.ones(0, np.float32),
                ids=np.zeros(0, np.uint64),
                code_dot_c=np.zeros(0, np.float32),
                raw=np.zeros((0, self.config.dim), np.float32) if self.keep_raw else None,
                scales=np.zeros(0, np.float32) if self._ex_bits else None,
            )
        if self._ex_bits:
            codes, scales, norms, factors, code_dot_c = self.quantizer.quantize_ex(
                vectors, centroid, self.config.total_bits
            )
        else:
            codes, norms, factors, code_dot_c = self.quantizer.quantize(vectors, centroid)
            scales = None
        return _Cluster(
            codes=codes,
            norms=norms,
            factors=factors,
            ids=ids,
            code_dot_c=code_dot_c,
            raw=vectors.copy() if self.keep_raw else None,
            scales=scales,
        )

    # ----------------------------------------------------------------- insert
    def insert_batch(self, vectors: np.ndarray, ids: np.ndarray) -> None:
        """Incremental insert: assign to nearest centroid, quantize, append as
        a delta segment (reference: insert_batch → delta segments)."""
        if self.centroids is None:
            raise VectorIndexError("index not trained")
        vectors = np.ascontiguousarray(vectors, dtype=np.float32)
        ids = np.asarray(ids, dtype=np.uint64)
        d2 = (
            np.sum(vectors**2, axis=1, keepdims=True)
            - 2.0 * vectors @ self.centroids.T
            + np.sum(self.centroids**2, axis=1)[None, :]
        )
        self._invalidate_device_cache()
        assign = np.argmin(d2, axis=1)
        for c in np.unique(assign):
            m = assign == c
            self.deltas[c].append(
                self._make_cluster(vectors[m], ids[m], self.centroids[c])
            )

    def merge_deltas(self) -> None:
        """Fold delta segments into base clusters (compaction of the index)."""
        self._invalidate_device_cache()
        for c, deltas in enumerate(self.deltas):
            if not deltas:
                continue
            segs = [self.clusters[c]] + deltas
            self.clusters[c] = _Cluster(
                codes=np.concatenate([s.codes for s in segs]),
                norms=np.concatenate([s.norms for s in segs]),
                factors=np.concatenate([s.factors for s in segs]),
                ids=np.concatenate([s.ids for s in segs]),
                code_dot_c=np.concatenate([np.asarray(s.code_dot_c) for s in segs]),
                scales=(
                    np.concatenate([np.asarray(s.scales) for s in segs])
                    if all(s.scales is not None for s in segs)
                    else None
                ),
                raw=(
                    np.concatenate([s.raw for s in segs])
                    if self.keep_raw and all(s.raw is not None for s in segs)
                    else None
                ),
            )
            self.deltas[c] = []

    @property
    def num_vectors(self) -> int:
        return sum(len(c.ids) for c in self.clusters) + sum(
            len(s.ids) for ds in self.deltas for s in ds
        )

    # ------------------------------------------------------- device residency
    def enable_device_cache(self) -> None:
        """Pin the shard's arrays in device HBM: subsequent searches upload
        only the query + per-cluster scalars (one device call, no candidate
        re-upload).  Invalidated automatically by insert/merge."""
        self._device_cache_enabled = True

    def _invalidate_device_cache(self) -> None:
        self._device_bundle = None

    def _get_device_bundle(self):
        import jax.numpy as jnp

        from lakesoul_tpu.vector.kernels import _pow2_bucket

        bundle = getattr(self, "_device_bundle", None)
        if bundle is not None:
            return bundle
        segs = [
            (c, seg)
            for c in range(len(self.clusters))
            for seg in self._cluster_segments(c)
            if len(seg.ids)
        ]
        if not segs:
            return None
        codes = np.concatenate([s.codes for _, s in segs])
        n = len(codes)
        n_pad = _pow2_bucket(n)
        pad = n_pad - n

        def padded(a, const=0.0, dtype=np.float32):
            a = np.asarray(a, dtype)
            return np.pad(a, [(0, pad)] + [(0, 0)] * (a.ndim - 1), constant_values=const)

        from lakesoul_tpu.vector.kernels import PAD_FACTOR, PAD_NORM, PAD_RAW

        bundle = {
            "codes": jnp.asarray(np.pad(codes, ((0, pad), (0, 0)))),
            "norms": jnp.asarray(padded(np.concatenate([s.norms for _, s in segs]), PAD_NORM)),
            "factors": jnp.asarray(padded(np.concatenate([s.factors for _, s in segs]), PAD_FACTOR)),
            "cdc": jnp.asarray(padded(np.concatenate([np.asarray(s.code_dot_c) for _, s in segs]))),
            "cluster_id": jnp.asarray(
                np.pad(
                    np.concatenate(
                        [np.full(len(s.ids), c, np.int32) for c, s in segs]
                    ),
                    (0, pad),
                )
            ),
            "scales": (
                jnp.asarray(
                    padded(np.concatenate([np.asarray(s.scales) for _, s in segs]), 1.0)
                )
                if all(s.scales is not None for _, s in segs)
                else None
            ),
            "raw": (
                jnp.asarray(
                    np.pad(
                        np.concatenate([s.raw for _, s in segs]),
                        ((0, pad), (0, 0)),
                        constant_values=PAD_RAW,
                    )
                )
                if self.keep_raw and all(s.raw is not None for _, s in segs)
                else None
            ),
            "ids": np.concatenate([s.ids for _, s in segs]),  # host side
            "n": n,
        }
        self._device_bundle = bundle
        return bundle

    def _search_device_resident(self, query, params: SearchParams, probe):
        import jax.numpy as jnp

        from lakesoul_tpu.utils import platform
        from lakesoul_tpu.vector.kernels import _fused_search_resident

        bundle = self._get_device_bundle()
        if bundle is None:
            return np.zeros(0, np.uint64), np.zeros(0, np.float32)
        q_glob = self.quantizer.rotate(query)
        xc = self._rotated_centroids() - q_glob[None, :]
        csq_c = np.sum(xc * xc, axis=1).astype(np.float32)
        csum_c = np.sum(xc, axis=1).astype(np.float32)
        probe_mask = np.zeros(len(self.centroids), dtype=bool)
        probe_mask[probe] = True
        do_rerank = bundle["raw"] is not None
        s = min(params.shortlist(), int(bundle["codes"].shape[0]))
        k = min(params.top_k, int(bundle["codes"].shape[0]))
        dists, idx = _fused_search_resident(
            bundle["codes"], bundle["norms"], bundle["factors"], bundle["cdc"],
            bundle["cluster_id"], jnp.asarray(probe_mask),
            jnp.asarray(csq_c), jnp.asarray(csum_c), jnp.asarray(q_glob),
            bundle["raw"] if do_rerank else jnp.zeros((1, 1), jnp.float32),
            jnp.asarray(query, jnp.float32),
            d=self.quantizer.padded_dim, s=s, k=k,
            use_pallas=platform.on_tpu(), do_rerank=do_rerank,
        )
        dists, idx = np.asarray(dists), np.asarray(idx)
        valid = (idx < bundle["n"]) & np.isfinite(dists)
        idx, dists = idx[valid], dists[valid]
        kk = min(params.top_k, len(idx))
        return bundle["ids"][idx[:kk]], dists[:kk]

    # ----------------------------------------------------------------- search
    def _rotated_centroids(self) -> np.ndarray:
        if self._centroids_rot is None or len(self._centroids_rot) != len(self.centroids):
            self._centroids_rot = self.quantizer.rotate(self.centroids)
        return self._centroids_rot

    def _rotated_centroid(self, c: int) -> np.ndarray:
        return self._rotated_centroids()[c]

    def _cluster_segments(self, c: int):
        yield self.clusters[c]
        yield from self.deltas[c]

    def search(
        self,
        query: np.ndarray,
        params: SearchParams = SearchParams(),
        *,
        allowed_ids: np.ndarray | None = None,
        rerank: bool = True,
    ) -> tuple[np.ndarray, np.ndarray]:
        """→ (ids [k] u64, distances [k] f32), nearest first.

        ``allowed_ids`` implements search_filtered (ivf/mod.rs:1149).
        ``rerank`` re-scores the RaBitQ candidates with exact distances when
        raw vectors are kept (the reference re-ranks caller-side,
        vector_index.py:263)."""
        if self.centroids is None:
            raise VectorIndexError("index not trained")
        query = np.asarray(query, dtype=np.float32)
        nprobe = min(params.nprobe, len(self.centroids))

        if (
            getattr(self, "_device_cache_enabled", False)
            and allowed_ids is None
            and rerank == self.keep_raw
        ):
            if not self._ex_bits:
                cd = np.sum((self.centroids - query[None, :]) ** 2, axis=1)
                probe = np.argsort(cd)[:nprobe]
                return self._search_device_resident(query, params, probe)
            # ex-codes: the batched resident kernel IS the single-query path
            # (Q=1 column) — same HBM-resident codes, one dispatch; it
            # computes its own probe set, so none is computed here
            out = self._batch_search_device_resident(query[None, :], params)
            if out is not None:
                ids_b, dists_b = out
                return ids_b[0], dists_b[0]

        cd = np.sum((self.centroids - query[None, :]) ** 2, axis=1)
        probe = np.argsort(cd)[:nprobe]

        # All probed segments are concatenated into ONE fused device call.
        # Rotation is linear, so the estimator works in the *global* query
        # frame: with Q = P(query) and xc = P(c) - Q (per cluster),
        #   dist² ≈ ||r||² + ||xc||² + 2·||r||·<o_bar, xc>/factor,
        # where <o_bar, xc> needs only bits·Q (one MXU scan) plus the
        # build-time per-row constant code_dot_c = bits·P(c) and two
        # per-cluster scalars (||xc||², Σxc) broadcast per row on the host.
        cand = {k: [] for k in ("ids", "codes", "norms", "factors", "cdc", "csq", "csum", "raw", "scales")}
        q_glob = self.quantizer.rotate(query)  # P(query), computed once
        ex = self._ex_bits
        for c in probe:
            xc = self._rotated_centroid(c) - q_glob
            xc_sq = np.float32(np.dot(xc, xc))
            xc_sum = np.float32(0.0) if ex else np.float32(np.sum(xc))  # ex path never uses csum
            for seg in self._cluster_segments(c):
                if len(seg.ids) == 0:
                    continue
                ids = seg.ids
                sel = slice(None)
                if allowed_ids is not None:
                    m = np.isin(ids, allowed_ids)
                    if not m.any():
                        continue
                    sel = m
                    ids = ids[m]
                n_seg = len(ids)
                cand["ids"].append(ids)
                cand["codes"].append(seg.codes[sel])
                cand["norms"].append(seg.norms[sel])
                cand["factors"].append(seg.factors[sel])
                cand["cdc"].append(np.asarray(seg.code_dot_c)[sel])
                cand["csq"].append(np.full(n_seg, xc_sq, np.float32))
                cand["csum"].append(np.full(n_seg, xc_sum, np.float32))
                cand["raw"].append(seg.raw[sel] if seg.raw is not None else None)
                if ex and seg.scales is None:
                    raise VectorIndexError(
                        "index config says total_bits > 1 but segment has no scales"
                        " (legacy 1-bit shard?) — rebuild the index"
                    )
                cand["scales"].append(seg.scales[sel] if seg.scales is not None else None)

        if not cand["ids"]:
            return np.zeros(0, np.uint64), np.zeros(0, np.float32)
        ids = np.concatenate(cand["ids"])

        from lakesoul_tpu.vector.kernels import fused_search, fused_search_ex

        use_rerank = rerank and self.keep_raw and all(r is not None for r in cand["raw"])
        if self._ex_bits:
            dists, idx = fused_search_ex(
                np.concatenate(cand["codes"]),
                np.concatenate(cand["scales"]),
                np.concatenate(cand["norms"]),
                np.concatenate(cand["factors"]),
                np.concatenate(cand["cdc"]),
                np.concatenate(cand["csq"]),
                q_glob,
                np.concatenate(cand["raw"]) if use_rerank else None,
                query,
                top_k=params.top_k,
                shortlist=params.shortlist(),
            )
            return _finalize_topk(ids, dists, idx, params.top_k)
        dists, idx = fused_search(
            np.concatenate(cand["codes"]),
            np.concatenate(cand["norms"]),
            np.concatenate(cand["factors"]),
            np.concatenate(cand["cdc"]),
            np.concatenate(cand["csq"]),
            np.concatenate(cand["csum"]),
            q_glob,
            np.concatenate(cand["raw"]) if use_rerank else None,
            query,
            d=self.quantizer.padded_dim,
            top_k=params.top_k,
            shortlist=params.shortlist(),
        )
        return _finalize_topk(ids, dists, idx, params.top_k)

    def search_filtered(self, query, allowed_ids, params: SearchParams = SearchParams()):
        return self.search(query, params, allowed_ids=np.asarray(allowed_ids, np.uint64))

    def tune_nprobe(
        self,
        queries: np.ndarray,
        *,
        target_recall: float = 0.95,
        top_k: int = 10,
        rerank_depth: int | None = None,
        candidates: list[int] | None = None,
        max_queries: int = 128,
    ) -> dict:
        """Pick the smallest ``nprobe`` whose measured recall@top_k on the
        given held-out queries meets ``target_recall`` (the faiss-autotune
        role; the reference picks nprobe by hand in its e2e tests,
        python/tests/vector/test_e2e_glove.py:182).

        Ground truth is exact brute force over the raw vectors, so the
        index must have been built with ``keep_raw=True``.  Returns
        ``{"nprobe", "recall", "target_met", "measured": [(nprobe,
        recall), ...]}`` — ``measured`` records every probed point UP TO
        the chosen one (the sweep stops at the first qualifying nprobe;
        pass explicit ``candidates`` to force a full curve)."""
        from lakesoul_tpu.errors import ConfigError

        raws, id_chunks = [], []
        for c in range(len(self.clusters)):
            for seg in self._cluster_segments(c):
                if seg.raw is None:
                    raise ConfigError(
                        "tune_nprobe needs raw vectors (build with keep_raw=True)"
                    )
                if len(seg.ids):
                    raws.append(seg.raw)
                    id_chunks.append(seg.ids)
        if not raws:
            raise ConfigError("tune_nprobe on an empty index")
        base = np.concatenate(raws)
        base_ids = np.concatenate(id_chunks)
        from lakesoul_tpu.vector.oracle import exact_topk, recall_at_k, subsample_queries

        # exact ground truth: top_k by L2 (matches the search metric) via the
        # shared recall oracle — ONE batched gram matmul for all queries
        queries = subsample_queries(queries, max_queries, self.config.seed)
        truth = exact_topk(base, base_ids, queries, top_k)
        nlist = len(self.clusters)
        if candidates is None:
            candidates, p = [], 1
            while p < nlist:
                candidates.append(p)
                p *= 2
            candidates.append(nlist)
        measured = []
        best = None
        for nprobe in sorted(set(candidates)):
            params = SearchParams(
                top_k=top_k, nprobe=nprobe, rerank_depth=rerank_depth
            )
            got_ids, _ = self.batch_search(queries, params)
            # denominator = achievable hits (a small index or duplicate ids
            # can make the truth sets smaller than top_k; perfect search
            # must be able to reach recall 1.0)
            recall = recall_at_k(truth, got_ids)
            measured.append((nprobe, recall))
            if best is None and recall >= target_recall:
                best = (nprobe, recall)
                break  # smallest qualifying nprobe: stop sweeping
        if best is None:
            best = measured[-1]
        return {
            "nprobe": best[0],
            "recall": best[1],
            "target_met": best[1] >= target_recall,
            "measured": measured,
        }

    def batch_search(self, queries: np.ndarray, params: SearchParams = SearchParams()):
        """Search many queries; with the device cache enabled, all queries run
        in ONE device call (amortizing dispatch/readback latency)."""
        queries = np.asarray(queries, np.float32)
        if getattr(self, "_device_cache_enabled", False):
            out = self._batch_search_device_resident(queries, params)
            if out is not None:
                return out
        results = [self.search(q, params) for q in queries]
        return [o[0] for o in results], [o[1] for o in results]

    def _batch_search_device_resident(self, queries: np.ndarray, params: SearchParams):
        nq = len(queries)
        # chunk oversized batches: the kernel holds the (Q, 8*d8) query block
        # and (tile, Q) output tile in VMEM, so Q is capped per call
        MAX_Q = 256
        if nq > MAX_Q:
            bundle = self._get_device_bundle()
            if bundle is None or (self._ex_bits and bundle["scales"] is None):
                return None  # same guards as _dispatch_resident, pre-chunking
            ids_all, d_all = [], []
            for start in range(0, nq, MAX_Q):
                ids_c, d_c = self._batch_search_device_resident(
                    queries[start : start + MAX_Q], params
                )
                ids_all.extend(ids_c)
                d_all.extend(d_c)
            return ids_all, d_all
        disp = self._dispatch_resident(queries, params)
        if disp is None:
            return None
        return self._resolve_resident(*disp, params)

    def search_async(self, query: np.ndarray, params: SearchParams = SearchParams()):
        """Dispatch ONE query on the device-resident bundle WITHOUT waiting
        and return a zero-arg resolver yielding (ids, dists).

        JAX dispatch is asynchronous, so a serving loop overlaps the chip
        round-trip by dispatching query i+1 before resolving query i — the
        per-call link latency then bounds *latency*, not throughput.  Falls
        back to the synchronous path (resolver returns a precomputed result)
        when no resident bundle applies."""
        query = np.asarray(query, dtype=np.float32)
        disp = None
        if getattr(self, "_device_cache_enabled", False):
            disp = self._dispatch_resident(query[None, :], params)
        if disp is None:
            out = self.search(query, params)
            return lambda: out
        dists, idx, nq, bundle = disp

        def resolve():
            ids_b, d_b = self._resolve_resident(dists, idx, nq, bundle, params)
            return ids_b[0], d_b[0]

        return resolve

    def _dispatch_resident(self, queries: np.ndarray, params: SearchParams):
        """Device dispatch of a ≤MAX_Q query block against the resident
        bundle; returns (device dists, device idx, nq, bundle) or None when
        the resident path doesn't apply.  Does NOT block on the result."""
        import jax.numpy as jnp

        from lakesoul_tpu.utils import platform
        from lakesoul_tpu.vector.kernels import _fused_search_resident_batch

        bundle = self._get_device_bundle()
        if bundle is None:
            return None
        if self._ex_bits and bundle["scales"] is None:
            return None  # legacy segments without scales: non-resident path
        nq = len(queries)
        # bucket Q to a pow2 so variable batch sizes reuse compiled shapes
        nq_pad = 8
        while nq_pad < nq:
            nq_pad *= 2
        if nq_pad != nq:
            queries = np.pad(queries, ((0, nq_pad - nq), (0, 0)))
        nprobe = min(params.nprobe, len(self.centroids))
        cd = (
            np.sum(queries[:nq] ** 2, axis=1, keepdims=True)
            - 2.0 * queries[:nq] @ self.centroids.T
            + np.sum(self.centroids**2, axis=1)[None, :]
        )  # [Q, nlist]
        probe = np.argsort(cd, axis=1)[:, :nprobe]
        probe_mask = np.zeros((len(self.centroids), nq_pad), dtype=bool)
        for qi in range(nq):  # pad queries stay fully masked → inf distances
            probe_mask[probe[qi], qi] = True
        q_glob = self.quantizer.rotate(queries)  # [Q, d]
        # closed forms — no [nlist, Q, d] intermediate:
        #   ||c - q||² = ||c||² - 2 c·q + ||q||² ;  Σ(c - q) = Σc - Σq
        cent = self._rotated_centroids()
        csq_c = (
            np.sum(cent * cent, axis=1)[:, None]
            - 2.0 * (cent @ q_glob.T)
            + np.sum(q_glob * q_glob, axis=1)[None, :]
        ).astype(np.float32)
        csum_c = (
            np.sum(cent, axis=1)[:, None] - np.sum(q_glob, axis=1)[None, :]
        ).astype(np.float32)
        do_rerank = bundle["raw"] is not None
        n_pad = int(bundle["codes"].shape[0])
        s = min(params.shortlist(), n_pad)
        k = min(params.top_k, n_pad)
        if self._ex_bits:
            from lakesoul_tpu.vector.kernels import _fused_search_resident_ex_batch

            dists, idx = _fused_search_resident_ex_batch(
                bundle["codes"], bundle["scales"], bundle["norms"], bundle["factors"],
                bundle["cdc"], bundle["cluster_id"], jnp.asarray(probe_mask),
                jnp.asarray(csq_c), jnp.asarray(q_glob),
                bundle["raw"] if do_rerank else jnp.zeros((1, 1), jnp.float32),
                jnp.asarray(queries),
                s=s, k=k, do_rerank=do_rerank,
            )
        else:
            dists, idx = _fused_search_resident_batch(
                bundle["codes"], bundle["norms"], bundle["factors"], bundle["cdc"],
                bundle["cluster_id"], jnp.asarray(probe_mask),
                jnp.asarray(csq_c), jnp.asarray(csum_c), jnp.asarray(q_glob),
                bundle["raw"] if do_rerank else jnp.zeros((1, 1), jnp.float32),
                jnp.asarray(queries),
                d=self.quantizer.padded_dim, s=s, k=k,
                use_pallas=platform.on_tpu(), do_rerank=do_rerank,
            )
        return dists, idx, nq, bundle

    @staticmethod
    def _resolve_resident(dists, idx, nq, bundle, params):
        """Host-side tail of a resident search: blocks on the device values
        (np.asarray) and maps kernel row indices back to caller ids."""
        dists, idx = np.asarray(dists), np.asarray(idx)
        ids_out, d_out = [], []
        for qi in range(nq):
            valid = (idx[qi] < bundle["n"]) & np.isfinite(dists[qi])
            sel = idx[qi][valid][: params.top_k]
            ids_out.append(bundle["ids"][sel])
            d_out.append(dists[qi][valid][: params.top_k])
        return ids_out, d_out
