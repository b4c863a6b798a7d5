"""Multi-shard ANN search: global probe selection, ragged dispatch,
cross-shard candidate union + exact re-rank.

Opening a plane loads every shard's IVF-RaBitQ index into a RESIDENT layout
(cluster-sorted rows, tile-aligned so the same arrays feed both the host
grouped-GEMM path and the Pallas item kernel).  A search micro-batch:

1. **probe selection** — one gram matmul of the batch against ALL shards'
   centroids; each query takes its ``nprobe`` nearest clusters *globally*
   (a hot query may spend its whole probe budget in one shard, a cold one
   fans out — per-query, not per-shard).  Rotation is orthonormal, so the
   same distance matrix doubles as the estimator's per-(query, cluster)
   ``csq`` — probe selection is free for the estimator.
2. **ragged scoring** — per shard, the (query, cluster) pairs that landed
   there become one ragged dispatch (annplane/ragged.py); every shard
   returns per-query estimator top-``shortlist`` candidates.
3. **exact re-rank + union** — candidates re-rank against raw vectors
   per shard (one batched einsum), then the per-query union across shards
   cuts to top-k by exact distance.  With ``keep_raw=False`` planes the
   union merges estimator distances instead.
"""

from __future__ import annotations

import time

import numpy as np

from lakesoul_tpu.annplane.config import AnnPlaneConfig
from lakesoul_tpu.annplane.manifest import PlaneManifestStore
from lakesoul_tpu.annplane.ragged import (
    TILE,
    PAD_B,
    fold_cluster,
    items_topk,
    plan_items,
    ragged_score_jnp,
    ragged_score_pallas,
    ragged_topk_host,
)
from lakesoul_tpu.errors import VectorIndexError
from lakesoul_tpu.obs import registry
from lakesoul_tpu.vector.config import VectorIndexConfig
from lakesoul_tpu.vector.index import SearchParams
from lakesoul_tpu.vector.kernels import PAD_RAW
from lakesoul_tpu.vector.manifest import ManifestStore
from lakesoul_tpu.vector.rabitq import RabitqQuantizer


class _ShardResident:
    """One shard's arrays in the ragged-search layout.

    Rows are cluster-sorted and padded per cluster to a TILE multiple; the
    pad rows carry ``b = PAD_B`` so any executor that touches them scores
    them out.  ``row_start/row_count`` index the REAL rows (host path),
    ``tile_start/tile_count`` the padded tiles (Pallas path) — same arrays,
    same row coordinates."""

    def __init__(self, index, *, tile: int = TILE):
        if index.centroids is None:
            raise VectorIndexError("shard index is not trained")
        cfg = index.config
        ex = cfg.total_bits > 1
        dpad = index.quantizer.padded_dim
        nlist = len(index.centroids)
        self.centroids = np.asarray(index.centroids, np.float32)
        self.tile = tile

        segs_per_cluster = [
            [s for s in index._cluster_segments(c) if len(s.ids)]
            for c in range(nlist)
        ]
        counts = np.array(
            [sum(len(s.ids) for s in segs) for segs in segs_per_cluster], np.int64
        )
        padded = (counts + tile - 1) // tile * tile
        n_pad = int(padded.sum()) or tile
        self.tile_start = np.concatenate(
            [[0], np.cumsum(padded[:-1] // tile)]
        ).astype(np.int32)
        self.tile_count = (padded // tile).astype(np.int32)
        self.row_start = (self.tile_start.astype(np.int64) * tile)
        self.row_count = counts

        self.codes = np.zeros((n_pad, dpad), np.float32)
        self.a = np.zeros(n_pad, np.float32)
        self.b = np.full(n_pad, PAD_B, np.float32)
        self.h = np.zeros(n_pad, np.float32)
        self.ids = np.zeros(n_pad, np.uint64)
        self.raw = (
            np.full((n_pad, cfg.dim), PAD_RAW, np.float32)
            if index.keep_raw else None
        )
        self.num_vectors = int(counts.sum())
        for c, segs in enumerate(segs_per_cluster):
            pos = int(self.row_start[c])
            for seg in segs:
                n = len(seg.ids)
                if ex:
                    if seg.scales is None:
                        raise VectorIndexError(
                            "ex-bits shard segment has no scales — rebuild"
                        )
                    self.codes[pos : pos + n] = (
                        seg.codes.astype(np.float32) * seg.scales[:, None]
                    )
                else:
                    bits = np.unpackbits(seg.codes, axis=1)[:, :dpad]
                    self.codes[pos : pos + n] = bits.astype(np.float32)
                a, b, h = fold_cluster(
                    seg.norms, seg.factors, np.asarray(seg.code_dot_c),
                    d=dpad, ex=ex,
                )
                self.a[pos : pos + n] = a
                self.b[pos : pos + n] = b
                self.h[pos : pos + n] = h
                self.ids[pos : pos + n] = seg.ids
                if self.raw is not None and seg.raw is not None:
                    self.raw[pos : pos + n] = seg.raw
                pos += n


class AnnPlane:
    """A loaded multi-shard plane, ready to serve ragged micro-batches."""

    def __init__(
        self,
        config: AnnPlaneConfig,
        shards: list[_ShardResident],
        *,
        manifest: dict | None = None,
        use_pallas: bool | None = None,
        pallas_interpret: bool = False,
    ):
        from lakesoul_tpu.utils import platform

        if not shards:
            raise VectorIndexError("ANN plane has no shards")
        self.plane_config = config
        self.config: VectorIndexConfig = config.index
        self.shards = shards
        self.manifest = manifest or {}
        self.use_pallas = platform.on_tpu() if use_pallas is None else use_pallas
        self.pallas_interpret = pallas_interpret
        # host path: score independent shards concurrently on the runtime
        # pool (numpy/BLAS release the GIL on the heavy ops); flip off for
        # single-core boxes or when the caller already parallelizes batches
        self.parallel_shards = True
        self.quantizer = RabitqQuantizer(
            self.config.dim, rotator=self.config.rotator, seed=self.config.seed
        )
        # plane-global cluster table: concatenated centroids with a
        # (shard, local cluster) map for every global cluster id
        self.centroids = np.concatenate([s.centroids for s in shards])
        self.shard_of = np.concatenate(
            [np.full(len(s.centroids), i, np.int32) for i, s in enumerate(shards)]
        )
        local = np.concatenate(
            [np.arange(len(s.centroids), dtype=np.int32) for s in shards]
        )
        self.local_cluster = local
        self._cent_sq = np.sum(self.centroids**2, axis=1)
        cent_rot = self.quantizer.rotate(self.centroids)
        self._cent_rot_sum = np.sum(cent_rot, axis=1).astype(np.float32)
        reg = registry()
        self._c_queries = reg.counter("lakesoul_ann_ragged_queries_total")
        self._c_pairs = reg.counter("lakesoul_ann_ragged_pairs_total")
        self._h_dispatch = reg.histogram("lakesoul_ann_ragged_dispatch_seconds")

    # ------------------------------------------------------------------- load
    @classmethod
    def open(
        cls,
        root: str,
        storage_options: dict | None = None,
        *,
        use_pallas: bool | None = None,
        pallas_interpret: bool = False,
        tile: int = TILE,
    ) -> "AnnPlane":
        store = PlaneManifestStore(root, storage_options)
        manifest = store.read()
        if manifest is None:
            raise VectorIndexError(f"no ANN plane at {root}")
        if not manifest.get("complete"):
            raise VectorIndexError(
                f"ANN plane at {root} is mid-build"
                f" ({len(manifest.get('shards', ()))} shard(s) durable);"
                " resume the builder first"
            )
        index_cfg = VectorIndexConfig.parse(manifest["index_config"])
        config = AnnPlaneConfig(
            index=index_cfg,
            shard_budget_bytes=manifest["shard_budget_bytes"],
            keep_raw=manifest["keep_raw"],
        )
        from lakesoul_tpu.annplane.build import shard_root

        shards = []
        for entry in manifest["shards"]:
            sstore = ManifestStore(
                shard_root(root, entry["shard"]), storage_options
            )
            # load the generation the plane record PINNED, not LATEST: a
            # concurrent rebuild bumps shard stores one by one, and reading
            # their moving pointers would mix generations into one plane
            shards.append(
                _ShardResident(sstore.read_at(entry["generation"]), tile=tile)
            )
        return cls(
            config, shards, manifest=manifest,
            use_pallas=use_pallas, pallas_interpret=pallas_interpret,
        )

    @property
    def dim(self) -> int:
        return self.config.dim

    @property
    def num_vectors(self) -> int:
        return sum(s.num_vectors for s in self.shards)

    # ----------------------------------------------------------------- search
    def search(self, query: np.ndarray, params: SearchParams = SearchParams()):
        ids, dists = self.batch_search(np.asarray(query, np.float32)[None, :], params)
        return ids[0], dists[0]

    def batch_search(
        self,
        queries: np.ndarray,
        params: SearchParams = SearchParams(),
        *,
        nprobes: np.ndarray | None = None,
    ):
        """→ (ids per query, dists per query).  ``nprobes`` overrides
        ``params.nprobe`` per query — the ragged dispatch fuses the mixed
        probe depths into one scoring pass per shard."""
        start = time.perf_counter()
        queries = np.atleast_2d(np.asarray(queries, np.float32))
        nq = len(queries)
        n_clusters = len(self.centroids)
        if nprobes is None:
            nprobes = np.full(nq, params.nprobe, np.int64)
        else:
            nprobes = np.asarray(nprobes, np.int64)
            if len(nprobes) != nq:
                raise VectorIndexError("nprobes length must match queries")
        nprobes = np.clip(nprobes, 1, n_clusters)
        s = params.shortlist()

        # global probe selection; cd doubles as the estimator csq (rotation
        # preserves distances)
        cd = (
            np.sum(queries**2, axis=1, keepdims=True)
            - 2.0 * queries @ self.centroids.T
            + self._cent_sq[None, :]
        ).astype(np.float32)
        max_np = int(nprobes.max())
        if max_np < n_clusters:
            sel = np.argpartition(cd, max_np - 1, axis=1)[:, :max_np]
        else:
            sel = np.broadcast_to(np.arange(n_clusters), (nq, n_clusters)).copy()
        sel_d = np.take_along_axis(cd, sel, axis=1)
        order = np.argsort(sel_d, axis=1)
        sel = np.take_along_axis(sel, order, axis=1)
        sel_d = np.take_along_axis(sel_d, order, axis=1)

        keep = np.arange(sel.shape[1])[None, :] < nprobes[:, None]
        pairs_q = np.repeat(np.arange(nq, dtype=np.int64), keep.sum(axis=1))
        pairs_gc = sel[keep]          # query-major by construction
        pairs_csq = sel_d[keep]
        self._c_queries.inc(nq)
        self._c_pairs.inc(len(pairs_gc))

        q_glob = self.quantizer.rotate(queries)
        ex = self.config.total_bits > 1
        if ex:
            pairs_csum = np.zeros(len(pairs_gc), np.float32)
        else:
            pairs_csum = (
                self._cent_rot_sum[pairs_gc]
                - np.sum(q_glob, axis=1).astype(np.float32)[pairs_q]
            )

        cand_ids: list[list[np.ndarray]] = [[] for _ in range(nq)]
        cand_d: list[list[np.ndarray]] = [[] for _ in range(nq)]
        shard_sel = self.shard_of[pairs_gc]
        jobs = []
        for si, shard in enumerate(self.shards):
            m = shard_sel == si
            if not m.any():
                continue
            sub_q = pairs_q[m]
            uq, inv = np.unique(sub_q, return_inverse=True)
            jobs.append((
                uq,
                (shard, queries[uq], q_glob[uq], inv,
                 self.local_cluster[pairs_gc[m]],
                 pairs_csq[m], pairs_csum[m], len(uq), s),
            ))
        # shards are independent read-only scans: fan them out on the shared
        # runtime pool (BLAS/numpy release the GIL, so a 9-shard plane uses
        # 9 cores per dispatch instead of serializing on the worker thread)
        from lakesoul_tpu.runtime.pool import get_pool

        pool = get_pool()
        if len(jobs) > 1 and self.parallel_shards and not pool.in_worker():
            futs = [
                (uq, pool.submit(self._shard_pass, *args)) for uq, args in jobs
            ]
            results = [(uq, f.result()) for uq, f in futs]
        else:
            results = [(uq, self._shard_pass(*args)) for uq, args in jobs]
        for uq, (ids_s, d_s) in results:
            for li, gq in enumerate(uq):
                cand_ids[gq].append(ids_s[li])
                cand_d[gq].append(d_s[li])

        out_ids, out_d = [], []
        for q in range(nq):
            if not cand_ids[q]:
                out_ids.append(np.zeros(0, np.uint64))
                out_d.append(np.zeros(0, np.float32))
                continue
            ids = np.concatenate(cand_ids[q])
            d = np.concatenate(cand_d[q])
            valid = np.isfinite(d)
            ids, d = ids[valid], d[valid]
            top = np.argsort(d, kind="stable")[: params.top_k]
            out_ids.append(ids[top])
            out_d.append(d[top])
        self._h_dispatch.observe(time.perf_counter() - start)
        return out_ids, out_d

    # ------------------------------------------------------------- internals
    def _shard_pass(self, shard, queries_sub, q_glob_sub, pairs_lq, pairs_lc,
                    csq, csum, nq_sub: int, s: int):
        """One shard's complete contribution: ragged score → shortlist →
        exact re-rank.  Pure function of read-only shard arrays — safe to
        run on any pool worker."""
        rows, est = self._score_shard(
            shard, q_glob_sub, pairs_lq, pairs_lc, csq, csum, nq_sub, s
        )
        return self._rerank_shard(shard, queries_sub, rows, est)

    def _score_shard(self, shard, q_glob_sub, pairs_lq, pairs_lc, csq, csum,
                     nq_sub: int, s: int):
        if self.use_pallas:
            item_q, item_tile, icsq, icsum = plan_items(
                pairs_lq, pairs_lc, csq, csum,
                shard.tile_start, shard.tile_count,
            )
            est = ragged_score_pallas(
                item_q, item_tile, icsq, icsum, q_glob_sub,
                shard.codes, shard.a, shard.b, shard.h,
                tile=shard.tile, interpret=self.pallas_interpret,
            )
            return items_topk(est, item_q, item_tile, nq_sub, s, tile=shard.tile)
        return ragged_topk_host(
            shard.codes, shard.a, shard.b, shard.h,
            shard.row_start, shard.row_count,
            pairs_lq, pairs_lc, csq, csum, q_glob_sub, nq_sub, s,
        )

    def _rerank_shard(self, shard, queries_sub, rows, est):
        """Exact re-rank of one shard's candidate rows (raw kept), else the
        estimator distances pass through; -1 rows stay +inf holes."""
        safe = np.clip(rows, 0, None)
        ids = shard.ids[safe]
        if shard.raw is None:
            d = est.copy()
            d[rows < 0] = np.inf
            return ids, d
        from lakesoul_tpu import native

        if native.available():
            exact = native.ann_exact_rerank(
                shard.raw, np.ascontiguousarray(rows, np.int64),
                np.ascontiguousarray(queries_sub, np.float32),
            )
            return ids, exact
        sub = shard.raw[safe]                       # [nq, s, dim]
        exact = (
            np.sum(sub * sub, axis=2)
            - 2.0 * np.einsum("qsd,qd->qs", sub, queries_sub)
            + np.sum(queries_sub * queries_sub, axis=1)[:, None]
        ).astype(np.float32)
        exact[rows < 0] = np.inf
        return ids, exact


def jnp_score_shard(plane: AnnPlane, shard: _ShardResident, q_glob_sub,
                    pairs_lq, pairs_lc, csq, csum, nq_sub: int, s: int):
    """jnp item-kernel twin of a shard scoring pass — the differential-test
    hook that pins host GEMMs == item kernel == Pallas(interpret)."""
    item_q, item_tile, icsq, icsum = plan_items(
        pairs_lq, pairs_lc, csq, csum, shard.tile_start, shard.tile_count
    )
    est = ragged_score_jnp(
        item_q, item_tile, icsq, icsum, q_glob_sub,
        shard.codes, shard.a, shard.b, shard.h, tile=shard.tile,
    )
    return items_topk(est, item_q, item_tile, nq_sub, s, tile=shard.tile)
