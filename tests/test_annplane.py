"""Sharded ANN plane: memory-bounded multi-shard build + shard-exact resume,
plane-manifest atomicity, ragged scoring (Pallas-interpret vs jnp item
kernel vs host grouped GEMMs), multi-shard vs single-shard parity against
the shared exact oracle, per-query nprobe fusion, fleet serving with typed
overload sheds at 64 concurrent clients, the Flight ``ann_search`` action
(JWT auth, per-table RBAC, UNAVAILABLE on shed), and the cross-chip top-k
merge dryrun on the virtual 8-device mesh."""

import threading

import numpy as np
import pytest

from lakesoul_tpu.annplane import (
    AnnPlane,
    AnnPlaneBinding,
    AnnPlaneConfig,
    PlaneManifestStore,
    ShardedAnnBuilder,
    ShardedAnnEndpoint,
    build_table_ann_plane,
    cross_chip_topk,
    dryrun_multichip,
)
from lakesoul_tpu.annplane import ragged
from lakesoul_tpu.errors import OverloadedError, VectorIndexError
from lakesoul_tpu.vector.config import VectorIndexConfig
from lakesoul_tpu.vector.index import IvfRabitqIndex, SearchParams
from lakesoul_tpu.vector.oracle import exact_topk, recall_at_k


def make_corpus(n=24_000, d=32, modes=64, seed=0, spread=3.0):
    rng = np.random.default_rng(seed)
    centers = rng.normal(size=(modes, d)).astype(np.float32) * spread
    vecs = (
        centers[rng.integers(0, modes, n)]
        + rng.normal(size=(n, d)).astype(np.float32)
    )
    queries = (
        centers[rng.integers(0, modes, 48)]
        + rng.normal(size=(48, d)).astype(np.float32)
    )
    return vecs, np.arange(n, dtype=np.uint64), queries


def plane_config(d=32, *, rows_per_shard=8_000, nlist=16, total_bits=4,
                 keep_raw=True, **kw):
    index = VectorIndexConfig(column="e", dim=d, nlist=nlist,
                              total_bits=total_bits, **kw)
    probe = AnnPlaneConfig(index=index, shard_budget_bytes=1 << 30,
                           keep_raw=keep_raw)
    return AnnPlaneConfig(
        index=index,
        shard_budget_bytes=rows_per_shard * probe.bytes_per_vector(),
        keep_raw=keep_raw,
    )


def stream(vecs, ids, batch=6_000):
    for lo in range(0, len(ids), batch):
        yield vecs[lo : lo + batch], ids[lo : lo + batch]


@pytest.fixture(scope="module")
def built_plane(tmp_path_factory):
    """One 3-shard plane shared by the search/serving tests (module-scoped:
    the build is the expensive part)."""
    vecs, ids, queries = make_corpus()
    cfg = plane_config()
    root = str(tmp_path_factory.mktemp("plane") / "p")
    manifest = ShardedAnnBuilder(root, cfg).build(stream(vecs, ids))
    plane = AnnPlane.open(root, use_pallas=False)
    return root, cfg, plane, manifest, vecs, ids, queries


class TestConfig:
    def test_rows_per_shard_from_budget(self):
        cfg = plane_config(rows_per_shard=5_000)
        assert cfg.rows_per_shard() == 5_000

    def test_digest_covers_layout(self):
        a = plane_config(rows_per_shard=5_000)
        b = plane_config(rows_per_shard=6_000)
        c = plane_config(rows_per_shard=5_000, nlist=32)
        assert a.digest() != b.digest()
        assert a.digest() != c.digest()
        assert a.digest() == plane_config(rows_per_shard=5_000).digest()

    def test_env_budget(self, monkeypatch):
        monkeypatch.setenv("LAKESOUL_ANN_SHARD_BUDGET_BYTES", "12345678")
        cfg = AnnPlaneConfig(index=VectorIndexConfig(column="e", dim=16))
        assert cfg.budget_bytes == 12345678
        monkeypatch.setenv("LAKESOUL_ANN_SHARD_BUDGET_BYTES", "bogus")
        with pytest.raises(VectorIndexError, match="BUDGET"):
            AnnPlaneConfig(index=VectorIndexConfig(column="e", dim=16))

    def test_budget_too_small_raises(self):
        with pytest.raises(VectorIndexError, match="cannot hold"):
            AnnPlaneConfig(
                index=VectorIndexConfig(column="e", dim=128),
                shard_budget_bytes=64,
            )


class TestBuilderAndResume:
    def test_multi_shard_build_rows_exact(self, tmp_path):
        vecs, ids, _ = make_corpus(n=20_000)
        cfg = plane_config()
        m = ShardedAnnBuilder(str(tmp_path / "p"), cfg).build(stream(vecs, ids))
        assert m["complete"] and m["total_rows"] == 20_000
        assert [s["row_start"] for s in m["shards"]] == [0, 8_000, 16_000]
        assert [s["row_end"] for s in m["shards"]] == [8_000, 16_000, 20_000]
        assert sum(s["num_vectors"] for s in m["shards"]) == 20_000

    def test_interrupted_build_resumes_shard_exact(self, tmp_path):
        vecs, ids, _ = make_corpus(n=20_000)
        cfg = plane_config()
        root = str(tmp_path / "p")
        builder = ShardedAnnBuilder(root, cfg)

        class Boom(Exception):
            pass

        def broken():
            yield vecs[:8_000], ids[:8_000]
            yield vecs[8_000:12_000], ids[8_000:12_000]
            raise Boom()

        with pytest.raises(Boom):
            builder.build(broken())
        partial = PlaneManifestStore(root).read()
        # only COMPLETE shards are durable; the half-buffered second shard
        # never became visible
        assert not partial["complete"]
        assert len(partial["shards"]) == 1
        assert partial["shards"][0]["row_end"] == 8_000

        m = builder.build(stream(vecs, ids))
        assert m["complete"] and len(m["shards"]) == 3
        # shard 0 was NOT rebuilt: same per-shard manifest generation
        assert m["shards"][0]["generation"] == partial["shards"][0]["generation"]

        fresh_root = str(tmp_path / "fresh")
        fresh = ShardedAnnBuilder(fresh_root, cfg).build(stream(vecs, ids))
        assert [
            (s["row_start"], s["row_end"], s["num_vectors"]) for s in m["shards"]
        ] == [
            (s["row_start"], s["row_end"], s["num_vectors"])
            for s in fresh["shards"]
        ]
        # and the resumed plane answers exactly like the from-scratch one
        a = AnnPlane.open(root, use_pallas=False)
        b = AnnPlane.open(fresh_root, use_pallas=False)
        params = SearchParams(top_k=10, nprobe=8)
        ia, da = a.search(vecs[123], params)
        ib, db = b.search(vecs[123], params)
        np.testing.assert_array_equal(ia, ib)
        np.testing.assert_allclose(da, db, rtol=1e-5, atol=1e-5)

    def test_config_change_forces_fresh_generation(self, tmp_path):
        vecs, ids, _ = make_corpus(n=12_000)
        root = str(tmp_path / "p")
        m1 = ShardedAnnBuilder(root, plane_config()).build(stream(vecs, ids))
        cfg2 = plane_config(rows_per_shard=5_000)
        m2 = ShardedAnnBuilder(root, cfg2).build(stream(vecs, ids))
        assert m2["generation"] == m1["generation"] + 1
        assert len(m2["shards"]) == 3  # 5k + 5k + 2k under the new layout
        plane = AnnPlane.open(root, use_pallas=False)
        assert plane.num_vectors == 12_000

    def test_completed_build_is_idempotent(self, tmp_path):
        vecs, ids, _ = make_corpus(n=9_000)
        cfg = plane_config()
        builder = ShardedAnnBuilder(str(tmp_path / "p"), cfg)
        m1 = builder.build(stream(vecs, ids))
        m2 = builder.build(stream(vecs, ids))
        assert m2 == m1  # durable plane: second build is a no-op read

    def test_empty_stream_raises(self, tmp_path):
        with pytest.raises(VectorIndexError, match="no vectors"):
            ShardedAnnBuilder(str(tmp_path / "p"), plane_config()).build(iter(()))

    def test_dim_mismatch_raises(self, tmp_path):
        vecs = np.zeros((10, 8), np.float32)
        with pytest.raises(VectorIndexError, match="expected"):
            ShardedAnnBuilder(str(tmp_path / "p"), plane_config(d=16)).build(
                [(vecs, np.arange(10, dtype=np.uint64))]
            )

    def test_build_from_table_via_bounded_scan(self, tmp_warehouse):
        import pyarrow as pa

        from lakesoul_tpu import LakeSoulCatalog

        catalog = LakeSoulCatalog(str(tmp_warehouse))
        d, n = 16, 6_000
        rng = np.random.default_rng(3)
        vals = rng.normal(size=(n, d)).astype(np.float32)
        schema = pa.schema(
            [("id", pa.int64()), ("emb", pa.list_(pa.float32(), d))]
        )
        t = catalog.create_table(
            "corpus", schema, properties={"lakesoul.file_format": "lsf"}
        )
        arr = pa.FixedSizeListArray.from_arrays(pa.array(vals.reshape(-1)), d)
        t.write_arrow(pa.table({"id": np.arange(n), "emb": arr}, schema=schema))
        manifest = build_table_ann_plane(
            t, "emb", id_column="id", nlist=8, total_bits=4,
            shard_budget_bytes=plane_config(d=d, rows_per_shard=2_500)
            .budget_bytes,
        )
        assert manifest["complete"] and manifest["total_rows"] == n
        assert len(manifest["shards"]) >= 2
        plane = AnnPlane.open(
            f"{t.info.table_path}/_ann_plane/emb", use_pallas=False
        )
        ids, _ = plane.search(vals[42], SearchParams(top_k=1, nprobe=8))
        assert int(ids[0]) == 42


class TestManifestAtomicity:
    def test_missing_reads_none(self, tmp_path):
        assert PlaneManifestStore(str(tmp_path / "nope")).read() is None

    def test_corrupt_record_raises_not_restarts(self, tmp_path):
        vecs, ids, _ = make_corpus(n=9_000)
        root = str(tmp_path / "p")
        ShardedAnnBuilder(root, plane_config()).build(stream(vecs, ids))
        store = PlaneManifestStore(root)
        # flip one byte of the pointed record
        from lakesoul_tpu.vector.manifest import _crc_unwrap

        with store.fs.open(f"{store.root_path}/PLANE", "rb") as f:
            rel = _crc_unwrap(f.read(), "PLANE").decode()
        path = f"{store.root_path}/{rel}"
        with store.fs.open(path, "rb") as f:
            blob = bytearray(f.read())
        blob[10] ^= 0xFF
        with store.fs.open(path, "wb") as f:
            f.write(bytes(blob))
        with pytest.raises(VectorIndexError, match="CRC"):
            store.read()

    def test_open_pins_shard_generations(self, tmp_path):
        """A concurrent rebuild swaps per-shard LATEST pointers one by one;
        a reader must load the generations its plane record PINNED, never a
        mixed plane."""
        vecs, ids, _ = make_corpus(n=9_000)
        root = str(tmp_path / "p")
        cfg = plane_config()
        ShardedAnnBuilder(root, cfg).build(stream(vecs, ids))
        from lakesoul_tpu.annplane.build import shard_root
        from lakesoul_tpu.vector.manifest import ManifestStore

        # simulate the racing rebuild: shard 0's LATEST now names a tiny
        # replacement index (generation bumped), plane record unchanged
        other = IvfRabitqIndex.train(vecs[:100], ids[:100], cfg.index)
        ManifestStore(shard_root(root, 0)).write_index(other)
        plane = AnnPlane.open(root, use_pallas=False)
        assert plane.num_vectors == 9_000  # NOT 100 + shard-1 rows

    def test_open_refuses_mid_build_plane(self, tmp_path):
        vecs, ids, _ = make_corpus(n=20_000)
        root = str(tmp_path / "p")

        class Boom(Exception):
            pass

        def broken():
            yield vecs[:9_000], ids[:9_000]
            raise Boom()

        with pytest.raises(Boom):
            ShardedAnnBuilder(root, plane_config()).build(broken())
        with pytest.raises(VectorIndexError, match="mid-build"):
            AnnPlane.open(root)


class TestRaggedKernels:
    def test_ragged_arange(self):
        out = ragged.ragged_arange(np.array([5, 0, 9]), np.array([3, 0, 2]))
        np.testing.assert_array_equal(out, [5, 6, 7, 9, 10])

    def _plan(self, seed=0, n_rows=4_096, d=64, nlist=12, nq=6, tile=128):
        rng = np.random.default_rng(seed)
        counts = rng.multinomial(n_rows, np.ones(nlist) / nlist)
        padded = (counts + tile - 1) // tile * tile
        n_pad = int(padded.sum())
        tile_start = np.concatenate([[0], np.cumsum(padded[:-1] // tile)]).astype(
            np.int32
        )
        tile_count = (padded // tile).astype(np.int32)
        row_start = tile_start.astype(np.int64) * tile
        codes = np.zeros((n_pad, d), np.float32)
        a = np.zeros(n_pad, np.float32)
        b = np.full(n_pad, ragged.PAD_B, np.float32)
        h = np.zeros(n_pad, np.float32)
        for c in range(nlist):
            rs, n_c = int(row_start[c]), int(counts[c])
            codes[rs : rs + n_c] = rng.normal(size=(n_c, d)).astype(np.float32)
            a[rs : rs + n_c] = rng.random(n_c).astype(np.float32) + 0.5
            b[rs : rs + n_c] = rng.random(n_c).astype(np.float32) * 10
            h[rs : rs + n_c] = rng.random(n_c).astype(np.float32)
        # ragged probe sets: query q probes a random subset of clusters
        pairs_q, pairs_c = [], []
        for q in range(nq):
            probed = rng.choice(nlist, rng.integers(1, nlist), replace=False)
            pairs_q.extend([q] * len(probed))
            pairs_c.extend(sorted(probed))
        pairs_q = np.asarray(pairs_q, np.int64)
        pairs_c = np.asarray(pairs_c, np.int64)
        csq = rng.random(len(pairs_q)).astype(np.float32) * 5
        csum = rng.random(len(pairs_q)).astype(np.float32)
        q_glob = rng.normal(size=(nq, d)).astype(np.float32)
        return dict(
            codes=codes, a=a, b=b, h=h, row_start=row_start,
            row_count=counts.astype(np.int64), tile_start=tile_start,
            tile_count=tile_count, pairs_q=pairs_q, pairs_c=pairs_c,
            csq=csq, csum=csum, q_glob=q_glob, nq=nq, tile=tile,
        )

    def test_host_vs_jnp_item_kernel(self):
        p = self._plan()
        rows_h, est_h = ragged.ragged_topk_host(
            p["codes"], p["a"], p["b"], p["h"], p["row_start"], p["row_count"],
            p["pairs_q"], p["pairs_c"], p["csq"], p["csum"], p["q_glob"],
            p["nq"], 16,
        )
        item_q, item_tile, icsq, icsum = ragged.plan_items(
            p["pairs_q"], p["pairs_c"], p["csq"], p["csum"],
            p["tile_start"], p["tile_count"],
        )
        est = ragged.ragged_score_jnp(
            item_q, item_tile, icsq, icsum, p["q_glob"],
            p["codes"], p["a"], p["b"], p["h"], tile=p["tile"],
        )
        rows_j, est_j = ragged.items_topk(
            est, item_q, item_tile, p["nq"], 16, tile=p["tile"]
        )
        for q in range(p["nq"]):
            # same candidate SET and same distances (order can differ on ties)
            np.testing.assert_allclose(
                np.sort(est_h[q]), np.sort(est_j[q]), rtol=1e-5, atol=1e-4
            )
            assert set(rows_h[q][rows_h[q] >= 0]) == set(rows_j[q][rows_j[q] >= 0])

    def test_numpy_fallback_matches_native(self, monkeypatch):
        """ragged_topk_host has two executors — the C kernel and the numpy
        grouped-GEMM fallback (searchsorted row recovery); both must return
        the same candidate sets and distances."""
        from lakesoul_tpu import native

        if not native.available():
            pytest.skip("native library unavailable — nothing to compare")
        p = self._plan(seed=11)
        args = (
            p["codes"], p["a"], p["b"], p["h"], p["row_start"], p["row_count"],
            p["pairs_q"], p["pairs_c"], p["csq"], p["csum"], p["q_glob"],
            p["nq"], 16,
        )
        rows_n, est_n = ragged.ragged_topk_host(*args)
        monkeypatch.setenv("LAKESOUL_TPU_DISABLE_NATIVE", "1")
        rows_f, est_f = ragged.ragged_topk_host(*args)
        for q in range(p["nq"]):
            np.testing.assert_allclose(
                np.sort(est_f[q]), np.sort(est_n[q]), rtol=1e-4, atol=1e-3
            )
            assert set(rows_f[q][rows_f[q] >= 0]) == set(rows_n[q][rows_n[q] >= 0])

    def test_pallas_interpret_vs_jnp(self):
        p = self._plan(seed=7, n_rows=1_024, nlist=6, nq=4)
        item_q, item_tile, icsq, icsum = ragged.plan_items(
            p["pairs_q"], p["pairs_c"], p["csq"], p["csum"],
            p["tile_start"], p["tile_count"],
        )
        ref = ragged.ragged_score_jnp(
            item_q, item_tile, icsq, icsum, p["q_glob"],
            p["codes"], p["a"], p["b"], p["h"], tile=p["tile"],
        )
        got = ragged.ragged_score_pallas(
            item_q, item_tile, icsq, icsum, p["q_glob"],
            p["codes"], p["a"], p["b"], p["h"], tile=p["tile"], interpret=True,
        )
        np.testing.assert_allclose(got, ref, rtol=1e-4, atol=1e-3)

    def test_pallas_item_chunks_match_one_call(self, monkeypatch):
        """The prefetched item tables must fit SMEM, so a micro-batch past
        MAX_ITEMS runs as several calls: same scores, item for item."""
        p = self._plan(seed=11, n_rows=1_024, nlist=6, nq=4)
        items = ragged.plan_items(
            p["pairs_q"], p["pairs_c"], p["csq"], p["csum"],
            p["tile_start"], p["tile_count"],
        )
        args = (*items, p["q_glob"], p["codes"], p["a"], p["b"], p["h"])
        whole = ragged.ragged_score_pallas(*args, tile=p["tile"], interpret=True)
        assert len(whole) > 5
        monkeypatch.setattr(ragged, "MAX_ITEMS", 5)
        chunked = ragged.ragged_score_pallas(*args, tile=p["tile"], interpret=True)
        np.testing.assert_array_equal(chunked, whole)

    def test_fold_cluster_matches_reference_estimator(self):
        """The folded (a, b, h) form reproduces the kernels' estimator: an
        est-only plane search equals IvfRabitqIndex.search(rerank=False)."""
        rng = np.random.default_rng(5)
        n, d = 4_000, 32
        vecs = rng.normal(size=(n, d)).astype(np.float32)
        ids = np.arange(n, dtype=np.uint64)
        for bits in (1, 4):
            cfg = plane_config(
                rows_per_shard=n + 1, nlist=8, total_bits=bits, keep_raw=False
            )
            index = IvfRabitqIndex.train(
                vecs, ids, cfg.index, keep_raw=False
            )
            from lakesoul_tpu.annplane.search import _ShardResident

            plane = AnnPlane(cfg, [_ShardResident(index)], use_pallas=False)
            params = SearchParams(top_k=10, nprobe=8, rerank_depth=10)
            q = vecs[17]
            p_ids, p_d = plane.search(q, params)
            r_ids, r_d = index.search(q, params, rerank=False)
            np.testing.assert_allclose(
                np.sort(p_d), np.sort(r_d), rtol=1e-3, atol=1e-2
            )


class TestMultiShardSearch:
    def test_recall_against_shared_oracle(self, built_plane):
        _, _, plane, _, vecs, ids, queries = built_plane
        params = SearchParams(top_k=10, nprobe=12, rerank_depth=80)
        got, _ = plane.batch_search(queries, params)
        truth = exact_topk(vecs, ids, queries, 10)
        assert recall_at_k(truth, got) >= 0.95

    def test_single_vs_multi_shard_parity(self, built_plane, tmp_path):
        """Same corpus, one shard vs three: full-probe searches return the
        same top-k distances (ids equal up to exact ties)."""
        _, cfg, plane, _, vecs, ids, queries = built_plane
        cfg1 = AnnPlaneConfig(
            index=cfg.index,
            shard_budget_bytes=cfg.bytes_per_vector() * (len(ids) + 1),
        )
        root1 = str(tmp_path / "one")
        ShardedAnnBuilder(root1, cfg1).build(stream(vecs, ids))
        single = AnnPlane.open(root1, use_pallas=False)
        assert len(single.shards) == 1 and len(plane.shards) == 3
        params = SearchParams(top_k=10, nprobe=10**6, rerank_depth=200)
        s_ids, s_d = single.batch_search(queries, params)
        m_ids, m_d = plane.batch_search(queries, params)
        for i in range(len(queries)):
            np.testing.assert_allclose(s_d[i], m_d[i], rtol=1e-4, atol=1e-4)
            tie_free = np.diff(s_d[i]) > 1e-5
            keep = np.concatenate([[True], tie_free]) & np.concatenate(
                [tie_free, [True]]
            )
            np.testing.assert_array_equal(s_ids[i][keep], m_ids[i][keep])

    def test_per_query_nprobe_fuses_exactly(self, built_plane):
        """A mixed-nprobe ragged batch returns exactly what per-query calls
        with the same nprobe return — raggedness changes cost, not answers."""
        _, _, plane, _, _, _, queries = built_plane
        params = SearchParams(top_k=5, nprobe=8)
        nprobes = np.array([1, 4, 16, 2, 8, 32, 3, 48], np.int64)
        sub = queries[: len(nprobes)]
        m_ids, m_d = plane.batch_search(sub, params, nprobes=nprobes)
        for i, npb in enumerate(nprobes):
            one_ids, one_d = plane.batch_search(
                sub[i : i + 1], SearchParams(top_k=5, nprobe=int(npb))
            )
            np.testing.assert_array_equal(m_ids[i], one_ids[0])
            np.testing.assert_allclose(m_d[i], one_d[0], rtol=1e-5, atol=1e-5)

    def test_one_bit_plane(self, tmp_path):
        vecs, ids, queries = make_corpus(n=10_000)
        cfg = plane_config(rows_per_shard=4_000, total_bits=1)
        root = str(tmp_path / "p1")
        ShardedAnnBuilder(root, cfg).build(stream(vecs, ids))
        plane = AnnPlane.open(root, use_pallas=False)
        got, _ = plane.batch_search(
            queries, SearchParams(top_k=10, nprobe=12, rerank_depth=80)
        )
        truth = exact_topk(vecs, ids, queries, 10)
        assert recall_at_k(truth, got) >= 0.9

    def test_keep_raw_false_serves_estimates(self, tmp_path):
        vecs, ids, queries = make_corpus(n=8_000)
        cfg = plane_config(rows_per_shard=3_000, keep_raw=False)
        root = str(tmp_path / "p")
        ShardedAnnBuilder(root, cfg).build(stream(vecs, ids))
        plane = AnnPlane.open(root, use_pallas=False)
        got, dists = plane.batch_search(queries, SearchParams(top_k=10, nprobe=16))
        assert all(len(g) == 10 for g in got)
        truth = exact_topk(vecs, ids, queries, 10)
        assert recall_at_k(truth, got) >= 0.6  # estimator-only floor

    def test_num_vectors_and_manifest(self, built_plane):
        _, _, plane, manifest, vecs, _, _ = built_plane
        assert plane.num_vectors == len(vecs)
        assert plane.manifest["complete"]


class TestServing:
    def test_endpoint_matches_direct(self, built_plane):
        _, _, plane, _, vecs, _, queries = built_plane
        params = SearchParams(top_k=5, nprobe=8)
        with ShardedAnnEndpoint(plane, params, max_wait_ms=1.0) as ep:
            futs = [ep.submit(q) for q in queries[:16]]
            direct_ids, direct_d = plane.batch_search(queries[:16], params)
            for i, f in enumerate(futs):
                ids, dists = f.result(timeout=30)
                np.testing.assert_array_equal(ids, direct_ids[i])
                np.testing.assert_allclose(dists, direct_d[i], rtol=1e-4, atol=1e-4)
            st = ep.stats()
        assert st["requests"] == 16
        assert "latency_p50" in st and "latency_p99" in st
        assert st["latency_p99"] >= st["latency_p50"] >= 0.0

    def test_mixed_nprobe_requests_share_one_batch(self, built_plane):
        _, _, plane, _, _, _, queries = built_plane
        params = SearchParams(top_k=5, nprobe=8)
        with ShardedAnnEndpoint(plane, params, max_wait_ms=20.0) as ep:
            futs = [
                ep.submit(queries[i], nprobe=[1, 8, 32, None][i % 4])
                for i in range(16)
            ]
            outs = [f.result(timeout=30) for f in futs]
            st = ep.stats()
        assert st["mean_batch"] > 1.0  # the window actually fused them
        for i, (ids, _) in enumerate(outs):
            want, _ = plane.batch_search(
                queries[i : i + 1],
                SearchParams(top_k=5, nprobe=[1, 8, 32, 8][i % 4]),
            )
            np.testing.assert_array_equal(ids, want[0])

    def test_overload_64_clients_typed_sheds(self, built_plane):
        """The PR-6 overload contract re-proven at the plane scale: 64
        concurrent clients against a tiny pending bound — every request
        either completes correctly or sheds TYPED; the endpoint survives."""
        _, _, plane, _, _, _, queries = built_plane
        params = SearchParams(top_k=1, nprobe=4)
        ep = ShardedAnnEndpoint(
            plane, params, max_batch=8, max_wait_ms=5.0, max_pending=16
        )
        sheds = [0] * 64
        errors = []

        def client(ci):
            for j in range(8):
                try:
                    ep.search(queries[(ci + j) % len(queries)], timeout=60)
                except OverloadedError:
                    sheds[ci] += 1
                except Exception as e:  # pragma: no cover — surfaced below
                    errors.append(e)

        threads = [
            threading.Thread(target=client, args=(ci,)) for ci in range(64)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        st = ep.stats()
        ep.close()
        assert not errors
        assert sum(sheds) > 0  # the bound actually bit
        assert st["rejected"] == sum(sheds)  # every shed was the typed kind
        assert st["requests"] == 64 * 8 - sum(sheds)

    def test_env_max_pending(self, built_plane, monkeypatch):
        _, _, plane, _, _, _, _ = built_plane
        monkeypatch.setenv("LAKESOUL_ANN_MAX_PENDING", "7")
        ep = ShardedAnnEndpoint(plane, SearchParams(top_k=1))
        try:
            assert ep.max_pending == 7
        finally:
            ep.close()


class TestFlightAnnSearch:
    @pytest.fixture()
    def gateway(self, tmp_warehouse, built_plane):
        import pyarrow as pa

        from lakesoul_tpu import LakeSoulCatalog
        from lakesoul_tpu.service.flight import (
            LakeSoulFlightClient,
            LakeSoulFlightServer,
        )
        from lakesoul_tpu.service.jwt import Claims

        _, _, plane, _, _, _, _ = built_plane
        catalog = LakeSoulCatalog(str(tmp_warehouse))
        schema = pa.schema([("id", pa.int64()), ("v", pa.float64())])
        catalog.create_table("corpus", schema)
        catalog.client.create_table(
            "secret", f"{tmp_warehouse}/secret", schema, domain="team1"
        )
        ep = ShardedAnnEndpoint(
            plane, SearchParams(top_k=5, nprobe=8), max_wait_ms=1.0
        )
        server = LakeSoulFlightServer(
            catalog, "grpc://127.0.0.1:0", jwt_secret="s3cr3t",
            ann_planes={
                "emb": AnnPlaneBinding(ep, "default", "corpus"),
                "locked": AnnPlaneBinding(ep, "default", "secret"),
            },
        )
        token = server.jwt_server.create_token(Claims(sub="alice", group="public"))
        yield server, f"grpc://127.0.0.1:{server.port}", token
        ep.close()
        server.shutdown()

    def test_search_and_rbac(self, gateway, built_plane):
        import json

        import pyarrow.flight as flight

        from lakesoul_tpu.service.flight import LakeSoulFlightClient

        _, _, plane, _, _, _, queries = built_plane
        server, location, token = gateway
        client = LakeSoulFlightClient(location, token=token)
        out = json.loads(
            client.action(
                "ann_search", {"plane": "emb", "query": queries[0].tolist()}
            )[0]
        )
        want, _ = plane.batch_search(
            queries[:1], SearchParams(top_k=5, nprobe=8)
        )
        assert out["ids"] == [int(i) for i in want[0]]
        # batch form + per-request nprobe + top_k trim
        outs = json.loads(
            client.action(
                "ann_search",
                {
                    "plane": "emb",
                    "queries": [q.tolist() for q in queries[:3]],
                    "nprobe": 16,
                    "top_k": 2,
                },
            )[0]
        )
        assert len(outs) == 3 and all(len(o["ids"]) == 2 for o in outs)
        # unknown plane is a server error, not a crash
        with pytest.raises(flight.FlightServerError, match="unknown ann plane"):
            client.action("ann_search", {"plane": "nope", "query": [0.0]})
        # RBAC: the plane inherits its table's domain
        with pytest.raises(flight.FlightError):
            client.action(
                "ann_search", {"plane": "locked", "query": queries[0].tolist()}
            )

    def test_unauthenticated_rejected(self, gateway):
        import pyarrow.flight as flight

        _server, location, _token = gateway
        raw = flight.FlightClient(location)
        with pytest.raises(flight.FlightError):
            list(raw.do_action(flight.Action("ann_search", b"{}")))

    def test_overload_maps_to_unavailable(self, tmp_warehouse, built_plane):
        import pyarrow as pa
        import pyarrow.flight as flight

        from lakesoul_tpu import LakeSoulCatalog
        from lakesoul_tpu.service.flight import LakeSoulFlightServer

        _, _, plane, _, _, _, queries = built_plane
        catalog = LakeSoulCatalog(str(tmp_warehouse))
        catalog.create_table(
            "corpus", pa.schema([("id", pa.int64())])
        )
        # a pending bound of 1 with a slow window: the second concurrent
        # submit sheds, and the gateway maps it to UNAVAILABLE
        ep = ShardedAnnEndpoint(
            plane, SearchParams(top_k=1, nprobe=4),
            max_batch=1, max_wait_ms=200.0, max_pending=1,
        )
        server = LakeSoulFlightServer(
            catalog, "grpc://127.0.0.1:0",
            ann_planes={"emb": AnnPlaneBinding(ep, "default", "corpus")},
        )
        try:
            client = flight.FlightClient(f"grpc://127.0.0.1:{server.port}")
            body = {"plane": "emb", "query": queries[0].tolist()}
            import json

            sheds = [0]

            def call():
                try:
                    list(
                        client.do_action(
                            flight.Action("ann_search", json.dumps(body).encode())
                        )
                    )
                except flight.FlightUnavailableError:
                    sheds[0] += 1

            threads = [threading.Thread(target=call) for _ in range(8)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()
            assert sheds[0] > 0
        finally:
            ep.close()
            server.shutdown()


class TestCrossChipMerge:
    def test_dryrun_multichip_8(self):
        out = dryrun_multichip(8)
        assert out["devices"] == 8 and len(out["dists"]) == 10

    def test_merge_matches_host(self):
        rng = np.random.default_rng(3)
        dists = rng.random((4, 6)).astype(np.float32)
        rows = rng.integers(0, 1000, (4, 6)).astype(np.int32)
        d, r, src = cross_chip_topk(dists, rows, k=8)
        order = np.argsort(dists.reshape(-1), kind="stable")[:8]
        np.testing.assert_allclose(d, dists.reshape(-1)[order], rtol=1e-6)
        np.testing.assert_array_equal(r, rows.reshape(-1)[order])
        np.testing.assert_array_equal(src, order // 6)

    def test_shape_mismatch_raises(self):
        with pytest.raises(VectorIndexError, match="mismatch"):
            cross_chip_topk(np.zeros((2, 3)), np.zeros((2, 4), np.int32))
