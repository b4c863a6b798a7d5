"""Regression tests pinning every ADVICE round-2 finding.

Each test exercises the exact failure scenario the advisor described, so the
fixes in meta/client.py (desc-prefix fallback), meta/store.py (prefix upper
bound), sql/parser.py (AS OF timezone), parallel/moe.py (int token ranks),
and catalog.py (prune accounting) stay fixed.
"""

import datetime
import os
import time

import numpy as np
import pyarrow as pa
import pytest

from lakesoul_tpu.meta import (
    CommitOp,
    DataCommitInfo,
    DataFileOp,
    MetaDataClient,
    PartitionInfo,
)
from lakesoul_tpu.meta.store import desc_prefix_upper_bound


SCHEMA = pa.schema([("id", pa.int64()), ("a", pa.string()), ("b", pa.string())])


def _hand_commit(client, info, desc, path):
    """Insert a partition version + data commit DIRECTLY into the store,
    bypassing the client's desc canonicalization — simulating a legacy or
    external writer (the advisor's 'b=2,a=1' scenario)."""
    cid = DataCommitInfo.new_commit_id()
    ts = int(time.time() * 1000)
    client.store.insert_data_commit_info(
        [
            DataCommitInfo(
                table_id=info.table_id,
                partition_desc=desc,
                commit_id=cid,
                file_ops=[DataFileOp(path=path, size=10)],
                commit_op=CommitOp.APPEND,
                committed=True,
                timestamp=ts,
            )
        ]
    )
    client.store.transaction_insert_partition_info(
        [
            PartitionInfo(
                table_id=info.table_id,
                partition_desc=desc,
                version=0,
                commit_op=CommitOp.APPEND,
                timestamp=ts,
                snapshot=[cid],
            )
        ]
    )


class TestDescPrefixFallback:
    """medium: the desc-prefix range fast path silently dropped legacy
    non-canonical descs from scans filtered on a leading range column."""

    def _table(self, tmp_path, ranges=("a", "b")):
        client = MetaDataClient(db_path=str(tmp_path / "meta.db"))
        info = client.create_table(
            "t", "/tmp/wh/t", SCHEMA, range_partitions=list(ranges)
        )
        return client, info

    def test_legacy_desc_seen_by_leading_range_filter(self, tmp_path):
        client, info = self._table(tmp_path)
        client.commit_data_files(
            info, {"a=1,b=1": [DataFileOp(path="/d/p1_0000.parquet")]}, CommitOp.APPEND
        )
        # legacy writer committed the same logical partition keys reversed
        _hand_commit(client, info, "b=2,a=1", "/d/legacy_0000.parquet")
        plan = client.get_scan_plan_partitions("t", {"a": "1"})
        descs = {u.partition_desc for u in plan}
        assert "b=2,a=1" in descs, "legacy non-canonical desc vanished from scan"
        assert "a=1,b=1" in descs

    def test_fast_path_restored_after_migration(self, tmp_path):
        client, info = self._table(tmp_path)
        client.commit_data_files(
            info, {"a=1,b=1": [DataFileOp(path="/d/p1_0000.parquet")]}, CommitOp.APPEND
        )
        _hand_commit(client, info, "b=2,a=1", "/d/legacy_0000.parquet")
        assert not client._descs_all_canonical(info)
        n = client.canonicalize_partition_descs("t")
        assert n == 1
        # store now holds only canonical descs, the flag is durable, and the
        # migrated partition still matches (as its canonical spelling)
        assert client._descs_all_canonical(info)
        fresh = MetaDataClient(store=client.store)
        assert fresh._descs_all_canonical(info)
        plan = client.get_scan_plan_partitions("t", {"a": "1"})
        assert {u.partition_desc for u in plan} == {"a=1,b=1", "a=1,b=2"}
        # data files survive the rename
        files = [f for u in plan for f in u.data_files]
        assert "/d/legacy_0000.parquet" in files

    def test_canonical_only_store_keeps_fast_path(self, tmp_path):
        """With only client-written descs the verification flips the
        global_config flag once; later commits don't re-trigger the scan."""
        client, info = self._table(tmp_path)
        client.commit_data_files(
            info, {"a=1,b=1": [DataFileOp(path="/d/p1_0000.parquet")]}, CommitOp.APPEND
        )
        assert client._descs_all_canonical(info)
        flag = client.store.get_global_config(
            client._CANONICAL_FLAG + info.table_id
        )
        assert flag == client.store.get_desc_epoch(info.table_id)

    def test_point_lookup_sees_colliding_legacy_chain(self, tmp_path):
        """A fully-specified partition filter must also union a legacy
        spelling of the SAME logical partition — the point-lookup hit is
        only trusted on a verified-canonical store."""
        client, info = self._table(tmp_path)
        client.commit_data_files(
            info, {"a=1,b=1": [DataFileOp(path="/d/p1_0000.parquet")]}, CommitOp.APPEND
        )
        _hand_commit(client, info, "b=1,a=1", "/d/legacy_0000.parquet")
        plan = client.get_scan_plan_partitions("t", {"a": "1", "b": "1"})
        files = {f for u in plan for f in u.data_files}
        assert files == {"/d/p1_0000.parquet", "/d/legacy_0000.parquet"}

    def test_drop_table_clears_bookkeeping_keys(self, tmp_path):
        from lakesoul_tpu.meta.store import DESC_EPOCH_KEY, DESCS_VERIFIED_KEY

        client, info = self._table(tmp_path)
        client.commit_data_files(
            info, {"a=1,b=1": [DataFileOp(path="/d/p1_0000.parquet")]}, CommitOp.APPEND
        )
        assert client._descs_all_canonical(info)
        assert client.store.get_global_config(DESC_EPOCH_KEY + info.table_id)
        client.drop_table("t")
        assert client.store.get_global_config(DESC_EPOCH_KEY + info.table_id) is None
        assert client.store.get_global_config(DESCS_VERIFIED_KEY + info.table_id) is None

    def test_hand_commit_after_verification_still_seen(self, tmp_path):
        """The verified-canonical flag must not outlive the partition set it
        verified: an external writer adding a non-canonical desc AFTER the
        flag was set (count changes) forces re-verification."""
        client, info = self._table(tmp_path)
        client.commit_data_files(
            info, {"a=1,b=1": [DataFileOp(path="/d/p1_0000.parquet")]}, CommitOp.APPEND
        )
        assert client._descs_all_canonical(info)  # sets the durable flag
        _hand_commit(client, info, "b=2,a=1", "/d/legacy_0000.parquet")
        plan = client.get_scan_plan_partitions("t", {"a": "1"})
        assert {u.partition_desc for u in plan} == {"a=1,b=1", "b=2,a=1"}
        # a fresh client sharing the store must not trust the stale flag
        fresh = MetaDataClient(store=client.store)
        assert not fresh._descs_all_canonical(info)

    def test_subset_key_desc_forces_fallback(self, tmp_path):
        """A desc holding only a PREFIX of the range columns ('a=1' on an
        (a, b) table) sorts below the 'a=1,' prefix bound; it must count as
        non-canonical so the full-scan fallback picks it up."""
        client, info = self._table(tmp_path)
        client.commit_data_files(
            info, {"a=1,b=1": [DataFileOp(path="/d/p1_0000.parquet")]}, CommitOp.APPEND
        )
        _hand_commit(client, info, "a=1", "/d/partial_0000.parquet")
        assert not client._descs_all_canonical(info)
        plan = client.get_scan_plan_partitions("t", {"a": "1"})
        assert {u.partition_desc for u in plan} == {"a=1,b=1", "a=1"}

    def test_migration_skips_colliding_chain(self, tmp_path):
        """Canonicalizing 'b=1,a=1' when 'a=1,b=1' already exists would merge
        two version chains; the migration must skip it (logged), finish, and
        leave the fallback active."""
        client, info = self._table(tmp_path)
        client.commit_data_files(
            info, {"a=1,b=1": [DataFileOp(path="/d/p1_0000.parquet")]}, CommitOp.APPEND
        )
        _hand_commit(client, info, "b=1,a=1", "/d/legacy_0000.parquet")
        _hand_commit(client, info, "b=9,a=9", "/d/l9_0000.parquet")
        n = client.canonicalize_partition_descs("t")
        assert n == 1  # b=9,a=9 rewritten; the colliding chain skipped
        descs = set(client.store.get_partition_descs(info.table_id))
        assert descs == {"a=1,b=1", "b=1,a=1", "a=9,b=9"}
        assert not client._descs_all_canonical(info)  # fallback stays on
        plan = client.get_scan_plan_partitions("t", {"a": "1"})
        assert {u.partition_desc for u in plan} == {"a=1,b=1", "b=1,a=1"}

    def test_new_legacy_desc_invalidates_negative_cache(self, tmp_path):
        client, info = self._table(tmp_path)
        _hand_commit(client, info, "b=1,a=1", "/d/l1_0000.parquet")
        assert not client._descs_all_canonical(info)
        # count changed → recheck runs; still non-canonical
        _hand_commit(client, info, "b=2,a=2", "/d/l2_0000.parquet")
        assert not client._descs_all_canonical(info)
        plan = client.get_scan_plan_partitions("t", {"a": "2"})
        assert {u.partition_desc for u in plan} == {"b=2,a=2"}


class TestEpochRestamp:
    """Client commits of new canonical descs must NOT degrade planning to a
    full desc re-scan: the store CASes the verified flag forward with the
    epoch bump in the same transaction."""

    def test_canonical_commit_keeps_plan_o1(self, tmp_path):
        client = MetaDataClient(db_path=str(tmp_path / "meta.db"))
        info = client.create_table(
            "t", "/tmp/wh/t", SCHEMA, range_partitions=["a", "b"]
        )
        client.commit_data_files(
            info, {"a=1,b=1": [DataFileOp(path="/d/p1_0000.parquet")]}, CommitOp.APPEND
        )
        assert client._descs_all_canonical(info)  # one verification scan
        calls = []
        orig = client.store.get_partition_descs
        client.store.get_partition_descs = lambda tid: (calls.append(tid) or orig(tid))
        try:
            for i in range(2, 5):
                client.commit_data_files(
                    info,
                    {f"a={i},b={i}": [DataFileOp(path=f"/d/p{i}_0000.parquet")]},
                    CommitOp.APPEND,
                )
                plan = client.get_scan_plan_partitions("t", {"a": str(i)})
                assert {u.partition_desc for u in plan} == {f"a={i},b={i}"}
            assert calls == [], "canonical commits must not force desc re-scans"
        finally:
            client.store.get_partition_descs = orig
        # and a fresh client trusts the restamped flag without scanning
        fresh = MetaDataClient(store=client.store)
        fresh.store.get_partition_descs = lambda tid: (calls.append(tid) or orig(tid))
        try:
            assert fresh._descs_all_canonical(info)
            assert calls == []
        finally:
            fresh.store.get_partition_descs = orig


class TestPgCollation:
    """The desc-prefix range must name the byte collation on PG: linguistic
    cluster collations treat ',' as primary-ignorable, breaking the bound
    math.  Runs against the wire-faithful psycopg2 fake (which registers the
    'C' collation like PG always has)."""

    def test_prefix_range_on_pg_store(self, tmp_path, monkeypatch):
        import sys

        import fake_psycopg2

        monkeypatch.setitem(sys.modules, "psycopg2", fake_psycopg2)
        from lakesoul_tpu.meta.store import PostgresMetadataStore

        dsn = f"postgresql://fake/{tmp_path.name}-collate"
        store = PostgresMetadataStore(dsn)
        try:
            assert 'COLLATE "C"' in store.DESC_RANGE_COLLATION
            client = MetaDataClient(store=store)
            info = client.create_table(
                "t", "/tmp/wh/t", SCHEMA, range_partitions=["a", "b"]
            )
            client.commit_data_files(
                info,
                {"a=1,b=1": [DataFileOp(path="/d/p_0000.parquet")]},
                CommitOp.APPEND,
            )
            got = store.get_all_latest_partition_info(info.table_id, desc_prefix="a=1,")
            assert [p.partition_desc for p in got] == ["a=1,b=1"]
            plan = client.get_scan_plan_partitions("t", {"a": "1"})
            assert len(plan) == 1
        finally:
            fake_psycopg2.reset(dsn)


class TestPrefixUpperBound:
    """low: prefix + '\\uffff' upper bound dropped descs whose next char is a
    supplementary-plane codepoint (sorts above U+FFFF)."""

    def test_upper_bound_helper(self):
        assert desc_prefix_upper_bound("a=1,") == "a=1" + chr(ord(",") + 1)
        # carry over max codepoints
        m = chr(0x10FFFF)
        assert desc_prefix_upper_bound("a" + m) == "b"
        assert desc_prefix_upper_bound(m * 3) is None
        # surrogate block is skipped, not produced
        assert desc_prefix_upper_bound(chr(0xD7FF)) == chr(0xE000)

    def test_supplementary_plane_desc_survives_prefix_range(self, tmp_path):
        client = MetaDataClient(db_path=str(tmp_path / "meta.db"))
        # a range column whose NAME starts beyond the BMP: the desc char
        # right after the 'a=1,' prefix is U+1F600
        emoji_col = "\U0001F600col"
        schema = pa.schema([("id", pa.int64()), ("a", pa.string()), (emoji_col, pa.string())])
        info = client.create_table(
            "emoji", "/tmp/wh/emoji", schema, range_partitions=["a", emoji_col]
        )
        client.commit_data_files(
            info,
            {f"a=1,{emoji_col}=x": [DataFileOp(path="/d/e_0000.parquet")]},
            CommitOp.APPEND,
        )
        got = client.store.get_all_latest_partition_info(
            info.table_id, desc_prefix="a=1,"
        )
        assert [p.partition_desc for p in got] == [f"a=1,{emoji_col}=x"]
        plan = client.get_scan_plan_partitions("emoji", {"a": "1"})
        assert len(plan) == 1


class TestAsOfTimezone:
    """low: naive AS OF literals were interpreted in the host's local zone."""

    @pytest.fixture()
    def nyc_tz(self):
        old = os.environ.get("TZ")
        os.environ["TZ"] = "America/New_York"
        time.tzset()
        yield
        if old is None:
            os.environ.pop("TZ", None)
        else:
            os.environ["TZ"] = old
        time.tzset()

    def _as_of_ms(self, sql):
        from lakesoul_tpu.sql.parser import parse

        return parse(sql).as_of_ms

    def test_naive_literal_is_utc(self, nyc_tz):
        want = datetime.datetime(
            2026, 1, 2, 3, 4, 5, tzinfo=datetime.timezone.utc
        ).timestamp() * 1000
        got = self._as_of_ms(
            "SELECT * FROM t TIMESTAMP AS OF '2026-01-02T03:04:05'"
        )
        assert got == int(want), "naive AS OF literal drifted with host TZ"

    def test_explicit_offset_wins(self, nyc_tz):
        got = self._as_of_ms(
            "SELECT * FROM t FOR SYSTEM_TIME AS OF '2026-01-02T03:04:05+02:00'"
        )
        want = datetime.datetime.fromisoformat(
            "2026-01-02T03:04:05+02:00"
        ).timestamp() * 1000
        assert got == int(want)

    def test_epoch_ms_unaffected(self, nyc_tz):
        assert self._as_of_ms("SELECT * FROM t FOR SYSTEM_TIME AS OF 1700000000000") \
            == 1700000000000


class TestExplainPruneAccounting:
    """low: buckets_pruned counted scan units; now units_pruned counts units
    and buckets_pruned counts distinct bucket ids gone entirely."""

    def test_multi_partition_counts(self, tmp_warehouse):
        from lakesoul_tpu.catalog import LakeSoulCatalog

        catalog = LakeSoulCatalog(str(tmp_warehouse))
        t = catalog.create_table(
            "acct",
            pa.schema([("id", pa.int64()), ("p", pa.string()), ("v", pa.int64())]),
            primary_keys=["id"],
            range_partitions=["p"],
            hash_bucket_num=4,
        )
        n = 64
        ids = np.arange(n)
        for part in ("x", "y"):
            t.write_arrow(
                pa.table(
                    {"id": ids, "p": np.repeat(part, n), "v": np.ones(n, np.int64)}
                )
            )
        d = t.scan().filter("id = 3").explain()
        assert d["units_before_bucket_prune"] == 8  # 2 partitions × 4 buckets
        assert d["units"] == 2  # the one matching bucket per partition
        assert d["units_pruned"] == 6
        # 3 whole buckets vanished across BOTH partitions — not 6
        assert d["buckets_pruned"] == 3
