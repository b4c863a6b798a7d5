"""Freshness layer: SLO evaluation math, the retry-hardened follower's
exactly-once resume contract (including across a compaction that rewrites
the files the recorded units point at), the notifier's failure isolation,
and the ``to_jax_iter(follow=...)`` training-source seam."""

from __future__ import annotations

import os
import random
import threading
import time

import pyarrow as pa
import pytest

from lakesoul_tpu import LakeSoulCatalog
from lakesoul_tpu.errors import ConfigError
from lakesoul_tpu.freshness import (
    FollowBatchSource,
    FollowerState,
    FreshFollower,
    SloMonitor,
    ThroughputSlo,
)
from lakesoul_tpu.meta.entity import now_millis
from lakesoul_tpu.runtime import faults
from lakesoul_tpu.runtime.resilience import RetryPolicy

SCHEMA = pa.schema([("id", pa.int64()), ("seq", pa.int64()), ("v", pa.float64())])


@pytest.fixture
def catalog(tmp_path):
    return LakeSoulCatalog(
        str(tmp_path / "wh"), db_path=str(tmp_path / "meta.db")
    )


def _commit(table, base: int, n: int) -> None:
    table.upsert(pa.table({
        "id": list(range(base, base + n)),
        "seq": list(range(base, base + n)),
        "v": [float(base + i) for i in range(n)],
    }, schema=SCHEMA))


def _rows(batches) -> list[int]:
    return [s for b in batches for s in b.column("seq").to_pylist()]


def _drain(follower) -> list[int]:
    return _rows(follower.iter_batches())


def _fast_policy(attempts: int = 10) -> RetryPolicy:
    return RetryPolicy(
        max_attempts=attempts, base_delay_s=0.001, max_delay_s=0.01, seed=7
    )


# ------------------------------------------------------------------- slo


class TestSloMonitor:
    def test_target_and_budget_accounting(self):
        m = SloMonitor(target_s=1.0, budget_fraction=0.5, slo="t1")
        for lat in (0.1, 0.2, 2.0, 0.3):
            m.observe(lat)
        snap = m.snapshot()
        assert snap["count"] == 4 and snap["violations"] == 1
        assert snap["allowed_violations"] == 2 and snap["in_budget"]
        m.observe(3.0)
        m.observe(4.0)
        # floor semantics: 6 observations x 0.5 = 3 allowed, 3 violations
        assert m.snapshot()["budget_remaining"] == 0 and m.in_budget()
        m.observe(9.0)  # 4 violations > floor(7 x 0.5) = 3: budget burned
        assert not m.in_budget()

    def test_violations_hit_the_labeled_counter(self):
        from lakesoul_tpu.obs import registry

        before = registry().counter(
            "lakesoul_slo_violations_total", slo="t2"
        ).value
        m = SloMonitor(target_s=0.5, slo="t2")
        m.observe(0.1)
        m.observe(1.5)
        after = registry().counter(
            "lakesoul_slo_violations_total", slo="t2"
        ).value
        assert after - before == 1

    def test_percentiles_are_exact_over_reservoir(self):
        m = SloMonitor(target_s=100.0, slo="t3")
        for i in range(100):
            m.observe(i / 100.0)
        snap = m.snapshot()
        assert snap["p50_s"] == pytest.approx(0.50, abs=0.02)
        assert snap["p99_s"] == pytest.approx(0.98, abs=0.02)
        assert snap["max_s"] == pytest.approx(0.99)

    def test_observe_commit_skips_unknown_timestamps(self):
        m = SloMonitor(target_s=1.0, slo="t4")
        assert m.observe_commit(0) == -1.0
        assert m.snapshot()["count"] == 0
        lat = m.observe_commit(now_millis() - 250)
        assert 0.2 <= lat <= 5.0
        assert m.snapshot()["count"] == 1

    def test_env_defaults(self, monkeypatch):
        monkeypatch.setenv("LAKESOUL_FRESHNESS_SLO_S", "3.5")
        monkeypatch.setenv("LAKESOUL_FRESHNESS_BUDGET", "0.25")
        m = SloMonitor(slo="t5")
        assert m.target_s == 3.5 and m.budget_fraction == 0.25

    def test_throughput_slo(self):
        s = ThroughputSlo(1.0, slo="tp1")
        s.start()
        s.add_rows(10_000)
        out = s.evaluate()
        assert out["ok"] and out["rows"] == 10_000
        slow = ThroughputSlo(1e12, slo="tp2")
        slow.start()
        slow.add_rows(1)
        time.sleep(0.01)
        assert not slow.evaluate()["ok"]

    def test_histogram_quantile_estimate(self):
        from lakesoul_tpu.obs.metrics import Histogram

        h = Histogram("lakesoul_test_q_seconds", buckets=(0.1, 1.0, 10.0))
        assert h.quantile(0.5) == 0.0  # empty
        for _ in range(90):
            h.observe(0.05)
        for _ in range(10):
            h.observe(5.0)
        assert h.quantile(0.5) <= 0.1
        assert 1.0 <= h.quantile(0.99) <= 10.0
        with pytest.raises(ValueError):
            h.quantile(1.5)


# -------------------------------------------------------------- follower


class TestFollowerExactlyOnce:
    def test_state_resume_is_row_identical(self, catalog):
        """Kill a follower mid-stream, restart from persisted state:
        concatenated delivery == an uninterrupted follow — no dup, no gap."""
        t = catalog.create_table("f1", SCHEMA, primary_keys=["id"], hash_bucket_num=2)
        start = now_millis() - 1
        for c in range(4):
            _commit(t, c * 10, 10)

        oracle = _drain(FreshFollower(
            t.scan().batch_size(7), start_timestamp_ms=start,
            poll_interval=0.01, max_polls=3,
        ))
        assert len(oracle) == 40

        f1 = FreshFollower(
            t.scan().batch_size(7), start_timestamp_ms=start,
            poll_interval=0.01, max_polls=3,
        )
        got: list[int] = []
        it = f1.iter_batches()
        for i, b in enumerate(it):
            got.extend(b.column("seq").to_pylist())
            if i == 1:
                state = f1.state_json()  # persisted next to the checkpoint
                break
        it.close()  # the "kill"
        f2 = FreshFollower(
            t.scan().batch_size(7),
            state=FollowerState.from_json(state),
            poll_interval=0.01, max_polls=3,
        )
        got += _drain(f2)
        assert got == oracle

    def test_resume_survives_compaction_rewriting_files(self, catalog):
        """The recorded pending units reference pre-compaction files; a
        compaction between kill and restart rewrites the table but the old
        files stay on disk until the cleaner runs — the resumed delivery
        is still row-identical, and the post-compaction commit arrives
        exactly once."""
        t = catalog.create_table("f2", SCHEMA, primary_keys=["id"], hash_bucket_num=2)
        start = now_millis() - 1
        for c in range(4):
            _commit(t, c * 10, 10)

        f1 = FreshFollower(
            t.scan().batch_size(7), start_timestamp_ms=start,
            poll_interval=0.01, max_polls=3,
        )
        got: list[int] = []
        it = f1.iter_batches()
        for i, b in enumerate(it):
            got.extend(b.column("seq").to_pylist())
            if i == 1:
                state = f1.state_json()
                break
        it.close()

        # between kill and restart: a compaction rewrites every file the
        # cursors/pending units point at, then one more commit lands
        assert t.compact() == 1
        _commit(t, 40, 10)

        f2 = FreshFollower(
            t.scan().batch_size(7),
            state=FollowerState.from_json(state),
            poll_interval=0.01, max_polls=3,
        )
        got += _drain(f2)
        # no dup, no gap: every written row exactly once (delivery order
        # across polls may group differently; the multiset must not)
        assert sorted(got) == list(range(50))
        assert len(got) == 50

    def test_lagged_consumer_resume_state(self, catalog):
        """resume_state(k) reconstructs the position of a consumer k rows
        in — the loader-pipeline shape where prefetch buffers run ahead."""
        t = catalog.create_table("f3", SCHEMA, primary_keys=["id"], hash_bucket_num=2)
        start = now_millis() - 1
        for c in range(3):
            _commit(t, c * 10, 10)
        oracle = _drain(FreshFollower(
            t.scan().batch_size(7), start_timestamp_ms=start,
            poll_interval=0.01, max_polls=3,
        ))

        f = FreshFollower(
            t.scan().batch_size(7), start_timestamp_ms=start,
            poll_interval=0.01, max_polls=3,
        )
        it = f.iter_batches()
        b1, b2 = next(it), next(it)
        next(it)  # the source ran ahead; consumer only finished 3 rows of b2
        consumed = len(b1) + 3
        rs = f.resume_state(consumed)
        it.close()
        got = (
            b1.column("seq").to_pylist()
            + b2.column("seq").to_pylist()[:3]
            + _drain(FreshFollower(
                t.scan().batch_size(7), state=rs,
                poll_interval=0.01, max_polls=3,
            ))
        )
        assert got == oracle

    def test_cursor_dict_compat_mutated_in_place(self, catalog):
        """The legacy coarse-grained resume: follow(cursors=dict) advances
        the caller's dict in place (follow_cursors_to_json round-trip)."""
        from lakesoul_tpu.meta.client import (
            follow_cursors_from_json,
            follow_cursors_to_json,
        )

        t = catalog.create_table("f4", SCHEMA, primary_keys=["id"], hash_bucket_num=1)
        _commit(t, 0, 5)
        cursors = catalog.client.init_follow_cursors(t.info.table_name, now_millis())
        _commit(t, 10, 5)
        f = FreshFollower(
            t.scan(), cursors=cursors, poll_interval=0.01, max_polls=2
        )
        assert sorted(_drain(f)) == list(range(10, 15))
        restored = follow_cursors_from_json(follow_cursors_to_json(cursors))
        _commit(t, 20, 5)
        f2 = FreshFollower(
            t.scan(), cursors=restored, poll_interval=0.01, max_polls=2
        )
        assert sorted(_drain(f2)) == list(range(20, 25))


class TestFollowerResilience:
    def test_transient_faults_absorbed_with_seeded_schedule(self, catalog, monkeypatch):
        """p=0.4 flaky faults on the poll + store reads: the stream
        retries on the shared policy and delivers byte-identically."""
        # the schedule is this test's own: the process-wide stream stands wherever the files that ran
        # before on this worker left it, and one state in twenty draws no poll fault in six polls
        monkeypatch.setattr(faults, "_RNG", random.Random(0))
        t = catalog.create_table("f5", SCHEMA, primary_keys=["id"], hash_bucket_num=2)
        start = now_millis() - 1
        for c in range(3):
            _commit(t, c * 10, 10)
        oracle = _drain(FreshFollower(
            t.scan().batch_size(7), start_timestamp_ms=start,
            poll_interval=0.01, max_polls=3,
        ))
        from lakesoul_tpu.obs import registry

        attempts_before = registry().counter(
            "lakesoul_retry_attempts_total", op="follow.poll"
        ).value
        faults.clear()
        faults.install("follow.poll:0.4:flaky")
        faults.install("object_store.cat_file:0.2:flaky")
        faults.install("object_store.open:0.2:flaky")
        try:
            got = _drain(FreshFollower(
                t.scan().batch_size(7), start_timestamp_ms=start,
                poll_interval=0.01, max_polls=6,
                retry_policy=_fast_policy(),
            ))
        finally:
            faults.clear()
        assert got == oracle
        attempts_after = registry().counter(
            "lakesoul_retry_attempts_total", op="follow.poll"
        ).value
        assert attempts_after > attempts_before  # the retry path really ran

    def test_decode_fault_mid_unit_does_not_duplicate(self, catalog):
        """A fault between batches of one unit re-opens the unit at the
        delivered offset: no replayed rows."""
        t = catalog.create_table("f6", SCHEMA, primary_keys=["id"], hash_bucket_num=1)
        start = now_millis() - 1
        _commit(t, 0, 50)  # one unit, several 7-row batches
        faults.clear()
        faults.install("object_store.open:0.5:flaky")
        faults.install("object_store.cat_file:0.5:flaky")
        try:
            got = _drain(FreshFollower(
                t.scan().batch_size(7), start_timestamp_ms=start,
                poll_interval=0.01, max_polls=3,
                retry_policy=_fast_policy(20),
            ))
        finally:
            faults.clear()
        assert sorted(got) == list(range(50)) and len(got) == 50

    def test_permanent_failure_raises_typed(self, catalog, monkeypatch):
        t = catalog.create_table("f7", SCHEMA)
        _commit_plain(t)

        def boom(*a, **k):
            raise ConfigError("permanent")

        monkeypatch.setattr(catalog.client, "poll_scan_plan", boom)
        f = FreshFollower(t.scan(), poll_interval=0.01, max_polls=2)
        with pytest.raises(ConfigError):
            list(f.iter_batches())

    def test_retry_exhaustion_raises_last_native_error(self, catalog):
        t = catalog.create_table("f8", SCHEMA)
        faults.clear()
        faults.install("follow.poll:1.0:flaky")  # every attempt fails
        try:
            f = FreshFollower(
                t.scan(), poll_interval=0.01, max_polls=2,
                retry_policy=_fast_policy(3),
            )
            with pytest.raises(ConnectionError):
                list(f.iter_batches())
        finally:
            faults.clear()


def _commit_plain(t):
    t.write_arrow(pa.table({
        "id": [1], "seq": [1], "v": [1.0]
    }, schema=SCHEMA))


class TestFollowerFreshnessMeasurement:
    def test_commit_to_visible_lands_in_histogram_and_budget(self, catalog):
        t = catalog.create_table("f9", SCHEMA, primary_keys=["id"], hash_bucket_num=1)
        start = now_millis() - 1
        slo = SloMonitor(target_s=30.0, slo="test-follow")
        _commit(t, 0, 10)
        _commit(t, 10, 10)
        f = FreshFollower(
            t.scan(), start_timestamp_ms=start,
            poll_interval=0.01, max_polls=3, slo=slo,
        )
        assert len(_drain(f)) == 20
        snap = slo.snapshot()
        # one observation per delivered unit (a poll groups the new commits
        # of a bucket into one unit, stamped with the EARLIEST commit's
        # instant), all fresh (sub-target)
        assert snap["count"] >= 1
        assert snap["violations"] == 0 and snap["in_budget"]
        assert 0.0 <= snap["p99_s"] < 30.0

    def test_scan_follow_surface_passes_slo_through(self, catalog):
        t = catalog.create_table("f10", SCHEMA, primary_keys=["id"], hash_bucket_num=1)
        slo = SloMonitor(target_s=30.0, slo="test-follow-2")
        stop = threading.Event()
        _commit(t, 0, 5)
        start = catalog.client.store.get_latest_partition_info(
            t.info.table_id, "-5"
        ).timestamp - 1
        seen = []
        for b in t.scan().follow(
            start, poll_interval=0.01, stop_event=stop, slo=slo
        ):
            seen.extend(b.column("seq").to_pylist())
            if len(seen) >= 5:
                stop.set()
        assert slo.snapshot()["count"] >= 1


class TestFollowDeprecationsAndShutdown:
    def test_settle_ms_deprecated_noop(self, catalog):
        t = catalog.create_table("f11", SCHEMA)
        stop = threading.Event()
        stop.set()
        with pytest.deprecated_call():
            assert list(t.scan().follow(stop_event=stop, settle_ms=250)) == []

    def test_stop_within_one_tick_even_on_long_poll_interval(self, catalog):
        """The satellite contract: the idle wait rides stop_event.wait, so
        a parked follower exits in ~0 s, not one poll_interval."""
        t = catalog.create_table("f12", SCHEMA)
        stop = threading.Event()
        done = threading.Event()

        def run():
            list(t.scan().follow(stop_event=stop, poll_interval=30.0))
            done.set()

        th = threading.Thread(target=run, daemon=True)
        th.start()
        time.sleep(0.3)  # park it on the 30 s wait
        t0 = time.monotonic()
        stop.set()
        assert done.wait(timeout=5.0)
        assert time.monotonic() - t0 < 2.0


# ------------------------------------------------------ notifier isolation


class TestNotifierIsolation:
    def _table_with_gap(self, catalog):
        t = catalog.create_table(
            "n1", SCHEMA, primary_keys=["id"], hash_bucket_num=1
        )
        for c in range(4):  # enough committed versions to open a gap
            _commit(t, c * 5, 5)
        return t

    def test_raising_listener_does_not_starve_others(self, catalog):
        from lakesoul_tpu.compaction.events import PollingWatermarkNotifier
        from lakesoul_tpu.obs import registry

        self._table_with_gap(catalog)
        n = PollingWatermarkNotifier(catalog.client.store, version_gap=2)
        seen: list = []

        def bad(ev):
            raise RuntimeError("listener bug")

        n.listen(bad)
        n.listen(seen.append)
        errors_before = registry().counter(
            "lakesoul_notifier_listener_errors_total"
        ).value
        delivered = n.poll()
        assert delivered >= 1
        assert len(seen) == delivered  # the good listener saw EVERY event
        errors_after = registry().counter(
            "lakesoul_notifier_listener_errors_total"
        ).value
        assert errors_after - errors_before == delivered  # one per bad call

    def test_store_errors_retried_then_survive_the_poll(self, catalog):
        """Transient candidate-derivation faults retry through the shared
        policy; exhaustion fails THIS poll only (returns 0) instead of
        propagating into the owning service loop."""
        from lakesoul_tpu.compaction.events import PollingWatermarkNotifier

        self._table_with_gap(catalog)
        store = catalog.client.store
        calls = {"n": 0}
        real = store.get_compaction_candidates

        class FlakyStore:
            def __getattr__(self, name):
                return getattr(store, name)

            def get_compaction_candidates(self, *a, **k):
                calls["n"] += 1
                if calls["n"] == 1:
                    raise ConnectionError("transient store blip")
                return real(*a, **k)

        n = PollingWatermarkNotifier(
            FlakyStore(), version_gap=2, retry_policy=_fast_policy()
        )
        seen: list = []
        n.listen(seen.append)
        assert n.poll() >= 1  # first attempt blipped, retry delivered
        assert calls["n"] >= 2

        class DeadStore:
            def get_compaction_candidates(self, *a, **k):
                raise ConnectionError("store down")

        dead = PollingWatermarkNotifier(
            DeadStore(), version_gap=2, retry_policy=_fast_policy(2)
        )
        dead.listen(seen.append)
        assert dead.poll() == 0  # exhaustion: logged + counted, never raised


# -------------------------------------------------- loader follow source


class TestJaxIterFollow:
    def _table(self, catalog, name="j1", commits=4, per=32):
        t = catalog.create_table(
            name, SCHEMA, primary_keys=["id"], hash_bucket_num=2
        )
        start = now_millis() - 1
        for c in range(commits):
            _commit(t, c * per, per)
        return t, start, commits * per

    def test_follow_is_a_continuous_training_source(self, catalog):
        t, start, total = self._table(catalog)
        stop = threading.Event()
        it = t.scan().batch_size(16).to_jax_iter(
            follow={
                "start_timestamp_ms": start,
                "poll_interval": 0.02,
                "stop_event": stop,
            },
            device_put=False,
        )
        seen: list[int] = []
        for batch in it:
            seen.extend(batch["seq"].tolist())
            if len(seen) >= total:
                stop.set()
                break
        assert sorted(seen) == list(range(total))

    def test_follow_state_json_resumes_exactly(self, catalog):
        t, start, total = self._table(catalog, name="j2")
        stop1 = threading.Event()
        it1 = t.scan().batch_size(16).to_jax_iter(
            follow={
                "start_timestamp_ms": start,
                "poll_interval": 0.02,
                "stop_event": stop1,
            },
            device_put=False,
        )
        seen: list[int] = []
        for i, batch in enumerate(it1):
            seen.extend(batch["seq"].tolist())
            if i == 3:
                saved = it1.follow_state_json()  # next to the model ckpt
                stop1.set()
                break
        stop2 = threading.Event()
        it2 = t.scan().batch_size(16).to_jax_iter(
            follow={
                "state": saved,
                "poll_interval": 0.02,
                "stop_event": stop2,
            },
            device_put=False,
        )
        for batch in it2:
            seen.extend(batch["seq"].tolist())
            if len(seen) >= total:
                stop2.set()
                break
        # rows prefetched-but-undelivered at the save point replayed, none
        # skipped, none doubled
        assert sorted(seen) == list(range(total))
        assert len(seen) == total

    def test_follow_rejects_checkpoint_and_device_cache(self, catalog):
        from lakesoul_tpu.data.jax_iter import LoaderCheckpoint

        t, start, _ = self._table(catalog, name="j3", commits=1)
        with pytest.raises(ConfigError):
            t.scan().to_jax_iter(follow=True, checkpoint=LoaderCheckpoint())
        with pytest.raises(ConfigError):
            t.scan().to_jax_iter(follow=True, cache="device")
        with pytest.raises(ConfigError):
            t.scan().to_jax_iter(device_put=False).follow_state_json()

    def test_batch_source_seam_resolution(self, catalog):
        from lakesoul_tpu.data.batch_source import (
            ScanBatchSource,
            batch_source_for,
        )

        t, start, _ = self._table(catalog, name="j4", commits=1)
        scan = t.scan()
        assert isinstance(batch_source_for(scan), ScanBatchSource)
        src = batch_source_for(scan, follow={"start_timestamp_ms": start})
        assert isinstance(src, FollowBatchSource)
        assert batch_source_for(scan, follow=src) is src
        # a persisted position (state JSON or FollowerState) resumes from
        # it — never silently degrades to follow-from-now
        state = FollowerState()
        for value in (state, state.to_json()):
            resumed = batch_source_for(scan, follow=value)
            assert isinstance(resumed, FollowBatchSource)
            assert resumed.resume_state(0) is not None
        with pytest.raises(ConfigError):
            batch_source_for(scan, follow=42)

    def test_follow_iterator_is_single_pass(self, catalog):
        """Re-iterating would rebuild the follower from the INITIAL state
        while the delivered-row counter kept growing — duplicated rows and
        a corrupt follow_state_json position.  It raises instead."""
        t, start, total = self._table(catalog, name="j5", commits=1)
        stop = threading.Event()
        it = t.scan().batch_size(16).to_jax_iter(
            follow={"start_timestamp_ms": start, "poll_interval": 0.02,
                    "stop_event": stop},
            device_put=False,
        )
        seen = 0
        for batch in it:
            seen += len(batch["seq"])
            if seen >= total:
                stop.set()
                break
        with pytest.raises(ConfigError):
            iter(it).__next__()


# -------------------------------------------------------- writer oracle


class TestWriterRole:
    def test_oracle_sha_is_order_invariant(self):
        from lakesoul_tpu.freshness.__main__ import oracle_sha

        rows = [(2, 0, 1.5), (1, 1, 2.5), (3, 0, 0.5)]
        assert oracle_sha(rows) == oracle_sha(list(reversed(rows)))
        assert oracle_sha(rows) != oracle_sha(rows[:2])

    def test_writer_rejects_in_commit_duplicate_pks(self, tmp_path):
        from lakesoul_tpu.freshness.__main__ import main

        with pytest.raises(SystemExit):
            main([
                "writer", "--warehouse", str(tmp_path / "wh"),
                "--rows-per-commit", "10", "--keyspace", "5",
            ])
