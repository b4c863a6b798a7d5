"""Disaggregated scan plane (PR 11 tentpole).

The acceptance contract, proven here at tier-1 speed with in-process
workers and real Flight exchanges (the subprocess SIGKILL chaos lives in
test_scanplane_chaos.py under the ``slow`` marker, with a quick smoke
variant at the bottom of this file):

- session plans are pinned, deterministic, and shared (same request+table
  state → same session id; ranges shard exactly like ``scan.shard``);
- worker-produced spool segments are byte-identical to the in-process
  scan — for every client rank, over both delivery modes (shared-memory
  fast path and socket);
- the DoExchange verb is JWT/RBAC-gated and admission-bounded exactly
  like do_get (typed UNAVAILABLE sheds under 64 concurrent exchanges);
- a client mid-stream survives its worker dying: the stream stalls until
  a peer produces the range, then completes with no duplicate and no
  missing batch; explicit resume (start_range/start_batch) redelivers
  from exactly the recorded position;
- the batch-source seam makes the plane a drop-in source for
  to_jax_iter / torch / ray — stats, queue-depth and stage attribution
  intact, with the workers' producer stages merged into the client's
  registry tagged ``worker=``.
"""

from __future__ import annotations

import json
import os
import pathlib
import subprocess
import sys
import threading
import time

import numpy as np
import pyarrow as pa
import pyarrow.flight as flight
import pytest

from lakesoul_tpu import LakeSoulCatalog
from lakesoul_tpu.errors import ConfigError
from lakesoul_tpu.obs import registry
from lakesoul_tpu.obs.stages import STAGE_FAMILY
from lakesoul_tpu.scanplane.client import ScanPlaneClient
from lakesoul_tpu.scanplane.delivery import ScanPlaneDelivery
from lakesoul_tpu.scanplane.session import ScanSession
from lakesoul_tpu.scanplane.worker import ScanPlaneWorker
from lakesoul_tpu.scanplane import spool as spool_mod
from lakesoul_tpu.service.flight import LakeSoulFlightServer

REPO = str(pathlib.Path(__file__).resolve().parent.parent)
SCHEMA = pa.schema([("id", pa.int64()), ("v", pa.float64()), ("f", pa.float32())])


def _make_table(tmp_path, *, rows=24_000, commits=3, pk=True, name="t"):
    catalog = LakeSoulCatalog(
        str(tmp_path / "wh"), db_path=str(tmp_path / "meta.db")
    )
    t = catalog.create_table(
        name, SCHEMA,
        primary_keys=["id"] if pk else None,
        hash_bucket_num=2 if pk else None,
    )
    rng = np.random.default_rng(7)
    per = rows // commits
    for _ in range(commits):
        ids = np.sort(rng.choice(rows * 2, per, replace=False)).astype(np.int64)
        t.upsert(pa.table({
            "id": ids,
            "v": rng.normal(size=per),
            "f": rng.normal(size=per).astype(np.float32),
        }, schema=SCHEMA)) if pk else t.write_arrow(pa.table({
            "id": ids, "v": rng.normal(size=per),
            "f": rng.normal(size=per).astype(np.float32),
        }, schema=SCHEMA))
    return catalog, t


class _Plane:
    """In-process fleet: flight server (spool delivery) + worker thread."""

    def __init__(self, catalog, tmp_path, *, workers=1, wait_s=30.0,
                 lease_ttl_s=10.0, jwt_secret=None, start_workers=True,
                 max_inflight=None, max_queue=None):
        self.spool = str(tmp_path / "spool")
        os.makedirs(self.spool, exist_ok=True)
        self.catalog = catalog
        self.delivery = ScanPlaneDelivery(catalog, self.spool, wait_s=wait_s)
        self.server = LakeSoulFlightServer(
            catalog, "grpc://127.0.0.1:0", scanplane=self.delivery,
            jwt_secret=jwt_secret, max_inflight=max_inflight,
            max_queue=max_queue,
        )
        threading.Thread(target=self.server.serve, daemon=True).start()
        self.location = f"grpc://127.0.0.1:{self.server.port}"
        self._stops = []
        self.workers = [
            ScanPlaneWorker(
                catalog, self.spool, lease_ttl_s=lease_ttl_s,
                poll_interval_s=0.02, worker_id=f"w{i}",
            )
            for i in range(workers)
        ]
        if start_workers:
            for w in self.workers:
                self.start_worker(w)

    def start_worker(self, w):
        stop = threading.Event()
        self._stops.append(stop)
        threading.Thread(
            target=w.run_forever, kwargs={"stop_event": stop}, daemon=True
        ).start()
        return stop

    def close(self):
        for s in self._stops:
            s.set()
        self.server.shutdown()


# ---------------------------------------------------------------- sessions


class TestSession:
    def test_plan_is_pinned_and_shared(self, tmp_path):
        catalog, t = _make_table(tmp_path, rows=6000)
        req = {"table": "t", "batch_size": 2048}
        a = ScanSession.plan(catalog, req)
        b = ScanSession.plan(catalog, {"table": "t", "batch_size": 2048,
                                       "namespace": "default"})
        assert a.session_id == b.session_id  # canonicalized request
        assert len(a.ranges) == len(t.scan().scan_plan())
        # a commit changes the version digest → a NEW session
        t.upsert(pa.table({
            "id": np.arange(8, dtype=np.int64),
            "v": np.zeros(8), "f": np.zeros(8, dtype=np.float32),
        }, schema=SCHEMA))
        c = ScanSession.plan(catalog, req)
        assert c.session_id != a.session_id

    def test_manifest_round_trip(self, tmp_path):
        catalog, _ = _make_table(tmp_path, rows=4000)
        session = ScanSession.plan(catalog, {"table": "t"})
        sdir = session.publish(str(tmp_path / "spool"))
        assert os.path.isdir(sdir)
        loaded = ScanSession.load(str(tmp_path / "spool"), session.session_id)
        assert loaded.to_json() == session.to_json()
        assert [u.data_files for u in loaded.ranges] == [
            u.data_files for u in session.ranges
        ]

    def test_client_ranges_match_scan_shard(self, tmp_path):
        catalog, t = _make_table(tmp_path, rows=8000)
        session = ScanSession.plan(catalog, {"table": "t"})
        units = t.scan().scan_plan()
        for world in (2, 3):
            for rank in range(world):
                picked = [
                    tuple(session.ranges[i].data_files)
                    for i in session.client_ranges(rank, world)
                ]
                sharded = [
                    tuple(u.data_files)
                    for u in t.scan().shard(rank, world).scan_plan()
                ]
                assert picked == sharded, (rank, world)
        assert session.client_ranges(None, None) == list(range(len(units)))

    def test_unsessionable_scans_rejected(self, tmp_path):
        from lakesoul_tpu.scanplane.session import session_request_from_scan

        catalog, t = _make_table(tmp_path, rows=2000)
        with pytest.raises(ConfigError, match="snapshot"):
            session_request_from_scan(t.scan().snapshot_at(1))
        with pytest.raises(ConfigError, match="cache"):
            session_request_from_scan(t.scan().cache())

    def test_cdc_delete_flag_rides_the_session(self, tmp_path):
        """with_cdc_deletes() must survive the request round trip — a
        worker rebuilding the scan server-side would otherwise silently
        DROP the delete rows the caller asked to keep."""
        from lakesoul_tpu.scanplane.session import (
            canonical_request,
            scan_for_request,
            session_request_from_scan,
        )

        catalog, t = _make_table(tmp_path, rows=2000)
        req = session_request_from_scan(t.scan().with_cdc_deletes())
        assert req["keep_cdc_deletes"] is True
        rebuilt = scan_for_request(catalog, req)
        assert rebuilt._keep_cdc_deletes is True
        # the flag is part of the session key: keep vs drop are DIFFERENT
        # sessions (different delivered rows on CDC tables)
        assert canonical_request(req) != canonical_request(
            session_request_from_scan(t.scan())
        )


# ------------------------------------------------------------------- spool


class TestSpool:
    def test_round_trip_zero_copy_and_sidecar(self, tmp_path):
        sdir = str(tmp_path)
        t = pa.table({"x": np.arange(1000, dtype=np.int64)})
        batches = t.to_batches(max_chunksize=256)
        side = spool_mod.write_range(
            sdir, 3, t.schema, iter(batches), holder="w0",
            meta={"worker": "w0", "fence": 2},
        )
        assert side["rows"] == 1000 and side["batches"] == 4
        assert spool_mod.range_ready(sdir, 3)
        assert spool_mod.ready_ranges(sdir) == {3}
        schema, got = spool_mod.read_range(sdir, 3)
        assert schema == t.schema
        assert [b.num_rows for b in got] == [256, 256, 256, 232]
        assert pa.Table.from_batches(got).equals(t)
        # zero-copy: the numpy view aliases the mapping, no materialization
        arr = got[0].column(0).to_numpy(zero_copy_only=True)
        assert arr[5] == 5
        assert spool_mod.read_sidecar(sdir, 3)["fence"] == 2

    def test_tmp_debris_swept_publication_atomic(self, tmp_path):
        sdir = str(tmp_path)
        # a dead producer's half-written files
        open(os.path.join(sdir, "range-00001.arrow.tmp-dead"), "wb").write(b"x")
        open(os.path.join(sdir, "range-00001.json.tmp-dead"), "w").write("{}")
        assert not spool_mod.range_ready(sdir, 1)
        spool_mod.sweep_tmp_debris(sdir, 1)
        assert os.listdir(sdir) == []


# ------------------------------------------------------------------ worker


class TestWorker:
    def test_produces_byte_identical_ranges(self, tmp_path):
        catalog, t = _make_table(tmp_path)
        spool_dir = str(tmp_path / "spool")
        session = ScanSession.plan(catalog, {"table": "t", "batch_size": 4096})
        session.publish(spool_dir)
        worker = ScanPlaneWorker(catalog, spool_dir, lease_ttl_s=10)
        counts = worker.poll_once()
        assert counts["produced"] == len(session.ranges)
        assert counts["errors"] == 0
        # concatenated spool batches == the serial in-process stream
        got = []
        sdir = session.dir(spool_dir)
        for i in range(len(session.ranges)):
            _, batches = spool_mod.read_range(sdir, i)
            got.extend(batches)
        want = list(t.scan().batch_size(4096).to_batches())
        assert len(got) == len(want)
        for a, b in zip(got, want):
            assert a.equals(b)
        # sidecars carry producer attribution: stages + fencing token
        side = spool_mod.read_sidecar(sdir, 0)
        assert side["fence"] >= 1 and side["worker"] == worker.worker_id
        assert "decode" in side.get("stages", {})

    def test_live_peer_lease_respected_then_taken_over(self, tmp_path):
        catalog, _ = _make_table(tmp_path, rows=4000)
        spool_dir = str(tmp_path / "spool")
        session = ScanSession.plan(catalog, {"table": "t"})
        session.publish(spool_dir)
        store = catalog.client.store
        key = f"scanplane/{session.session_id}/0"
        # a live peer holds range 0 with a long TTL: respected
        assert store.acquire_lease(key, "peer", 60_000) is not None
        worker = ScanPlaneWorker(catalog, spool_dir, lease_ttl_s=5)
        counts = worker.poll_once()
        assert counts["lease_held"] == 1
        assert not spool_mod.range_ready(session.dir(spool_dir), 0)
        # the peer dies (lease expires): the worker takes over and produces
        expired = store.get_lease(key)
        assert store.renew_lease(key, "peer", expired.fencing_token, 1) is not None
        time.sleep(0.05)
        counts = worker.poll_once()
        assert counts["produced"] >= 1
        assert spool_mod.range_ready(session.dir(spool_dir), 0)
        # the takeover bumped the fencing token past the dead peer's
        assert spool_mod.read_sidecar(session.dir(spool_dir), 0)["fence"] == 2


# ---------------------------------------------------- exchange: inline mode


@pytest.fixture()
def inline_gateway(tmp_path):
    catalog, t = _make_table(tmp_path)
    server = LakeSoulFlightServer(catalog, "grpc://127.0.0.1:0")
    yield catalog, t, server, f"grpc://127.0.0.1:{server.port}"
    server.shutdown()


class TestExchangeInline:
    def test_byte_identity_and_shards(self, inline_gateway):
        _, t, _, loc = inline_gateway
        client = ScanPlaneClient(loc)
        local = list(t.scan().batch_size(4096).to_batches())
        remote = list(client.iter_batches({"table": "t", "batch_size": 4096}))
        assert len(remote) == len(local)
        for a, b in zip(remote, local):
            assert a.equals(b)
        for rank in range(3):
            want = list(t.scan().batch_size(4096).shard(rank, 3).to_batches())
            got = list(client.iter_batches(
                {"table": "t", "batch_size": 4096}, rank=rank, world=3
            ))
            assert len(got) == len(want)
            assert all(a.equals(b) for a, b in zip(got, want))

    def test_projection_and_filter_ride_the_session(self, inline_gateway):
        _, t, _, loc = inline_gateway
        client = ScanPlaneClient(loc)
        scan = t.scan().select(["id", "f"]).filter("id < 1000").batch_size(2048)
        want = list(scan.to_batches())
        got = list(client.iter_batches({
            "table": "t", "columns": ["id", "f"],
            "filter": scan._filter._to_dict(), "batch_size": 2048,
        }))
        assert sum(b.num_rows for b in got) == sum(b.num_rows for b in want)
        assert all(a.equals(b) for a, b in zip(got, want))
        assert got[0].schema.names == ["id", "f"]

    def test_unknown_verb_rejected(self, inline_gateway):
        *_, loc = inline_gateway
        fc = flight.FlightClient(loc)
        desc = flight.FlightDescriptor.for_command(
            json.dumps({"verb": "nope", "table": "t"}).encode()
        )
        writer, reader = fc.do_exchange(desc)
        with pytest.raises(flight.FlightServerError, match="unknown exchange verb"):
            with writer:
                reader.read_chunk()


# ------------------------------------------------------ exchange: auth/RBAC


class TestExchangeAuth:
    def _secured(self, tmp_path):
        catalog, t = _make_table(tmp_path, rows=4000)
        catalog.client.create_table(
            "priv", f"{tmp_path}/wh/default/priv", SCHEMA, domain="team1"
        )
        server = LakeSoulFlightServer(
            catalog, "grpc://127.0.0.1:0", jwt_secret="s3cr3t"
        )
        from lakesoul_tpu.service.jwt import Claims

        token = server.jwt_server.create_token(
            Claims(sub="alice", group="public")
        )
        return catalog, t, server, f"grpc://127.0.0.1:{server.port}", token

    def test_unauthenticated_exchange_rejected(self, tmp_path):
        *_, server, loc, _ = self._secured(tmp_path)
        try:
            client = ScanPlaneClient(loc, max_attempts=1)  # no auth header
            with pytest.raises(flight.FlightUnauthenticatedError):
                list(client.iter_batches({"table": "t"}))
        finally:
            server.shutdown()

    def test_rbac_denied_on_foreign_domain_table(self, tmp_path):
        *_, server, loc, token = self._secured(tmp_path)
        try:
            client = ScanPlaneClient(loc, token=token, max_attempts=1)
            with pytest.raises(flight.FlightUnauthorizedError):
                list(client.iter_batches({"table": "priv"}))
            # the same identity streams public tables fine
            rows = sum(
                b.num_rows for b in client.iter_batches({"table": "t"})
            )
            assert rows > 0
        finally:
            server.shutdown()

    def test_tampered_token_rejected(self, tmp_path):
        *_, server, loc, token = self._secured(tmp_path)
        try:
            bad = token[:-4] + ("AAAA" if token[-4:] != "AAAA" else "BBBB")
            client = ScanPlaneClient(loc, token=bad, max_attempts=1)
            with pytest.raises(flight.FlightUnauthenticatedError):
                list(client.iter_batches({"table": "t"}))
        finally:
            server.shutdown()


# ------------------------------------------- exchange: overload (64 clients)


class TestExchangeOverload:
    def test_64_concurrent_exchanges_typed_sheds_bounded_queue(self, tmp_path):
        """The new verb rides the SAME admission gate as do_get/do_put:
        beyond max_inflight + max_queue, exchanges shed with Flight
        UNAVAILABLE (typed, retryable) instead of stacking an unbounded
        backlog — the test_resilience overload pattern on DoExchange."""
        catalog, t = _make_table(tmp_path, rows=32_000)
        server = LakeSoulFlightServer(
            catalog, "grpc://127.0.0.1:0", max_inflight=2, max_queue=2,
        )
        loc = f"grpc://127.0.0.1:{server.port}"
        want_rows = t.scan().count_rows()
        results = {"ok": 0, "shed": 0}
        guard = threading.Lock()
        gate = threading.Event()

        def client_run():
            gate.wait()
            c = ScanPlaneClient(loc, max_attempts=1)  # no retry: count sheds
            try:
                rows = sum(
                    b.num_rows
                    for b in c.iter_batches({"table": "t", "batch_size": 2048})
                )
                assert rows == want_rows
                with guard:
                    results["ok"] += 1
            except flight.FlightUnavailableError:
                with guard:
                    results["shed"] += 1

        threads = [threading.Thread(target=client_run) for _ in range(64)]
        try:
            for th in threads:
                th.start()
            gate.set()
            for th in threads:
                th.join(120.0)
            assert results["ok"] + results["shed"] == 64
            assert results["ok"] > 0 and results["shed"] > 0, results
            snap = server.admission.snapshot()
            assert snap["inflight"] == 0 and snap["waiting"] == 0
        finally:
            server.shutdown()


# ------------------------------------- spool delivery, shm, death, resume


class TestSpoolDelivery:
    def test_shm_and_socket_paths_byte_identical(self, tmp_path):
        catalog, t = _make_table(tmp_path)
        plane = _Plane(catalog, tmp_path)
        try:
            local = list(t.scan().batch_size(4096).to_batches())
            before = registry().snapshot().get(
                'lakesoul_scanplane_client_ranges_total{mode="shm"}', 0
            )
            shm_client = ScanPlaneClient(plane.location, shm=True)
            got = list(shm_client.iter_batches({"table": "t", "batch_size": 4096}))
            assert len(got) == len(local)
            assert all(a.equals(b) for a, b in zip(got, local))
            after = registry().snapshot().get(
                'lakesoul_scanplane_client_ranges_total{mode="shm"}', 0
            )
            assert after > before  # the fast path actually engaged
            sock_client = ScanPlaneClient(plane.location, shm=False)
            got2 = list(sock_client.iter_batches({"table": "t", "batch_size": 4096}))
            assert all(a.equals(b) for a, b in zip(got2, local))
        finally:
            plane.close()

    def test_worker_stages_merged_into_client_registry(self, tmp_path):
        catalog, t = _make_table(tmp_path)
        plane = _Plane(catalog, tmp_path)
        try:
            client = ScanPlaneClient(plane.location)
            list(client.iter_batches({"table": "t", "batch_size": 8192}))
            tagged = [
                k for k in registry().snapshot()
                if k.startswith("lakesoul_scan_stage_seconds")
                and 'worker="w0"' in k
            ]
            assert any('stage="decode"' in k for k in tagged), tagged
        finally:
            plane.close()

    def test_client_survives_worker_death_mid_stream(self, tmp_path):
        """A client consuming while its worker dies: the stream stalls on
        the unproduced range until a peer produces it, then completes —
        no duplicate, no missing batches (the mid-stream recovery leg of
        the DoExchange coverage satellite)."""
        catalog, t = _make_table(tmp_path)
        plane = _Plane(catalog, tmp_path, workers=0, wait_s=60)
        try:
            session = plane.delivery.resolve_session(
                {"table": "t", "batch_size": 4096}
            )
            nranges = len(session.ranges)
            assert nranges >= 2
            store = catalog.client.store
            # the doomed worker "w-dead" produces ONLY range 0 (a live
            # lease from this test blocks the rest), then dies
            held = []
            for i in range(1, nranges):
                key = f"scanplane/{session.session_id}/{i}"
                held.append((key, store.acquire_lease(key, "blocker", 60_000)))
            w_dead = ScanPlaneWorker(
                catalog, plane.spool, worker_id="w-dead", lease_ttl_s=5
            )
            counts = w_dead.poll_once()
            assert counts["produced"] == 1 and counts["lease_held"] == nranges - 1

            got = []
            done = threading.Event()
            errors = []

            def consume():
                try:
                    c = ScanPlaneClient(plane.location)
                    for b in c.iter_batches({"table": "t", "batch_size": 4096}):
                        got.append(b)
                    done.set()
                except BaseException as e:  # surfaced below
                    errors.append(e)
                    done.set()

            threading.Thread(target=consume, daemon=True).start()
            # the stream delivers range 0 then stalls (worker dead, leases
            # still held by the "dead" holder)
            time.sleep(0.5)
            assert not done.is_set()
            assert len(got) >= 1
            # the dead holder's leases expire → a peer takes over
            for key, lease in held:
                store.release_lease(key, "blocker", lease.fencing_token)
            peer = ScanPlaneWorker(
                catalog, plane.spool, worker_id="w-peer", lease_ttl_s=5
            )
            peer.poll_once()
            assert done.wait(30.0), "client never completed after takeover"
            assert not errors, errors
            want = list(t.scan().batch_size(4096).to_batches())
            assert len(got) == len(want)
            assert all(a.equals(b) for a, b in zip(got, want))
        finally:
            plane.close()

    def test_reconnect_pin_survives_commits_and_fails_loudly_when_gone(
        self, tmp_path
    ):
        """Resume-by-position is only exactly-once against the SAME plan:
        a pinned session keeps serving its pinned ranges even after the
        table advances (the manifest is still spooled), and a pin that no
        longer resolves (pruned spool) fails the stream loudly instead of
        silently serving a different plan's rows."""
        import shutil

        from lakesoul_tpu.errors import LakeSoulError

        catalog, t = _make_table(tmp_path)
        plane = _Plane(catalog, tmp_path)
        try:
            req = {"table": "t", "batch_size": 4096}
            pinned = plane.delivery.resolve_session(req)
            # a commit lands mid-stream: unpinned requests mint a NEW
            # session, the pinned one still resolves to the OLD plan
            t.upsert(pa.table({
                "id": np.arange(4, dtype=np.int64),
                "v": np.zeros(4), "f": np.zeros(4, dtype=np.float32),
            }, schema=SCHEMA))
            fresh = plane.delivery.resolve_session(req)
            assert fresh.session_id != pinned.session_id
            again = plane.delivery.resolve_session(
                {**req, "session": pinned.session_id}
            )
            assert again.session_id == pinned.session_id
            assert again.version_digest == pinned.version_digest
            # the pinned spool vanishes (prune): the stream must die loud.  The worker polls the
            # spool and may be writing a range into it: stop it, and remove until nothing is left
            for stop in plane._stops:
                stop.set()
            deadline = time.monotonic() + 10.0  # a worker leaves its loop within a poll of 0.02 s
            while os.path.exists(pinned.dir(plane.spool)):
                shutil.rmtree(pinned.dir(plane.spool), ignore_errors=True)
                assert time.monotonic() < deadline, "a stopped worker still writes into the pinned spool"
            with pytest.raises(LakeSoulError, match="no longer exists"):
                plane.delivery.resolve_session(
                    {**req, "session": pinned.session_id}
                )
        finally:
            plane.close()

    def test_explicit_resume_positions_redeliver_exactly(self, tmp_path):
        catalog, t = _make_table(tmp_path)
        plane = _Plane(catalog, tmp_path)
        try:
            req = {"table": "t", "batch_size": 2048}
            for shm in (True, False):
                client = ScanPlaneClient(plane.location, shm=shm)
                full = list(client.iter_batches(req))
                # ranges have >1 batch each at this batch size; resume from
                # (range 1, batch 2) must equal the tail of the full stream
                session = plane.delivery.resolve_session(req)
                first_range_batches = spool_mod.read_sidecar(
                    session.dir(plane.spool),
                    session.client_ranges(None, None)[0],
                )["batches"]
                resumed = list(client.iter_batches(
                    req, start_range=1, start_batch=2
                ))
                want = full[first_range_batches + 2:]
                assert len(resumed) == len(want)
                assert all(a.equals(b) for a, b in zip(resumed, want)), shm
        finally:
            plane.close()


# --------------------------------------------------- seam: jax / torch / ray


class TestBatchSourceSeam:
    def test_jax_iter_drop_in_with_stats_and_attribution(self, tmp_path):
        catalog, t = _make_table(tmp_path)
        plane = _Plane(catalog, tmp_path)
        try:
            client = ScanPlaneClient(plane.location)
            scan = t.scan().batch_size(2048).via_scanplane(client)
            it = scan.to_jax_iter(
                device_put=False, drop_remainder=False, consumer="trainer-0"
            )
            remote_rows = sum(len(b["id"]) for b in it)
            assert remote_rows == t.scan().count_rows()
            stats = it.stats()
            assert stats["rows"] == remote_rows and stats["batches"] > 0
            assert stats["rows_per_sec"] > 0
            # per-client queue attribution (the consumer= satellite)
            queue_consumers = {
                labels.get("consumer") for labels, _ in registry().series(STAGE_FAMILY)
                if labels.get("stage") == "queue"
            }
            assert "trainer-0" in queue_consumers
            # byte-identity through the full loader: collate output equals
            # the local loader's
            local_it = t.scan().batch_size(2048).to_jax_iter(
                device_put=False, drop_remainder=False
            )
            remote_it = scan.to_jax_iter(device_put=False, drop_remainder=False)
            for rb, lb in zip(remote_it, local_it):
                assert set(rb) == set(lb)
                for k in rb:
                    np.testing.assert_array_equal(rb[k], lb[k])
        finally:
            plane.close()

    def test_to_batches_and_to_arrow_route_remote(self, tmp_path):
        catalog, t = _make_table(tmp_path)
        plane = _Plane(catalog, tmp_path)
        try:
            client = ScanPlaneClient(plane.location)
            scan = t.scan().batch_size(4096).via_scanplane(client)
            local = list(t.scan().batch_size(4096).to_batches())
            got = list(scan.to_batches())
            assert all(a.equals(b) for a, b in zip(got, local))
            assert len(got) == len(local)
            # limit + skip stay client-side and exact
            lim = list(scan.limit(5000).to_batches())
            assert sum(b.num_rows for b in lim) == 5000
            assert scan.to_arrow().equals(
                pa.Table.from_batches(local)
            )
        finally:
            plane.close()

    def test_torch_adapter_rides_the_seam(self, tmp_path, monkeypatch):
        import types

        tud = types.ModuleType("torch.utils.data")

        class _IterableDataset:
            pass

        tud.IterableDataset = _IterableDataset
        torch_mod = types.ModuleType("torch")
        utils_mod = types.ModuleType("torch.utils")
        utils_mod.data = tud
        torch_mod.utils = utils_mod
        monkeypatch.setitem(sys.modules, "torch", torch_mod)
        monkeypatch.setitem(sys.modules, "torch.utils", utils_mod)
        monkeypatch.setitem(sys.modules, "torch.utils.data", tud)

        catalog, t = _make_table(tmp_path)
        plane = _Plane(catalog, tmp_path)
        try:
            client = ScanPlaneClient(plane.location)
            local = list(t.scan().batch_size(4096).to_torch())
            remote = list(
                t.scan().batch_size(4096).via_scanplane(client).to_torch()
            )
            assert len(remote) == len(local) > 0
            assert all(a.equals(b) for a, b in zip(remote, local))
        finally:
            plane.close()

    def test_ray_adapter_fans_out_per_range(self, tmp_path, monkeypatch):
        # wire-faithful ray stub (test_adapters contract)
        import types
        from collections.abc import Mapping

        import pandas as pd

        class _StubDataset:
            def __init__(self, rows):
                self.rows = rows

            def map_batches(self, fn, *, batch_size=None, batch_format="pandas"):
                out = []
                size = batch_size or max(1, len(self.rows))
                for start in range(0, len(self.rows), size):
                    df = pd.DataFrame(self.rows[start:start + size])
                    result = fn(df)
                    out.extend(result.to_pylist())
                return _StubDataset(out)

            def to_arrow(self):
                return pa.Table.from_pylist(self.rows)

        ray = types.ModuleType("ray")
        ray_data = types.ModuleType("ray.data")
        ray_data.from_items = lambda items: _StubDataset(
            [dict(it) if isinstance(it, Mapping) else {"item": it} for it in items]
        )
        ray.data = ray_data
        monkeypatch.setitem(sys.modules, "ray", ray)
        monkeypatch.setitem(sys.modules, "ray.data", ray_data)

        from lakesoul_tpu.data.ray_adapter import read_lakesoul

        catalog, t = _make_table(tmp_path)
        plane = _Plane(catalog, tmp_path)
        try:
            client = ScanPlaneClient(plane.location)
            scan = t.scan().batch_size(4096).via_scanplane(client)
            ds = read_lakesoul(scan)
            got = ds.to_arrow().sort_by("id")
            want = t.to_arrow().sort_by("id")
            assert got.num_rows == want.num_rows
            assert got.column("id").to_pylist() == want.column("id").to_pylist()
            assert got.column("v").to_pylist() == want.column("v").to_pylist()
        finally:
            plane.close()


# --------------------------------------------------------- subprocess smoke


class TestServiceEntrySmoke:
    def test_service_entry_serves_a_drive_client(self, tmp_path):
        """Quick tier-1 smoke of the REAL deployable entry: service role
        (gateway + 1 worker child) plus the drive role as a verification
        client — sha-identical to the in-process scan.  The SIGKILL chaos
        variants live in test_scanplane_chaos.py (slow)."""
        catalog, t = _make_table(tmp_path, rows=8000)
        env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO)
        svc = subprocess.Popen(
            [sys.executable, "-m", "lakesoul_tpu.scanplane", "service",
             "--warehouse", str(tmp_path / "wh"),
             "--db-path", str(tmp_path / "meta.db"),
             "--workers", "1", "--spool", str(tmp_path / "spool"),
             "--lease-ttl-s", "5", "--poll-s", "0.05"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )
        try:
            handle = json.loads(svc.stdout.readline())
            drv = subprocess.run(
                [sys.executable, "-m", "lakesoul_tpu.scanplane", "drive",
                 "--location", handle["location"], "--table", "t",
                 "--batch-size", "4096"],
                env=env, capture_output=True, text=True, timeout=120,
            )
            assert drv.returncode == 0, drv.stderr[-2000:]
            out = json.loads(drv.stdout)
            assert out["rows"] == t.scan().count_rows()
            # sha of the remote stream == sha of the local stream
            import hashlib

            digest = hashlib.sha256()
            for b in t.scan().batch_size(4096).to_batches():
                sink = pa.BufferOutputStream()
                with pa.ipc.new_stream(sink, b.schema) as w:
                    w.write_batch(b)
                digest.update(sink.getvalue().to_pybytes())
            assert out["sha256"] == digest.hexdigest()
        finally:
            svc.terminate()
            try:
                svc.wait(10.0)
            except subprocess.TimeoutExpired:
                svc.kill()
