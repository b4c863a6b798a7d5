"""leakcheck: the runtime resource-leak detector must catch seeded
thread/child/debris/fd/heap leaks (each with its creation stack), stay
silent on well-behaved lifecycles and sanctioned pool threads,
instrument/restore the creation seams cleanly, and record-never-raise —
plus regression pins for the three leaks the boundedness pack surfaced
and this PR fixed at source (exporter serve-thread join, autoscaler
retire reaping, stale-spool pruning)."""

from __future__ import annotations

import os
import subprocess
import sys
import tempfile
import threading
import time

import pytest

from lakesoul_tpu.analysis import leakcheck


@pytest.fixture()
def armed():
    leakcheck.reset()
    leakcheck.enable()
    yield
    leakcheck.disable()
    leakcheck.reset()


# ----------------------------------------------------------- control surface


def test_env_gate(monkeypatch):
    monkeypatch.delenv("LAKESOUL_LEAKCHECK", raising=False)
    assert not leakcheck.env_requested()
    monkeypatch.setenv("LAKESOUL_LEAKCHECK", "1")
    assert leakcheck.env_requested()
    monkeypatch.setenv("LAKESOUL_LEAKCHECK", "0")
    assert not leakcheck.env_requested()


def test_instrument_and_restore():
    """enable() swaps the four creation seams; disable() puts the real
    callables back — no wrapper may survive, other suites patch the same
    seams."""
    from lakesoul_tpu.runtime import atomicio

    real_start = threading.Thread.start
    real_init = subprocess.Popen.__init__
    real_stage = atomicio.stage_stream
    real_mkdtemp = tempfile.mkdtemp
    leakcheck.reset()
    leakcheck.enable()
    try:
        assert leakcheck.enabled()
        assert threading.Thread.start is not real_start
        assert subprocess.Popen.__init__ is not real_init
        assert atomicio.stage_stream is not real_stage
        assert tempfile.mkdtemp is not real_mkdtemp
        leakcheck.enable()  # idempotent: no double wrap
    finally:
        leakcheck.disable()
        leakcheck.reset()
    assert not leakcheck.enabled()
    assert threading.Thread.start is real_start
    assert subprocess.Popen.__init__ is real_init
    assert atomicio.stage_stream is real_stage
    assert tempfile.mkdtemp is real_mkdtemp


# ------------------------------------------------------------- seeded leaks


def test_seeded_thread_leak_with_creation_stack(armed):
    stop = threading.Event()
    leaked = threading.Thread(target=stop.wait, name="seeded-leak", daemon=True)
    try:
        with leakcheck.scope("seeded") as s:
            leaked.start()
        kinds = [v.kind for v in s.leaks]
        assert kinds == ["thread-leak"]
        v = s.leaks[0]
        assert "seeded-leak" in v.message
        # the creation stack rides on the report — it names THIS file
        assert v.stacks and "test_leakcheck" in v.stacks[0]
        # recorded, never raised: the scope exits normally and the
        # violation sits in the module registry for the fixture to assert
        assert v in leakcheck.violations()
    finally:
        stop.set()
        leaked.join(timeout=5.0)


def test_joined_thread_and_sanctioned_pool_thread_silent(armed):
    stop = threading.Event()
    with leakcheck.scope("clean") as s:
        # joined before scope end — not a leak
        t = threading.Thread(target=stop.wait, daemon=True)
        t.start()
        stop.set()
        t.join(timeout=5.0)
        # the process-wide pool singleton's threads outlive scopes by
        # design; the sanctioned prefix exempts them
        hold = threading.Event()
        pool_t = threading.Thread(
            target=hold.wait, name="lakesoul-rt-sanctioned", daemon=True
        )
        pool_t.start()
    try:
        assert s.leaks == [], "\n".join(v.render() for v in s.leaks)
    finally:
        hold.set()
        pool_t.join(timeout=5.0)


def test_seeded_child_leak_then_reaped_clean(armed):
    with leakcheck.scope("spawned") as s:
        child = subprocess.Popen(
            [sys.executable, "-c", "import time; time.sleep(60)"],
            stdout=subprocess.DEVNULL,
            stderr=subprocess.DEVNULL,
        )
    try:
        assert [v.kind for v in s.leaks] == ["child-leak"]
        assert str(child.pid) in s.leaks[0].message
        assert s.leaks[0].stacks and "test_leakcheck" in s.leaks[0].stacks[0]
    finally:
        child.kill()
        child.wait(timeout=10.0)
    # a reaped child is not a leak
    leakcheck.reset()
    with leakcheck.scope("reaped") as s2:
        done = subprocess.Popen([sys.executable, "-c", "pass"])
        done.wait(timeout=30.0)
    assert s2.leaks == [], "\n".join(v.render() for v in s2.leaks)


def test_staged_tmp_debris_vs_committed(armed, tmp_path):
    from lakesoul_tpu.runtime import atomicio

    with leakcheck.scope("staged") as s:
        staged = atomicio.stage_stream(
            str(tmp_path / "doc.json"), lambda f: f.write(b"{}")
        )
        # ... and nothing ever commits or aborts it
    assert [v.kind for v in s.leaks] == ["debris"]
    assert staged.tmp in s.leaks[0].message
    staged.abort()
    leakcheck.reset()
    with leakcheck.scope("committed") as s2:
        ok = atomicio.stage_stream(
            str(tmp_path / "ok.json"), lambda f: f.write(b"{}")
        )
        ok.commit()
    assert s2.leaks == [], "\n".join(v.render() for v in s2.leaks)
    assert (tmp_path / "ok.json").read_bytes() == b"{}"


def test_mkdtemp_debris_vs_pruned(armed):
    import shutil

    with leakcheck.scope("scratch") as s:
        d = tempfile.mkdtemp(prefix="leakcheck-seed-")
    try:
        assert [v.kind for v in s.leaks] == ["debris"]
        assert d in s.leaks[0].message
    finally:
        shutil.rmtree(d, ignore_errors=True)
    leakcheck.reset()
    with leakcheck.scope("pruned") as s2:
        d2 = tempfile.mkdtemp(prefix="leakcheck-seed-")
        shutil.rmtree(d2)
    assert s2.leaks == [], "\n".join(v.render() for v in s2.leaks)


def test_fd_leak_only_for_scratch_targets(armed, tmp_path):
    scratch = tmp_path / "spool.tmp-seed"
    scratch.write_bytes(b"x")
    plain = tmp_path / "warehouse.bin"
    plain.write_bytes(b"y")
    with leakcheck.scope("fds") as s:
        held_scratch = open(scratch, "rb")
        held_plain = open(plain, "rb")  # legitimate cache shape: silent
    try:
        assert [v.kind for v in s.leaks] == ["fd-leak"]
        assert ".tmp-" in s.leaks[0].message
    finally:
        held_scratch.close()
        held_plain.close()


def test_heap_budget_gate(armed):
    import tracemalloc

    tracemalloc.start()
    try:
        with leakcheck.scope("heap", heap_budget=1_000_000) as s:
            ballast = bytearray(8_000_000)
        assert [v.kind for v in s.leaks] == ["heap-growth"]
        assert "budget 1000000" in s.leaks[0].message
        del ballast
        leakcheck.reset()
        with leakcheck.scope("flat", heap_budget=1_000_000) as s2:
            small = bytearray(1024)
            del small
        assert s2.leaks == []
    finally:
        tracemalloc.stop()


def test_disabled_records_nothing():
    leakcheck.reset()
    assert not leakcheck.enabled()
    stop = threading.Event()
    t = threading.Thread(target=stop.wait, daemon=True)
    with leakcheck.scope("dark") as s:
        t.start()
        d = tempfile.mkdtemp(prefix="leakcheck-dark-")
    try:
        # untracked artifacts can't be reported; the un-instrumented
        # thread IS visible via threading.enumerate, but carries no stack
        assert all(v.kind == "thread-leak" for v in s.leaks)
        for v in s.leaks:
            assert v.stacks == ()
    finally:
        stop.set()
        t.join(timeout=5.0)
        os.rmdir(d)
        leakcheck.reset()


# ------------------------------------------------------ lifecycle slopes


def test_open_scan_serve_close_cycles_hold_counts_flat(armed, tmp_path):
    """Repeated whole lifecycles (open a catalog, scan the table, serve one
    real ``/metrics`` scrape, shut down): the first-third against
    last-third averages of fds, threads and tracked children must be flat.
    A lifecycle that leaks one of them a cycle climbs by ``cycles / 3`` and
    fails; the allowances (2 fds, 1 thread) are for the pool's lazy start."""
    import gc
    import urllib.request

    import numpy as np
    import pyarrow as pa

    from lakesoul_tpu import LakeSoulCatalog
    from lakesoul_tpu.obs.exporter import serve_prometheus

    wh = str(tmp_path / "wh")
    n_rows, cycles = 4_000, 9
    table = LakeSoulCatalog(wh).create_table(
        "soak", pa.schema([("id", pa.int64()), ("v", pa.float64())])
    )
    table.write_arrow(pa.table({
        "id": np.arange(n_rows, dtype=np.int64),
        "v": np.random.default_rng(0).normal(size=n_rows),
    }))
    del table
    samples = []
    for _ in range(cycles):
        cat = LakeSoulCatalog(wh)
        assert len(cat.table("soak").to_arrow()) == n_rows
        srv = serve_prometheus(port=0, host="127.0.0.1")
        port = srv.server_address[1]
        with urllib.request.urlopen(
            f"http://127.0.0.1:{port}/metrics", timeout=10
        ) as resp:
            assert resp.status == 200 and resp.read()
        srv.shutdown()
        srv.server_close()
        del cat, srv
        gc.collect()
        snap = leakcheck.snapshot()
        samples.append((snap.fd_count, snap.thread_count, len(snap.children)))

    third = cycles // 3

    def slope(idx: int) -> float:
        first = [s[idx] for s in samples[:third]]
        last = [s[idx] for s in samples[-third:]]
        return sum(last) / third - sum(first) / third

    assert slope(0) <= 2.0, samples
    assert slope(1) <= 1.0, samples
    assert slope(2) <= 0.0, samples


# ------------------------------------------- regression pins (fixed leaks)


def test_exporter_shutdown_joins_serve_thread(armed):
    """PIN: serve_prometheus used to start an anonymous un-joinable
    thread; shutdown() must now join it — under leakcheck the serve scope
    ends thread-clean."""
    from lakesoul_tpu.obs.exporter import serve_prometheus

    with leakcheck.scope("exporter") as s:
        srv = serve_prometheus(port=0, host="127.0.0.1")
        thread = srv._serve_thread
        assert thread.name == "lakesoul-metrics-exporter"
        srv.shutdown()
        srv.server_close()
        assert not thread.is_alive()
    assert s.leaks == [], "\n".join(v.render() for v in s.leaks)


def test_autoscaler_retire_reaps_terminated_child(armed, tmp_path):
    """PIN: retire() used to pop+terminate and drop the handle — a zombie
    until interpreter exit.  It must now park the child on a retiring
    list that reap()/stop_all() waits, collecting the exit status."""
    from lakesoul_tpu.fleet.autoscale import WorkerSpawner

    spawner = WorkerSpawner(str(tmp_path), str(tmp_path))
    spawner.worker_argv = lambda worker_id: [
        sys.executable, "-c", "import time; time.sleep(60)",
    ]
    with leakcheck.scope("retire") as s:
        spawner.spawn()
        child = spawner._children[0]
        spawner.retire()
        deadline = time.monotonic() + 10.0
        while child.poll() is None and time.monotonic() < deadline:
            spawner.reap()
            time.sleep(0.05)
        spawner.stop_all()
        # the exit status was collected — not a zombie, not a leak
        assert child.returncode is not None
        assert spawner._retiring == [] and spawner._children == []
    assert s.leaks == [], "\n".join(v.render() for v in s.leaks)


def test_prune_stale_spools_sweeps_dead_owner(tmp_path):
    """PIN: spool dirs are pid-stamped at creation; a dir whose owner died
    without atexit (SIGKILL) must be swept by the next process's prune,
    while live-owner and markerless dirs are spared."""
    from lakesoul_tpu.runtime import atomicio
    from lakesoul_tpu.scanplane.delivery import (
        _OWNER_MARKER,
        _SPOOL_PREFIX,
        prune_stale_spools,
    )

    base = tmp_path / "shm"
    base.mkdir()
    dead = base / (_SPOOL_PREFIX + "dead")
    dead.mkdir()
    # a pid that cannot exist: max_pid is bounded well below 2**22 + 7
    atomicio.publish_atomic(str(dead / _OWNER_MARKER), str(2**22 + 7))
    live = base / (_SPOOL_PREFIX + "live")
    live.mkdir()
    atomicio.publish_atomic(str(live / _OWNER_MARKER), str(os.getpid()))
    foreign = base / (_SPOOL_PREFIX + "markerless")
    foreign.mkdir()
    unrelated = base / "not-a-spool"
    unrelated.mkdir()

    removed = prune_stale_spools(str(base))
    assert str(dead) in removed and not dead.exists()
    assert live.exists() and foreign.exists() and unrelated.exists()


def test_default_spool_dir_is_owned_and_sweepable(tmp_path, monkeypatch):
    """PIN: default_spool_dir stamps the owner pid so a successor can
    tell live scratch from debris."""
    import lakesoul_tpu.scanplane.delivery as delivery

    monkeypatch.setattr(delivery, "_spool_base", lambda: str(tmp_path))
    d = delivery.default_spool_dir()
    assert os.path.isdir(d)
    marker = os.path.join(d, delivery._OWNER_MARKER)
    with open(marker) as f:
        assert int(f.read()) == os.getpid()
    # own live spool survives a prune pass
    assert d not in delivery.prune_stale_spools(str(tmp_path))
    import shutil

    shutil.rmtree(d, ignore_errors=True)
