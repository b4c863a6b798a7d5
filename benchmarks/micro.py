"""Micro-benchmark harness (the reference's ``benches/`` role:
rust/lakesoul-io/benches/{spill_bench,partial_merge,cache_bench}.rs and the
criterion harnesses).  Each leg prints one JSON line with a throughput figure
so regressions are visible run-to-run.

    python benchmarks/micro.py merge      # k-way MOR merge rows/s
    python benchmarks/micro.py scan_stages # per-stage scan breakdown + degeneracy budget
    python benchmarks/micro.py formats    # decode rows/s per physical format
    python benchmarks/micro.py streaming  # bounded-memory streaming merge rows/s
    python benchmarks/micro.py cache      # page-cache hit/miss throughput
    python benchmarks/micro.py spill      # writer auto-flush (spill) + re-merge
    python benchmarks/micro.py meta       # plan 1 partition out of 100k (ms)
    python benchmarks/micro.py pipeline   # serial vs runtime-pipelined scan
    python benchmarks/micro.py chaos      # clean vs faulted-scan degradation
    python benchmarks/micro.py lint       # lakelint wall-time over the package
    python benchmarks/micro.py topology   # SIGKILL→takeover latency (leased compaction)
    python benchmarks/micro.py scanplane  # disaggregated scan: 8 clients, 1→4 workers
    python benchmarks/micro.py freshness  # ingest-to-train SLO under three-role chaos
    python benchmarks/micro.py ann_scale  # sharded ANN plane: 10M x 128d build/recall/QPS
    python benchmarks/micro.py tensor_replay # epoch-1 stream vs epoch-2 device replay (8-dev mesh)
    python benchmarks/micro.py obs_fleet  # fleet obs: 3-role chaos, 1 snapshot, traces, postmortems
    python benchmarks/micro.py fleet      # multi-host trainers: 1→2→4 emulated hosts + kill-a-host
    python benchmarks/micro.py soak       # repeated open→scan→serve→close: flat fd/thread/heap gate
    python benchmarks/micro.py all
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import tempfile
import time

import numpy as np
import pyarrow as pa

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)


def _emit(leg: str, value: float, unit: str, **extra) -> None:
    print(json.dumps({"bench": leg, "value": round(value, 1), "unit": unit, **extra}))


def bench_merge(n_rows: int = 2_000_000, n_files: int = 8) -> None:
    """k-way merge throughput over sorted int64 PK runs (partial_merge.rs
    role): overlapping key ranges, UseLast semantics."""
    from lakesoul_tpu.io.merge import merge_sorted_tables

    rng = np.random.default_rng(0)
    per = n_rows // n_files
    tables = []
    for i in range(n_files):
        keys = np.sort(rng.choice(n_rows * 2, per, replace=False)).astype(np.int64)
        tables.append(pa.table({
            "id": keys,
            "v": rng.normal(size=per),
        }))
    start = time.perf_counter()
    out = merge_sorted_tables(tables, ["id"])
    dt = time.perf_counter() - start
    _emit("merge_i64_kway", n_rows / dt, "rows/s", files=n_files, out_rows=len(out))

    # string keys exercise the bytes loser tree
    s_tables = [
        t.set_column(0, "id", pa.array([f"k{v:012d}" for v in t.column("id").to_pylist()]))
        for t in (tb.slice(0, per // 4) for tb in tables)
    ]
    n_s = sum(len(t) for t in s_tables)
    start = time.perf_counter()
    merge_sorted_tables(s_tables, ["id"])
    dt = time.perf_counter() - start
    _emit("merge_bytes_kway", n_s / dt, "rows/s", files=n_files)


# no-PK degeneracy budget: on a compacted/no-PK scan the non-decode stages
# (merge + fill + rebatch + collate) may cost at most this fraction of the
# decode stage — the machine-checked form of "the plan degenerates to raw
# decode".  The leg FAILS (assert) when the budget is exceeded.  Measured
# steady state is ~0.3-0.4x (merge/fill ~0; collate pays one memcpy only on
# the ~1/8 of windows that span a file boundary); the pre-PR-8
# concat-per-window rebatcher measured well past 1.0x, so 0.5 is a real
# regression tripwire, not a formality.
SCAN_STAGES_BUDGET = float(os.environ.get("LAKESOUL_SCAN_STAGES_BUDGET", 0.5))


def bench_scan_stages(n_rows: int = 4_000_000, n_files: int = 8) -> None:
    """Per-stage scan→train breakdown (decode / merge / fill / rebatch /
    collate / queue / device_put; arxiv 2604.21275's stage-attribution
    discipline) over two legs:

    - ``scan_stages_no_pk``: a plain multi-file LSF table through the full
      loader — the degenerate plan.  Enforces the budget above: the scan
      path may not burn more than ``SCAN_STAGES_BUDGET`` of decode time on
      non-decode stages, so a regression that reintroduces a copy FAILS the
      leg rather than shaving a throughput number nobody notices.
    - ``scan_stages_mor``: the same rows with a PK + 25% upsert wave — the
      real merge-on-read breakdown, published for the record (merge>0 is
      the POINT here; no budget)."""
    from lakesoul_tpu import LakeSoulCatalog
    from lakesoul_tpu.obs.stages import stage_seconds

    rng = np.random.default_rng(0)
    schema = pa.schema([
        ("id", pa.int64()),
        ("label", pa.int32()),
        ("f0", pa.float32()), ("f1", pa.float32()),
        ("f2", pa.float32()), ("f3", pa.float32()),
    ])

    def chunk(lo: int, n: int) -> pa.Table:
        return pa.table({
            "id": np.arange(lo, lo + n, dtype=np.int64),
            "label": rng.integers(0, 10, n).astype(np.int32),
            **{f"f{j}": rng.normal(size=n).astype(np.float32) for j in range(4)},
        }, schema=schema)

    from lakesoul_tpu.obs.stages import queue_seconds_by_consumer

    def drive(t, consumer: str) -> tuple[int, float, dict, dict]:
        before = stage_seconds()
        q_before = queue_seconds_by_consumer()
        start = time.perf_counter()
        rows = 0
        for b in t.scan().batch_size(65_536).to_jax_iter(
            device_put=False, drop_remainder=False, consumer=consumer
        ):
            rows += len(b["id"])
        wall = time.perf_counter() - start
        after = stage_seconds()
        q_after = queue_seconds_by_consumer()
        q_delta = {
            k: round(v - q_before.get(k, 0.0), 4)
            for k, v in q_after.items()
            if v - q_before.get(k, 0.0) > 0
        }
        return rows, wall, {k: after[k] - before[k] for k in after}, q_delta

    def publish(leg: str, rows: int, wall: float, stages: dict, **extra) -> dict:
        total = sum(stages.values()) or 1.0
        breakdown = {
            k: {"s": round(v, 4), "pct": round(100.0 * v / total, 1)}
            for k, v in stages.items()
        }
        _emit(leg, rows / wall, "rows/s", stages=breakdown, **extra)
        return breakdown

    per = n_rows // n_files
    with tempfile.TemporaryDirectory() as d:
        catalog = LakeSoulCatalog(
            os.path.join(d, "wh"), db_path=os.path.join(d, "meta.db")
        )
        plain = catalog.create_table(
            "plain", schema, properties={"lakesoul.file_format": "lsf"}
        )
        for i in range(n_files):
            plain.write_arrow(chunk(i * per, per))
        # best-of-3 on the RATIO: the stages sum to ~100 ms here, so one
        # scheduler hiccup can double a stage; transient noise only ever
        # inflates the ratio, so the min across repeats is the achievable
        # degeneracy — what the budget is about
        best = None
        for _ in range(3):
            rows, wall, stages, q_split = drive(plain, "no_pk")
            assert rows == n_rows, (rows, n_rows)
            overhead = (
                stages["merge"] + stages["fill"]
                + stages["rebatch"] + stages["collate"]
            )
            frac = overhead / max(stages["decode"], 1e-9)
            if best is None or frac < best[0]:
                best = (frac, rows, wall, stages, overhead, q_split)
        frac, rows, wall, stages, overhead, q_split = best
        publish(
            "scan_stages_no_pk", rows, wall, stages,
            overhead_over_decode=round(frac, 3), budget=SCAN_STAGES_BUDGET,
            queue_by_consumer=q_split,
        )
        assert frac <= SCAN_STAGES_BUDGET, (
            f"no-PK degeneracy violated: (merge+fill+rebatch+collate)="
            f"{overhead:.3f}s is {frac:.2f}x decode "
            f"({stages['decode']:.3f}s) — budget {SCAN_STAGES_BUDGET}"
        )

        mor = catalog.create_table(
            "mor", schema, primary_keys=["id"], hash_bucket_num=2,
            properties={"lakesoul.file_format": "lsf"},
        )
        for i in range(n_files):
            mor.write_arrow(chunk(i * per, per))
        ids = rng.choice(n_rows, n_rows // 4, replace=False).astype(np.int64)
        wave = pa.table({
            "id": np.sort(ids),
            "label": rng.integers(0, 10, len(ids)).astype(np.int32),
            **{f"f{j}": rng.normal(size=len(ids)).astype(np.float32) for j in range(4)},
        }, schema=schema)
        mor.upsert(wave)
        rows, wall, stages, q_split = drive(mor, "mor")
        assert rows == n_rows, (rows, n_rows)
        publish(
            "scan_stages_mor", rows, wall, stages, upsert_frac=0.25,
            queue_by_consumer=q_split,
        )


def bench_formats(n_rows: int = 2_000_000) -> None:
    """Decode throughput per registered physical format (file_format.rs role;
    LSF is the Vortex-role fast-decode format)."""
    from lakesoul_tpu.io.config import IOConfig
    from lakesoul_tpu.io.formats import format_by_name

    rng = np.random.default_rng(0)
    cols = {"id": np.arange(n_rows, dtype=np.int64)}
    for i in range(8):
        cols[f"f{i}"] = rng.normal(size=n_rows).astype(np.float32)
    t = pa.table(cols)
    with tempfile.TemporaryDirectory() as d:
        for name, ext in (("parquet", ".parquet"), ("arrow", ".arrow"), ("lsf", ".lsf")):
            fmt = format_by_name(name)
            path = os.path.join(d, f"t{ext}")
            cfg = IOConfig(compression="lz4")
            start = time.perf_counter()
            size = fmt.write_table(t, path, config=cfg)
            wdt = time.perf_counter() - start
            best = 1e9
            for _ in range(3):
                start = time.perf_counter()
                got = fmt.read_table(path)
                best = min(best, time.perf_counter() - start)
            assert got.num_rows == n_rows
            _emit(
                f"decode_{name}", n_rows / best, "rows/s",
                write_rows_per_s=round(n_rows / wdt, 1), file_mb=round(size / 1e6, 1),
            )


def bench_cache(n_objects: int = 64, obj_kb: int = 256) -> None:
    """Read-through page cache throughput, cold vs warm (cache_bench.rs
    role), over a latency-injected store."""
    import fsspec
    from fsspec.implementations.memory import MemoryFileSystem

    class SlowFS(MemoryFileSystem):
        protocol = "slowmicro"
        latency = 0.005

        def cat_file(self, *a, **k):
            time.sleep(self.latency)
            return super().cat_file(*a, **k)

    if "slowmicro" not in fsspec.registry:
        fsspec.register_implementation("slowmicro", SlowFS, clobber=True)
    from lakesoul_tpu.io.object_store import cache_stats, filesystem_for

    mem = fsspec.filesystem("slowmicro")
    blob = os.urandom(obj_kb * 1024)
    # MemoryFileSystem only strips its own "memory://" prefix: custom-protocol
    # keys must be written in the same URL form they are read with
    for i in range(n_objects):
        mem.pipe_file(f"slowmicro://micro/o{i}", blob)
    cache_dir = tempfile.mkdtemp(prefix="lsf_cache_bench")
    opts = {"lakesoul.cache_dir": cache_dir}
    try:
        def sweep():
            total = 0
            start = time.perf_counter()
            for i in range(n_objects):
                fs, p = filesystem_for(f"slowmicro://micro/o{i}", opts)
                total += len(fs.cat_file(p))
            return total / (time.perf_counter() - start)

        cold = sweep()
        warm = sweep()
        stats = cache_stats(opts)
        _emit(
            "page_cache", warm / 1e6, "MB/s warm",
            cold_mb_per_s=round(cold / 1e6, 1), hit_rate=round(stats["hit_rate"], 4),
        )
    finally:
        shutil.rmtree(cache_dir, ignore_errors=True)


def bench_spill(n_rows: int = 1_000_000) -> None:
    """Writer byte-budget auto-flush (sorted spill runs) + bounded streaming
    re-merge (spill_bench.rs role)."""
    from lakesoul_tpu import LakeSoulCatalog

    with tempfile.TemporaryDirectory() as wh:
        catalog = LakeSoulCatalog(wh)
        schema = pa.schema([("id", pa.int64()), ("v", pa.float64())])
        t = catalog.create_table(
            "spill", schema, primary_keys=["id"], hash_bucket_num=1,
            properties={"lakesoul.memory_budget_bytes": str(8 << 20)},
        )
        rng = np.random.default_rng(0)
        ids = rng.permutation(n_rows).astype(np.int64)
        vals = rng.normal(size=n_rows)
        start = time.perf_counter()
        # several commits of overlapping sorted runs: the staged files ARE
        # the spill runs; the bounded streaming merger re-combines them
        step = n_rows // 8
        for lo in range(0, n_rows, step):
            t.write_arrow(pa.table(
                {"id": ids[lo:lo + step], "v": vals[lo:lo + step]}, schema=schema
            ))
        wdt = time.perf_counter() - start
        files = [f for u in t.scan().scan_plan() for f in u.data_files]
        start = time.perf_counter()
        rows = sum(len(b) for b in t.scan().batch_size(65_536).to_batches())
        rdt = time.perf_counter() - start
        assert rows == n_rows
        _emit(
            "spill_write", n_rows / wdt, "rows/s",
            runs=len(files), read_rows_per_s=round(n_rows / rdt, 1),
        )


def bench_streaming_merge(n_rows: int = 2_000_000, n_files: int = 8) -> None:
    """Bounded-memory k-way streaming merge (sorted_stream_merger.rs role),
    parquet vs LSF streams: per-stream batch DECODE dominates this path
    (~87% of wall on parquet), so the native format's cheap decode is the
    lever on streaming MOR throughput."""
    from lakesoul_tpu.io.formats import format_by_name
    from lakesoul_tpu.io.streaming_merge import iter_merged_windows

    rng = np.random.default_rng(0)
    per = n_rows // n_files
    with tempfile.TemporaryDirectory() as d:
        schema = None
        runs = []
        for i in range(n_files):
            keys = np.sort(rng.choice(n_rows * 2, per, replace=False)).astype(np.int64)
            t = pa.table({
                "id": keys,
                "v": rng.normal(size=per),
                "f0": rng.normal(size=per).astype(np.float32),
                "f1": rng.normal(size=per).astype(np.float32),
            })
            schema = t.schema
            runs.append(t)
        for name, ext in (("parquet", ".parquet"), ("lsf", ".lsf")):
            fmt = format_by_name(name)
            files = []
            for i, t in enumerate(runs):
                p = os.path.join(d, f"run{i}{ext}")
                fmt.write_table(t, p)
                files.append(p)
            start = time.perf_counter()
            rows = sum(
                len(w)
                for w in iter_merged_windows(files, ["id"], file_schema=schema)
            )
            dt = time.perf_counter() - start
            _emit(f"streaming_merge_{name}", n_rows / dt, "rows/s in",
                  files=n_files, out_rows=rows)


def bench_meta_prune(n_partitions: int = 100_000) -> None:
    """Partition-filter pushdown at scale: plan one partition out of
    ``n_partitions`` (the reference's 3.0 headline claims ≈50 ms against a
    table with millions of partitions on PostgreSQL;
    website/blog/2025-09-05-lakesoul-3.0.0-release/index.md:8).  Metadata
    only — commits are synthesized through the client with fake file paths,
    which is exactly what that claim measures."""
    from lakesoul_tpu.meta.client import MetaDataClient
    from lakesoul_tpu.meta.entity import CommitOp, DataFileOp

    with tempfile.TemporaryDirectory() as d:
        client = MetaDataClient(db_path=f"{d}/meta.db")
        schema = pa.schema([("id", pa.int64()), ("day", pa.string()), ("v", pa.float64())])
        info = client.create_table(
            "wide", f"{d}/wide", schema, primary_keys=["id"],
            range_partitions=["day"],
        )
        start = time.perf_counter()
        # batched commits: 1000 partitions per commit_data_files call; file
        # names carry the trailing _NNNN hash-bucket suffix the planner
        # extracts (client.extract_hash_bucket_id)
        step = 1000
        for lo in range(0, n_partitions, step):
            files = {
                f"day=d{p:07d}": [
                    DataFileOp(path=f"{d}/wide/day=d{p:07d}/part-0_0000.lsf", size=1024)
                ]
                for p in range(lo, min(lo + step, n_partitions))
            }
            client.commit_data_files(info, files, CommitOp.APPEND)
        ingest_dt = time.perf_counter() - start

        probe = f"d{(n_partitions * 2 // 5):07d}"  # an existing mid-table partition
        start = time.perf_counter()
        units = client.get_scan_plan_partitions("wide", {"day": probe})
        one_dt = time.perf_counter() - start
        assert len(units) >= 1
        start = time.perf_counter()
        all_units = client.get_scan_plan_partitions("wide")
        all_dt = time.perf_counter() - start
        assert len(all_units) == n_partitions
        _emit(
            "meta_prune_one_of_n", one_dt * 1e3, "ms",
            n_partitions=n_partitions,
            full_plan_ms=round(all_dt * 1e3, 1),
            ingest_partitions_per_s=round(n_partitions / ingest_dt, 1),
        )


def bench_pipeline_scan(
    n_rows: int = 800_000, n_files: int = 8, latency_s: float = 0.04
) -> None:
    """Serial vs runtime-pipelined scan of one multi-file (multi-row-group)
    table on a latency-injected object store — the overlap win the
    lakesoul_tpu/runtime/ subsystem exists for: with one worker every file
    GET serializes; with the pool, fetch+decode of all files overlap (and
    MOR-free postprocess overlaps decode).  The batch streams must be
    BYTE-IDENTICAL between modes (the pipeline's ordered-merge guarantee);
    this leg asserts it."""
    import fsspec
    from fsspec.implementations.memory import MemoryFileSystem

    class SlowScanFS(MemoryFileSystem):
        protocol = "slowscan"
        latency = latency_s

        def _open(self, *a, **k):
            time.sleep(SlowScanFS.latency)  # per-object GET latency
            return super()._open(*a, **k)

        def cat_file(self, *a, **k):
            time.sleep(SlowScanFS.latency)
            return super().cat_file(*a, **k)

    if "slowscan" not in fsspec.registry:
        fsspec.register_implementation("slowscan", SlowScanFS, clobber=True)

    from lakesoul_tpu import LakeSoulCatalog
    from lakesoul_tpu.runtime import shutdown_pool

    def set_pool(n: int) -> None:
        shutdown_pool()
        os.environ["LAKESOUL_RUNTIME_THREADS"] = str(n)

    prev_threads = os.environ.get("LAKESOUL_RUNTIME_THREADS")
    rng = np.random.default_rng(0)
    with tempfile.TemporaryDirectory() as d:
        catalog = LakeSoulCatalog(
            "slowscan://pipe-bench/wh", db_path=os.path.join(d, "meta.db")
        )
        schema = pa.schema(
            [("id", pa.int64()), ("f0", pa.float32()), ("f1", pa.float32())]
        )
        t = catalog.create_table("scanme", schema)
        per = n_rows // n_files
        for i in range(n_files):
            t.write_arrow(pa.table({
                "id": np.arange(i * per, (i + 1) * per),
                "f0": rng.normal(size=per).astype(np.float32),
                "f1": rng.normal(size=per).astype(np.float32),
            }, schema=schema))
        try:
            set_pool(1)
            start = time.perf_counter()
            serial = list(t.scan().batch_size(65_536).to_batches())
            serial_dt = time.perf_counter() - start

            set_pool(8)
            start = time.perf_counter()
            piped = list(t.scan().batch_size(65_536).to_batches(num_threads=8))
            piped_dt = time.perf_counter() - start
        finally:
            shutdown_pool()
            if prev_threads is None:
                os.environ.pop("LAKESOUL_RUNTIME_THREADS", None)
            else:
                os.environ["LAKESOUL_RUNTIME_THREADS"] = prev_threads

        # determinism contract: byte-identical batch order across modes
        assert len(serial) == len(piped), (len(serial), len(piped))
        for a, b in zip(serial, piped):
            assert a.equals(b)
        rows = sum(len(b) for b in serial)
        assert rows == n_rows
        _emit(
            "pipeline_scan", n_rows / piped_dt, "rows/s",
            serial_rows_per_s=round(n_rows / serial_dt, 1),
            speedup=round(serial_dt / piped_dt, 2),
            files=n_files, fetch_latency_ms=latency_s * 1e3,
        )


def bench_chaos(n_rows: int = 400_000, n_files: int = 8, p: float = 0.3) -> None:
    """Clean vs chaos-faulted scan throughput (the resilience layer's cost
    leg): the same table is scanned twice, the second time with p=0.3
    transient faults injected into every object-store open/info call
    (runtime/faults.py `flaky` kind).  The retry policy must absorb every
    fault — the leg asserts the batch streams are BYTE-IDENTICAL — and the
    published `degradation` ratio (faulted/clean throughput) is the price
    of absorption.  Retry counters ride in the obs delta."""
    import numpy as np

    from lakesoul_tpu import LakeSoulCatalog
    from lakesoul_tpu.runtime import faults

    saved = {
        k: os.environ.get(k)
        for k in ("LAKESOUL_RETRY_MAX_ATTEMPTS", "LAKESOUL_RETRY_BASE_S",
                  "LAKESOUL_RETRY_CAP_S")
    }
    os.environ.update({
        "LAKESOUL_RETRY_MAX_ATTEMPTS": "10",
        "LAKESOUL_RETRY_BASE_S": "0.001",
        "LAKESOUL_RETRY_CAP_S": "0.01",
    })
    rng = np.random.default_rng(0)
    try:
        with tempfile.TemporaryDirectory() as d:
            catalog = LakeSoulCatalog(
                "memory://chaos-bench/wh", db_path=os.path.join(d, "meta.db")
            )
            schema = pa.schema([("id", pa.int64()), ("v", pa.float64())])
            t = catalog.create_table("chaos", schema)
            per = n_rows // n_files
            for i in range(n_files):
                t.write_arrow(pa.table({
                    "id": np.arange(i * per, (i + 1) * per),
                    "v": rng.normal(size=per),
                }, schema=schema))

            start = time.perf_counter()
            clean = list(t.scan().batch_size(65_536).to_batches())
            clean_dt = time.perf_counter() - start

            faults.clear()
            faults.install(f"object_store.open:{p}:flaky")
            faults.install(f"object_store.info:{p}:flaky")
            try:
                start = time.perf_counter()
                faulted = list(t.scan().batch_size(65_536).to_batches())
                faulted_dt = time.perf_counter() - start
            finally:
                faults.clear()

            assert len(clean) == len(faulted)
            for a, b in zip(clean, faulted):
                assert a.equals(b), "chaos run diverged from the clean scan"
            _emit(
                "chaos_scan", n_rows / faulted_dt, "rows/s",
                clean_rows_per_s=round(n_rows / clean_dt, 1),
                degradation=round((n_rows / faulted_dt) / (n_rows / clean_dt), 3),
                fault_p=p, files=n_files,
            )
    finally:
        for k, v in saved.items():
            if v is None:
                os.environ.pop(k, None)
            else:
                os.environ[k] = v


def bench_lint() -> None:
    """Analyzer wall-time over the whole package (CI-gate cost leg: the
    lint gate runs on every PR, so its cost is tracked next to the perf
    legs; target < 10 s for all 40 rules INCLUDING the project call-graph
    build the interprocedural rules share, the device-index/taint passes
    of the JAX/TPU pack, the thread-root/lockset passes of the
    concurrency pack, the filesystem-op index of the durability pack,
    the SQL-site/taint passes of the isolation pack, and the shared
    container/thread/child lifecycle index of the boundedness pack).  Per-rule wall milliseconds ride along in the leg
    JSON so a future rule regression is attributable to ONE rule id — note
    a shared index (call graph, device index, thread roots) bills to the
    first rule that builds it."""
    from lakesoul_tpu.analysis import run_repo
    from lakesoul_tpu.analysis.engine import Project, Module, package_root

    # parse+rule cost is dominated by file IO the first time; report the
    # steady-state of a fresh run, which is what CI pays
    timings: dict = {}
    start = time.perf_counter()
    findings, _ = run_repo(timings=timings)
    dt = time.perf_counter() - start
    n_files = sum(
        len([f for f in files if f.endswith(".py")])
        for _, _, files in os.walk(os.path.join(REPO, "lakesoul_tpu"))
    )
    # the call-graph build in isolation, so a regression is attributable
    project = Project(root=package_root().parent)
    for p in sorted(package_root().rglob("*.py")):
        mod = Module.load(p, package_root().parent)
        if mod is not None:
            project.modules.append(mod)
    start = time.perf_counter()
    graph = project.callgraph()
    cg_dt = time.perf_counter() - start
    _emit(
        "lint_package", dt * 1e3, "ms",
        files=n_files, findings=len(findings),
        files_per_s=round(n_files / dt, 1),
        callgraph_ms=round(cg_dt * 1e3, 1),
        rules=len(timings),
        rule_ms={
            rule_id: round(seconds * 1e3, 1)
            for rule_id, seconds in sorted(
                timings.items(), key=lambda kv: -kv[1]
            )
        },
        **{f"callgraph_{k}": v for k, v in graph.stats().items()},
    )
    assert dt < 10.0, f"lint gate took {dt:.1f}s — budget is 10s"


def bench_topology(
    n_versions: int = 12, rows_per_commit: int = 2000, ttl_s: float = 2.0
) -> None:
    """Multi-process failover cost leg: how long a partition whose leased
    compactor was SIGKILLed mid-job waits until a peer service completes
    it (kill → peer-commits latency, dominated by one lease TTL), and the
    proof that the failover path changes NOTHING about the data — the
    failover-compacted table scans byte-identical to a clean-compacted
    copy of the same commit sequence.  ``LAKESOUL_RETRY_SEED`` pins every
    backoff schedule so the run reproduces."""
    import signal
    import subprocess

    from lakesoul_tpu import LakeSoulCatalog
    from lakesoul_tpu.compaction.service import LeasedCompactionService
    from lakesoul_tpu.meta.entity import CommitOp

    schema = pa.schema([("id", pa.int64()), ("v", pa.float64())])
    rng = np.random.default_rng(0)
    batches = [
        pa.table({
            "id": np.arange(rows_per_commit, dtype=np.int64),
            "v": rng.normal(size=rows_per_commit),
        }, schema=schema)
        for _ in range(n_versions)
    ]

    def build(wh: str, db: str):
        catalog = LakeSoulCatalog(wh, db_path=db)
        t = catalog.create_table(
            "t", schema, primary_keys=["id"], hash_bucket_num=1
        )
        for b in batches:
            t.upsert(b)
        return catalog, t

    def sorted_ipc(table: pa.Table) -> bytes:
        import io

        out = table.sort_by("id").combine_chunks()
        sink = io.BytesIO()
        with pa.ipc.new_stream(sink, out.schema) as w:
            w.write_table(out)
        return sink.getvalue()

    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO,
        "LAKESOUL_RETRY_SEED": "7",
        "LAKESOUL_FAULTS": "compaction.leased_job:1:hang:300",
    })
    with tempfile.TemporaryDirectory() as d:
        # clean run: same commits, in-process leased compaction
        cat1, t1 = build(os.path.join(d, "wh1"), os.path.join(d, "m1.db"))
        LeasedCompactionService(
            cat1, lease_ttl_s=30, poll_interval_s=0.01
        ).poll_once()
        clean_bytes = sorted_ipc(t1.refresh().to_arrow())

        # failover run: victim service process hangs inside the leased job
        wh2, db2 = os.path.join(d, "wh2"), os.path.join(d, "m2.db")
        cat2, t2 = build(wh2, db2)
        store = cat2.client.store
        proc = subprocess.Popen(
            [sys.executable, "-m", "lakesoul_tpu.compaction",
             "--warehouse", wh2, "--db-path", db2,
             "--lease-ttl-s", str(ttl_s), "--poll-s", "0.1",
             "--service-id", "victim"],
            env=env, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
        )
        key = f"compaction/{t2.info.table_id}/-5"
        try:
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                if store.get_lease(key) is not None:
                    break
                time.sleep(0.05)
            assert store.get_lease(key) is not None, "victim never leased"
        finally:
            proc.send_signal(signal.SIGKILL)
            proc.wait(10.0)
        killed_at = time.monotonic()
        peer = LeasedCompactionService(
            cat2, service_id="peer", lease_ttl_s=ttl_s, poll_interval_s=0.1
        )
        drain_deadline = time.monotonic() + 60.0
        while store.get_compaction_candidates():
            if time.monotonic() > drain_deadline:
                raise RuntimeError(
                    "peer failed to drain compaction candidates within 60s: "
                    f"{store.get_compaction_candidates()}"
                )
            peer.poll_once()
            time.sleep(0.05)
        takeover_ms = (time.monotonic() - killed_at) * 1e3

        head = store.get_latest_partition_info(t2.info.table_id, "-5")
        assert head.commit_op == CommitOp.COMPACTION
        assert head.expression == "fence=2", head.expression
        failover_bytes = sorted_ipc(t2.refresh().to_arrow())
        assert failover_bytes == clean_bytes, (
            "failover-compacted scan diverged from the clean run"
        )
        _emit(
            "topology_takeover", takeover_ms, "ms",
            lease_ttl_s=ttl_s,
            takeovers=peer.stats.takeovers,
            byte_identical=True,
            rows=n_versions * rows_per_commit,
        )


# the scanplane leg's scaling gate: aggregate client rows/s must grow at
# least this factor from 1 → 4 worker processes (near-linear modulo fixed
# session/connect overheads); the leg FAILS below it
SCANPLANE_SCALE_FLOOR = float(os.environ.get("LAKESOUL_SCANPLANE_SCALE_FLOOR", 3.0))


def bench_scanplane(
    n_rows: int = 6_000_000, n_buckets: int = 16, n_clients: int = 8,
    ttl_s: float = 2.0, store_latency_s: float = 0.35,
) -> None:
    """Disaggregated scan plane at fleet shape (ROADMAP item 3): ≥8
    concurrent trainer-client PROCESSES stream one MOR table's shards
    through the Flight gateway while decode/merge workers run as separate
    leased processes.  Worker range production carries an injected
    per-range store latency (``scanplane.range:1:delay`` — the same
    latency-emulation discipline as the ``pipeline``/``cache`` legs: the
    deployment this layer scales is remote object storage, where range
    fetch+decode is latency-bound, not host-memcpy-bound).  Three claims,
    all asserted:

    - **byte identity**: every client's stream sha256 equals the
      single-process ``scan.shard(rank, world)`` scan of the same table;
    - **scaling**: aggregate client rows/s grows ≥``SCANPLANE_SCALE_FLOOR``
      from 1 → 4 worker processes (the handoff-bound single process was
      the queue-stage wall PR 8 left standing — this leg is the scale-out
      answer to it);
    - **exactly-once under SIGKILL**: a worker killed while HOLDING a
      range lease delays that range by ≤ one lease TTL (a peer takes
      over, fencing token bumped), and every client still completes with
      the same shas — no duplicate, no missing batches."""
    import signal
    import subprocess
    import threading

    from lakesoul_tpu import LakeSoulCatalog
    from lakesoul_tpu.scanplane import spool as sp
    from lakesoul_tpu.scanplane.delivery import ScanPlaneDelivery
    from lakesoul_tpu.scanplane.session import ScanSession
    from lakesoul_tpu.service.flight import LakeSoulFlightServer

    rng = np.random.default_rng(0)
    schema = pa.schema([
        ("id", pa.int64()), ("label", pa.int32()),
        ("f0", pa.float32()), ("f1", pa.float32()),
        ("f2", pa.float32()), ("f3", pa.float32()),
    ])
    batch_size = 65_536

    def child_env() -> dict:
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
            "LAKESOUL_RETRY_SEED": "7",
        })
        return env

    def spawn_worker(wh, db, spool, worker_id, **extra_env):
        env = child_env()
        env.update(extra_env)
        return subprocess.Popen(
            [sys.executable, "-m", "lakesoul_tpu.scanplane", "worker",
             "--warehouse", wh, "--db-path", db, "--spool", spool,
             "--lease-ttl-s", str(ttl_s), "--poll-s", "0.05",
             "--worker-id", worker_id],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True,
        )

    def spawn_client(location, rank):
        return subprocess.Popen(
            [sys.executable, "-m", "lakesoul_tpu.scanplane", "drive",
             "--location", location, "--table", "t",
             "--batch-size", str(batch_size),
             "--rank", str(rank), "--world", str(n_clients)],
            env=child_env(), stdout=subprocess.PIPE,
            stderr=subprocess.PIPE, text=True,
        )

    def run_fleet(catalog, wh, db, n_workers, spool, *, chaos=False):
        """One fleet run; returns (outputs by rank, wall_s, takeover_s).

        Order matters for a clean measurement: clients launch FIRST (they
        connect, create the session, and park on the empty spool), then
        the workers; the wall clock runs from all-workers-ready to the
        last client's final byte — fleet delivery throughput, not python
        interpreter boot."""
        os.makedirs(spool, exist_ok=True)
        delivery = ScanPlaneDelivery(catalog, spool, wait_s=180)
        server = LakeSoulFlightServer(
            catalog, "grpc://127.0.0.1:0", scanplane=delivery
        )
        threading.Thread(target=server.serve, daemon=True).start()
        location = f"grpc://127.0.0.1:{server.port}"
        workers = []
        takeover_s = None
        try:
            clients = [spawn_client(location, r) for r in range(n_clients)]
            # the first connected client publishes the session manifest —
            # its appearance means the fleet is parked and waiting
            session = ScanSession.plan(
                catalog, {"table": "t", "batch_size": batch_size}
            )
            manifest = os.path.join(spool, session.session_id, "manifest.json")
            deadline = time.monotonic() + 120.0
            while not os.path.exists(manifest):
                assert time.monotonic() < deadline, "no client connected"
                time.sleep(0.02)
            victim = None
            if chaos:
                victim = spawn_worker(
                    wh, db, spool, "victim",
                    LAKESOUL_FAULTS="scanplane.range:1:hang:300",
                )
                workers.append(victim)
                workers.append(spawn_worker(wh, db, spool, "peer"))
            else:
                workers.extend(
                    spawn_worker(
                        wh, db, spool, f"w{i}",
                        LAKESOUL_FAULTS=(
                            f"scanplane.range:1:delay:{store_latency_s}"
                        ),
                    )
                    for i in range(n_workers)
                )
            for w in workers:
                w.stdout.readline()  # readiness line
            fleet_t0 = time.time()
            if chaos:
                # watch the lease table until the victim HOLDS a range,
                # then SIGKILL it
                store = catalog.client.store
                keys = [
                    f"scanplane/{session.session_id}/{i}"
                    for i in range(len(session.ranges))
                ]
                held = None
                deadline = time.monotonic() + 120.0
                while held is None and time.monotonic() < deadline:
                    for k in keys:
                        lease = store.get_lease(k)
                        if lease is not None and lease.holder == "victim":
                            held = k
                            break
                    time.sleep(0.02)
                assert held is not None, "victim never leased a range"
                victim.send_signal(signal.SIGKILL)
                victim.wait(10.0)
                killed = time.monotonic()
                index = int(held.rsplit("/", 1)[-1])
                sdir = session.dir(spool)
                while not sp.range_ready(sdir, index):
                    assert time.monotonic() - killed < 60.0, "no takeover"
                    time.sleep(0.02)
                takeover_s = time.monotonic() - killed
                assert takeover_s < ttl_s + 4.0, takeover_s
                # the fencing trail proves the takeover: the surviving peer
                # produced the victim's range under a BUMPED token (exact
                # value depends on how many held/fenced cycles the two
                # workers interleaved before the kill; the controlled
                # single-step trail is pinned in test_scanplane_chaos.py)
                side = sp.read_sidecar(sdir, index)
                assert side["worker"] == "peer" and side["fence"] >= 2, side
            outputs = {}
            for rank, c in enumerate(clients):
                out, err = c.communicate(timeout=600)
                lines = [ln for ln in out.splitlines() if ln.startswith("{")]
                assert c.returncode == 0 and lines, err[-2000:]
                outputs[rank] = json.loads(lines[-1])
            wall = max(o["ended_unix"] for o in outputs.values()) - fleet_t0
            return outputs, wall, takeover_s
        finally:
            for w in workers:
                if w.poll() is None:
                    w.terminate()
            for w in workers:
                try:
                    w.wait(10.0)
                except subprocess.TimeoutExpired:
                    w.kill()
            server.shutdown()

    with tempfile.TemporaryDirectory() as d:
        wh, db = os.path.join(d, "wh"), os.path.join(d, "meta.db")
        catalog = LakeSoulCatalog(wh, db_path=db)
        t = catalog.create_table(
            "t", schema, primary_keys=["id"], hash_bucket_num=n_buckets,
            properties={"lakesoul.file_format": "lsf"},
        )
        t.write_arrow(pa.table({
            "id": np.arange(n_rows, dtype=np.int64),
            "label": rng.integers(0, 10, n_rows).astype(np.int32),
            **{f"f{j}": rng.normal(size=n_rows).astype(np.float32)
               for j in range(4)},
        }, schema=schema))
        ids = np.sort(
            rng.choice(n_rows, n_rows // 4, replace=False)
        ).astype(np.int64)
        t.upsert(pa.table({
            "id": ids,
            "label": rng.integers(0, 10, len(ids)).astype(np.int32),
            **{f"f{j}": rng.normal(size=len(ids)).astype(np.float32)
               for j in range(4)},
        }, schema=schema))

        # single-process baseline shas: the byte-identity oracle per rank
        import hashlib

        def shard_sha(rank: int) -> tuple[str, int]:
            digest = hashlib.sha256()
            rows = 0
            for b in (
                t.scan().batch_size(batch_size)
                .shard(rank, n_clients).to_batches()
            ):
                sink = pa.BufferOutputStream()
                with pa.ipc.new_stream(sink, b.schema) as w:
                    w.write_batch(b)
                digest.update(sink.getvalue().to_pybytes())
                rows += b.num_rows
            return digest.hexdigest(), rows

        oracle = {r: shard_sha(r) for r in range(n_clients)}
        total_rows = sum(rows for _, rows in oracle.values())

        # spool on tmpfs when available: the shm fast path is then literal
        # shared memory; each run gets a FRESH spool so production repeats
        spool_base = "/dev/shm" if os.path.isdir("/dev/shm") else d
        rates = {}
        for n_workers in (1, 4):
            spool = os.path.join(
                tempfile.mkdtemp(prefix="lss-", dir=spool_base)
            )
            try:
                outputs, wall, _ = run_fleet(catalog, wh, db, n_workers, spool)
                for rank, out in outputs.items():
                    sha, rows = oracle[rank]
                    assert out["rows"] == rows, (rank, out["rows"], rows)
                    assert out["sha256"] == sha, f"rank {rank} diverged"
                rates[n_workers] = total_rows / wall
            finally:
                shutil.rmtree(spool, ignore_errors=True)
        scale = rates[4] / rates[1]

        # chaos variant: 2 workers, SIGKILL the one holding a lease
        spool = os.path.join(tempfile.mkdtemp(prefix="lss-", dir=spool_base))
        try:
            outputs, chaos_wall, takeover_s = run_fleet(
                catalog, wh, db, 2, spool, chaos=True
            )
            for rank, out in outputs.items():
                sha, rows = oracle[rank]
                # exactly-once through the kill: same rows, same bytes
                assert out["rows"] == rows and out["sha256"] == sha, rank
        finally:
            shutil.rmtree(spool, ignore_errors=True)

        _emit(
            "scanplane_fleet", rates[4], "rows/s",
            clients=n_clients,
            rows=total_rows,
            workers_1_rows_per_s=round(rates[1], 1),
            workers_4_rows_per_s=round(rates[4], 1),
            scale_1_to_4=round(scale, 2),
            scale_floor=SCANPLANE_SCALE_FLOOR,
            byte_identical=True,
            chaos_takeover_s=round(takeover_s, 2),
            chaos_exactly_once=True,
            lease_ttl_s=ttl_s,
            emulated_store_latency_s=store_latency_s,
        )
        assert scale >= SCANPLANE_SCALE_FLOOR, (
            f"scan plane scaled only {scale:.2f}x from 1→4 workers —"
            f" floor is {SCANPLANE_SCALE_FLOOR}x"
        )


# freshness-leg SLO gates (env-tunable for slow boxes): the leg FAILS if
# the p99 commit-to-visible latency or the sustained delivery rate misses
FRESHNESS_SLO_S = float(os.environ.get("LAKESOUL_FRESHNESS_SLO_S", 10.0))
FRESHNESS_TPUT_FLOOR = float(
    os.environ.get("LAKESOUL_FRESHNESS_THROUGHPUT_FLOOR", 100.0)
)


def bench_freshness(
    commits: int = 15, rows_per_commit: int = 400, ttl_s: float = 2.0,
    fault_p: float = 0.3,
) -> None:
    """The always-fresh-lakehouse leg (ROADMAP item 4): three REAL roles
    against one warehouse — ``python -m lakesoul_tpu.freshness writer``
    streaming checkpointed CDC upserts, the real ``python -m
    lakesoul_tpu.compaction`` leased service (SIGKILLed mid-leased-job,
    with a peer taking over under the fencing trail), and a follower
    trainer in THIS process under p=0.3 flaky-store + flaky-poll faults.
    Publishes ``freshness_seconds`` p50/p99 (commit-to-visible, measured
    at the follower's consumer hand-off) and sustained rows/s, and FAILS
    unless both declared SLOs hold AND delivery exactly matches the
    writer's oracle.  ``LAKESOUL_RETRY_SEED`` pins every backoff schedule
    so the run reproduces."""
    import signal
    import subprocess
    import threading

    from lakesoul_tpu import LakeSoulCatalog
    from lakesoul_tpu.freshness import FreshFollower, SloMonitor, ThroughputSlo
    from lakesoul_tpu.freshness.__main__ import oracle_sha
    from lakesoul_tpu.meta.entity import CommitOp, now_millis
    from lakesoul_tpu.runtime import faults
    from lakesoul_tpu.runtime.resilience import RetryPolicy

    schema = pa.schema([
        ("id", pa.int64()), ("seq", pa.int64()), ("v", pa.float64()),
    ])
    env = dict(os.environ)
    env.update({
        "JAX_PLATFORMS": "cpu",
        "PYTHONPATH": REPO,
        "LAKESOUL_RETRY_SEED": "7",
    })
    victim_env = dict(env, LAKESOUL_FAULTS="compaction.leased_job:1:hang:300")
    expected = commits * rows_per_commit

    with tempfile.TemporaryDirectory() as d:
        wh, db = os.path.join(d, "wh"), os.path.join(d, "meta.db")
        catalog = LakeSoulCatalog(wh, db_path=db)
        t = catalog.create_table(
            "fresh", schema, primary_keys=["id"], hash_bucket_num=2, cdc=True
        )
        start_ts = now_millis() - 1
        store = catalog.client.store
        lease_key = f"compaction/{t.info.table_id}/-5"

        def compactor(service_id: str, e: dict) -> subprocess.Popen:
            return subprocess.Popen(
                [sys.executable, "-m", "lakesoul_tpu.compaction",
                 "--warehouse", wh, "--db-path", db,
                 "--lease-ttl-s", str(ttl_s), "--poll-s", "0.1",
                 "--version-gap", "3", "--service-id", service_id],
                env=e, stdout=subprocess.DEVNULL, stderr=subprocess.DEVNULL,
            )

        victim = compactor("victim", victim_env)
        writer = subprocess.Popen(
            [sys.executable, "-m", "lakesoul_tpu.freshness", "writer",
             "--warehouse", wh, "--db-path", db, "--table", "fresh",
             "--commits", str(commits),
             "--rows-per-commit", str(rows_per_commit),
             "--interval-s", "0.15"],
            env=env, stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )

        peer_box: dict = {}
        killed_at: dict = {}

        def kill_and_replace():
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline:
                if store.get_lease(lease_key) is not None:
                    victim.send_signal(signal.SIGKILL)
                    victim.wait(10.0)
                    killed_at["t"] = time.monotonic()
                    peer_box["peer"] = compactor("peer", env)
                    return
                time.sleep(0.05)

        watcher = threading.Thread(target=kill_and_replace, daemon=True)

        slo = SloMonitor(target_s=FRESHNESS_SLO_S, budget_fraction=0.05,
                         slo="bench-freshness")
        tput = ThroughputSlo(FRESHNESS_TPUT_FLOOR, slo="bench-freshness-tput")
        stop = threading.Event()
        follower = FreshFollower(
            catalog.table("fresh").scan().batch_size(2048),
            start_timestamp_ms=start_ts,
            poll_interval=0.05,
            stop_event=stop,
            retry_policy=RetryPolicy(
                max_attempts=12, base_delay_s=0.002, max_delay_s=0.05, seed=7
            ),
            slo=slo,
        )

        rows: list[tuple[int, int, float]] = []
        faults.clear()
        faults.install(f"follow.poll:{fault_p}:flaky")
        faults.install(f"object_store.cat_file:{fault_p}:flaky")
        faults.install(f"object_store.open:{fault_p}:flaky")
        try:
            tput.start()
            watcher.start()

            def consume():
                for b in follower.iter_batches():
                    rows.extend(zip(
                        b.column("seq").to_pylist(),
                        b.column("id").to_pylist(),
                        b.column("v").to_pylist(),
                    ))
                    if len(rows) >= expected:
                        stop.set()

            th = threading.Thread(target=consume, daemon=True)
            th.start()
            deadline = time.monotonic() + 180.0
            while th.is_alive() and time.monotonic() < deadline:
                th.join(timeout=0.2)
            stop.set()
            th.join(timeout=15.0)
            tput.add_rows(len(rows))
        finally:
            faults.clear()
            out, _ = writer.communicate(timeout=60.0)
            if victim.poll() is None:
                victim.send_signal(signal.SIGKILL)

        try:
            oracle = json.loads(out.strip().splitlines()[-1])
            assert writer.returncode == 0
            assert len(rows) == expected, (
                f"delivered {len(rows)} of {expected} rows"
            )
            assert oracle_sha(rows) == oracle["sha256"], (
                "delivered rows diverged from the writer oracle"
            )
            assert "t" in killed_at, "victim compactor never held a lease"

            snap = slo.snapshot()
            rate = tput.evaluate()
            assert snap["in_budget"] and snap["p99_s"] <= FRESHNESS_SLO_S, snap
            assert rate["ok"], rate

            # the peer completes the compaction under the fencing trail
            fence_deadline = time.monotonic() + 60.0
            fenced = []
            while time.monotonic() < fence_deadline and not fenced:
                fenced = [
                    v for v in store.get_partition_versions(
                        t.info.table_id, "-5"
                    )
                    if v.commit_op == CommitOp.COMPACTION
                    and v.expression.startswith("fence=")
                ]
                if not fenced:
                    time.sleep(0.2)
            assert fenced and any(
                int(v.expression.split("=", 1)[1]) >= 2 for v in fenced
            ), "no fenced takeover CompactionCommit"
        finally:
            peer = peer_box.get("peer")
            if peer is not None and peer.poll() is None:
                peer.send_signal(signal.SIGKILL)
                peer.wait(10.0)

        _emit(
            "freshness", snap["p99_s"], "s",
            freshness_p50_s=round(snap["p50_s"], 4),
            freshness_p99_s=round(snap["p99_s"], 4),
            freshness_max_s=round(snap["max_s"], 4),
            slo_target_s=FRESHNESS_SLO_S,
            slo_in_budget=snap["in_budget"],
            slo_violations=snap["violations"],
            commits_observed=snap["count"],
            rows=len(rows),
            rows_per_s=round(rate["rows_per_s"], 1),
            throughput_floor=FRESHNESS_TPUT_FLOOR,
            oracle_exact=True,
            compactor_sigkilled=True,
            takeover_fenced=True,
            fault_p=fault_p,
            lease_ttl_s=ttl_s,
        )


# ann_scale gates (env-tunable for slow boxes): the leg FAILS on a recall
# floor breach or a serving-QPS floor breach — same discipline as the
# scan_stages degeneracy budget.  The QPS floor is 10x the committed
# single-shard serving baseline (~125 QPS, BENCH_r05 ann_qps_serving).
ANN_SCALE_ROWS = int(os.environ.get("LAKESOUL_ANN_SCALE_ROWS", 10_000_000))
ANN_SCALE_DIM = int(os.environ.get("LAKESOUL_ANN_SCALE_DIM", 128))
ANN_SCALE_RECALL_FLOOR = float(
    os.environ.get("LAKESOUL_ANN_SCALE_RECALL_FLOOR", 0.95)
)
ANN_SCALE_QPS_FLOOR = float(os.environ.get("LAKESOUL_ANN_SCALE_QPS_FLOOR", 1250.0))
ANN_SCALE_RSS_CEILING_MB = int(
    os.environ.get("LAKESOUL_ANN_SCALE_RSS_CEILING_MB", 4096)
)
ANN_SCALE_SHARD_BUDGET = int(
    os.environ.get("LAKESOUL_ANN_SHARD_BUDGET_BYTES", 768 << 20)
)


def _ann_scale_corpus_chunks(n_rows: int, dim: int, chunk: int = 500_000):
    """Deterministic clustered corpus, regenerable chunk by chunk: the exact
    oracle streams over a SECOND generation of the same chunks instead of
    holding 5 GB of raw vectors."""
    rng_c = np.random.default_rng(20260801)
    centers = (rng_c.normal(size=(4096, dim)) * 3.0).astype(np.float32)
    for lo in range(0, n_rows, chunk):
        n = min(chunk, n_rows - lo)
        rng = np.random.default_rng(77_000 + lo // chunk)
        vecs = (
            centers[rng.integers(0, len(centers), n)]
            + rng.normal(size=(n, dim)).astype(np.float32)
        )
        yield lo, vecs


def _ann_scale_queries(dim: int, n_q: int = 64):
    rng_c = np.random.default_rng(20260801)
    centers = (rng_c.normal(size=(4096, dim)) * 3.0).astype(np.float32)
    rng = np.random.default_rng(99)
    return (
        centers[rng.integers(0, len(centers), n_q)]
        + rng.normal(size=(n_q, dim)).astype(np.float32)
    )


def _ann_serve_qps(plane, params, *, n_clients=64, per_client=64, depth=16,
                   max_batch=1024, max_wait_ms=3.0, name="serve"):
    """Serving QPS: ``n_clients`` threads, each pipelining ``depth`` async
    submits (the serving pattern of a fleet of low-latency clients), through
    ONE ragged micro-batching endpoint."""
    import collections
    import threading

    from lakesoul_tpu.annplane import ShardedAnnEndpoint

    queries = _ann_scale_queries(plane.dim, 256)
    with ShardedAnnEndpoint(
        plane, params, max_batch=max_batch, max_wait_ms=max_wait_ms,
        max_pending=2 * n_clients * depth, name=name,
    ) as ep:
        ep.search(queries[0])  # warm the dispatch path
        start = time.perf_counter()

        def client(ci):
            inflight = collections.deque()
            for j in range(per_client):
                inflight.append(ep.submit(queries[(ci * 31 + j) % len(queries)]))
                if len(inflight) >= depth:
                    inflight.popleft().result(timeout=120)
            while inflight:
                inflight.popleft().result(timeout=120)

        threads = [
            threading.Thread(target=client, args=(ci,)) for ci in range(n_clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        wall = time.perf_counter() - start
        stats = ep.stats()
    return n_clients * per_client / wall, stats


def bench_ann_scale() -> None:
    """The production-scale ANN leg (ROADMAP item 1): a >=10M x 128d corpus
    written to a real LSF table, streamed through the BOUNDED scan path into
    a memory-bounded multi-shard build (peak RSS asserted against a ceiling
    far below the 6.6 GB resident corpus), then served at fleet shape.
    Publishes and GATES:

    - build rows/s + peak RSS <= ``LAKESOUL_ANN_SCALE_RSS_CEILING_MB``;
    - multi-shard search recall@10 vs the streaming exact oracle
      >= ``LAKESOUL_ANN_SCALE_RECALL_FLOOR`` (leg FAILS below, like the
      scan_stages degeneracy budget);
    - ragged-batched serving QPS (64 pipelined clients) >=
      ``LAKESOUL_ANN_SCALE_QPS_FLOOR`` = 10x the committed ~125 QPS
      single-shard baseline;
    - the 64-client overload story at the new scale: typed sheds only;
    - a 1/2/4-shard sweep on a 600k sub-corpus: recall held at every shard
      count (sharding must not cost recall) with QPS per count published.
    """
    import pyarrow as pa

    from lakesoul_tpu import LakeSoulCatalog
    from lakesoul_tpu.annplane import (
        AnnPlane,
        AnnPlaneConfig,
        ShardedAnnBuilder,
        ShardedAnnEndpoint,
        iter_table_vectors,
    )
    from lakesoul_tpu.errors import OverloadedError
    from lakesoul_tpu.utils.memory import peak_rss_mb
    from lakesoul_tpu.vector.config import VectorIndexConfig
    from lakesoul_tpu.vector.index import SearchParams
    from lakesoul_tpu.vector.oracle import (
        StreamingExactOracle,
        exact_topk,
        recall_at_k,
    )

    dim = ANN_SCALE_DIM
    n_rows = ANN_SCALE_ROWS
    queries = _ann_scale_queries(dim)
    params = SearchParams(top_k=10, nprobe=48, rerank_depth=64)

    def shard_sweep_leg() -> dict:
        """1/2/4-shard sweep on a 600k sub-corpus: sharding must not cost
        recall (floor enforced at EVERY count), QPS per count published.
        Runs AFTER the 10M build so the RSS assertion sees a clean peak."""
        import gc

        sub_n = 600_000
        sub_vecs = np.concatenate(
            [v for _, v in _ann_scale_corpus_chunks(sub_n, dim, chunk=200_000)]
        )
        sub_ids = np.arange(sub_n, dtype=np.uint64)
        sub_truth = exact_topk(sub_vecs, sub_ids, queries, 10)
        sweep = {}
        with tempfile.TemporaryDirectory() as d:
            for n_shards in (1, 2, 4):
                index_cfg = VectorIndexConfig(
                    column="emb", dim=dim, nlist=256, total_bits=4
                )
                probe = AnnPlaneConfig(
                    index=index_cfg, shard_budget_bytes=1 << 40
                )
                rows_per = -(-sub_n // n_shards)
                cfg = AnnPlaneConfig(
                    index=index_cfg,
                    shard_budget_bytes=rows_per * probe.bytes_per_vector(),
                )
                root = os.path.join(d, f"plane{n_shards}")
                ShardedAnnBuilder(root, cfg).build(
                    (sub_vecs[lo : lo + 200_000], sub_ids[lo : lo + 200_000])
                    for lo in range(0, sub_n, 200_000)
                )
                plane = AnnPlane.open(root, use_pallas=False)
                assert len(plane.shards) == n_shards, (
                    len(plane.shards), n_shards,
                )
                got, _ = plane.batch_search(queries, params)
                recall = recall_at_k(sub_truth, got)
                qps, _ = _ann_serve_qps(
                    plane, params, n_clients=16, per_client=32, depth=4,
                    name=f"sweep{n_shards}",
                )
                sweep[n_shards] = {
                    "recall_at_10": round(recall, 4), "qps": round(qps, 1),
                }
                assert recall >= ANN_SCALE_RECALL_FLOOR, (
                    f"{n_shards}-shard recall {recall:.4f} breached the"
                    f" {ANN_SCALE_RECALL_FLOOR} floor"
                )
                del plane
                gc.collect()
        return sweep

    # ---- the 10M leg: table write -> bounded-scan build ------------------
    with tempfile.TemporaryDirectory() as d:
        catalog = LakeSoulCatalog(
            os.path.join(d, "wh"), db_path=os.path.join(d, "meta.db")
        )
        schema = pa.schema(
            [("id", pa.int64()), ("emb", pa.list_(pa.float32(), dim))]
        )
        table = catalog.create_table(
            "corpus", schema, properties={"lakesoul.file_format": "lsf"}
        )
        # peak_rss_mb is the PROCESS-lifetime high-water mark: under
        # `micro.py all` an earlier leg may already own the peak, which
        # would gate the wrong thing — only assert when this leg starts
        # with clean headroom (standalone runs, the committed mode)
        rss_at_leg_start = peak_rss_mb()
        rss_gate_armed = rss_at_leg_start < 0.5 * ANN_SCALE_RSS_CEILING_MB
        write_start = time.perf_counter()
        for lo, vecs in _ann_scale_corpus_chunks(n_rows, dim):
            table.write_arrow(pa.table({
                "id": np.arange(lo, lo + len(vecs), dtype=np.int64),
                "emb": pa.FixedSizeListArray.from_arrays(
                    pa.array(vecs.reshape(-1)), dim
                ),
            }, schema=schema))
        write_dt = time.perf_counter() - write_start

        index_cfg = VectorIndexConfig(
            column="emb", dim=dim, nlist=512, total_bits=4
        )
        cfg = AnnPlaneConfig(
            index=index_cfg, shard_budget_bytes=ANN_SCALE_SHARD_BUDGET
        )
        root = os.path.join(d, "plane")
        build_start = time.perf_counter()
        manifest = ShardedAnnBuilder(root, cfg).build(
            iter_table_vectors(table, "emb", "id", batch_size=262_144)
        )
        build_dt = time.perf_counter() - build_start
        build_rss = peak_rss_mb()
        assert manifest["complete"] and manifest["total_rows"] == n_rows
        if rss_gate_armed:
            assert build_rss <= ANN_SCALE_RSS_CEILING_MB, (
                f"build peak RSS {build_rss:.0f} MB exceeded the declared"
                f" {ANN_SCALE_RSS_CEILING_MB} MB ceiling (shard budget"
                f" {ANN_SCALE_SHARD_BUDGET >> 20} MiB)"
            )
        else:
            sys.stderr.write(
                f"ann_scale: RSS gate skipped — peak was already"
                f" {rss_at_leg_start:.0f} MB at leg start (earlier legs own"
                " the high-water mark)\n"
            )

        # streaming exact oracle over a REGENERATION of the corpus: truth
        # never holds more than one chunk + Q x k running best
        oracle = StreamingExactOracle(queries, 10)
        for lo, vecs in _ann_scale_corpus_chunks(n_rows, dim):
            oracle.consume(vecs, np.arange(lo, lo + len(vecs), dtype=np.uint64))
        truth = oracle.truth()

        plane = AnnPlane.open(root, use_pallas=False)
        got, _ = plane.batch_search(queries, params)
        recall = recall_at_k(truth, got)
        assert recall >= ANN_SCALE_RECALL_FLOOR, (
            f"10M recall@10 {recall:.4f} breached the"
            f" {ANN_SCALE_RECALL_FLOOR} floor"
        )

        qps, serve_stats = _ann_serve_qps(plane, params)
        # overload at the new scale: 64 clients, tiny pending bound — every
        # rejection must be the typed shed (anything else would have landed
        # in errors and failed the count check)
        import threading

        ep = ShardedAnnEndpoint(
            plane, params, max_batch=16, max_wait_ms=5.0, max_pending=32,
            name="overload",
        )
        sheds = [0]
        served = [0]
        errors = []

        def hammer(ci):
            for j in range(16):
                try:
                    ep.search(queries[(ci + j) % len(queries)], timeout=120)
                    served[0] += 1
                except OverloadedError:
                    sheds[0] += 1
                except Exception as e:  # pragma: no cover — asserted below
                    errors.append(e)

        threads = [
            threading.Thread(target=hammer, args=(ci,)) for ci in range(64)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        overload_stats = ep.stats()
        ep.close()
        assert not errors, errors[:3]
        assert sheds[0] > 0, "overload hammer never tripped the pending bound"

        shard_sweep = shard_sweep_leg()

        _emit(
            "ann_scale", qps, "QPS",
            rows=n_rows,
            dim=dim,
            shards=len(manifest["shards"]),
            shard_budget_mb=ANN_SCALE_SHARD_BUDGET >> 20,
            build_rows_per_s=round(n_rows / build_dt, 1),
            table_write_rows_per_s=round(n_rows / write_dt, 1),
            build_peak_rss_mb=round(build_rss, 1),
            rss_ceiling_mb=ANN_SCALE_RSS_CEILING_MB,
            rss_gate_armed=rss_gate_armed,
            recall_at_10=round(recall, 4),
            recall_floor=ANN_SCALE_RECALL_FLOOR,
            qps_floor=ANN_SCALE_QPS_FLOOR,
            qps_vs_committed_baseline=round(qps / 125.2, 1),
            serving_mean_batch=round(serve_stats["mean_batch"], 1),
            serving_latency_p50_s=round(serve_stats["latency_p50"], 4),
            serving_latency_p99_s=round(serve_stats["latency_p99"], 4),
            nprobe=params.nprobe,
            overload_sheds=sheds[0],
            overload_served=served[0],
            overload_rejected_typed=overload_stats["rejected"],
            shard_sweep=shard_sweep,
        )
        assert qps >= ANN_SCALE_QPS_FLOOR, (
            f"ragged serving {qps:.0f} QPS below the {ANN_SCALE_QPS_FLOOR}"
            " floor (10x the committed single-shard baseline)"
        )


# tensor_replay gate: epoch-2 device replay must beat epoch-1 streaming by
# this factor (byte-identity asserted separately).  Replay serves pinned
# device shards — no decode, no collate, no put — so the measured margin is
# an order of magnitude; 2.0 is the declared floor a regression (a host
# round trip sneaking into the replay path, accidental re-collate) trips.
TENSOR_REPLAY_FLOOR = float(os.environ.get("LAKESOUL_TENSOR_REPLAY_FLOOR", 2.0))


def _tensor_replay_child() -> None:
    """Runs in a subprocess with an 8-device CPU mesh (XLA_FLAGS must be
    set BEFORE jax imports, so the parent leg spawns this).  Prints one
    JSON result line."""
    import hashlib

    import jax
    import jax.numpy as jnp  # noqa: F401 — force backend init under the flags
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    from lakesoul_tpu import LakeSoulCatalog
    from lakesoul_tpu.tensorplane import tensor_field
    from lakesoul_tpu.tensorplane.smoke import uncovered_kernels

    devices = jax.devices()
    assert len(devices) >= 8, f"mesh leg needs 8 devices, got {len(devices)}"
    mesh = Mesh(np.array(devices[:8]), ("dp",))
    sharding = NamedSharding(mesh, P("dp"))

    n_rows, width, batch = 131_072, 64, 1_024
    rng = np.random.default_rng(0)
    with tempfile.TemporaryDirectory() as d:
        catalog = LakeSoulCatalog(d)
        schema = pa.schema([
            ("id", pa.int64()),
            tensor_field("emb", (width,), "float32"),
            ("label", pa.int32()),
        ])
        t = catalog.create_table(
            "tensors", schema, properties={"lakesoul.file_format": "lsf"}
        )
        for lo in range(0, n_rows, 32_768):
            n = min(32_768, n_rows - lo)
            emb = rng.normal(size=(n, width)).astype(np.float32)
            t.write_arrow(pa.table({
                "id": np.arange(lo, lo + n, dtype=np.int64),
                "emb": pa.FixedSizeListArray.from_arrays(
                    pa.array(emb.ravel()), width
                ).cast(schema.field("emb").type),
                "label": rng.integers(0, 10, n).astype(np.int32),
            }, schema=schema))

        def epoch_rows_per_s(it) -> tuple[float, int]:
            start = time.perf_counter()
            rows = 0
            last = None
            for b in it:
                rows += b["emb"].shape[0]
                last = b
            jax.block_until_ready(last)
            return rows / (time.perf_counter() - start), rows

        def epoch_hashes(it) -> list[str]:
            out = []
            for b in it:
                h = hashlib.sha256()
                for k in sorted(b):
                    h.update(np.asarray(b[k]).tobytes())
                out.append(h.hexdigest())
            return out

        # --- fully-resident leg: epoch-1 stream (+pin) vs epoch-2 replay
        it = t.scan().batch_size(batch).to_jax_iter(
            cache="device", sharding=sharding
        )
        stream_rps, rows1 = epoch_rows_per_s(it)
        assert it.stats()["replay"]["ready"]
        replay_rps, rows2 = epoch_rows_per_s(it)
        assert rows1 == rows2 == n_rows
        # byte-identity: a third (replay) epoch vs a freshly streamed loader
        replay_sha = epoch_hashes(it)
        stream_sha = epoch_hashes(
            t.scan().batch_size(batch).to_jax_iter(sharding=sharding)
        )
        assert replay_sha == stream_sha, "replay diverged from stream"

        # --- budget-spill leg: half the epoch resident, tail re-streamed.
        # The budget is PER DEVICE: a dp-sharded batch bills each of the 8
        # chips an eighth of its host bytes
        per_batch_dev = batch * (width * 4 + 4 + 4) // 8
        budget = (n_rows // batch // 2) * per_batch_dev + 64
        it_sp = t.scan().batch_size(batch).to_jax_iter(
            cache="device", sharding=sharding, replay_budget_bytes=budget
        )
        spill_stream_rps, _ = epoch_rows_per_s(it_sp)
        st = it_sp.stats()["replay"]
        assert st["spilled"], st
        hybrid_rps, rows_h = epoch_rows_per_s(it_sp)
        assert rows_h == n_rows
        assert epoch_hashes(it_sp) == stream_sha, "hybrid epoch diverged"

        print(json.dumps({
            "rows": n_rows,
            "tensor_width": width,
            "batch": batch,
            "devices": len(devices),
            "stream_rows_per_s": round(stream_rps, 1),
            "replay_rows_per_s": round(replay_rps, 1),
            "replay_over_stream": round(replay_rps / stream_rps, 2),
            "spill_resident_batches": st["resident_batches"],
            "spill_budget_bytes": budget,
            "hybrid_rows_per_s": round(hybrid_rps, 1),
            "hybrid_over_stream": round(hybrid_rps / spill_stream_rps, 2),
            "byte_identity": True,
            "platform": devices[0].platform,
            "uncovered_kernels": uncovered_kernels(),
        }))


def bench_tensor_replay() -> None:
    """Epoch-1 streaming delivery vs epoch-2 device-resident replay on the
    8-device CPU mesh (tensorplane/replay.py), with byte-identity asserted
    per batch, a budget-spill hybrid variant, and the smoke register's
    kernel-coverage check.  FAILS when replay does not beat streaming by
    ``TENSOR_REPLAY_FLOOR``.  The register itself runs elsewhere: compiled
    on the chip by ``chip_smoke.py``, interpreted in tier-1."""
    import subprocess

    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = (
        env.get("XLA_FLAGS", "") + " --xla_force_host_platform_device_count=8"
    ).strip()
    proc = subprocess.run(
        [sys.executable, os.path.abspath(__file__), "_tensor_replay_child"],
        env=env, capture_output=True, text=True, timeout=900,
    )
    assert proc.returncode == 0, proc.stderr[-4000:]
    result = json.loads(proc.stdout.strip().splitlines()[-1])
    ratio = result["replay_over_stream"]
    _emit(
        "tensor_replay", result["replay_rows_per_s"], "rows/s",
        floor=TENSOR_REPLAY_FLOOR, **result,
    )
    assert ratio >= TENSOR_REPLAY_FLOOR, (
        f"epoch-2 replay beat streaming only {ratio:.2f}x — below the"
        f" declared {TENSOR_REPLAY_FLOOR} floor"
    )
    assert result["byte_identity"]
    assert not result["uncovered_kernels"], result["uncovered_kernels"]


# obs_fleet overhead budget: fleet telemetry (member/recorder flushes during
# the scan window, fleet-wide, plus ONE aggregator merge) may cost at most
# this fraction of the scan-leg wall time.  The leg FAILS on breach — the
# observability plane must be cheap enough to leave on everywhere.
OBS_FLEET_BUDGET = float(os.environ.get("LAKESOUL_OBS_FLEET_BUDGET", 0.01))


def bench_obs_fleet(
    n_rows: int = 2_000_000, n_buckets: int = 8,
    commits: int = 8, rows_per_commit: int = 250,
    ttl_s: float = 1.5, fault_p: float = 0.3, flush_s: float = 1.0,
    store_latency_s: float = 0.35,
) -> None:
    """The fleet-observability acceptance run: a three-role chaos fleet —
    a freshness writer + leased compactor (SIGKILLed while HOLDING its
    lease) + in-process fresh follower under p=0.3 flaky faults, then a
    scanplane fleet (2 workers + a drive client, all separate processes) —
    every role publishing to ONE obs spool.  Asserts the plane's four
    claims:

    - ONE aggregated fleet snapshot with per-role series (build_info per
      role, counters summed fleet-wide, freshness SLO evaluated from the
      MERGED histogram);
    - an end-to-end commit → decode → delivery trace whose spans come
      from ≥ 2 distinct processes, assembled from the spool by trace id;
    - a recoverable postmortem for the SIGKILLed compactor (stale by
      heartbeat age, flight-recorder dump + last-flushed snapshot intact);
    - overhead budget: scan-window flush cost (fleet-wide delta of
      ``lakesoul_obs_flush_seconds``) + one aggregator merge ≤
      ``OBS_FLEET_BUDGET`` of the scan-leg wall time (FAILS on breach)."""
    import signal
    import subprocess
    import threading

    from lakesoul_tpu import LakeSoulCatalog
    from lakesoul_tpu.freshness import FreshFollower, SloMonitor
    from lakesoul_tpu.obs import fleet, parse_series_key
    from lakesoul_tpu.obs.tracing import ENV_TRACE_ID, new_trace_id
    from lakesoul_tpu.runtime import faults
    from lakesoul_tpu.runtime.resilience import RetryPolicy
    from lakesoul_tpu.scanplane.delivery import ScanPlaneDelivery
    from lakesoul_tpu.scanplane.session import ScanSession
    from lakesoul_tpu.service.flight import LakeSoulFlightServer

    rng = np.random.default_rng(0)
    batch_size = 65_536
    trace_id = new_trace_id()
    spool_base = "/dev/shm" if os.path.isdir("/dev/shm") else None

    with tempfile.TemporaryDirectory() as d, \
            tempfile.TemporaryDirectory(prefix="lsobs-", dir=spool_base) as shm:
        obs_spool = os.path.join(shm, "obs")
        scan_spool = os.path.join(shm, "scan")
        os.makedirs(obs_spool)
        os.makedirs(scan_spool)
        wh, db = os.path.join(d, "wh"), os.path.join(d, "meta.db")
        catalog = LakeSoulCatalog(wh, db_path=db)

        def child_env(**extra) -> dict:
            env = dict(os.environ)
            env.update({
                "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
                "LAKESOUL_RETRY_SEED": "7",
                "LAKESOUL_OBS_SPOOL": obs_spool,
                "LAKESOUL_OBS_FLUSH_S": str(flush_s),
                ENV_TRACE_ID: trace_id,
            })
            env.update(extra)
            return env

        saved_trace = os.environ.get(ENV_TRACE_ID)
        os.environ[ENV_TRACE_ID] = trace_id  # driver spans join the trace
        pub = fleet.arm("bench-driver", spool_dir=obs_spool, flush_s=flush_s)
        try:
            # ---- phase A: freshness writer + leased compactor chaos + in-
            # process follower under flaky faults ------------------------
            schema_f = pa.schema([
                ("id", pa.int64()), ("seq", pa.int64()), ("v", pa.float64()),
            ])
            from lakesoul_tpu.meta.entity import now_millis

            tf = catalog.create_table(
                "fresh", schema_f, primary_keys=["id"], hash_bucket_num=2,
                cdc=True,
            )
            start_ts = now_millis() - 1
            store = catalog.client.store
            lease_key = f"compaction/{tf.info.table_id}/-5"

            def compactor(service_id: str, env: dict) -> subprocess.Popen:
                return subprocess.Popen(
                    [sys.executable, "-m", "lakesoul_tpu.compaction",
                     "--warehouse", wh, "--db-path", db,
                     "--lease-ttl-s", str(ttl_s), "--poll-s", "0.1",
                     "--version-gap", "3", "--service-id", service_id],
                    env=env, stdout=subprocess.DEVNULL,
                    stderr=subprocess.DEVNULL,
                )

            victim = compactor("victim", child_env(
                LAKESOUL_FAULTS="compaction.leased_job:1:hang:300"
            ))
            peer_box: dict = {}
            writer = subprocess.Popen(
                [sys.executable, "-m", "lakesoul_tpu.freshness", "writer",
                 "--warehouse", wh, "--db-path", db, "--table", "fresh",
                 "--commits", str(commits),
                 "--rows-per-commit", str(rows_per_commit),
                 "--interval-s", "0.1"],
                env=child_env(), stdout=subprocess.PIPE,
                stderr=subprocess.PIPE, text=True,
            )

            killed: dict = {}

            def kill_when_leased():
                deadline = time.monotonic() + 60.0
                while time.monotonic() < deadline:
                    lease = store.get_lease(lease_key)
                    if lease is not None and lease.holder == "victim":
                        victim.send_signal(signal.SIGKILL)
                        victim.wait(10.0)
                        killed["pid"] = victim.pid
                        killed["t"] = time.monotonic()
                        # the replacement compactor takes over under the
                        # fencing trail (proven by the freshness leg; here
                        # it keeps a live compactor member in the fleet)
                        peer_box["peer"] = compactor("peer", child_env())
                        return
                    time.sleep(0.05)

            watcher = threading.Thread(target=kill_when_leased, daemon=True)
            watcher.start()

            expected = commits * rows_per_commit
            slo = SloMonitor(target_s=FRESHNESS_SLO_S, budget_fraction=0.05,
                             slo="obs-fleet")
            stop = threading.Event()
            follower = FreshFollower(
                catalog.table("fresh").scan().batch_size(2048),
                start_timestamp_ms=start_ts,
                poll_interval=0.05,
                stop_event=stop,
                retry_policy=RetryPolicy(
                    max_attempts=12, base_delay_s=0.002, max_delay_s=0.05,
                    seed=7,
                ),
                slo=slo,
            )
            delivered = 0
            faults.clear()
            faults.install(f"follow.poll:{fault_p}:flaky")
            faults.install(f"object_store.cat_file:{fault_p}:flaky")
            faults.install(f"object_store.open:{fault_p}:flaky")
            try:
                def consume():
                    nonlocal delivered
                    for b in follower.iter_batches():
                        delivered += b.num_rows
                        if delivered >= expected:
                            stop.set()

                th = threading.Thread(target=consume, daemon=True)
                th.start()
                deadline = time.monotonic() + 120.0
                while th.is_alive() and time.monotonic() < deadline:
                    th.join(timeout=0.2)
                stop.set()
                th.join(timeout=15.0)
            finally:
                faults.clear()
                writer.communicate(timeout=60.0)
                watcher.join(timeout=15.0)
                if victim.poll() is None:
                    victim.send_signal(signal.SIGKILL)
                    victim.wait(10.0)
                peer = peer_box.get("peer")
                if peer is not None:
                    peer.terminate()
                    peer.wait(10.0)
            assert delivered == expected, (delivered, expected)
            assert "pid" in killed, "victim compactor never held a lease"

            # ---- phase B: scanplane fleet, the wall-clock the obs plane
            # is budgeted against ------------------------------------------
            schema_t = pa.schema([
                ("id", pa.int64()), ("label", pa.int32()),
                ("f0", pa.float32()), ("f1", pa.float32()),
            ])
            t = catalog.create_table(
                "t", schema_t, primary_keys=["id"],
                hash_bucket_num=n_buckets,
                properties={"lakesoul.file_format": "lsf"},
            )
            t.write_arrow(pa.table({
                "id": np.arange(n_rows, dtype=np.int64),
                "label": rng.integers(0, 10, n_rows).astype(np.int32),
                "f0": rng.normal(size=n_rows).astype(np.float32),
                "f1": rng.normal(size=n_rows).astype(np.float32),
            }, schema=schema_t))

            agg = fleet.FleetAggregator(obs_spool, stale_after_s=5.0)

            def flush_sum(snapshot: dict) -> float:
                h = snapshot.get("lakesoul_obs_flush_seconds")
                return float(h["sum"]) if isinstance(h, dict) else 0.0

            delivery = ScanPlaneDelivery(catalog, scan_spool, wait_s=180)
            server = LakeSoulFlightServer(
                catalog, "grpc://127.0.0.1:0", scanplane=delivery
            )
            threading.Thread(target=server.serve, daemon=True).start()
            location = f"grpc://127.0.0.1:{server.port}"
            workers: list = []
            try:
                for i in range(2):
                    workers.append(subprocess.Popen(
                        [sys.executable, "-m", "lakesoul_tpu.scanplane",
                         "worker", "--warehouse", wh, "--db-path", db,
                         "--spool", scan_spool,
                         "--lease-ttl-s", str(ttl_s), "--poll-s", "0.05",
                         "--worker-id", f"w{i}"],
                        # per-range store latency: the same emulation
                        # discipline as the scanplane/pipeline legs — the
                        # deployment this budget protects scans remote
                        # object storage, not page cache
                        env=child_env(LAKESOUL_FAULTS=(
                            f"scanplane.range:1:delay:{store_latency_s}"
                        )),
                        stdout=subprocess.PIPE,
                        stderr=subprocess.DEVNULL, text=True,
                    ))
                for w in workers:
                    w.stdout.readline()  # readiness line

                def scan_pass(bsz: int) -> tuple[float, float]:
                    """One drive process over a fresh session; returns
                    (scan wall, fleet flush seconds spent in the window).
                    The window opens at scan start (fleet boot flushes are
                    arming cost, not per-scan overhead) and closes right
                    after the drive's atexit flush lands."""
                    drive = subprocess.Popen(
                        [sys.executable, "-m", "lakesoul_tpu.scanplane",
                         "drive", "--location", location, "--table", "t",
                         "--batch-size", str(bsz),
                         "--rank", "0", "--world", "1"],
                        env=child_env(), stdout=subprocess.PIPE,
                        stderr=subprocess.PIPE, text=True,
                    )
                    session = ScanSession.plan(
                        catalog, {"table": "t", "batch_size": bsz}
                    )
                    manifest = os.path.join(
                        scan_spool, session.session_id, "manifest.json"
                    )
                    deadline = time.monotonic() + 120.0
                    while not os.path.exists(manifest):
                        assert time.monotonic() < deadline, "drive never connected"
                        time.sleep(0.02)
                    # no flush_now here: forcing a flush to measure flushes
                    # would bill the measurement to the budget; periodic
                    # flushes lag the window edges by ≤ flush_s on each
                    # side, unbiased in expectation
                    f0 = flush_sum(agg.aggregate()["snapshot"])
                    t0 = time.time()
                    out, err = drive.communicate(timeout=600)
                    lines = [
                        ln for ln in out.splitlines() if ln.startswith("{")
                    ]
                    assert drive.returncode == 0 and lines, err[-2000:]
                    drive_out = json.loads(lines[-1])
                    assert drive_out["rows"] == n_rows, drive_out
                    wall = drive_out["ended_unix"] - t0
                    f1 = flush_sum(agg.aggregate()["snapshot"])
                    return wall, max(0.0, f1 - f0)

                # best-of-2 passes: flush timers land in the window at
                # ±1-flush granularity, so a single pass is noisy; a
                # DIFFERENT batch size forces a fresh session (same-size
                # requests coalesce onto the already-produced spool)
                passes = [scan_pass(batch_size), scan_pass(batch_size + 4096)]
                # two flush periods so the workers' final spans/heartbeats
                # reach the spool (SIGTERM skips atexit by design)
                time.sleep(2.5 * flush_s)
            finally:
                for w in workers:
                    if w.poll() is None:
                        w.terminate()
                for w in workers:
                    try:
                        w.wait(10.0)
                    except subprocess.TimeoutExpired:
                        w.kill()
                server.shutdown()

            # the victim's heartbeat age must provably exceed the staleness
            # threshold (a fast scan leg can finish inside it)
            since_kill = time.monotonic() - killed["t"]
            if since_kill < 5.5:
                time.sleep(5.5 - since_kill)

            # ---- the four claims ----------------------------------------
            merge_t0 = time.perf_counter()
            doc = agg.aggregate()
            merge_s = time.perf_counter() - merge_t0
            snapshot = doc["snapshot"]

            roles = set()
            for key in snapshot:
                if key.startswith("lakesoul_build_info"):
                    _, labels = parse_series_key(key)
                    roles.add((labels or {}).get("role"))
            assert roles >= {
                "bench-driver", "freshness-writer", "compactor",
                "scanplane-worker", "scanplane-drive",
            }, roles
            fr = doc["slos"]["freshness"]
            # one observation per delivered (commit, bucket) hand-off — at
            # least one per commit made it through the flaky faults
            assert fr["count"] >= commits and fr["in_budget"], fr
            assert doc["fleet"]["rows"] >= n_rows + expected
            assert doc["fleet"]["rows_per_s"] > 0

            trace = agg.trace(trace_id)
            names = [s["name"] for s in trace]
            pids = {s["pid"] for s in trace}
            assert "freshness.commit" in names, names
            assert "scanplane.drive.deliver" in names, names
            assert len(pids) >= 2, pids
            commit_t = min(
                s["t_unix"] for s in trace if s["name"] == "freshness.commit"
            )
            deliver_t = max(
                s["t_unix"] for s in trace
                if s["name"] == "scanplane.drive.deliver"
            )
            assert commit_t < deliver_t  # commit → delivery, end to end

            stale_ids = {m["service_id"] for m in agg.stale_members()}
            assert "victim" in stale_ids, [
                (m["service_id"], round(time.time() - m["heartbeat_unix"], 2))
                for m in agg.members()
            ]
            pm = next(
                p for p in agg.postmortems() if p["service_id"] == "victim"
            )
            assert pm["role"] == "compactor" and pm["pid"] == killed["pid"]
            assert any(
                k.startswith("lakesoul_build_info") for k in pm["last_snapshot"]
            ), "victim's last-flushed snapshot not recovered"

            overheads = [(fl + merge_s) / wall for wall, fl in passes]
            best = overheads.index(min(overheads))
            scan_wall, flush_win = passes[best]
            overhead = overheads[best]
            _emit(
                "obs_fleet", 100.0 * overhead, "% of scan wall",
                budget_pct=100.0 * OBS_FLEET_BUDGET,
                scan_wall_s=round(scan_wall, 3),
                scan_rows=n_rows,
                scan_rows_per_s=round(n_rows / scan_wall, 1),
                flush_scan_window_s=round(flush_win, 5),
                pass_overheads_pct=[round(100 * o, 2) for o in overheads],
                merge_s=round(merge_s, 5),
                flush_interval_s=flush_s,
                members=len(doc["members"]),
                stale_members=len(stale_ids),
                roles=sorted(r for r in roles if r),
                fleet_rows=doc["fleet"]["rows"],
                fleet_rows_per_s=doc["fleet"]["rows_per_s"],
                freshness_slo_in_budget=fr["in_budget"],
                freshness_commits=fr["count"],
                follower_rows=delivered,
                fault_p=fault_p,
                trace_spans=len(trace),
                trace_processes=len(pids),
                trace_commit_to_delivery=True,
                victim_sigkilled=True,
                postmortem_recovered=True,
            )
            assert overhead <= OBS_FLEET_BUDGET, (
                f"obs overhead {100 * overhead:.2f}% of scan wall — budget is"
                f" {100 * OBS_FLEET_BUDGET:.2f}%"
            )
        finally:
            if saved_trace is None:
                os.environ.pop(ENV_TRACE_ID, None)
            else:
                os.environ[ENV_TRACE_ID] = saved_trace
            if pub is not None:
                pub.stop()


# the fleet leg's scaling gate: aggregate trainer rows/s must grow at
# least this factor from 1 → 2 emulated hosts (near-linear modulo fixed
# session/connect overheads); the leg FAILS below it
FLEET_SCALE_FLOOR = float(os.environ.get("LAKESOUL_FLEET_SCALE_FLOOR", 1.7))


def bench_fleet(
    n_rows: int = 2_000_000, n_buckets: int = 16, ttl_s: float = 2.0,
    total_devices: int = 8, step_s: float = 0.15,
) -> None:
    """Multi-host training surface at fleet shape (ROADMAP item 2): N
    emulated hosts — each a REAL gateway process plus a REAL trainer
    process (``python -m lakesoul_tpu.fleet train`` under
    ``LAKESOUL_FLEET_PROCESS_INDEX/_COUNT``, bound to a disjoint device
    subset via ``xla_force_host_platform_device_count``) — consume one
    table through the scan fabric on the forced ``stream`` transport (the
    no-shared-medium cross-host floor).  Three claims, all asserted:

    - **per-rank sha identity**: every rank's collated-host-array sha256
      equals the single-process ``scan.shard(rank, world)`` stream;
    - **scaling**: aggregate trainer rows/s grows ≥``FLEET_SCALE_FLOOR``
      from 1 → 2 hosts (4-host figure emitted alongside) over a warm
      spool with an emulated fixed per-batch training step (``step_s`` —
      each host's devices are busy per batch, the realistic consumption
      shape): N hosts step over disjoint shards concurrently, so the
      fabric's aggregate feed rate must scale with hosts.  Production is
      bench_scanplane's axis;
    - **kill-a-host chaos**: SIGKILL one host's gateway AND one
      autoscaler-owned worker mid-run → the surviving rank completes
      exactly-once, the autoscaler backfills the dead worker within one
      lease TTL, and the orphaned rank relaunched against the surviving
      gateway completes the same session exactly-once."""
    import hashlib
    import signal
    import subprocess
    import threading

    from lakesoul_tpu import LakeSoulCatalog
    from lakesoul_tpu.fleet.multihost import digest_batch
    from lakesoul_tpu.scanplane.session import ScanSession
    from lakesoul_tpu.scanplane.worker import ScanPlaneWorker

    rng = np.random.default_rng(0)
    schema = pa.schema([
        ("id", pa.int64()), ("label", pa.int32()),
        ("f0", pa.float32()), ("f1", pa.float32()),
        ("f2", pa.float32()), ("f3", pa.float32()),
    ])
    batch_size = 65_536

    def child_env(**extra) -> dict:
        env = dict(os.environ)
        env.update({
            "JAX_PLATFORMS": "cpu", "PYTHONPATH": REPO,
            "LAKESOUL_RETRY_SEED": "7", "LAKESOUL_RETRY_CAP_S": "0.5",
        })
        env.update(extra)
        return env

    def spawn_gateway(wh, db, spool):
        proc = subprocess.Popen(
            [sys.executable, "-m", "lakesoul_tpu.scanplane", "service",
             "--warehouse", wh, "--db-path", db, "--spool", spool,
             "--workers", "0"],
            env=child_env(), stdout=subprocess.PIPE,
            stderr=subprocess.DEVNULL, text=True,
        )
        handle = proc.stdout.readline()
        assert handle, "gateway died before printing its handle"
        return proc, json.loads(handle)["location"]

    def spawn_trainer(wh, db, location, rank, world, step_s=0.0):
        # each emulated host owns a DISJOINT device subset of the mesh
        return subprocess.Popen(
            [sys.executable, "-m", "lakesoul_tpu.fleet", "train",
             "--warehouse", wh, "--db-path", db, "--table", "t",
             "--batch-size", str(batch_size), "--location", location,
             "--step-s", str(step_s)],
            env=child_env(
                LAKESOUL_FLEET_PROCESS_INDEX=str(rank),
                LAKESOUL_FLEET_PROCESS_COUNT=str(world),
                LAKESOUL_FLEET_TRANSPORT="stream",
                XLA_FLAGS=(
                    "--xla_force_host_platform_device_count="
                    f"{total_devices // world}"
                ),
            ),
            stdout=subprocess.PIPE, stderr=subprocess.PIPE, text=True,
        )

    def finish(proc, *, timeout=600.0) -> dict:
        out, err = proc.communicate(timeout=timeout)
        lines = [ln for ln in out.splitlines() if ln.startswith("{")]
        assert proc.returncode == 0 and lines, err[-2000:]
        return json.loads(lines[-1])

    with tempfile.TemporaryDirectory() as d:
        wh, db = os.path.join(d, "wh"), os.path.join(d, "meta.db")
        catalog = LakeSoulCatalog(wh, db_path=db)
        t = catalog.create_table(
            "t", schema, primary_keys=["id"], hash_bucket_num=n_buckets,
        )
        t.write_arrow(pa.table({
            "id": np.arange(n_rows, dtype=np.int64),
            "label": rng.integers(0, 10, n_rows).astype(np.int32),
            **{f"f{j}": rng.normal(size=n_rows).astype(np.float32)
               for j in range(4)},
        }, schema=schema))
        ids = np.sort(
            rng.choice(n_rows, n_rows // 4, replace=False)
        ).astype(np.int64)
        t.upsert(pa.table({
            "id": ids,
            "label": rng.integers(0, 10, len(ids)).astype(np.int32),
            **{f"f{j}": rng.normal(size=len(ids)).astype(np.float32)
               for j in range(4)},
        }, schema=schema))

        # single-process shard-scan oracles, hashed EXACTLY as the train
        # role hashes (collated host arrays through digest_batch)
        def shard_sha(rank: int, world: int) -> "tuple[str, int]":
            scan = t.scan().batch_size(batch_size)
            if world > 1:
                scan = scan.shard(rank, world)
            digest = hashlib.sha256()
            rows = 0
            for batch in scan.to_jax_iter(
                device_put=False, drop_remainder=False
            ):
                rows += digest_batch(digest, batch)
            return digest.hexdigest(), rows

        oracle = {
            world: {r: shard_sha(r, world) for r in range(world)}
            for world in (1, 2, 4)
        }
        total_rows = sum(rows for _, rows in oracle[1].values())

        # warm spool for the scaling legs: production (bench_scanplane's
        # axis) runs once up front; the measured window is pure delivery —
        # gateway stream + collate + hash per host
        spool_base = "/dev/shm" if os.path.isdir("/dev/shm") else d
        spool = tempfile.mkdtemp(prefix="lsf-", dir=spool_base)
        try:
            ScanSession.plan(
                catalog, {"table": "t", "batch_size": batch_size}
            ).publish(spool)
            ScanPlaneWorker(catalog, spool, lease_ttl_s=30).poll_once()

            rates = {}
            for world in (1, 2, 4):
                gws = []
                try:
                    gws = [spawn_gateway(wh, db, spool) for _ in range(world)]
                    trainers = [
                        spawn_trainer(wh, db, gws[r][1], r, world,
                                      step_s=step_s)
                        for r in range(world)
                    ]
                    outs = [finish(p) for p in trainers]
                    for rank, doc in enumerate(outs):
                        sha, rows = oracle[world][rank]
                        assert doc["rows"] == rows, (world, rank)
                        assert doc["sha256"] == sha, (
                            f"rank {rank}/{world} diverged from the"
                            " single-process shard scan"
                        )
                    window = max(o["ended_unix"] for o in outs) \
                        - min(o["started_unix"] for o in outs)
                    rates[world] = total_rows / window
                finally:
                    for gw, _ in gws:
                        gw.terminate()
                    for gw, _ in gws:
                        try:
                            gw.wait(10.0)
                        except subprocess.TimeoutExpired:
                            gw.kill()
            scale2 = rates[2] / rates[1]
            scale4 = rates[4] / rates[1]
        finally:
            shutil.rmtree(spool, ignore_errors=True)

        # kill-a-host chaos: COLD spool, the worker fleet owned by a real
        # autoscaler; SIGKILL host B's gateway + one autoscaler child
        spool = tempfile.mkdtemp(prefix="lsf-", dir=spool_base)
        events = []
        worker_pids = set()
        procs = []
        backfill_s = None
        try:
            gw_a, loc_a = spawn_gateway(wh, db, spool)
            procs.append(gw_a)
            gw_b, loc_b = spawn_gateway(wh, db, spool)
            procs.append(gw_b)
            scaler = subprocess.Popen(
                [sys.executable, "-m", "lakesoul_tpu.fleet", "autoscale",
                 "--warehouse", wh, "--db-path", db, "--spool", spool,
                 "--min-workers", "2", "--max-workers", "4",
                 "--lease-ttl-s", str(ttl_s), "--poll-s", "0.1",
                 "--worker-lease-ttl-s", str(ttl_s),
                 "--worker-poll-s", "0.05"],
                env=child_env(), stdout=subprocess.PIPE,
                stderr=subprocess.DEVNULL, text=True,
            )
            procs.append(scaler)

            def pump():
                for line in scaler.stdout:
                    try:
                        ev = json.loads(line)
                    except ValueError:
                        continue
                    ev["_at"] = time.monotonic()
                    if ev.get("event") == "spawn":
                        worker_pids.add(ev["pid"])
                    events.append(ev)

            threading.Thread(target=pump, daemon=True).start()
            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline and len(worker_pids) < 2:
                assert scaler.poll() is None, "autoscaler exited early"
                time.sleep(0.05)
            assert len(worker_pids) >= 2, "autoscaler never reached min"

            rank0 = spawn_trainer(wh, db, loc_a, 0, 2)
            procs.append(rank0)
            rank1 = spawn_trainer(wh, db, loc_b, 1, 2)
            procs.append(rank1)
            time.sleep(1.0)
            victim_pid = sorted(worker_pids)[0]
            gw_b.send_signal(signal.SIGKILL)
            os.kill(victim_pid, signal.SIGKILL)
            killed_at = time.monotonic()

            deadline = time.monotonic() + 60.0
            while time.monotonic() < deadline and backfill_s is None:
                snap = list(events)
                for i, ev in enumerate(snap):
                    if ev.get("event") == "worker_exit" \
                            and ev.get("pid") == victim_pid:
                        later = [e for e in snap[i + 1:]
                                 if e.get("event") == "spawn"]
                        if later:
                            backfill_s = later[0]["_at"] - killed_at
                        break
                time.sleep(0.05)
            assert backfill_s is not None, "autoscaler never backfilled"
            assert backfill_s < ttl_s, (
                f"backfill took {backfill_s:.2f}s — one lease TTL is {ttl_s}s"
            )

            doc0 = finish(rank0)
            sha, rows = oracle[2][0]
            assert doc0["rows"] == rows and doc0["sha256"] == sha, (
                "surviving rank diverged through the kill"
            )
            # the orphaned rank, relaunched against the SURVIVING gateway,
            # completes the same session exactly-once (delivered state
            # lives in the spool fabric, not the dead gateway)
            try:
                rank1.communicate(timeout=60.0)
            except subprocess.TimeoutExpired:
                rank1.kill()
                rank1.communicate(timeout=10.0)
            relaunched = spawn_trainer(wh, db, loc_a, 1, 2)
            procs.append(relaunched)
            doc1 = finish(relaunched)
            sha, rows = oracle[2][1]
            assert doc1["rows"] == rows and doc1["sha256"] == sha, (
                "relaunched rank diverged after the gateway kill"
            )
        finally:
            for p in procs:
                if p.poll() is None:
                    p.terminate()
            for p in procs:
                try:
                    p.wait(10.0)
                except subprocess.TimeoutExpired:
                    p.kill()
            for pid in worker_pids:
                try:
                    os.kill(pid, signal.SIGKILL)
                except (ProcessLookupError, PermissionError):
                    pass
            shutil.rmtree(spool, ignore_errors=True)

        _emit(
            "fleet", rates[2], "rows/s",
            rows=total_rows,
            transport="stream",
            hosts_1_rows_per_s=round(rates[1], 1),
            hosts_2_rows_per_s=round(rates[2], 1),
            hosts_4_rows_per_s=round(rates[4], 1),
            scale_1_to_2=round(scale2, 2),
            scale_1_to_4=round(scale4, 2),
            scale_floor=FLEET_SCALE_FLOOR,
            devices_per_host={w: total_devices // w for w in (1, 2, 4)},
            per_rank_sha_identical=True,
            emulated_step_s=step_s,
            chaos_backfill_s=round(backfill_s, 3),
            chaos_exactly_once=True,
            lease_ttl_s=ttl_s,
        )
        assert scale2 >= FLEET_SCALE_FLOOR, (
            f"fleet scaled only {scale2:.2f}x from 1→2 hosts —"
            f" floor is {FLEET_SCALE_FLOOR}x"
        )


# soak leak-slope gate: over repeated open→scan→serve→close cycles the
# traced-heap high-water may climb at most this many bytes between the
# first-third and last-third cycle averages.  Steady state measures ~0
# (caches warm during the first third); a per-cycle retention of even one
# scanned table (~0.6 MB at the default leg shape) blows the budget, so
# this is an O(cycles) leak tripwire, not a formality.
SOAK_HEAP_BUDGET = float(os.environ.get("LAKESOUL_SOAK_HEAP_BUDGET", 4_000_000))


def bench_soak(cycles: int = 12, n_rows: int = 40_000) -> None:
    """Resource-boundedness replay (the runtime half of lakelint's
    boundedness pack): run ``cycles`` full open→scan→serve→close lifecycles
    — open a catalog over a seeded warehouse, scan the table through the
    loader path, serve one real ``/metrics`` scrape from the Prometheus
    exporter, shut everything down — sampling ``leakcheck.snapshot()``
    (fds + live threads) and the tracemalloc heap after every cycle.

    The gate is the SLOPE, not the absolute: first-third vs last-third
    cycle averages must be flat (fds within 2, threads within 1, heap
    within ``SOAK_HEAP_BUDGET`` bytes).  A lifecycle that leaks one fd,
    thread, or table per cycle fails the leg outright — the same
    fail-don't-shave contract as ``scan_stages``."""
    import gc
    import tracemalloc
    import urllib.request

    from lakesoul_tpu import LakeSoulCatalog
    from lakesoul_tpu.analysis import leakcheck
    from lakesoul_tpu.obs.exporter import serve_prometheus

    wh = tempfile.mkdtemp(prefix="lakesoul-soak-")
    try:
        rng = np.random.default_rng(0)
        seed_cat = LakeSoulCatalog(wh)
        table = seed_cat.create_table(
            "soak",
            pa.schema([("id", pa.int64()), ("v", pa.float64())]),
        )
        table.write_arrow(pa.table({
            "id": np.arange(n_rows, dtype=np.int64),
            "v": rng.normal(size=n_rows),
        }))
        del table, seed_cat
        gc.collect()

        tracemalloc.start()
        samples = []
        start = time.perf_counter()
        for _ in range(cycles):
            cat = LakeSoulCatalog(wh)  # open
            rows = len(cat.table("soak").to_arrow())  # scan
            assert rows == n_rows
            srv = serve_prometheus(port=0, host="127.0.0.1")  # serve
            port = srv.server_address[1]
            with urllib.request.urlopen(
                f"http://127.0.0.1:{port}/metrics", timeout=10
            ) as resp:
                assert resp.status == 200 and resp.read()
            srv.shutdown()  # close
            srv.server_close()
            del cat, srv
            gc.collect()
            snap = leakcheck.snapshot()
            samples.append((
                snap.fd_count,
                snap.thread_count,
                tracemalloc.get_traced_memory()[0],
            ))
        dt = time.perf_counter() - start
        tracemalloc.stop()

        third = max(1, cycles // 3)

        def slope(idx: int) -> float:
            first = [s[idx] for s in samples[:third]]
            last = [s[idx] for s in samples[-third:]]
            return sum(last) / len(last) - sum(first) / len(first)

        fd_slope, thread_slope, heap_slope = slope(0), slope(1), slope(2)
        _emit(
            "soak_cycles", cycles / dt, "cycles/s",
            cycles=cycles, rows_per_cycle=n_rows,
            fd_slope=round(fd_slope, 2),
            thread_slope=round(thread_slope, 2),
            heap_slope_bytes=round(heap_slope, 1),
            fd_high_water=max(s[0] for s in samples),
            thread_high_water=max(s[1] for s in samples),
            heap_high_water=max(s[2] for s in samples),
            heap_budget=SOAK_HEAP_BUDGET,
        )
        assert fd_slope <= 2.0, (
            f"soak fd high-water climbs {fd_slope:.2f}/third — an fd leaks"
            " somewhere in the open→scan→serve→close lifecycle"
        )
        assert thread_slope <= 1.0, (
            f"soak thread count climbs {thread_slope:.2f}/third — a thread"
            " outlives its cycle (nothing joined or stopped it)"
        )
        assert heap_slope <= SOAK_HEAP_BUDGET, (
            f"soak heap climbs {heap_slope:.0f} bytes/third — budget"
            f" {SOAK_HEAP_BUDGET:.0f} (LAKESOUL_SOAK_HEAP_BUDGET)"
        )
    finally:
        shutil.rmtree(wh, ignore_errors=True)


LEGS = {
    "merge": bench_merge,
    "scan_stages": bench_scan_stages,
    "formats": bench_formats,
    "streaming": bench_streaming_merge,
    "cache": bench_cache,
    "spill": bench_spill,
    "meta": bench_meta_prune,
    "pipeline": bench_pipeline_scan,
    "chaos": bench_chaos,
    "lint": bench_lint,
    "topology": bench_topology,
    "scanplane": bench_scanplane,
    "freshness": bench_freshness,
    "ann_scale": bench_ann_scale,
    "tensor_replay": bench_tensor_replay,
    "obs_fleet": bench_obs_fleet,
    "fleet": bench_fleet,
    "soak": bench_soak,
}


def _obs_snapshot() -> dict:
    from lakesoul_tpu.obs import registry

    return registry().snapshot()


def _emit_obs(leg: str, before: dict) -> None:
    """Registry DELTA over one leg (the registry is process-cumulative), so
    BENCH_*.json rounds can record loader/scan/merge throughput counters
    alongside wall-clock figures.  Histograms compress to count/sum/mean;
    series a leg didn't move are dropped."""
    obs = {}
    for name, value in sorted(_obs_snapshot().items()):
        if isinstance(value, dict):
            prev = before.get(name, {"count": 0, "sum": 0.0})
            count = value["count"] - prev["count"]
            total = value["sum"] - prev["sum"]
            if count:
                obs[name] = {
                    "count": count,
                    "sum": round(total, 6),
                    "mean": round(total / count, 6),
                }
        else:
            prev = before.get(name, 0)
            delta = value - prev if isinstance(prev, (int, float)) else value
            if delta:
                obs[name] = round(delta, 3) if isinstance(delta, float) else delta
    print(json.dumps({"bench": leg, "obs": obs}))


def main() -> None:
    which = sys.argv[1] if len(sys.argv) > 1 else "all"
    if which == "_tensor_replay_child":
        _tensor_replay_child()  # subprocess arm of the tensor_replay leg
        return
    legs = list(LEGS) if which == "all" else [which]
    for leg in legs:
        before = _obs_snapshot()
        LEGS[leg]()
        _emit_obs(leg, before)


if __name__ == "__main__":
    main()
