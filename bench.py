"""Benchmark: rows/sec/chip from a hash-partitioned lakehouse table into a
jitted JAX training loop (the north-star metric, BASELINE.json), plus ANN
serving QPS and a remote-store (latency-injected) leg.

Legs and honesty rules:

1. **MOR delivery (headline)** — our table (native LSF format, hash-bucketed,
   one upsert wave so merge-on-read does real work) → scan → merge →
   device_put → jitted MLP train step on the chip.
2. **Arms-length baselines** — the same rows written as a plain parquet
   dataset by pyarrow itself (zstd level 1, no dictionary — the reference
   writer's settings, writer/mod.rs:215-240), consumed by a pure
   pyarrow.dataset → torch DataLoader pipeline with ZERO repo imports.
   Two measurements: `baseline_e2e` delivers into the SAME jitted train
   step on the same chip (BASELINE.md's comparator — "GPU-DataLoader
   rows/sec/chip" is a delivery-to-accelerator metric) and sets
   vs_baseline; the host-decode-only loop (no device, strictly less work)
   is kept as vs_baseline_host_decode_only for r1/r2 continuity.
3. **HBM-resident replay** — the loader's cache="device" epoch cache:
   steady-state epochs replay from device memory with zero storage/host/
   link traffic.  Separately labeled; it measures the epoch-cache feature,
   not delivery from storage.
4. **ANN QPS** — device-resident IVF-RaBitQ batch search over a 200k x 64d
   shard; reports QPS and recall@10 vs brute force (full probe + exact
   re-rank at depth 100: the resident kernel scans every packed code
   regardless of nprobe, so full probing is free on this path).
5. **Remote leg** — a smaller table on a latency-injected in-memory object
   store (10 ms per GET — GCS-like) read cold then warm through the owned
   page cache.
6. **Scale legs** — a ≥100M-row table (env-tunable):
   (a) bounded-memory STREAMING read with a 256 MB budget pinned in table
   properties; the leg records rows/s AND its own subprocess peak RSS and
   FAILS if RSS crosses the 2 GB ceiling — throughput must not come from
   materializing the table; (b) multi-process sharded loaders: N worker
   processes concurrently scan shard(rank, world) slices over the shared
   store (the multi-host input-pipeline shape), aggregate rows/s.

7. **Hard ANN leg** — an overlapping mixture with MORE
   clusters than nlist, so recall@10 at the realistic nprobe=8 operating
   point sits well below 1.0 and MOVES if the index regresses (the easy leg
   stays for continuity; ref anchors on GloVe, test_e2e_glove.py:182).
8. **HTTP object-store leg** — the stream-scale table
   served over a real local HTTP server (ranged GETs on real sockets, the
   GCS-emulator shape): bounded-memory cold scan + page-cache warm scan,
   reporting rows/s, hit rate and subprocess peak RSS.

A measurement run needs the chip.  The first leg asks JAX, in a process of
its own, what it found; if that is not a TPU the run stops there with a
non-zero exit code.  Every device leg checks again in its own process and
reports the device it ran on (``platform``, ``device_kind``, count), and no
leg is ever handed ``JAX_PLATFORMS=cpu`` to carry on without one: the
parent sets it only for the host-only legs, which must not claim the chip.
The parent itself never touches JAX, and one device leg is alive at a time
(a chip belongs to one process).

Killable without losing evidence:

- every completed leg immediately prints a CUMULATIVE result line to stdout
  and rewrites ``BENCH_partial.json`` (a generated file, not committed), so
  a timeout still leaves the latest partial record as the parseable tail;
- a global wall-clock budget (env ``LAKESOUL_BENCH_BUDGET_S``, default
  2700 s) gates every leg: once spent, remaining legs are recorded under
  ``"skipped"`` instead of running;
- a leg that fails or exceeds the remaining budget is recorded under
  ``"leg_errors"`` and the bench moves on to the next leg, but any entry
  there makes the exit code non-zero.

The LAST stdout line is always the cumulative JSON record; ``"complete":
true`` marks a full run (every leg ran or was explicitly skipped).
"""

from __future__ import annotations

import json
import os
import shutil
import sys
import time

import numpy as np
import pyarrow as pa

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

N_ROWS = int(os.environ.get("LAKESOUL_BENCH_ROWS", 20_000_000))
# the scale leg: ≥100M rows through the bounded-memory
# streaming path + multi-process sharded loaders over shared storage
STREAM_ROWS = int(os.environ.get("LAKESOUL_BENCH_STREAM_ROWS", 100_000_000))
STREAM_BUDGET_MB = int(os.environ.get("LAKESOUL_BENCH_STREAM_BUDGET_MB", 256))
# hard ceiling the streaming leg must stay under (budget + runtime floor);
# exceeding it FAILS the leg loudly instead of reporting a pretty number
STREAM_RSS_CEILING_MB = int(os.environ.get("LAKESOUL_BENCH_STREAM_CEILING_MB", 2048))
SHARD_WORKERS = int(os.environ.get("LAKESOUL_BENCH_SHARD_WORKERS", 4))
UPSERT_FRAC = 0.05
N_FEATURES = 16
BUCKETS = 8
# 512k rows x 16 f32 ≈ 32 MB per transfer: per-dispatch latency (not
# bandwidth) dominates the host→chip link, so fewer, larger batches win.
# Clamped so small smoke runs still produce full (jit-friendly) batches.
BATCH = min(
    int(os.environ.get("LAKESOUL_BENCH_BATCH", 524288)),
    max(1024, N_ROWS // 8),
)
# optimizer steps fused into one device dispatch (lax.scan group); per-call
# link latency amortizes over the group
STEPS_PER_CALL = int(os.environ.get("LAKESOUL_BENCH_STEPS_PER_CALL", 8))
REMOTE_ROWS = min(N_ROWS, 2_000_000)
ANN_N, ANN_D, ANN_Q = 200_000, 64, 4096
# global wall-clock budget: once spent, remaining legs are SKIPPED (with a
# record) instead of letting the driver's timeout erase all evidence
BUDGET_S = float(os.environ.get("LAKESOUL_BENCH_BUDGET_S", 2700))
HTTP_PORT = int(os.environ.get("LAKESOUL_BENCH_HTTP_PORT", 18742))
_START = time.monotonic()


def _remaining() -> float:
    return BUDGET_S - (time.monotonic() - _START)


class Emitter:
    """Cumulative result record, re-emitted after every completed leg.

    stdout gets one full JSON line per update (the driver's tail is always
    the freshest partial record) and ``BENCH_partial.json`` is rewritten
    alongside, so a timeout at ANY point leaves parseable evidence of
    everything measured so far."""

    def __init__(self):
        self.record: dict = {
            "complete": False,
            "legs_done": [],
            "skipped": [],
            "leg_errors": {},
            "budget_s": BUDGET_S,
        }

    def update(self, leg: str, fields: dict) -> None:
        self.record.update(fields)
        self.record["legs_done"].append(leg)
        self._emit()

    def skip(self, leg: str, reason: str) -> None:
        self.record["skipped"].append({"leg": leg, "reason": reason})
        self._emit()

    def error(self, leg: str, err: str) -> None:
        self.record["leg_errors"][leg] = err[-500:]
        self._emit()

    def _emit(self) -> None:
        self.record["elapsed_s"] = round(time.monotonic() - _START, 1)
        line = json.dumps(self.record)
        print(line, flush=True)
        try:
            with open(os.path.join(REPO, "BENCH_partial.json"), "w") as f:
                f.write(line + "\n")
        except OSError:
            pass

    def leg(self, name: str, fn, publish=None, *, cost_s: float = 60.0):
        """Run one leg inside the budget; failures and overruns are recorded
        and the next leg still runs (``main`` turns any of them into a
        non-zero exit).  ``cost_s`` is the minimum remaining budget the leg
        needs to be worth starting; ``publish(out)`` maps the leg's result
        to record fields, merged and re-emitted on success."""
        if _remaining() < cost_s:
            self.skip(name, f"budget: {_remaining():.0f}s left < {cost_s:.0f}s estimate")
            return None
        try:
            out = fn()
        except Exception as e:  # noqa: BLE001 — one leg must not kill the round
            self.error(name, f"{type(e).__name__}: {e}")
            return None
        self.update(name, publish(out) if publish is not None else {})
        return out


def _bench_schema():
    fields = [("id", pa.int64())] + [(f"f{i}", pa.float32()) for i in range(N_FEATURES)]
    fields.append(("label", pa.int32()))
    return pa.schema(fields)


def _chunks(n_rows, start_at=0, chunk=500_000, seed=0):
    rng = np.random.default_rng(seed)
    for start in range(0, n_rows, chunk):
        n = min(chunk, n_rows - start)
        cols = {"id": np.arange(start_at + start, start_at + start + n, dtype=np.int64)}
        for i in range(N_FEATURES):
            cols[f"f{i}"] = rng.normal(size=n).astype(np.float32)
        cols["label"] = rng.integers(0, 2, n).astype(np.int32)
        yield pa.table(cols, schema=_bench_schema())


def _upsert_wave(t, seed: int, n_rows: int | None = None,
                 chunk: int = 2_000_000) -> None:
    """One MOR-provoking upsert wave: re-write UPSERT_FRAC of the keys,
    chunked so the wave never materializes whole in the driver.  Keys are
    sampled without replacement from DISJOINT id sub-ranges per chunk —
    `rng.choice(N, replace=False)` would permute the full N-row population
    (O(N) transient memory: ~8 GB at 1B rows) for a tiny sample."""
    n_rows = n_rows or N_ROWS
    rng = np.random.default_rng(seed)
    n_up = int(n_rows * UPSERT_FRAC)
    n_chunks = max(1, -(-n_up // chunk))
    span = n_rows // n_chunks

    def sample(n, k):
        # O(k) rejection sampling (k/n ≈ UPSERT_FRAC, so retries are rare)
        out = np.unique(rng.integers(0, n, int(k * 1.1) + 16, dtype=np.int64))
        while out.size < k:
            out = np.unique(
                np.concatenate([out, rng.integers(0, n, k, dtype=np.int64)])
            )
        rng.shuffle(out)
        return out[:k]

    for c in range(n_chunks):
        take = min(chunk, n_up - c * chunk)
        lo = c * span
        piece = lo + sample(min(span, n_rows - lo), take)
        cols = {"id": piece}
        for i in range(N_FEATURES):
            cols[f"f{i}"] = rng.normal(size=len(piece)).astype(np.float32)
        cols["label"] = rng.integers(0, 2, len(piece)).astype(np.int32)
        t.upsert(pa.table(cols, schema=_bench_schema()))


def build_table(catalog):
    """Our table in the framework's native LSF format + an upsert wave → real
    MOR.  Using LSF is the point of having a native format (the reference
    ships Vortex for the same reason): zero-copy mmap decode, ~9x parquet-lz4
    on this schema.  The baseline keeps the reference writer's parquet
    settings and zero repo code — the comparison stays arms-length."""
    name = f"bench_{N_ROWS}_lsf"
    if catalog.table_exists(name):
        return catalog.table(name)
    t = catalog.create_table(
        name, _bench_schema(), primary_keys=["id"], hash_bucket_num=BUCKETS,
        properties={"lakesoul.file_format": "lsf"},
    )
    for chunk in _chunks(N_ROWS):
        t.write_arrow(chunk)
    _upsert_wave(t, seed=1)
    return t


def build_stream_table(catalog):
    """The ≥100M-row table for the scale legs: LSF, hash-bucketed, a small
    memory budget pinned in table properties (forces the bounded STREAMING
    read path), and one upsert wave so the streaming merge does real
    merge-on-read work — not just sequential decode."""
    name = f"bench_stream_{STREAM_ROWS}_lsf"
    if catalog.table_exists(name):
        t = catalog.table(name)
        if t.info.properties.get("bench.complete") == "1":
            return t
        # a previous run died mid-build: measuring a partial table would be
        # a silent lie — rebuild from scratch
        catalog.drop_table(name)
    t = catalog.create_table(
        name, _bench_schema(), primary_keys=["id"], hash_bucket_num=BUCKETS,
        properties={
            "lakesoul.file_format": "lsf",
            "lakesoul.memory_budget_bytes": str(STREAM_BUDGET_MB << 20),
        },
    )
    for chunk in _chunks(STREAM_ROWS, chunk=2_000_000):
        t.write_arrow(chunk)
    _upsert_wave(t, seed=11, n_rows=STREAM_ROWS)
    t.set_properties({"bench.complete": "1"})
    return t


def bench_stream_bounded(t) -> dict:
    """Sustained bounded-memory streaming over the scale table: rows/s and
    the process's peak RSS, which must stay under STREAM_RSS_CEILING_MB —
    the whole point is that throughput does NOT come from materializing the
    table (ref stance: benches/spill_bench.rs, cache_bench.rs).  Runs in a
    fresh subprocess so the high-water mark is this leg's own; measured via
    VmHWM — ru_maxrss survives exec and would report the bench driver's
    peak (utils/memory.py).  No JAX in this leg (pure host path)."""
    from lakesoul_tpu.obs.stages import stage_seconds
    from lakesoul_tpu.utils.memory import peak_rss_mb as _peak

    stages0 = stage_seconds()
    start = time.perf_counter()
    rows = 0
    for batch in t.scan().batch_size(262_144).to_batches():
        rows += len(batch)
    wall = time.perf_counter() - start
    peak_rss_mb = _peak()
    if peak_rss_mb > STREAM_RSS_CEILING_MB:
        raise RuntimeError(
            f"stream leg peak RSS {peak_rss_mb:.0f} MB exceeded the"
            f" {STREAM_RSS_CEILING_MB} MB ceiling (budget {STREAM_BUDGET_MB} MB)"
        )
    return {
        "rows": rows,
        "rows_per_s": rows / wall,
        "peak_rss_mb": round(peak_rss_mb, 1),
        "budget_mb": STREAM_BUDGET_MB,
        "ceiling_mb": STREAM_RSS_CEILING_MB,
        # per-stage attribution (lakesoul_scan_stage_seconds delta): the
        # breakdown every scan-path perf claim is judged against
        "scan_stages": {
            k: round(v - stages0[k], 3) for k, v in stage_seconds().items()
        },
    }


def bench_sharded_loaders(n_workers: int) -> dict:
    """Multi-process DP loaders over SHARED storage: every worker scans its
    ``shard(rank, world)`` slice of the scale table concurrently (the
    multi-host input-pipeline shape, SURVEY §2.8 row 1 — rank sharding over
    scan units, coordination only through the shared store).  Aggregate
    rows/s from first start to last finish."""
    import subprocess as sp

    start = time.perf_counter()
    procs = [
        sp.Popen(
            [sys.executable, __file__, "--leg", f"shard_worker:{rank}:{n_workers}"],
            stdout=sp.PIPE, stderr=sp.PIPE, text=True,
        )
        for rank in range(n_workers)
    ]
    rows = 0
    try:
        for rank, p in enumerate(procs):
            out, err = p.communicate(timeout=3600)
            lines = [ln for ln in out.splitlines() if ln.startswith("{")]
            if p.returncode != 0 or not lines:
                sys.stderr.write(err[-2000:])
                raise RuntimeError(
                    f"shard worker {rank}/{n_workers} failed (rc={p.returncode})"
                )
            rows += json.loads(lines[-1])["rows"]
    finally:
        for p in procs:  # never leave siblings scanning in the background
            if p.poll() is None:
                p.kill()
    wall = time.perf_counter() - start
    return {"rows": rows, "rows_per_s": rows / wall, "workers": n_workers}


def build_baseline_dataset(root: str) -> str:
    """Arms-length baseline data: plain parquet files written by pyarrow with
    the reference writer's settings — no repo code involved."""
    import pyarrow.parquet as pq

    data_dir = os.path.join(root, f"baseline_{N_ROWS}")
    if os.path.isdir(data_dir) and os.listdir(data_dir):
        return data_dir
    os.makedirs(data_dir, exist_ok=True)
    for i, chunk in enumerate(_chunks(N_ROWS)):
        pq.write_table(
            chunk,
            os.path.join(data_dir, f"part-{i:05d}.parquet"),
            compression="zstd",
            compression_level=1,
            use_dictionary=False,
        )
    return data_dir


def _require_tpu() -> dict:
    """The device this process measures on, as JAX reports it.  A leg that
    finds no TPU fails: a number from another backend is not this
    benchmark's metric."""
    import jax

    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    if device["platform"] != "tpu":
        raise RuntimeError(f"bench device legs need a TPU, JAX found {device}")
    return device


def _drain(x) -> None:
    """Wait for queued device work to finish before stopping a timer."""
    import jax

    jax.block_until_ready(x)


def bench_lakesoul(t, *, epochs: int = 2, device_cache: bool = False) -> float:
    import jax
    import jax.numpy as jnp
    import optax

    from lakesoul_tpu.models.mlp import init_mlp_params, mlp_loss

    params = init_mlp_params(jax.random.key(0), N_FEATURES, hidden=256)
    tx = optax.adam(1e-3)
    opt_state = tx.init(params)

    @jax.jit
    def step(params, opt_state, x, y):
        # x arrives [F, k*B]: the host ships ONE contiguous array per k-step
        # group, and lax.scan runs k REAL optimizer steps (batch size BATCH
        # each) in a single dispatch — per-call latency on the chip link
        # amortizes over k steps.  The
        # reshape/transpose to [k, B, F] happens on-chip where it's HBM-
        # bandwidth cheap and folds into the first matmul's layout.
        k = x.shape[1] // BATCH
        xs = x.reshape(N_FEATURES, k, BATCH).transpose(1, 2, 0)
        ys = y.reshape(k, BATCH).astype(jnp.int32)

        def body(carry, xy):
            p, o = carry
            xb, yb = xy
            loss, grads = jax.value_and_grad(mlp_loss)(p, xb, yb)
            updates, o = tx.update(grads, o, p)
            return (optax.apply_updates(p, updates), o), loss

        (params, opt_state), losses = jax.lax.scan(body, (params, opt_state), (xs, ys))
        return params, opt_state, losses[-1]

    # ONE [F, rows] array per group: concatenating F contiguous columns is a
    # straight memcpy — ~6x cheaper on a 1-core host than np.stack's strided
    # transpose — and one big transfer beats many small ones on the link.
    # Features ship as bfloat16 (the TPU-native input dtype: halves wire
    # bytes, the MXU matmul promotes against f32 params — standard practice
    # per the scaling playbook).  The tail group is trimmed to a BATCH
    # multiple (every delivered row still passes through an optimizer step
    # and is counted exactly).
    import ml_dtypes

    def col_transform(b):
        n = (len(b["label"]) // BATCH) * BATCH
        x = np.concatenate(
            [b[f"f{i}"][:n] for i in range(N_FEATURES)]
        ).reshape(N_FEATURES, -1).astype(ml_dtypes.bfloat16)
        # class labels ride as int8 (widened on-chip): 4 → 1 wire bytes/row
        return {"x": x, "y": b["label"][:n].astype(np.int8)}

    group_rows = BATCH * STEPS_PER_CALL

    def batches(io_threads=None):
        return t.scan().batch_size(group_rows).to_jax_iter(
            transform=col_transform, io_threads=io_threads, drop_remainder=False,
        )

    # warm-up: AOT-compile every group shape from ShapeDtypeStructs, so no
    # compilation lands inside the timed epochs.  The rebatcher emits fixed group_rows
    # windows plus one BATCH-trimmed tail, so the shapes derive from the
    # delivered row count (metadata-only on compacted tables).
    total = t.scan().count_rows()
    shapes = []
    if total >= group_rows:
        shapes.append(((N_FEATURES, group_rows), (group_rows,)))
    tail = (total % group_rows) // BATCH * BATCH
    if tail:
        shapes.append(((N_FEATURES, tail), (tail,)))
    sds = jax.tree.map(lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype), (params, opt_state))
    compiled = {
        xs: step.lower(
            sds[0], sds[1],
            jax.ShapeDtypeStruct(xs, jnp.bfloat16),
            jax.ShapeDtypeStruct(ys, jnp.int8),
        ).compile()
        for xs, ys in shapes
    }

    best = 0.0
    loss = None
    if device_cache:
        # HBM-resident leg: the loader's cache="device" pins the epoch in
        # device memory on the first pass (20M rows x 33 B/row ≈ 660 MB —
        # well inside one chip's HBM); steady-state epochs replay resident
        # arrays with ZERO storage/host/link traffic.  Reported separately —
        # this measures the epoch-cache feature, not delivery from storage.
        it = t.scan().batch_size(group_rows).to_jax_iter(
            transform=col_transform, io_threads=2, drop_remainder=False,
            cache="device",
        )
        for batch in it:  # fill epoch (trains too, untimed)
            if len(batch["y"]):
                params, opt_state, loss = compiled[batch["x"].shape](
                    params, opt_state, batch["x"], batch["y"]
                )
        _drain(loss)
        epoch_iter = lambda: it
    else:
        epoch_iter = lambda: batches(io_threads=2)
    for _ in range(epochs):  # best-of-N epochs damps filesystem/cache variance
        rows = 0
        start = time.perf_counter()
        # io_threads=2: lz4/lsf decode releases the GIL, overlapping unit
        # decode with device transfer even on small hosts
        for batch in epoch_iter():
            if not len(batch["y"]):
                continue
            params, opt_state, loss = compiled[batch["x"].shape](
                params, opt_state, batch["x"], batch["y"]
            )
            rows += len(batch["y"])  # exact, like the baseline counts
        _drain(loss)
        dt = time.perf_counter() - start
        best = max(best, rows / dt)
    return best


def bench_torch_baseline(data_dir: str) -> float:
    """Pure pyarrow.dataset → torch DataLoader loop.  No repo imports."""
    try:
        import torch
        from torch.utils.data import DataLoader, IterableDataset
    except ImportError:
        return float("nan")

    import pyarrow.dataset as pads

    files = sorted(
        os.path.join(data_dir, f) for f in os.listdir(data_dir) if f.endswith(".parquet")
    )

    class DS(IterableDataset):
        def __iter__(self):
            import torch.utils.data as tud

            info = tud.get_worker_info()
            mine = (
                files
                if info is None
                else [f for i, f in enumerate(files) if i % info.num_workers == info.id]
            )
            if not mine:
                return
            ds = pads.dataset(mine, format="parquet")
            yield from ds.to_batches(batch_size=BATCH)

    def collate(batches):
        b = batches[0]
        x = np.stack(
            [b.column(f"f{i}").to_numpy(zero_copy_only=False) for i in range(N_FEATURES)],
            axis=1,
        )
        y = b.column("label").to_numpy(zero_copy_only=False).astype(np.int32)
        return torch.from_numpy(x), torch.from_numpy(y)

    best = 0.0
    # give the baseline its best configuration: in-process decode AND
    # process-worker decode (the standard DataLoader parallelism).  The
    # worker leg forks, which is only safe because the baseline runs BEFORE
    # any JAX/TPU initialization (see main()).
    for workers in (0, 2):
        try:
            for _ in range(2):
                loader = DataLoader(
                    DS(), batch_size=1, collate_fn=collate, num_workers=workers
                )
                rows = 0
                acc = torch.zeros(())
                start = time.perf_counter()
                for x, y in loader:
                    acc = acc + x.sum() * 0  # consume
                    rows += len(x)
                dt = time.perf_counter() - start
                best = max(best, rows / dt)
        except Exception:
            if workers == 0:
                raise  # in-process leg must work; worker leg may not fork
    return best


def bench_torch_baseline_e2e(data_dir: str) -> "tuple[float, dict | None]":
    """The BASELINE.md comparator measured end to end: a stock
    pyarrow.dataset → torch DataLoader pipeline DELIVERING INTO the same
    jitted train step on the same chip ("rows/sec/chip ≥ GPU-DataLoader
    rows/sec/chip" is a delivery-to-accelerator metric).  No repo imports:
    the model is the same 16→256→2 adam MLP written inline, fed the way a
    framework-less user feeds it — float32 [B, F] host batches, synchronous
    device_put, jit on first call.  The baseline keeps DataLoader worker
    parallelism: every jax device op is deferred until after the persistent
    workers have forked (fork-before-backend-init is safe; the workers
    survive across epochs, so no later fork sees an initialized runtime).
    Returns (best rows/s, the device the step ran on)."""
    try:
        import torch
        from torch.utils.data import DataLoader, IterableDataset
    except ImportError:
        return float("nan"), None

    import pyarrow.dataset as pads

    files = sorted(
        os.path.join(data_dir, f) for f in os.listdir(data_dir) if f.endswith(".parquet")
    )

    class DS(IterableDataset):
        def __iter__(self):
            import torch.utils.data as tud

            info = tud.get_worker_info()
            mine = (
                files
                if info is None
                else [f for i, f in enumerate(files) if i % info.num_workers == info.id]
            )
            if not mine:
                return
            ds = pads.dataset(mine, format="parquet")
            yield from ds.to_batches(batch_size=BATCH)

    def collate(batches):
        b = batches[0]
        x = np.stack(
            [b.column(f"f{i}").to_numpy(zero_copy_only=False) for i in range(N_FEATURES)],
            axis=1,
        )
        y = b.column("label").to_numpy(zero_copy_only=False).astype(np.int32)
        return torch.from_numpy(x), torch.from_numpy(y)

    state = {}  # jax model state, built lazily AFTER workers fork

    def make_step():
        import jax
        import jax.numpy as jnp
        import optax

        def loss_fn(params, x, y):
            h = jax.nn.relu(x @ params[0]["w"] + params[0]["b"])
            logits = h @ params[1]["w"] + params[1]["b"]
            logp = jax.nn.log_softmax(logits)
            return -jnp.mean(jnp.take_along_axis(logp, y[:, None], axis=-1))

        tx = optax.adam(1e-3)
        params = []
        key = jax.random.key(0)
        for a, b in zip((N_FEATURES, 256), (256, 2)):
            key, sub = jax.random.split(key)
            params.append({"w": jax.random.normal(sub, (a, b)) * (2.0 / a) ** 0.5,
                           "b": jnp.zeros((b,))})

        @jax.jit
        def step(params, opt_state, x, y):
            loss, grads = jax.value_and_grad(loss_fn)(params, x, y)
            updates, opt_state = tx.update(grads, opt_state, params)
            return optax.apply_updates(params, updates), opt_state, loss

        state.update(
            params=params, opt_state=tx.init(params), step=step,
            device=_require_tpu(),
        )

    best = 0.0
    for workers in (2, 0):
        try:
            kw = {"num_workers": workers, "persistent_workers": True} if workers else {}
            # ONE loader across epochs: persistent workers fork exactly once,
            # at first iteration — BEFORE any jax device op in this leg
            # (state is built lazily below), so the forked children never
            # inherit an initialized TPU runtime and no timed epoch pays
            # worker startup twice
            loader = DataLoader(DS(), batch_size=1, collate_fn=collate, **kw)
            for _ in range(2):  # best-of: first epoch pays the jit compile
                import jax

                rows = 0
                loss = None
                start = time.perf_counter()
                for x, y in loader:
                    if not state:
                        make_step()  # workers are alive; jax init is safe now
                    state["params"], state["opt_state"], loss = state["step"](
                        state["params"], state["opt_state"],
                        jax.device_put(x.numpy()), jax.device_put(y.numpy()),
                    )
                    rows += len(x)
                _drain(loss)
                dt = time.perf_counter() - start
                best = max(best, rows / dt)
        except Exception as e:
            if workers == 0:
                raise  # the in-process leg must work; the worker leg may not
            # a degraded baseline inflates vs_baseline — say so, loudly
            sys.stderr.write(
                f"bench: baseline_e2e worker leg failed ({e!r}); "
                "baseline is the single-process measurement only\n"
            )
    return best, state.get("device")


def bench_ann() -> dict:
    """Device-resident ANN search: batch QPS, recall@10 (full probe AND the
    reference's realistic nprobe=8 operating point), serving QPS.

    Serving QPS = per-request traffic from 16 concurrent clients through the
    micro-batching AnnEndpoint (vector/serving.py)."""
    from lakesoul_tpu.vector.config import VectorIndexConfig
    from lakesoul_tpu.vector.index import IvfRabitqIndex, SearchParams

    rng = np.random.default_rng(0)
    # mixture of gaussians — real embedding spaces are clustered; pure
    # isotropic noise has NO cluster structure, which makes IVF probing
    # look arbitrarily bad at low nprobe regardless of the index quality
    centers = rng.normal(size=(256, ANN_D)).astype(np.float32) * 1.5
    assign = rng.integers(0, len(centers), ANN_N)
    vectors = centers[assign] + rng.normal(size=(ANN_N, ANN_D)).astype(np.float32)
    ids = np.arange(ANN_N, dtype=np.uint64)
    cfg = VectorIndexConfig(column="emb", dim=ANN_D, nlist=128, total_bits=4)
    index = IvfRabitqIndex.train(vectors, ids, cfg, keep_raw=True)
    index.enable_device_cache()
    # HELD-OUT queries: fresh samples from the same mixture (not perturbed
    # dataset vectors, whose true neighbors are trivially themselves) — the
    # recall metric keeps headroom to catch index-quality regressions
    queries = (
        centers[rng.integers(0, len(centers), ANN_Q)]
        + rng.normal(size=(ANN_Q, ANN_D)).astype(np.float32)
    )
    # full probe + deep exact re-rank: the device-resident kernel scans every
    # packed code regardless of nprobe (the probe set only gates inclusion),
    # so probing all clusters costs nothing extra on this path and recall is
    # bounded only by the re-rank shortlist (measured 1.00 at depth 100)
    params = SearchParams(top_k=10, nprobe=128, rerank_depth=100)
    index.batch_search(queries[:256], params)  # warm-up the chunk shape (MAX_Q)
    qps = 0.0
    for _ in range(2):  # best-of-2 damps chip-link variance
        start = time.perf_counter()
        got_ids, _ = index.batch_search(queries, params)
        qps = max(qps, ANN_Q / (time.perf_counter() - start))
    # single-query serving path: requests arrive one at a time from many
    # concurrent clients and ride the micro-batching AnnEndpoint (collect a
    # few ms → ONE fused batch dispatch → fan out) — the TPU serving answer
    # to per-request traffic.  A strictly serial loop measures one round
    # trip per request, not the framework, so the serving figure is the
    # per-request throughput metric here.
    import threading

    from lakesoul_tpu.vector.serving import AnnEndpoint

    index.search(queries[0], params)  # warm the Q=1..8 compiled shapes
    n_clients, per_client = 16, 16
    with AnnEndpoint(index, params, max_batch=256, max_wait_ms=5.0) as ep:
        ep.search(queries[0])  # warm the endpoint path end to end
        start = time.perf_counter()

        def client(lo):
            for q in queries[lo : lo + per_client]:
                ep.search(q, timeout=120)

        threads = [
            threading.Thread(target=client, args=(i * per_client,))
            for i in range(n_clients)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        qps_single = n_clients * per_client / (time.perf_counter() - start)
    # realistic-probe leg: the reference asserts
    # recall@10 ≥ 0.5 at nprobe 4–8 (python/tests/vector/test_e2e_glove.py:
    # 182) — quote the same operating point alongside the full-probe figure
    params8 = SearchParams(top_k=10, nprobe=8, rerank_depth=100)
    got_ids8, _ = index.batch_search(queries, params8)

    # recall on a subsample (brute force over 200k x 4096 is the expensive bit)
    sample = rng.choice(ANN_Q, 100, replace=False)
    hits = hits8 = 0
    for s in sample:
        q = queries[s]
        d2 = np.sum((vectors - q) ** 2, axis=1)
        true = set(np.argpartition(d2, 10)[:10].tolist())
        hits += len(true & {int(i) for i in got_ids[s]})
        hits8 += len(true & {int(i) for i in got_ids8[s]})
    return {
        "qps": qps,
        "recall": hits / (len(sample) * 10),
        "qps_serving": qps_single,
        "recall_nprobe8": hits8 / (len(sample) * 10),
    }


def bench_remote() -> tuple[float, float, float]:
    """Latency-injected object store: (cold rows/s, warm rows/s, hit rate)."""
    import fsspec
    from fsspec.implementations.memory import MemoryFileSystem

    class SlowMemFS(MemoryFileSystem):
        """10 ms per GET — a GCS-like RTT on every ranged read."""

        protocol = "slowmem"
        latency = 0.010

        def cat_file(self, *a, **k):
            time.sleep(self.latency)
            return super().cat_file(*a, **k)

        def _open(self, *a, **k):
            if a and isinstance(a[0], str) and "w" not in (k.get("mode") or (a[1] if len(a) > 1 else "rb")):
                time.sleep(self.latency)
            return super()._open(*a, **k)

    if "slowmem" not in fsspec.registry:
        fsspec.register_implementation("slowmem", SlowMemFS, clobber=True)

    from lakesoul_tpu import LakeSoulCatalog
    from lakesoul_tpu.io.object_store import cache_stats

    cache_dir = os.path.join(REPO, ".bench_data", "page_cache")
    shutil.rmtree(cache_dir, ignore_errors=True)
    # the in-memory 'remote' store is process-local: fresh metadata every run
    meta_db = os.path.join(REPO, ".bench_data", "remote_meta.db")
    for suffix in ("", "-wal", "-shm"):
        try:
            os.remove(meta_db + suffix)
        except OSError:
            pass
    opts = {"lakesoul.cache_dir": cache_dir}
    catalog = LakeSoulCatalog(
        "slowmem://bench_wh", storage_options=opts, db_path=meta_db
    )
    name = f"remote_{REMOTE_ROWS}"
    if not catalog.table_exists(name):
        t = catalog.create_table(
            name, _bench_schema(), primary_keys=["id"], hash_bucket_num=4
        )
        for chunk in _chunks(REMOTE_ROWS, seed=2):
            t.write_arrow(chunk)
    t = catalog.table(name)

    def scan_once():
        rows = 0
        start = time.perf_counter()
        for b in t.scan().batch_size(BATCH).to_batches():
            rows += len(b)
        return rows / (time.perf_counter() - start)

    cold = scan_once()
    before = cache_stats(opts)
    warm = scan_once()
    after = cache_stats(opts)
    # hit rate of the WARM scan alone (the cold scan is all misses by design)
    warm_hits = after["hits"] - before["hits"]
    warm_misses = after["misses"] - before["misses"]
    rate = warm_hits / max(1, warm_hits + warm_misses)
    return cold, warm, rate


def bench_ann_hard() -> dict:
    """The NON-saturated ANN leg: the easy leg's
    metric pinned at 1.0 and could not catch index-quality regressions.
    Here the mixture has 8x MORE clusters than the index has lists (1024
    centers vs nlist=128, tighter spacing, 8-bit planes) so nprobe=8 covers
    only a fraction of the true neighborhoods — recall@10 lands mid-range
    (~0.6-0.9, like the reference's GloVe anchor at nprobe 4-8,
    python/tests/vector/test_e2e_glove.py:182) and MOVES if quantization,
    probing, or re-ranking regress."""
    from lakesoul_tpu.vector.config import VectorIndexConfig
    from lakesoul_tpu.vector.index import IvfRabitqIndex, SearchParams

    rng = np.random.default_rng(7)
    n, d, n_q = 200_000, 64, 1024
    centers = rng.normal(size=(1024, d)).astype(np.float32)  # unit spacing: overlap
    assign = rng.integers(0, len(centers), n)
    vectors = centers[assign] + rng.normal(size=(n, d)).astype(np.float32)
    ids = np.arange(n, dtype=np.uint64)
    cfg = VectorIndexConfig(column="emb", dim=d, nlist=128, total_bits=4)
    index = IvfRabitqIndex.train(vectors, ids, cfg, keep_raw=True)
    index.enable_device_cache()
    queries = (
        centers[rng.integers(0, len(centers), n_q)]
        + rng.normal(size=(n_q, d)).astype(np.float32)
    )
    params8 = SearchParams(top_k=10, nprobe=8, rerank_depth=100)
    got8, _ = index.batch_search(queries, params8)
    params32 = SearchParams(top_k=10, nprobe=32, rerank_depth=100)
    got32, _ = index.batch_search(queries, params32)
    sample = rng.choice(n_q, 100, replace=False)
    hits8 = hits32 = 0
    for s in sample:
        d2 = np.sum((vectors - queries[s]) ** 2, axis=1)
        true = set(np.argpartition(d2, 10)[:10].tolist())
        hits8 += len(true & {int(i) for i in got8[s]})
        hits32 += len(true & {int(i) for i in got32[s]})
    return {
        "recall_nprobe8": hits8 / (len(sample) * 10),
        "recall_nprobe32": hits32 / (len(sample) * 10),
        "clusters": len(centers),
        "nlist": 128,
    }


# --------------------------------------------------------------- HTTP store
HTTP_ROOT = os.path.join(REPO, ".bench_data", "http_store")


def _register_benchhttp():
    """fsspec protocol ``benchhttp://``: WRITES pass through to the local
    directory the HTTP server serves (table builds run at disk speed);
    READS issue real ranged HTTP GETs against the local server — actual
    sockets, actual request latency, the GCS-emulator shape.  Metadata stat/list stays local (it is not the measured data
    path and the leg labels itself accordingly)."""
    import fsspec
    from fsspec.implementations.local import LocalFileSystem
    from fsspec.spec import AbstractBufferedFile

    class BenchHttpFS(LocalFileSystem):
        protocol = "benchhttp"
        root = HTTP_ROOT
        port = HTTP_PORT
        # LocalFileSystem is cachable-by-class; a distinct subclass keeps
        # instances separate from plain "file" usage
        cachable = False

        @classmethod
        def _strip_protocol(cls, path):
            path = str(path)
            if path.startswith("benchhttp://"):
                path = path[len("benchhttp://"):]
            path = "/" + path.lstrip("/")
            return cls.root + path if not path.startswith(cls.root) else path

        def _http_get(self, rel: str, start=None, end=None) -> bytes:
            import urllib.request

            req = urllib.request.Request(
                f"http://127.0.0.1:{self.port}/{rel.lstrip('/')}"
            )
            if start is not None:
                req.add_header("Range", f"bytes={start}-{max(start, end - 1)}")
            with urllib.request.urlopen(req, timeout=60) as resp:
                return resp.read()

        def _rel(self, path) -> str:
            p = self._strip_protocol(path)
            return p[len(self.root):].lstrip("/")

        def cat_file(self, path, start=None, end=None, **kw):
            if start is None and end is None:
                return self._http_get(self._rel(path))
            size = self.info(path)["size"]
            if start is None:
                start = 0
            if start < 0:
                start += size
            if end is None or end > size:
                end = size
            if end <= start:
                return b""
            return self._http_get(self._rel(path), start, end)

        def _open(self, path, mode="rb", block_size=None, **kw):
            if "r" not in mode:
                return super()._open(path, mode=mode, block_size=block_size, **kw)
            fs = self

            class F(AbstractBufferedFile):
                def _fetch_range(self, start, end):
                    return fs._http_get(fs._rel(self.path), start, end)

            return F(self, path, mode="rb", block_size=block_size or 4 << 20,
                     size=self.info(path)["size"])

    if "benchhttp" not in fsspec.registry:
        fsspec.register_implementation("benchhttp", BenchHttpFS, clobber=True)
    return BenchHttpFS


def _start_http_server():
    """Range-supporting static file server over HTTP_ROOT — the 'emulator'."""
    import threading
    from http.server import BaseHTTPRequestHandler, ThreadingHTTPServer

    from lakesoul_tpu.service.storage_proxy import parse_range

    root = HTTP_ROOT

    class Handler(BaseHTTPRequestHandler):
        def log_message(self, *a):
            pass

        def do_GET(self):
            import urllib.parse

            rel = urllib.parse.unquote(self.path.lstrip("/"))
            full = os.path.join(root, rel)
            if not os.path.isfile(full):
                self.send_error(404)
                return
            size = os.path.getsize(full)
            try:
                rng = parse_range(self.headers.get("Range"), size)
            except ValueError:
                self.send_error(416)
                return
            start, end = rng if rng is not None else (0, size)
            self.send_response(206 if rng else 200)
            if rng:
                self.send_header("Content-Range", f"bytes {start}-{end - 1}/{size}")
            self.send_header("Content-Length", str(end - start))
            self.end_headers()
            with open(full, "rb") as f:
                f.seek(start)
                remaining = end - start
                while remaining > 0:
                    piece = f.read(min(1 << 20, remaining))
                    if not piece:
                        break
                    self.wfile.write(piece)
                    remaining -= len(piece)

    srv = ThreadingHTTPServer(("127.0.0.1", HTTP_PORT), Handler)
    threading.Thread(target=srv.serve_forever, daemon=True).start()
    return srv


def _http_catalog(cache: bool):
    from lakesoul_tpu import LakeSoulCatalog

    _register_benchhttp()
    os.makedirs(HTTP_ROOT, exist_ok=True)
    cache_dir = os.path.join(REPO, ".bench_data", "http_page_cache")
    opts = {"lakesoul.cache_dir": cache_dir} if cache else {}
    return LakeSoulCatalog(
        "benchhttp://wh",
        storage_options=opts,
        db_path=os.path.join(REPO, ".bench_data", "http_meta.db"),
    ), opts


def build_http_table() -> None:
    """Stream-scale table under the benchhttp warehouse (writes are local
    passthrough; the build costs what the local build costs)."""
    catalog, _ = _http_catalog(cache=False)
    name = f"bench_http_{STREAM_ROWS}_lsf"
    if catalog.table_exists(name):
        t = catalog.table(name)
        if t.info.properties.get("bench.complete") == "1":
            return
        catalog.drop_table(name)
    t = catalog.create_table(
        name, _bench_schema(), primary_keys=["id"], hash_bucket_num=BUCKETS,
        properties={
            "lakesoul.file_format": "lsf",
            "lakesoul.memory_budget_bytes": str(STREAM_BUDGET_MB << 20),
        },
    )
    for chunk in _chunks(STREAM_ROWS, chunk=2_000_000, seed=17):
        t.write_arrow(chunk)
    t.set_properties({"bench.complete": "1"})


def _spawn_http_server():
    """The emulator server runs in its OWN process (exactly like a real
    fake-gcs-server would), so the measured leg's peak RSS is the READER's
    memory alone — the bounded-memory contract is about the client."""
    import subprocess as sp
    import urllib.error
    import urllib.request

    proc = sp.Popen(
        [sys.executable, __file__, "--leg", "http_server"],
        stdout=sp.DEVNULL, stderr=sp.DEVNULL,
    )
    deadline = time.monotonic() + 30
    while time.monotonic() < deadline:
        if proc.poll() is not None:
            # the fresh server died (e.g. port held by a stale orphan) —
            # answering-port + dead-child means the answerer is NOT ours
            raise RuntimeError(
                f"http emulator exited rc={proc.returncode} (stale server"
                f" on port {HTTP_PORT}?)"
            )
        try:
            urllib.request.urlopen(
                f"http://127.0.0.1:{HTTP_PORT}/__ready__", timeout=1
            )
            return proc
        except urllib.error.HTTPError:
            return proc  # 404 = server is up and answering
        except OSError:
            time.sleep(0.2)
    proc.kill()
    raise RuntimeError("http emulator server did not come up")


def bench_http_stream(warm: bool) -> dict:
    """Bounded-memory scan of the stream-scale table over REAL ranged HTTP
    GETs; the warm leg re-reads through the owned page cache.  Reports
    rows/s, this subprocess's peak RSS (same ceiling contract as the local
    stream leg), and — warm — the page-cache hit rate."""
    from lakesoul_tpu.io.object_store import cache_stats
    from lakesoul_tpu.utils.memory import peak_rss_mb as _peak

    cache_dir = os.path.join(REPO, ".bench_data", "http_page_cache")
    if not warm:
        shutil.rmtree(cache_dir, ignore_errors=True)
    catalog, opts = _http_catalog(cache=True)
    srv = _spawn_http_server()
    try:
        t = catalog.table(f"bench_http_{STREAM_ROWS}_lsf")
        before = cache_stats(opts)
        start = time.perf_counter()
        rows = 0
        for batch in t.scan().batch_size(262_144).to_batches():
            rows += len(batch)
        wall = time.perf_counter() - start
        after = cache_stats(opts)
        hits = after["hits"] - before["hits"]
        misses = after["misses"] - before["misses"]
        peak = _peak()
        if peak > STREAM_RSS_CEILING_MB:
            raise RuntimeError(
                f"http stream leg peak RSS {peak:.0f} MB exceeded the"
                f" {STREAM_RSS_CEILING_MB} MB ceiling"
            )
        return {
            "rows": rows,
            "rows_per_s": rows / wall,
            "hit_rate": hits / max(1, hits + misses),
            "peak_rss_mb": round(peak, 1),
        }
    finally:
        srv.terminate()
        srv.wait(timeout=10)


def _run_leg(leg: str, *, env: dict | None = None) -> dict:
    """Execute one leg in a FRESH subprocess and parse its JSON line.

    Isolation matters twice over: (a) the torch-DataLoader baseline forks,
    which must never share a process with an initialized TPU runtime, and
    (b) a chip belongs to one process at a time, so each device leg gets
    its own and releases the chip when it exits.  The subprocess timeout is
    the REMAINING global budget: an overrunning leg is killed and recorded,
    it cannot eat the whole round."""
    import subprocess as sp

    timeout = max(60.0, _remaining())
    out = sp.run(
        [sys.executable, __file__, "--leg", leg],
        capture_output=True, text=True, timeout=timeout,
        env={**os.environ, **(env or {})},
    )
    last = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    if out.returncode != 0 or not last:
        sys.stderr.write(out.stderr[-2000:])
        raise RuntimeError(f"bench leg {leg!r} failed (rc={out.returncode})")
    return json.loads(last[-1])


_DEVICE_LEGS = ("device", "train", "train_hbm", "baseline_e2e", "ann", "ann_hard")
_HOST_LEGS = (
    "stream", "build_main", "build_stream", "build_http",
    "http_stream_cold", "http_stream_warm", "http_server",
)


def run_one_leg(leg: str) -> None:
    if leg in _HOST_LEGS or leg.startswith("shard_worker:"):
        # pure host legs: never let a stray jax use grab the device
        os.environ["JAX_PLATFORMS"] = "cpu"

    from lakesoul_tpu import LakeSoulCatalog

    if leg in _DEVICE_LEGS:
        from lakesoul_tpu.utils.compile_cache import configure_compile_cache

        configure_compile_cache()
    warehouse = os.path.join(REPO, ".bench_data")
    if leg == "device":
        print(json.dumps({"device": _require_tpu()}))
        return
    if leg == "build_main":
        catalog = LakeSoulCatalog(warehouse)
        build_table(catalog)
        build_baseline_dataset(warehouse)
        print(json.dumps({"ok": 1}))
        return
    if leg == "build_stream":
        catalog = LakeSoulCatalog(warehouse)
        build_stream_table(catalog)
        print(json.dumps({"ok": 1}))
        return
    if leg == "build_http":
        build_http_table()
        print(json.dumps({"ok": 1}))
        return
    if leg == "http_server":
        srv = _start_http_server()
        # die WITH the parent leg: if the leg subprocess is killed at the
        # budget boundary, an orphaned server would hold the fixed port
        # forever and poison later runs with a stale tree
        parent = os.getppid()
        try:
            while os.getppid() == parent:
                time.sleep(2)
        finally:
            srv.shutdown()
        return
    if leg == "http_stream_cold":
        print(json.dumps(bench_http_stream(warm=False)))
        return
    if leg == "http_stream_warm":
        print(json.dumps(bench_http_stream(warm=True)))
        return
    if leg == "baseline":
        print(json.dumps({"baseline": bench_torch_baseline(
            os.path.join(warehouse, f"baseline_{N_ROWS}"))}))
        return
    if leg == "baseline_e2e":
        value, device = bench_torch_baseline_e2e(
            os.path.join(warehouse, f"baseline_{N_ROWS}"))
        print(json.dumps({"baseline": value, "device": device}))
        return
    if leg == "remote":
        cold, warm, rate = bench_remote()
        print(json.dumps({"cold": cold, "warm": warm, "hit_rate": rate}))
        return
    if leg == "ann":
        device = _require_tpu()
        print(json.dumps({**bench_ann(), "device": device}))
        return
    if leg == "ann_hard":
        device = _require_tpu()
        print(json.dumps({**bench_ann_hard(), "device": device}))
        return
    if leg == "stream":
        catalog = LakeSoulCatalog(warehouse)
        print(json.dumps(bench_stream_bounded(
            catalog.table(f"bench_stream_{STREAM_ROWS}_lsf"))))
        return
    if leg.startswith("shard_worker:"):
        _, rank, world = leg.split(":")
        catalog = LakeSoulCatalog(warehouse)
        t = catalog.table(f"bench_stream_{STREAM_ROWS}_lsf")
        rows = 0
        for batch in t.scan().shard(int(rank), int(world)).batch_size(262_144).to_batches():
            rows += len(batch)
        print(json.dumps({"rows": rows}))
        return
    device = _require_tpu()
    catalog = LakeSoulCatalog(warehouse)
    t = catalog.table(f"bench_{N_ROWS}_lsf")
    from lakesoul_tpu.obs.stages import stage_seconds

    if leg == "train_hbm":
        print(json.dumps({
            "rows_per_s": bench_lakesoul(t, epochs=3, device_cache=True),
            "device": device,
        }))
        return
    stages0 = stage_seconds()
    value = bench_lakesoul(t, epochs=5)
    print(json.dumps({
        "rows_per_s": value,
        "device": device,
        # per-stage attribution over ALL epochs of the leg (ratios are what
        # matter; the throughput figure is best-of-epochs above)
        "scan_stages": {
            k: round(v - stages0[k], 3) for k, v in stage_seconds().items()
        },
    }))


def main() -> int:
    if len(sys.argv) > 2 and sys.argv[1] == "--leg":
        run_one_leg(sys.argv[2])
        return 0

    emit = Emitter()
    emit.record.update(
        {
            "metric": "rows/sec/chip into JAX train loop (hash table)",
            "value": None,
            "unit": "rows/s/chip",
            "vs_baseline": None,
            # worker processes time-slice the same cores; on a 1-core host
            # the sharded leg proves concurrent shared-store correctness,
            # not scale-out
            "host_cores": os.cpu_count(),
        }
    )
    # ---- the chip, before any build: a run without one is not a benchmark.
    # The parent NEVER initializes JAX itself; the leg's own process asks.
    if emit.leg("device", lambda: _run_leg("device"), lambda out: out, cost_s=0) is None:
        return 1

    # ---- builds (subprocesses: killable at the budget boundary) ----------
    built_main = emit.leg(
        "build_main", lambda: _run_leg("build_main"), cost_s=120
    ) is not None
    from lakesoul_tpu import LakeSoulCatalog

    warehouse = os.path.join(REPO, ".bench_data")
    catalog = LakeSoulCatalog(warehouse)

    # ---- host-only legs (JAX held to the CPU so none of them claims the chip)
    baseline_host = None
    if not built_main:
        emit.skip("baseline_host", "build_main did not complete")
    else:
        baseline_host = emit.leg(
            "baseline_host",
            lambda: _run_leg("baseline", env={"JAX_PLATFORMS": "cpu"})["baseline"],
            lambda out: (
                {"baseline_host_rows_per_s": round(out, 1)} if out == out else {}
            ),
            cost_s=240,
        )
    emit.leg(
        "remote", lambda: _run_leg("remote", env={"JAX_PLATFORMS": "cpu"}),
        lambda out: {
            "remote_cold_rows_per_s": round(out["cold"], 1),
            "remote_warm_rows_per_s": round(out["warm"], 1),
            "cache_hit_rate": round(out["hit_rate"], 4),
        },
        cost_s=180,
    )

    # ---- headline train legs --------------------------------------------
    value = None
    if not built_main:
        # "complete" promises every leg ran or was EXPLICITLY skipped: a
        # failed build must not silently omit its dependents
        for name in ("mor_uncompacted", "headline", "baseline_e2e", "train_hbm"):
            emit.skip(name, "build_main did not complete")
    if built_main:
        t = catalog.table(f"bench_{N_ROWS}_lsf")

        def mor_leg():
            # live MOR: a cached table left compacted by a previous run gets
            # a fresh upsert wave so this leg never measures no-merge decode
            if all(len(u.data_files) <= 1 for u in t.scan().scan_plan()):
                _upsert_wave(t, seed=3)
            return _run_leg("train")["rows_per_s"]

        emit.leg(
            "mor_uncompacted", mor_leg,
            lambda out: {"mor_uncompacted_rows_per_s": round(out, 1)},
            cost_s=420,
        )

        def headline_leg():
            # headline: steady-state delivery after compaction, the state a
            # served table sits in (ref stance: read throughput = bucket
            # parallelism + aggressive compaction, SURVEY §7)
            t.compact()
            return _run_leg("train")

        def headline_fields(out):
            fields = {"value": round(out["rows_per_s"], 1)}
            if out.get("scan_stages"):
                # committed breakdown: every scan-path claim is a number
                fields["scan_stages"] = out["scan_stages"]
            if baseline_host is not None and baseline_host == baseline_host:
                fields["vs_baseline_host_decode_only"] = round(
                    out["rows_per_s"] / baseline_host, 3
                )
            return fields

        headline_out = emit.leg("headline", headline_leg, headline_fields, cost_s=420)
        value = headline_out["rows_per_s"] if headline_out else None

        def baseline_e2e_fields(out):
            if out != out:  # torch missing → NaN: never fake a 1.0 ratio
                return {}
            return {
                "baseline_e2e_rows_per_s": round(out, 1),
                # vs_baseline compares like for like: both sides deliver rows
                # into the SAME jitted train step on the same device
                "vs_baseline": (
                    round(value / out, 3) if value is not None else None
                ),
            }

        emit.leg(
            "baseline_e2e",
            lambda: _run_leg("baseline_e2e")["baseline"],
            baseline_e2e_fields,
            cost_s=300,
        )
        emit.leg(
            "train_hbm",
            lambda: _run_leg("train_hbm")["rows_per_s"],
            lambda out: {"hbm_resident_replay_rows_per_s": round(out, 1)},
            cost_s=300,
        )

    # ---- ANN legs --------------------------------------------------------
    emit.leg(
        "ann", lambda: _run_leg("ann"),
        lambda out: {
            "ann_qps": round(out["qps"], 1),
            "ann_qps_serving": round(out["qps_serving"], 1),
            "ann_recall_at_10": round(out["recall"], 4),
            "ann_recall_at_10_nprobe8": round(out["recall_nprobe8"], 4),
        },
        cost_s=240,
    )
    emit.leg(
        "ann_hard", lambda: _run_leg("ann_hard"),
        lambda out: {
            "ann_hard_recall_at_10_nprobe8": round(out["recall_nprobe8"], 4),
            "ann_hard_recall_at_10_nprobe32": round(out["recall_nprobe32"], 4),
            "ann_hard_clusters": out["clusters"],
        },
        cost_s=180,
    )

    # ---- stream-scale legs (most expensive; cached across runs) ----------
    built_stream = emit.leg(
        "build_stream", lambda: _run_leg("build_stream"), cost_s=420
    ) is not None
    if not built_stream:
        for name in ("stream", "sharded_loaders"):
            emit.skip(name, "build_stream did not complete")
    if built_stream:
        ts = catalog.table(f"bench_stream_{STREAM_ROWS}_lsf")

        def stream_leg():
            # the stream leg must exercise the streaming MERGE, not plain
            # decode: a previously-compacted cached table gets a fresh wave
            if all(len(u.data_files) <= 1 for u in ts.scan().scan_plan()):
                _upsert_wave(ts, seed=13, n_rows=STREAM_ROWS)
            return _run_leg("stream")

        emit.leg(
            "stream", stream_leg,
            lambda out: {
                "stream_rows": out["rows"],
                "stream_rows_per_s": round(out["rows_per_s"], 1),
                "stream_peak_rss_mb": out["peak_rss_mb"],
                "stream_budget_mb": out["budget_mb"],
                "stream_rss_ceiling_mb": out["ceiling_mb"],
                "stream_scan_stages": out.get("scan_stages"),
            },
            cost_s=300,
        )
        emit.leg(
            "sharded_loaders", lambda: bench_sharded_loaders(SHARD_WORKERS),
            lambda out: {
                "sharded_loaders_rows_per_s": round(out["rows_per_s"], 1),
                "sharded_loaders_workers": out["workers"],
            },
            cost_s=300,
        )

    # ---- HTTP object-store legs (GCS-emulator shape) ---------------------
    built_http = emit.leg(
        "build_http", lambda: _run_leg("build_http"), cost_s=420
    ) is not None
    if not built_http:
        for name in ("http_stream_cold", "http_stream_warm"):
            emit.skip(name, "build_http did not complete")
    if built_http:
        emit.leg(
            "http_stream_cold", lambda: _run_leg("http_stream_cold"),
            lambda out: {
                "http_stream_rows": out["rows"],
                "http_stream_cold_rows_per_s": round(out["rows_per_s"], 1),
                "http_stream_peak_rss_mb": out["peak_rss_mb"],
            },
            cost_s=300,
        )
        emit.leg(
            "http_stream_warm", lambda: _run_leg("http_stream_warm"),
            lambda out: {
                "http_stream_warm_rows_per_s": round(out["rows_per_s"], 1),
                "http_stream_warm_hit_rate": round(out["hit_rate"], 4),
            },
            cost_s=240,
        )

    # ---- freshness chaos leg (ingest-to-train SLO under fire) ------------
    def freshness_leg():
        """Run benchmarks/micro.py freshness in a fresh subprocess (three
        real roles + SIGKILL + flaky faults; see bench_freshness) and
        commit its published figures into the trajectory."""
        import subprocess as sp

        out = sp.run(
            [sys.executable, os.path.join(REPO, "benchmarks", "micro.py"),
             "freshness"],
            capture_output=True, text=True,
            timeout=max(60.0, _remaining()),
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        lines = [
            json.loads(ln) for ln in out.stdout.splitlines()
            if ln.startswith("{")
        ]
        legs = [d for d in lines if d.get("bench") == "freshness" and "value" in d]
        if out.returncode != 0 or not legs:
            sys.stderr.write(out.stderr[-2000:])
            raise RuntimeError(
                f"freshness leg failed (rc={out.returncode})"
            )
        return legs[-1]

    emit.leg(
        "freshness", freshness_leg,
        lambda out: {
            "freshness_seconds": {
                "p50": out["freshness_p50_s"],
                "p99": out["freshness_p99_s"],
                "max": out["freshness_max_s"],
            },
            "freshness_slo_target_s": out["slo_target_s"],
            "freshness_slo_in_budget": out["slo_in_budget"],
            "freshness_rows_per_s": out["rows_per_s"],
            "freshness_rows": out["rows"],
            "freshness_oracle_exact": out["oracle_exact"],
            "freshness_chaos": {
                "fault_p": out["fault_p"],
                "compactor_sigkilled": out["compactor_sigkilled"],
                "takeover_fenced": out["takeover_fenced"],
                "lease_ttl_s": out["lease_ttl_s"],
            },
        },
        cost_s=240,
    )

    # ---- soak leg (resource-boundedness: flat fd/thread/heap slopes) -----
    def soak_leg():
        """Run benchmarks/micro.py soak in a fresh subprocess (repeated
        open→scan→serve→close lifecycles; see bench_soak) — a fresh
        runtime matters MORE here than elsewhere, the leg gates on this
        process's own fd/thread/heap slopes — and commit its published
        figures as BENCH_soak.json."""
        import subprocess as sp

        out = sp.run(
            [sys.executable, os.path.join(REPO, "benchmarks", "micro.py"),
             "soak"],
            capture_output=True, text=True,
            timeout=max(60.0, _remaining()),
            env={**os.environ, "JAX_PLATFORMS": "cpu"},
        )
        lines = [
            json.loads(ln) for ln in out.stdout.splitlines()
            if ln.startswith("{")
        ]
        legs = [d for d in lines if d.get("bench") == "soak_cycles" and "value" in d]
        if out.returncode != 0 or not legs:
            sys.stderr.write(out.stderr[-2000:])
            raise RuntimeError(
                f"soak leg failed (rc={out.returncode})"
            )
        with open(os.path.join(REPO, "BENCH_soak.json"), "w") as f:
            f.write(json.dumps(legs[-1]) + "\n")
        return legs[-1]

    emit.leg(
        "soak", soak_leg,
        lambda out: {
            "soak_cycles_per_s": out["value"],
            "soak_cycles": out["cycles"],
            "soak_slopes": {
                "fd": out["fd_slope"],
                "thread": out["thread_slope"],
                "heap_bytes": out["heap_slope_bytes"],
            },
            "soak_high_water": {
                "fd": out["fd_high_water"],
                "thread": out["thread_high_water"],
                "heap_bytes": out["heap_high_water"],
            },
            "soak_heap_budget": out["heap_budget"],
        },
        cost_s=60,
    )

    emit.record["complete"] = True
    emit._emit()
    return 1 if emit.record["leg_errors"] else 0


if __name__ == "__main__":
    raise SystemExit(main())
