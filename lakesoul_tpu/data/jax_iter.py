"""JAX delivery: stream a table scan into TPU HBM.

This is the north-star path (BASELINE.json): merged RecordBatches from the
host data plane are re-batched to a fixed size (jit needs static shapes),
converted zero-copy to numpy, and moved to device with **double-buffered
``jax.device_put``** so host decode/merge overlaps the device step — the
role CUDA pinned-memory staging plays for the reference's GPU loaders.

Pipeline:  scan units → [runtime pipeline: read + merge → collate →
           prefetch(bounded queue)] → [foreground: device_put k batches
           ahead] → training loop

The host side runs on the shared execution runtime
(:mod:`lakesoul_tpu.runtime`): a ``collate`` map stage feeding a bounded
``prefetch`` pump replaces the hand-rolled producer thread, so the loader
inherits the pipeline contract — backpressure, cooperative cancellation
(an abandoned training loop stops the decode promptly), propagated
exceptions with the scan's trace id, deadlines, and
``LAKESOUL_FAULTS`` fault injection — and its queue depth / stage
latencies land in the ``lakesoul_runtime_*`` obs series.

Sharding: ``LakeSoulScan.shard()/auto_shard()`` splits scan units across
processes (data parallelism over the pod); within a process, batches can be
placed on a ``jax.sharding.Sharding`` (e.g. batch-sharded over a local mesh)
so a ``pjit`` step consumes them without resharding.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Iterator

import numpy as np
import pyarrow as pa

from lakesoul_tpu.obs import registry
from lakesoul_tpu.obs.stages import stage
from lakesoul_tpu.runtime import pipeline as rt_pipeline
from lakesoul_tpu.tensorplane.dlpack import aligned_empty


class LoaderStats:
    """Thread-safe loader-throughput telemetry (the Deep Lake fetch/decode/
    collate visibility role): rows/sec, batches/sec, producer-queue depth,
    consumer stall time, per-epoch totals.

    ``snapshot()`` is what training loops read between steps; the same
    counters feed the process registry (``lakesoul_loader_*``), so a
    gateway's ``/metrics`` shows loader throughput next to everything else.
    Elapsed time counts only time spent inside epochs — an iterator parked
    between epochs does not dilute rows/sec."""

    def __init__(self):
        self._lock = threading.Lock()
        self.rows = 0
        self.batches = 0
        self.epochs = 0
        self.stall_s = 0.0
        self.queue_depth = 0
        self.epoch_rows: list[int] = []
        self._active_s = 0.0
        self._epoch_start: float | None = None
        self._cur_epoch_rows = 0
        self._reported_depth = 0  # this loader's share of the depth gauge
        # hot path: fetch each registry metric ONCE (the obs contract), not
        # per delivered batch — delivery then pays only the metric's own lock
        reg = registry()
        self._m_rows = reg.counter("lakesoul_loader_rows_total")
        self._m_batches = reg.counter("lakesoul_loader_batches_total")
        self._m_stall = reg.counter("lakesoul_loader_stall_seconds_total")
        self._m_epochs = reg.counter("lakesoul_loader_epochs_total")
        self._m_depth = reg.gauge("lakesoul_loader_queue_depth")

    def epoch_begin(self) -> None:
        with self._lock:
            self._epoch_start = time.perf_counter()
            self._cur_epoch_rows = 0

    def epoch_end(self, completed: bool) -> None:
        with self._lock:
            if self._epoch_start is not None:
                self._active_s += time.perf_counter() - self._epoch_start
                self._epoch_start = None
            if completed:
                self.epochs += 1
                self.epoch_rows.append(self._cur_epoch_rows)
                del self.epoch_rows[:-64]  # bound the history
            # settle this loader's contribution to the shared depth gauge:
            # a parked/finished loader must not pin a stale depth
            settle = self._reported_depth
            self._reported_depth = 0
        if settle:
            self._m_depth.dec(settle)
        if completed:
            self._m_epochs.inc()

    def delivered(self, rows: int, stall_s: float, queue_depth: int) -> None:
        with self._lock:
            self.rows += rows
            self.batches += 1
            self._cur_epoch_rows += rows
            self.stall_s += stall_s
            self.queue_depth = queue_depth
            # DELTA update on the shared gauge: concurrent loaders (train +
            # eval) then aggregate on /metrics instead of clobbering each
            # other's last write
            delta = queue_depth - self._reported_depth
            self._reported_depth = queue_depth
        self._m_rows.inc(rows)
        self._m_batches.inc()
        self._m_stall.inc(stall_s)
        if delta:
            self._m_depth.inc(delta)

    def snapshot(self) -> dict:
        with self._lock:
            elapsed = self._active_s
            if self._epoch_start is not None:
                elapsed += time.perf_counter() - self._epoch_start
            return {
                "rows": self.rows,
                "batches": self.batches,
                "epochs": self.epochs,
                "epoch_rows": list(self.epoch_rows),
                "elapsed_s": elapsed,
                "rows_per_sec": (self.rows / elapsed) if elapsed > 0 else 0.0,
                "batches_per_sec": (self.batches / elapsed) if elapsed > 0 else 0.0,
                "stall_s": self.stall_s,
                "queue_depth": self.queue_depth,
            }


class LoaderCheckpoint:
    """Mid-epoch input-stream position (tf.data-checkpoint role).

    The trainer persists this NEXT TO its model checkpoint: after resuming,
    a loader built with the restored object continues exactly after the
    last delivered batch — no replayed or skipped rows.  Position is the
    delivered-row count over the scan's deterministic unit order, guarded by
    a digest of the table version (a commit in between makes the position
    meaningless, so resume refuses it).

    ::

        ckpt = LoaderCheckpoint()
        for batch in scan.to_jax_iter(checkpoint=ckpt):
            step(batch)
            save(model_state, ckpt.to_json())   # atomically, per N steps
        # after a crash:
        ckpt = LoaderCheckpoint.from_json(saved)
        for batch in scan.to_jax_iter(checkpoint=ckpt):  # resumes mid-epoch
            ...
    """

    def __init__(self, rows_delivered: int = 0, plan_digest: str | None = None):
        self.rows_delivered = rows_delivered
        self.plan_digest = plan_digest

    def to_json(self) -> str:
        import json

        return json.dumps(
            {"rows_delivered": self.rows_delivered, "plan_digest": self.plan_digest}
        )

    @classmethod
    def from_json(cls, s: str) -> "LoaderCheckpoint":
        import json

        d = json.loads(s)
        return cls(d["rows_delivered"], d.get("plan_digest"))


def _is_stringlike(t: pa.DataType) -> bool:
    """String/binary columns (incl. dictionary-encoded ones, which Parquet
    readers commonly produce) keep the documented stay-as-object contract."""
    if pa.types.is_dictionary(t):
        return _is_stringlike(t.value_type)
    return (
        pa.types.is_string(t)
        or pa.types.is_large_string(t)
        or pa.types.is_binary(t)
        or pa.types.is_large_binary(t)
    )


def _default_collate(
    batch: pa.RecordBatch | pa.Table,
    tensor_shapes: "dict[str, tuple[int, ...]] | None" = None,
) -> dict[str, np.ndarray]:
    """Arrow → dict of numpy arrays (zero-copy where possible).  Fixed-width
    columns map directly; ``fixed_size_list`` tensor columns (token rows,
    image pixels) collate to real fixed-width arrays — 2-D by default, or
    the full declared logical shape when the loader resolved one from the
    table's tensor declarations (``tensor_shapes``, computed ONCE per
    loader from the projected schema instead of re-probing Arrow types per
    batch); strings stay as object arrays (caller should tokenize/encode
    upstream for TPU consumption).  Anything that only lowers to
    dtype=object (variable lists, structs, maps) fails LOUDLY: the old
    object-array fallback survived until ``jax.device_put`` rejected the
    batch deep inside the pipeline, with no hint of which column was
    responsible."""
    from lakesoul_tpu.errors import ConfigError

    out: dict[str, np.ndarray] = {}
    table = pa.table(batch) if isinstance(batch, pa.RecordBatch) else batch
    for name in table.column_names:
        col = table.column(name)
        if pa.types.is_fixed_size_list(col.type):
            arr = col.combine_chunks()  # lakelint: ignore[hot-path-materialize] fallback for windows the zero-copy view path declined (nulls/odd layouts); the fused path never reaches here
            width = col.type.list_size
            flat = arr.flatten().to_numpy(zero_copy_only=False)
            if flat.dtype != object and len(flat) == len(arr) * width:
                shape = (tensor_shapes or {}).get(name) or (width,)
                out[name] = flat.reshape((len(arr),) + tuple(shape))
                continue
        try:
            arr = col.to_numpy(zero_copy_only=False)
        except (pa.ArrowInvalid, pa.ArrowNotImplementedError) as e:
            raise ConfigError(
                f"column {name!r} has Arrow type {col.type} which only "
                "collates to dtype=object — object arrays cannot be "
                "device_put; flatten/encode the column upstream or pass a "
                "collate_fn that handles it"
            ) from e
        if arr.dtype == object and not _is_stringlike(col.type):
            raise ConfigError(
                f"column {name!r} has Arrow type {col.type} which only "
                "collates to dtype=object — object arrays cannot be "
                "device_put; flatten/encode the column upstream or pass a "
                "collate_fn that handles it"
            )
        out[name] = arr
    return out


def _np_column_views(
    batch: pa.RecordBatch,
    tensor_shapes: "dict[str, tuple[int, ...]] | None" = None,
) -> dict[str, np.ndarray] | None:
    """Zero-copy per-column numpy views of one record batch, or None when any
    column cannot be viewed without conversion (nulls, strings/objects,
    bit-packed bools, variable nesting) — the window then falls back to the
    arrow-table collate path, which handles those exactly as before.
    Declared tensor columns view straight to their logical shape
    (``(rows, *shape)``): the declaration was resolved once per loader, so
    the hot path never re-discovers ``fixed_size_list`` per batch."""
    views: dict[str, np.ndarray] = {}
    for i, name in enumerate(batch.schema.names):
        col = batch.column(i)
        t = col.type
        try:
            if pa.types.is_fixed_size_list(t):
                if col.null_count:
                    return None
                flat = col.flatten().to_numpy(zero_copy_only=True)
                shape = (tensor_shapes or {}).get(name) or (t.list_size,)
                views[name] = flat.reshape((len(col),) + tuple(shape))
            else:
                if col.null_count:
                    return None
                views[name] = col.to_numpy(zero_copy_only=True)
        except (pa.ArrowInvalid, pa.ArrowNotImplementedError, ValueError):
            return None
    return views


class _Window:
    """One fixed-size row window over the pending batches, materialization
    deferred: the window holds zero-copy (batch, views, start, length) parts
    and either collates STRAIGHT from the numpy views into one output buffer
    per column (fast path — no intermediate table ever exists) or assembles
    a table from batch slices for the fallback/custom-collate path."""

    __slots__ = ("parts", "nrows", "fast", "tensor_shapes")

    def __init__(self, parts, nrows: int, tensor_shapes=None):
        self.parts = parts  # [(record_batch, views_or_None, start, length)]
        self.nrows = nrows
        self.fast = all(v is not None for _, v, _, _ in parts)
        self.tensor_shapes = tensor_shapes  # declared shapes for fallbacks

    def __len__(self) -> int:
        return self.nrows

    def to_table(self) -> pa.Table:
        # zero-copy: slices share the source batch buffers; the table's
        # chunked columns are exactly what the old concat-based rebatcher
        # handed to collate
        return pa.Table.from_batches(
            [b.slice(s, ln) for b, _, s, ln in self.parts]
        )

    def collate(self) -> dict[str, np.ndarray]:
        """Fused rebatch+collate: one ``out[pos:pos+len] = view[s:s+len]``
        memcpy per (column, part) into freshly allocated per-column output
        buffers.  A window that is a single slice of one batch (the common
        case: the scan already emits ``batch_size``-row batches, so windows
        align) doesn't even copy — the numpy views pass straight through,
        sliced."""
        if len(self.parts) == 1:
            b, views, s, ln = self.parts[0]
            if s == 0 and ln == len(b):
                return dict(views)
            return {name: v[s : s + ln] for name, v in views.items()}
        first_views = self.parts[0][1]
        out: dict[str, np.ndarray] = {}
        for name, proto in first_views.items():
            # 64-byte-aligned output buffers (tensorplane.dlpack): the
            # XLA CPU client only zero-copies aligned host buffers, so
            # alignment is what makes the device_put hand-off
            # provably copy-free instead of malloc-luck-dependent
            buf = aligned_empty((self.nrows,) + proto.shape[1:], proto.dtype)
            pos = 0
            for _, views, s, ln in self.parts:
                v = views[name]
                if v.dtype != proto.dtype:
                    # batches disagree on dtype (schema drift): numpy would
                    # cast silently — take the exact table path instead
                    return _default_collate(self.to_table(), self.tensor_shapes)
                buf[pos : pos + ln] = v[s : s + ln]
                pos += ln
            out[name] = buf
        return out


class _Rebatcher:
    """Accumulate arrow batches and emit fixed-size row windows — chunk-aware:
    pending batches are never concatenated (the old ``pa.concat_tables`` per
    pop rebuilt a table of everything buffered, per window); a window is a
    list of zero-copy slice descriptors resolved at collate time."""

    def __init__(self, batch_size: int, *, capture_views: bool = True,
                 tensor_shapes: "dict[str, tuple[int, ...]] | None" = None):
        self.batch_size = batch_size
        # a custom collate_fn consumes tables, never views — skip the
        # per-batch view capture entirely on that path
        self._capture_views = capture_views
        self._tensor_shapes = tensor_shapes
        self._pending: list[tuple[pa.RecordBatch, dict | None]] = []
        self._offset = 0  # consumed rows of the FIRST pending batch
        self._rows = 0

    def push(self, batch: pa.RecordBatch | pa.Table) -> "list[_Window]":
        if isinstance(batch, pa.Table):
            incoming = batch.to_batches()
        else:
            incoming = [batch]
        for b in incoming:
            if len(b) == 0:
                continue
            views = (
                _np_column_views(b, self._tensor_shapes)
                if self._capture_views else None
            )
            self._pending.append((b, views))
            self._rows += len(b)
        out = []
        while self._rows >= self.batch_size:
            out.append(self._pop(self.batch_size))
        return out

    def _pop(self, n: int) -> _Window:
        parts = []
        need = n
        while need:
            b, views = self._pending[0]
            avail = len(b) - self._offset
            take = min(avail, need)
            parts.append((b, views, self._offset, take))
            need -= take
            if take == avail:
                self._pending.pop(0)
                self._offset = 0
            else:
                self._offset += take
        self._rows -= n
        return _Window(parts, n, self._tensor_shapes)

    def tail(self) -> _Window | None:
        if self._rows == 0:
            return None
        out = self._pop(self._rows)
        return out


class JaxBatchIterator:
    """Iterator of device-resident, fixed-size batches.

    Args:
        scan: a LakeSoulScan (its batch_size sets the emitted batch size).
        collate_fn: arrow table → pytree of numpy arrays.  Default: dict of
            per-column arrays.
        transform: optional numpy-level pytree transform (e.g. tokenize,
            reshape features) applied on the host thread.
        device_put: move batches to device (default True; False yields host
            numpy pytrees — useful for tests and CPU pipelines).
        sharding: optional jax.sharding.Sharding for the device placement
            (e.g. NamedSharding(mesh, P("dp")) to batch-shard locally).
        prefetch: queue depth for the host pipeline (decode ahead).
        device_prefetch: how many batches to keep resident on device ahead of
            the consumer (double buffering = 2).
        drop_remainder: drop the final short batch (jit-friendly default True).
        io_threads: decode scan units on this many threads (multi-core hosts;
            see LakeSoulScan.to_batches).
        follow: make the loader a CONTINUOUS training source over the
            table's commit log (the freshness layer): ``True`` follows
            from now, a dict passes follower options
            (``start_timestamp_ms``, ``state``, ``poll_interval``,
            ``stop_event``, ``slo``, ``retry_policy`` — see
            :class:`lakesoul_tpu.freshness.follower.FreshFollower`), or an
            existing ``FollowBatchSource``.  The stream never ends on its
            own — set a ``stop_event`` to shut it down within one poll
            tick.  Resume via :meth:`follow_state_json`, NOT via
            ``checkpoint`` (the follower carries its own exactly-once
            position; mixing the two raises).  Note the pipeline-lag
            semantics under ``device_put=True``: the double buffer keeps
            ``device_prefetch`` transfers in flight and the rebatcher
            holds sub-``batch_size`` remainders, so when ingest PAUSES
            the consumer trails the stream head by up to
            ``device_prefetch`` windows + one partial window until more
            commits arrive (continuous traffic — the follow workload —
            keeps the lag bounded and flowing; latency-critical
            low-traffic consumers should use ``device_put=False``, where
            delivery is immediate).  The freshness SLO measures at the
            source hand-off either way.
        consumer: attribution tag for this loader's ``queue`` stall series
            (``lakesoul_scan_stage_seconds{stage=queue,consumer=...}``) —
            with several concurrent loaders (a trainer fleet on one host)
            the tag says WHICH client starved.  Default ``"local"``.
        cache: ``"device"`` pins delivered batches in device memory on the
            first complete epoch via the tensor plane's
            :class:`~lakesoul_tpu.tensorplane.replay.DeviceReplayCache`;
            re-iterating then replays the resident shards with ZERO
            storage/host/link traffic (the tf.data ``.cache()`` role,
            placed in HBM where re-reads are free).  Residency is
            budgeted per device (``replay_budget_bytes`` /
            ``LAKESOUL_REPLAY_BUDGET_BYTES``; unset = unbounded, the
            caller opted in knowing rows × bytes/row): past the budget
            the cache records a typed, metered spill and later epochs
            replay the resident prefix from HBM then re-stream only the
            tail.  An epoch abandoned early leaves the cache unfilled
            (partial replay would silently drop data).
        replay_budget_bytes: per-device HBM budget for ``cache='device'``
            (overrides ``LAKESOUL_REPLAY_BUDGET_BYTES``).
        replay_permute: re-permute the resident epoch on device each
            replay (seeded; batch order + on-device row permutation) —
            only honoured while fully resident, a spilled cache replays
            in stream order so the hybrid epoch stays position-exact.
        replay_seed: seed pinning the permutation schedule.
        multihost: shard the scan by this process's position on the data
            axis (``jax.process_index()/process_count()``, overridable via
            ``LAKESOUL_FLEET_PROCESS_INDEX``/``_COUNT`` for emulated
            multi-host) before the pipeline resolves it — N hosts then
            consume disjoint, union-complete shards, and ``cache='device'``
            pins exactly the local shard.  A scan already ``shard()``-ed
            the same way passes through; a conflicting shard raises.
    """

    def __init__(
        self,
        scan,
        *,
        collate_fn: Callable[[pa.Table], Any] | None = None,
        transform: Callable[[Any], Any] | None = None,
        device_put: bool = True,
        sharding=None,
        prefetch: int = 4,
        device_prefetch: int = 2,
        drop_remainder: bool = True,
        io_threads: int | None = None,
        checkpoint: "LoaderCheckpoint | None" = None,
        cache: str | None = None,
        replay_budget_bytes: int | None = None,
        replay_permute: bool = False,
        replay_seed: int = 0,
        consumer: str | None = None,
        follow=None,
        multihost: bool = False,
    ):
        from lakesoul_tpu.errors import ConfigError

        if multihost:
            # shard BEFORE anything else resolves the scan: the batch
            # source, plan digest, replay cache and checkpoint must all
            # see the local host's shard, never the global table.  The
            # process axis comes from jax.process_index()/process_count()
            # (LAKESOUL_FLEET_PROCESS_INDEX/COUNT override for emulated
            # multi-host); a consistently pre-sharded scan passes through,
            # a conflicting one raises (fleet/multihost.py).
            from lakesoul_tpu.fleet.multihost import shard_scan

            scan = shard_scan(scan)

        if cache not in (None, "device"):
            raise ConfigError(f"unknown cache mode {cache!r}; expected 'device'")
        if cache != "device" and (
            replay_budget_bytes is not None or replay_permute or replay_seed
        ):
            # same contract as the other invalid combos in this
            # constructor: a replay knob without the replay cache must not
            # silently train un-permuted / un-budgeted
            raise ConfigError(
                "replay_budget_bytes/replay_permute/replay_seed require"
                " cache='device'"
            )
        if follow is not None and follow is not False:
            if checkpoint is not None:
                raise ConfigError(
                    "follow and checkpoint are mutually exclusive: the"
                    " follower carries its own exactly-once position"
                    " (follow_state_json)"
                )
            if cache == "device":
                raise ConfigError(
                    "cache='device' cannot cache an unbounded follow stream"
                )
        if cache == "device" and checkpoint is not None:
            # a replayed epoch never touches the input stream, so a loader
            # checkpoint could not represent its position
            raise ConfigError("cache='device' and checkpoint are mutually exclusive")
        if cache == "device" and not device_put:
            raise ConfigError("cache='device' requires device_put=True")
        self._cache_mode = cache
        self._replay = None
        # exactly ONE active generator may fill the shared cache: two
        # interleaved iterations of the same loader would both offer into
        # it, sealing a doubled epoch (every replay batch served twice) or
        # tripping offer()-after-seal mid-stream — the first streaming
        # generator claims the fill, later concurrent ones stream plain
        self._fill_claimed = False
        if cache == "device":
            from lakesoul_tpu.tensorplane.replay import DeviceReplayCache

            self._replay = DeviceReplayCache(
                budget_bytes=replay_budget_bytes,
                permute=replay_permute,
                seed=replay_seed,
            )
        self._stats = LoaderStats()
        self._scan = scan
        self._collate = collate_fn or _default_collate
        # declared tensor shapes, resolved ONCE from the projected schema
        # (tensorplane/columns.py): the collate layer reshapes straight to
        # (batch, *shape) instead of re-probing Arrow types per batch
        try:
            from lakesoul_tpu.tensorplane.columns import tensor_specs

            self._tensor_shapes = {
                name: spec.shape
                for name, spec in tensor_specs(scan.projected_schema()).items()
            } or None
        except Exception:  # scans without resolvable schemas keep the
            self._tensor_shapes = None  # per-type collate contract
        # the queue stage carries this loader's consumer tag so multi-client
        # stall is attributable per client
        self._consumer = consumer or "local"
        self._transform = transform
        self._device_put = device_put
        self._sharding = sharding
        self._prefetch = max(1, prefetch)
        self._device_prefetch = max(1, device_prefetch)
        self._drop_remainder = drop_remainder
        self._io_threads = io_threads
        self._checkpoint = checkpoint
        # follow mode: ONE seam source for the iterator's lifetime — its
        # follower owns the exactly-once position follow_state_json() reads
        self._follow_source = None
        self._follow_started = False
        if follow is not None and follow is not False:
            from lakesoul_tpu.data.batch_source import batch_source_for

            self._follow_source = batch_source_for(scan, follow=follow)
        self._rows_out = 0  # consumer-delivered rows (follow resume anchor)
        if checkpoint is not None:
            digest = self._plan_digest()
            if checkpoint.plan_digest is None:
                checkpoint.plan_digest = digest
            elif checkpoint.plan_digest != digest:
                from lakesoul_tpu.errors import ConfigError

                raise ConfigError(
                    "loader checkpoint was taken against a different table"
                    " version/scan — the saved position is meaningless"
                )

    def _plan_digest(self) -> str:
        import hashlib

        return hashlib.md5(repr(self._scan._cache_key()).encode()).hexdigest()

    def stats(self) -> dict:
        """Loader telemetry snapshot: rows/batches (+ per-sec over in-epoch
        wall time), epochs, per-epoch row totals, consumer stall seconds,
        and current producer-queue depth — plus the replay cache's
        residency stats under ``"replay"`` in cache='device' mode.  Cheap
        enough to read every step."""
        snap = self._stats.snapshot()
        if self._replay is not None:
            snap["replay"] = self._replay.stats()
        return snap

    @property
    def _device_cached(self):
        """Compat view of the pinned epoch (pre-tensorplane attribute):
        the resident (rows, batch) list while a fully-resident cache is
        serving, else None."""
        if self._replay is not None and self._replay.ready \
                and not self._replay.spilled:
            return self._replay._batches
        return None

    def follow_state_json(self) -> str:
        """Resume-ready follower position covering exactly the batches this
        iterator has DELIVERED (rows sitting in the prefetch/device
        pipelines replay on restart — never skipped, never duplicated).
        Persist it next to the model checkpoint; a restarted trainer
        continues with ``scan.to_jax_iter(follow={"state": saved, ...})``.
        Only meaningful in follow mode."""
        from lakesoul_tpu.errors import ConfigError

        if self._follow_source is None:
            raise ConfigError("follow_state_json() requires follow mode")
        return self._follow_source.resume_state(self._rows_out).to_json()

    # ------------------------------------------------------------- pipeline
    def _epoch_windows(self, extra_skip: int = 0) -> "Iterator[_Window]":
        """Fixed-size row windows over one epoch's scan (the pipeline
        source).  Resume: the scan's unit order is deterministic, so the
        checkpoint's delivered-row count is a complete position; the scan
        skips whole units via metadata row counts without decoding them and
        decode-discards only the residual prefix of one unit.
        ``extra_skip`` is the spilled-replay tail resume: the resident
        prefix rows the cache already serves from device memory."""
        skip = (self._checkpoint.rows_delivered if self._checkpoint else 0) \
            + extra_skip
        rb = _Rebatcher(
            self._scan._batch_size,
            capture_views=self._collate is _default_collate,
            tensor_shapes=self._tensor_shapes,
        )
        # the batch-source seam: in-process decode, a scan-plane fleet
        # (scan.via_scanplane) OR a continuous follow stream (follow=) —
        # everything downstream (rebatch, collate, prefetch, device_put,
        # stats) is identical either way
        from lakesoul_tpu.data.batch_source import batch_source_for

        source = (
            self._follow_source
            if self._follow_source is not None
            else batch_source_for(self._scan)
        )
        for arrow_batch in source.iter_batches(
            num_threads=self._io_threads, skip_rows=skip
        ):
            with stage("rebatch"):
                windows = rb.push(arrow_batch)
            yield from windows
        if not self._drop_remainder:
            tail = rb.tail()
            if tail is not None:
                yield tail

    def _host_pipeline(self, extra_skip: int = 0):
        """One epoch's host pipeline on the shared runtime: scan windows →
        collate/transform → bounded prefetch pump."""
        return (
            rt_pipeline("loader")
            .source(self._epoch_windows(extra_skip))
            .map(lambda w: (len(w), self._host_batch(w)), name="collate")
            .prefetch(self._prefetch, name="prefetch")
            .run()
        )

    def _host_batch(self, window):
        with stage("collate"):
            if isinstance(window, _Window):
                if window.fast and self._collate is _default_collate:
                    # fused zero-copy path: views → output buffers, no
                    # intermediate table, no per-column combine_chunks
                    batch = window.collate()
                elif self._collate is _default_collate:
                    batch = _default_collate(window.to_table(), self._tensor_shapes)
                else:
                    batch = self._collate(window.to_table())
            else:
                batch = self._collate(window)
            if self._transform is not None:
                batch = self._transform(batch)
        return batch

    def _fresh_containers(self, batch):
        """Rebuild the pytree's containers (leaves — device arrays — stay
        shared): consumers that mutate a yielded dict in place must never
        poison the cached epoch."""
        import jax

        return jax.tree_util.tree_map(lambda x: x, batch)

    def __iter__(self):
        if self._follow_source is not None:
            from lakesoul_tpu.errors import ConfigError

            if self._follow_started:
                # a second pass would rebuild the follower from the INITIAL
                # state while _rows_out kept accumulating: duplicated
                # delivery now and a follow_state_json() position pointing
                # into a snapshot ring that never saw those rows later
                raise ConfigError(
                    "a follow-mode iterator is single-pass (the stream is"
                    " unbounded): build a new iterator — resuming with"
                    " follow={'state': it.follow_state_json()} — instead"
                    " of re-iterating"
                )
            self._follow_started = True
        if self._replay is not None and self._replay.ready:
            # steady state: replay the HBM-resident epoch — no storage, no
            # host pipeline, no link traffic; a spilled cache replays its
            # resident prefix then re-streams ONLY the tail (the offers
            # stopped at the first budget rejection, so the prefix is
            # contiguous and `resident_rows` is an exact resume position)
            self._stats.epoch_begin()
            completed = False
            try:
                for rows, b in self._replay.replay():
                    self._stats.delivered(rows, 0.0, 0)
                    self._rows_out += rows
                    yield self._fresh_containers(b)
                if self._replay.spilled:
                    completed = yield from self._deliver_stream(
                        extra_skip=self._replay.resident_rows
                    )
                else:
                    completed = True
            finally:
                self._stats.epoch_end(completed)
            return
        self._stats.epoch_begin()
        completed = False
        filling = self._replay is not None and not self._fill_claimed
        if filling:
            self._fill_claimed = True
        try:
            offer = self._replay.offer if filling else None
            completed = yield from self._deliver_stream(offer=offer)
            if completed and filling:
                # only a COMPLETE epoch becomes the resident cache: an
                # abandoned iteration (consumer break → GeneratorExit)
                # never reaches here
                self._replay.seal()
        finally:
            if filling:
                if not self._replay.ready:
                    self._replay.abandon()
                self._fill_claimed = False
            self._stats.epoch_end(completed)

    def _deliver_stream(self, extra_skip: int = 0, offer=None):
        """One streaming epoch: host pipeline → (device_put double buffer)
        → consumer.  Returns True when the pipeline ran to exhaustion AND
        every batch reached the consumer.  ``offer`` is the replay cache's
        pin hook: a pinned batch is handed to the consumer as fresh
        containers so in-place mutation cannot poison the cached epoch."""
        pipe = self._host_pipeline(extra_skip)
        produced_all = False  # the pipeline ran to exhaustion

        def host_iter():
            nonlocal produced_all
            try:
                while True:
                    try:
                        with stage("queue", consumer=self._consumer) as waited:
                            item = next(pipe)
                    except StopIteration:
                        produced_all = True
                        return
                    # telemetry at the host hand-off: this is the loader's
                    # produced throughput and how long the consumer starved
                    self._stats.delivered(item[0], waited.elapsed, pipe.queue_depth())
                    yield item
            finally:
                # quiesce, don't just signal: an abandoned producer that
                # keeps decoding in the background races whatever the caller
                # does next (a resumed iterator over the same table, a test's
                # monkeypatch, shutdown).  close() cancels the pipeline and
                # joins its pump; the bounded wait only rides out a unit
                # decode that is already in flight.
                pipe.close()

        def delivered(rows: int) -> None:
            # position advances when a batch reaches the CONSUMER: a trainer
            # saving (model, checkpoint) after step k resumes exactly at k+1
            self._rows_out += rows
            if self._checkpoint is not None:
                self._checkpoint.rows_delivered += rows

        if not self._device_put:
            for rows, host_batch in host_iter():
                delivered(rows)  # BEFORE yield: a post-step save includes it
                yield host_batch
            return produced_all

        # the tensor plane places each batch on the sharding (else the
        # default device) and checks it landed there.  The collate buffers
        # are 64-byte aligned so that on CPU nothing copies; on TPU only
        # the H2D DMA does (demoted dtypes pay the cast).
        from lakesoul_tpu.tensorplane.dlpack import deliver

        sharding = self._sharding

        def put(b):
            # dispatch cost only: the H2D copy itself overlaps the
            # training step (that's the double buffering's point)
            with stage("device_put"):
                return deliver(b, sharding)

        def emit(r, b):
            delivered(r)
            if offer is not None and offer(r, b):
                return self._fresh_containers(b)  # cache keeps the pristine one
            return b

        # double buffering: keep device_prefetch transfers in flight so the
        # H2D copy of batch k+1 overlaps the step on batch k
        buf: list = []
        for rows, host_batch in host_iter():
            buf.append((rows, put(host_batch)))
            if len(buf) > self._device_prefetch:
                r, b = buf.pop(0)
                yield emit(r, b)
        for r, b in buf:
            yield emit(r, b)
        # a consumer break during the tail flush raises GeneratorExit above
        # and never reaches here: the epoch is NOT complete
        return produced_all
