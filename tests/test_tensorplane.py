"""Tensor plane: declared tensor columns must validate on write with typed
errors and a real Spark-JSON spelling, delivery must land on the target it
was given (and be provably zero-copy on host backends), the measured
aliasing probe must tell copies from aliases per dtype, the
device-resident replay cache must serve epoch ≥ 2 byte-identical to
epoch 1 (fully resident AND across a budget spill), permutation must be
deterministic under a pinned seed, and the smoke register must cover 100%
of the repo's Pallas kernels and raise on the first failing case."""

from __future__ import annotations

import numpy as np
import pyarrow as pa
import pytest

from lakesoul_tpu import LakeSoulCatalog
from lakesoul_tpu.errors import ConfigError, TensorColumnError
from lakesoul_tpu.tensorplane import (
    DeviceReplayCache,
    aligned_empty,
    deliver,
    device_put_copies,
    tensor_field,
    tensor_shape_of,
    tensor_specs,
    validate_tensor_batch,
)

SHAPE = (4, 8)
WIDTH = 32


def tensor_schema() -> pa.Schema:
    return pa.schema([
        ("id", pa.int64()),
        tensor_field("emb", SHAPE, "float32"),
        ("label", pa.int32()),
    ])


def tensor_table(n: int, seed: int = 0, schema: pa.Schema | None = None) -> pa.Table:
    schema = schema or tensor_schema()
    rng = np.random.default_rng(seed)
    emb = rng.normal(size=(n, WIDTH)).astype(np.float32)
    return pa.table({
        "id": np.arange(n, dtype=np.int64),
        "emb": pa.FixedSizeListArray.from_arrays(
            pa.array(emb.ravel()), WIDTH
        ).cast(schema.field("emb").type),
        "label": rng.integers(0, 5, n).astype(np.int32),
    }, schema=schema)


@pytest.fixture
def tensor_lsf_table(tmp_warehouse):
    catalog = LakeSoulCatalog(str(tmp_warehouse))
    t = catalog.create_table(
        "tensors", tensor_schema(),
        properties={"lakesoul.file_format": "lsf"},
    )
    t.write_arrow(tensor_table(2048))
    return t


def read_epoch(it) -> list[dict]:
    return [{k: np.copy(np.asarray(v)) for k, v in b.items()} for b in it]


def assert_epochs_byte_identical(a: list[dict], b: list[dict]) -> None:
    assert len(a) == len(b) and len(a) > 0
    for x, y in zip(a, b):
        assert x.keys() == y.keys()
        for k in x:
            assert x[k].dtype == y[k].dtype and x[k].shape == y[k].shape
            assert x[k].tobytes() == y[k].tobytes(), k


# --------------------------------------------------------------- columns


class TestTensorColumns:
    def test_declaration_and_spec(self):
        f = tensor_field("emb", SHAPE, "float32")
        assert pa.types.is_fixed_size_list(f.type)
        assert f.type.list_size == WIDTH
        assert not f.nullable and not f.type.value_field.nullable
        assert tensor_shape_of(f) == SHAPE
        specs = tensor_specs(tensor_schema())
        assert set(specs) == {"emb"}
        assert specs["emb"].shape == SHAPE and specs["emb"].width == WIDTH

    def test_undeclared_fsl_is_one_dimensional_legacy(self):
        f = pa.field("legacy", pa.list_(pa.float32(), 7))
        assert tensor_shape_of(f) == (7,)  # pre-declaration collate contract
        assert tensor_specs(pa.schema([f])) == {}  # never write-validated

    def test_bad_declarations_typed(self):
        with pytest.raises(ConfigError):
            tensor_field("e", (0, 4))
        with pytest.raises(ConfigError):
            tensor_field("e", (4,), "string")
        bad = pa.field(
            "e", pa.list_(pa.float32(), 8),
            metadata={b"lakesoul:tensor": b'{"shape": [3, 3]}'},
        )
        with pytest.raises(ConfigError, match="does not flatten"):
            tensor_shape_of(bad)

    def test_spark_json_round_trip_interop(self):
        """The satellite: fixed_size_list gets a REAL Spark-JSON spelling
        (ArrayType + fixedLength), not the raw-Arrow-name fallback, and it
        round-trips through the wire encoding."""
        import json

        from lakesoul_tpu.meta.entity import schema_from_json, schema_to_json

        schema = tensor_schema()
        doc = json.loads(schema_to_json(schema))
        emb = next(f for f in doc["fields"] if f["name"] == "emb")
        # a Spark reader that ignores the annotation still sees a legal
        # variable-length ArrayType of the right element type
        assert emb["type"]["type"] == "array"
        assert emb["type"]["elementType"] == "float"
        assert emb["type"]["containsNull"] is False
        assert emb["type"]["fixedLength"] == WIDTH
        # the logical shape rides the field's Spark metadata map, so the
        # JSON mirror alone round-trips a multi-dim declaration
        assert emb["metadata"] == {"lakesoul:tensor": {"shape": [4, 8]}}
        back = schema_from_json(schema_to_json(schema))
        assert back.field("emb").type.equals(schema.field("emb").type)
        assert pa.types.is_fixed_size_list(back.field("emb").type)
        assert back.field("emb").type.list_size == WIDTH
        assert tensor_shape_of(back.field("emb")) == SHAPE

    def test_catalog_metadata_survives_ipc_round_trip(self, tmp_warehouse):
        catalog = LakeSoulCatalog(str(tmp_warehouse))
        catalog.create_table("t", tensor_schema())
        reread = catalog.table("t").schema
        assert tensor_shape_of(reread.field("emb")) == SHAPE


# ---------------------------------------------------------------- writer


class TestWriterValidation:
    def test_wrong_width_typed(self, tmp_warehouse):
        catalog = LakeSoulCatalog(str(tmp_warehouse))
        t = catalog.create_table("w", tensor_schema())
        bad = pa.table({
            "id": np.arange(4, dtype=np.int64),
            "emb": pa.FixedSizeListArray.from_arrays(
                pa.array(np.zeros(4 * 16, np.float32)), 16
            ),
            "label": np.zeros(4, np.int32),
        })
        with pytest.raises(TensorColumnError, match="emb.*fixed_size_list\\[16\\]"):
            t.write_arrow(bad)

    def test_wrong_dtype_typed(self, tmp_warehouse):
        catalog = LakeSoulCatalog(str(tmp_warehouse))
        t = catalog.create_table("w2", tensor_schema())
        bad = pa.table({
            "id": np.arange(2, dtype=np.int64),
            "emb": pa.FixedSizeListArray.from_arrays(
                pa.array(np.zeros(2 * WIDTH, np.float64)), WIDTH
            ),
            "label": np.zeros(2, np.int32),
        })
        with pytest.raises(TensorColumnError, match="emb"):
            t.write_arrow(bad)

    def test_null_row_and_missing_column_typed(self, tmp_warehouse):
        catalog = LakeSoulCatalog(str(tmp_warehouse))
        t = catalog.create_table("w3", tensor_schema())
        null_row = pa.table({
            "id": np.arange(2, dtype=np.int64),
            "emb": pa.array(
                [[1.0] * WIDTH, None],
                type=pa.list_(pa.field("element", pa.float32(), False), WIDTH),
            ),
            "label": np.zeros(2, np.int32),
        })
        with pytest.raises(TensorColumnError, match="null row"):
            t.write_arrow(null_row)
        missing = pa.table({
            "id": np.arange(2, dtype=np.int64),
            "label": np.zeros(2, np.int32),
        })
        with pytest.raises(TensorColumnError, match="missing"):
            t.write_arrow(missing)

    def test_validate_helper_direct(self):
        specs = tensor_specs(tensor_schema())
        validate_tensor_batch(tensor_table(8), specs)  # clean passes

    def test_valid_write_lands_and_reads_back(self, tensor_lsf_table):
        got = tensor_lsf_table.scan().to_arrow()
        assert len(got) == 2048
        assert got.schema.field("emb").type.list_size == WIDTH


# ---------------------------------------------------------------- dlpack


class TestDlpackDelivery:
    def test_aligned_empty_alignment(self):
        for shape, dt in [((8,), np.float32), ((3, 5), np.int64), ((2, 2, 2), np.float64)]:
            a = aligned_empty(shape, dt)
            assert a.shape == shape and a.dtype == dt
            assert a.ctypes.data % 64 == 0
            a[:] = 1  # writable

    def test_probe_measures_aliasing_per_dtype(self):
        # CPU CI: float32 is the device dtype → device_put aliases aligned
        # buffers (the PR-9 find); int64/float64 demote → real copies
        assert not device_put_copies(np.float32)
        assert device_put_copies(np.int64)
        assert device_put_copies(np.float64)

    def test_deliver_zero_copy_alias_on_host(self):
        """The tentpole proof on a host backend: the delivered array's
        buffer IS the collate buffer — zero host copies anywhere."""
        src = aligned_empty((64, 8), np.float32)
        src[:] = np.arange(512, dtype=np.float32).reshape(64, 8)
        out = deliver({"x": src})
        assert out["x"].unsafe_buffer_pointer() == src.ctypes.data
        np.testing.assert_array_equal(np.asarray(out["x"]), src)

    def test_deliver_lands_on_default_device(self):
        """sharding=None means "where a bare device_put would land", not
        "wherever the import left it": under jax.default_device every leaf
        is on THAT device and still uncommitted, so a jitted step may move
        it to where its params live."""
        import jax

        target = jax.devices()[3]
        batch = {
            "x": aligned_empty((64, 8), np.float32),   # dtype jax keeps
            "y": aligned_empty((64,), np.int64),       # dtype jax demotes
        }
        batch["x"][:] = 1.0
        batch["y"][:] = 2
        with jax.default_device(target):
            out = deliver(batch)
        for leaf in jax.tree_util.tree_leaves(out):
            assert leaf.devices() == {target}
            assert not leaf.committed
        np.testing.assert_array_equal(np.asarray(out["y"]), batch["y"])

    def test_deliver_takes_the_named_sharding(self):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        mesh = Mesh(np.array(jax.devices()[:4]).reshape(2, 2), ("dp", "sp"))
        sharding = NamedSharding(mesh, P("dp", "sp"))
        src = aligned_empty((8, 16), np.float32)
        src[:] = np.arange(128, dtype=np.float32).reshape(8, 16)
        out = deliver({"x": src}, sharding)["x"]
        assert out.sharding.is_equivalent_to(sharding, out.ndim)
        assert out.sharding.device_set == set(jax.devices()[:4])
        assert {s.data.shape for s in out.addressable_shards} == {(4, 8)}
        np.testing.assert_array_equal(np.asarray(out), src)

    def test_deliver_rejects_a_leaf_left_elsewhere(self, monkeypatch):
        """The placement is checked, not assumed."""
        import jax

        from lakesoul_tpu.errors import IOError_

        elsewhere = jax.devices()[5]
        real_put = jax.device_put
        monkeypatch.setattr(
            jax, "device_put", lambda x, *a, **k: real_put(x, elsewhere)
        )
        with pytest.raises(IOError_, match="expected"):
            deliver({"x": np.zeros(4, np.float32)})

    def test_loader_delivers_to_default_device_and_sharding(self, tensor_lsf_table):
        import jax
        from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

        target = jax.devices()[3]
        with jax.default_device(target):
            batches = list(tensor_lsf_table.scan().batch_size(512).to_jax_iter())
        assert len(batches) == 4
        for b in batches:
            for leaf in jax.tree_util.tree_leaves(b):
                assert leaf.devices() == {target}
        mesh = Mesh(np.array(jax.devices()), ("dp",))
        sharding = NamedSharding(mesh, P("dp"))
        for b in tensor_lsf_table.scan().batch_size(512).to_jax_iter(sharding=sharding):
            for leaf in jax.tree_util.tree_leaves(b):
                assert leaf.sharding.is_equivalent_to(sharding, leaf.ndim)

    def test_deliver_demoted_dtype_still_correct(self):
        src = aligned_empty((16,), np.int64)
        src[:] = np.arange(16)
        out = deliver({"y": src})
        np.testing.assert_array_equal(np.asarray(out["y"]), src)

    def test_collate_output_buffers_are_aligned(self):
        """Windows that span batch boundaries collate into aligned_empty
        buffers, so the delivery hand-off stays zero-copy-capable
        deterministically instead of depending on where malloc landed."""
        from lakesoul_tpu.data.jax_iter import _Rebatcher

        rng = np.random.default_rng(3)
        rb = _Rebatcher(96, tensor_shapes={"emb": SHAPE})
        windows = []
        for i in range(3):  # 3 x 64-row batches → every window spans two
            emb = rng.normal(size=(64, WIDTH)).astype(np.float32)
            windows += rb.push(pa.record_batch(
                pa.table({
                    "id": np.arange(64 * i, 64 * (i + 1), dtype=np.int64),
                    "emb": pa.FixedSizeListArray.from_arrays(
                        pa.array(emb.ravel()), WIDTH
                    ),
                }).combine_chunks().to_batches()[0]
            ))
        assert len(windows) == 2
        for w in windows:
            assert len(w.parts) == 2 and w.fast  # genuinely multi-part
            out = w.collate()
            assert out["emb"].shape == (96,) + SHAPE  # declared shape
            for col in out.values():
                assert col.ctypes.data % 64 == 0  # aligned_empty output


# ---------------------------------------------------------------- replay


def _dp_sharding(n_dev: int):
    """None for one device, else rows split over a ``dp`` axis of the CPU
    mesh: the residency budget is per device, so each holds 1/n of a batch."""
    if n_dev == 1:
        return None
    import jax
    from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

    return NamedSharding(Mesh(np.array(jax.devices()[:n_dev]), ("dp",)), P("dp"))


class TestReplayCache:
    @pytest.mark.parametrize("n_dev", [1, 8], ids=["one-device", "dp8"])
    def test_epoch2_byte_identical_to_epoch1(self, tensor_lsf_table, n_dev):
        sharding = _dp_sharding(n_dev)
        it = tensor_lsf_table.scan().batch_size(256).to_jax_iter(
            cache="device", sharding=sharding
        )
        e1 = read_epoch(it)
        st = it.stats()["replay"]
        assert st["ready"] and not st["spilled"]
        assert st["resident_rows"] == 2048 and st["resident_batches"] == 8
        replayed = list(it)
        if sharding is not None:  # a replayed leaf stays where it was pinned
            leaf = replayed[0]["emb"]
            assert leaf.sharding.is_equivalent_to(sharding, leaf.ndim)
        e2 = read_epoch(replayed)
        assert_epochs_byte_identical(e1, e2)
        assert e2[0]["emb"].shape == (256,) + SHAPE  # declared shape on device
        # epoch 3 still replays (and still matches)
        assert_epochs_byte_identical(e1, read_epoch(it))
        # and equals a loader that never cached
        assert_epochs_byte_identical(e1, read_epoch(
            tensor_lsf_table.scan().batch_size(256).to_jax_iter(sharding=sharding)
        ))

    @pytest.mark.parametrize("n_dev", [1, 8], ids=["one-device", "dp8"])
    def test_budget_overflow_spills_typed_and_metered(self, tensor_lsf_table, n_dev):
        from lakesoul_tpu.obs import registry

        # f32 emb + demoted id + label, the share one device holds
        per_batch = 256 * (WIDTH * 4 + 4 + 4) // n_dev
        spill_before = registry().counter(
            "lakesoul_replay_spilled_batches_total"
        ).value
        it = tensor_lsf_table.scan().batch_size(256).to_jax_iter(
            cache="device", replay_budget_bytes=3 * per_batch + 64,
            sharding=_dp_sharding(n_dev),
        )
        e1 = read_epoch(it)
        st = it.stats()["replay"]
        assert st["spilled"] and st["ready"]
        assert 1 <= st["resident_batches"] < 8
        assert st["resident_rows"] == st["resident_batches"] * 256
        assert st["resident_bytes"] <= 3 * per_batch + 64
        spill = it._replay.spill
        assert spill.budget_bytes == 3 * per_batch + 64
        assert spill.resident_batches == st["resident_batches"]
        assert registry().counter(
            "lakesoul_replay_spilled_batches_total"
        ).value > spill_before
        # the hybrid epoch — resident prefix from device + re-streamed tail —
        # is byte-identical to the streamed epoch, twice
        assert_epochs_byte_identical(e1, read_epoch(it))
        assert_epochs_byte_identical(e1, read_epoch(it))

    def test_abandoned_epoch_leaves_cache_unfilled(self, tensor_lsf_table):
        it = tensor_lsf_table.scan().batch_size(256).to_jax_iter(cache="device")
        for _ in it:
            break  # abandon: partial replay would silently drop data
        assert not it._replay.ready and it._replay.resident_batches == 0
        assert len(read_epoch(it)) == 8  # next pass streams and completes

    def test_permutation_deterministic_under_pinned_seed(self, tensor_lsf_table):
        def replayed(seed):
            it = tensor_lsf_table.scan().batch_size(256).to_jax_iter(
                cache="device", replay_permute=True, replay_seed=seed
            )
            list(it)  # epoch 1 fills
            return read_epoch(it), it

        a, it_a = replayed(7)
        b, _ = replayed(7)
        assert_epochs_byte_identical(a, b)  # same seed → identical epoch 2
        ids = np.concatenate([x["id"] for x in a])
        assert not np.array_equal(ids, np.arange(2048))  # actually permuted
        assert np.array_equal(np.sort(ids), np.arange(2048))  # nothing lost
        # next epoch of the SAME iterator draws a different permutation...
        c = read_epoch(it_a)
        ids_c = np.concatenate([x["id"] for x in c])
        assert not np.array_equal(ids_c, ids)
        assert np.array_equal(np.sort(ids_c), np.arange(2048))
        # ...and a different seed differs from epoch 2 of seed 7
        d, _ = replayed(8)
        ids_d = np.concatenate([x["id"] for x in d])
        assert not np.array_equal(ids_d, ids)

    def test_spilled_cache_replays_in_stream_order(self, tensor_lsf_table):
        per_batch = 256 * (WIDTH * 4 + 4 + 4)
        it = tensor_lsf_table.scan().batch_size(256).to_jax_iter(
            cache="device", replay_permute=True, replay_seed=1,
            replay_budget_bytes=2 * per_batch + 64,
        )
        e1 = read_epoch(it)
        assert it.stats()["replay"]["spilled"]
        # permutation is NOT honoured while spilled: the hybrid epoch must
        # stay position-exact against the streamed tail
        assert_epochs_byte_identical(e1, read_epoch(it))

    def test_env_budget_and_bad_values(self, tensor_lsf_table, monkeypatch):
        per_batch = 256 * (WIDTH * 4 + 4 + 4)
        monkeypatch.setenv("LAKESOUL_REPLAY_BUDGET_BYTES", str(2 * per_batch + 64))
        it = tensor_lsf_table.scan().batch_size(256).to_jax_iter(cache="device")
        list(it)
        assert it.stats()["replay"]["spilled"]
        assert it.stats()["replay"]["resident_batches"] == 2
        monkeypatch.setenv("LAKESOUL_REPLAY_BUDGET_BYTES", "not-a-number")
        with pytest.raises(ConfigError):
            tensor_lsf_table.scan().to_jax_iter(cache="device")

    def test_interleaved_iterations_share_cache_safely(self, tensor_lsf_table):
        """Two concurrently-active iterations of ONE cache='device' loader:
        only the first claims the fill, so the sealed epoch holds each
        batch exactly once (no doubled replay, no offer-after-seal crash)
        and both streams deliver the full table."""
        it = tensor_lsf_table.scan().batch_size(256).to_jax_iter(cache="device")
        a, b = iter(it), iter(it)
        rows_a = rows_b = 0
        for x, y in zip(a, b):  # fully interleaved to completion
            rows_a += x["id"].shape[0]
            rows_b += y["id"].shape[0]
        assert rows_a == rows_b == 2048
        st = it.stats()["replay"]
        assert st["ready"]
        assert st["resident_rows"] == 2048 and st["resident_batches"] == 8
        replay = read_epoch(it)
        assert len(replay) == 8  # not 16: the epoch was sealed ONCE
        ids = np.concatenate([x["id"] for x in replay])
        assert np.array_equal(np.sort(ids), np.arange(2048))
        # partial-then-finish interleave: the survivor must not crash on a
        # sealed cache either
        it2 = tensor_lsf_table.scan().batch_size(256).to_jax_iter(cache="device")
        g1, g2 = iter(it2), iter(it2)
        next(g1)
        consumed = 1 + sum(1 for _ in g2)  # g2 (non-owner) runs to the end
        assert consumed == 9
        rest = sum(1 for _ in g1)  # owner finishes afterwards and seals
        assert rest == 7
        assert it2.stats()["replay"]["resident_batches"] == 8

    def test_replay_kwargs_without_cache_typed(self, tensor_lsf_table):
        scan = tensor_lsf_table.scan()
        with pytest.raises(ConfigError, match="cache='device'"):
            scan.to_jax_iter(replay_permute=True)
        with pytest.raises(ConfigError, match="cache='device'"):
            scan.to_jax_iter(replay_budget_bytes=1 << 20)
        with pytest.raises(ConfigError, match="cache='device'"):
            scan.to_jax_iter(replay_seed=7)

    def test_every_refused_offer_is_metered(self):
        from lakesoul_tpu.obs import registry

        counter = registry().counter("lakesoul_replay_spilled_batches_total")
        before = counter.value
        cache = DeviceReplayCache(budget_bytes=1024)
        batch = deliver({"x": aligned_empty((64, 4), np.float32)})  # 1 KiB
        assert cache.offer(64, batch)
        for _ in range(5):  # the crossing offer + 4 more refusals
            assert not cache.offer(64, batch)
        assert counter.value - before == 5

    def test_cache_state_machine_misuse_typed(self):
        cache = DeviceReplayCache(budget_bytes=1 << 20)
        with pytest.raises(ConfigError):
            list(cache.replay())  # before seal
        cache.seal()
        with pytest.raises(ConfigError):
            cache.offer(1, {"x": np.zeros(1, np.float32)})  # after seal
        with pytest.raises(ConfigError):
            DeviceReplayCache(budget_bytes=0)

    def test_batch_bills_per_device_shard_bytes(self):
        """Residency accounting bills what ONE device actually holds — the
        leaf's shard shape.  On this 1-device CI the shard IS the leaf; the
        replicated case (each device holds the FULL array) is pinned via
        an explicit single-device sharding, which is replication's shape."""
        import jax

        from lakesoul_tpu.tensorplane.replay import _batch_device_bytes

        out = deliver({"x": aligned_empty((64, 8), np.float32)})
        shard = out["x"].sharding.shard_shape(out["x"].shape)
        assert _batch_device_bytes(out) == int(np.prod(shard)) * 4
        # a replicated leaf must bill its FULL bytes per device — never
        # nbytes / ndev (that under-bills by the replication factor)
        replicated = jax.device_put(np.zeros((64, 8), np.float32))
        assert _batch_device_bytes({"x": replicated}) == replicated.nbytes
        # host arrays (no sharding) bill conservatively at full size
        assert _batch_device_bytes({"x": np.zeros((4, 4), np.float32)}) == 64


# ----------------------------------------------------------------- smoke


class TestSmokeRegister:
    def test_register_covers_every_pallas_kernel(self):
        """The acceptance criterion: the smoke register covers 100% of the
        Pallas kernels lakelint's device index enumerates — a new kernel
        cannot land without joining the register."""
        from lakesoul_tpu.tensorplane.smoke import (
            enumerate_pallas_kernels,
            smoke_cases,
            uncovered_kernels,
        )

        assert enumerate_pallas_kernels(), "device index found no Pallas kernels?"
        assert uncovered_kernels() == []
        # and the register names no kernel that no longer exists
        covered = {k for c in smoke_cases() for k in c.kernels}
        assert covered <= set(enumerate_pallas_kernels())

    def test_register_runs_tiny_in_interpret_mode(self):
        """Every case runs at the sizes and in the Pallas mode the caller
        names: tiny and interpreted here, each kernel against its jnp
        twin; the multichip shapes run on the 8-device CPU mesh."""
        import jax

        from lakesoul_tpu.tensorplane.smoke import TINY, run_smoke, smoke_cases

        report = run_smoke(interpret=True, sizes=TINY)
        assert report["interpret"] is True
        assert report["device_count"] == len(jax.devices())
        assert [c["name"] for c in report["cases"]] == [
            c.name for c in smoke_cases()
        ]
        for entry in report["cases"]:
            assert entry["status"] == "pass", entry
            assert entry["seconds"] >= 0
        by_name = {c["name"]: c for c in report["cases"]}
        assert by_name["annplane.ragged_score"]["detail"]["items"] == TINY.items
        assert by_name["vector.bruteforce"]["detail"]["rows"] == TINY.rows

    def test_failing_case_fails_the_run(self, monkeypatch):
        """No case's failure is recorded and skipped over: a kernel that
        disagrees with its twin, or a kernel missing from the register,
        raises out of run_smoke."""
        from lakesoul_tpu.tensorplane import smoke

        def disagree(interpret, sizes):
            np.testing.assert_allclose(np.ones(3), np.zeros(3))

        broken = [
            smoke.SmokeCase("vector.packed_dot", "pallas", disagree,
                            kernels=smoke.smoke_cases()[1].kernels)
        ]
        real_cases = smoke.smoke_cases
        monkeypatch.setattr(smoke, "smoke_cases", lambda: broken)
        monkeypatch.setattr(smoke, "uncovered_kernels", lambda: [])
        with pytest.raises(AssertionError, match="Not equal to tolerance"):
            smoke.run_smoke(interpret=True, sizes=smoke.TINY, kinds=("pallas",))
        monkeypatch.undo()
        monkeypatch.setattr(smoke, "smoke_cases", lambda: real_cases()[1:])
        with pytest.raises(AssertionError, match="missing from the smoke register"):
            smoke.run_smoke(interpret=True, sizes=smoke.TINY, kinds=("pallas",))
