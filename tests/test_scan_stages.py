"""Scan-path efficiency contracts (PR 8):

- the zero-copy rebatch/collate is BYTE-IDENTICAL to the old
  concat_tables + combine_chunks implementation (kept verbatim here as the
  reference) across chunked / sliced / null-bearing / fixed-size-list /
  string / bool inputs;
- a no-PK (and a compacted-PK) scan DEGENERATES to raw decode: the merge
  and fill stages report ~0 in the ``lakesoul_scan_stage_seconds``
  breakdown while decode carries the leg;
- the stage breakdown itself populates for a real MOR scan.
"""

from __future__ import annotations

import numpy as np
import pyarrow as pa
import pytest

from lakesoul_tpu.data.jax_iter import _Rebatcher, _Window, _default_collate
from lakesoul_tpu.obs import stage_counts, stage_seconds


# --------------------------------------------------------------------------
# reference implementation: the pre-PR-8 rebatcher + collate, verbatim
# --------------------------------------------------------------------------


class _OldRebatcher:
    def __init__(self, batch_size: int):
        self.batch_size = batch_size
        self._pending: list[pa.Table] = []
        self._rows = 0

    def push(self, batch):
        t = pa.table(batch) if isinstance(batch, pa.RecordBatch) else batch
        self._pending.append(t)
        self._rows += len(t)
        while self._rows >= self.batch_size:
            yield self._pop(self.batch_size)

    def _pop(self, n: int) -> pa.Table:
        big = pa.concat_tables(self._pending)
        out = big.slice(0, n)
        rest = big.slice(n)
        self._pending = [rest] if len(rest) else []
        self._rows = len(rest)
        return out

    def tail(self):
        if self._rows == 0:
            return None
        out = pa.concat_tables(self._pending)
        self._pending, self._rows = [], 0
        return out


def _old_windows(batches, batch_size, drop_remainder):
    rb = _OldRebatcher(batch_size)
    for b in batches:
        yield from rb.push(b)
    if not drop_remainder:
        t = rb.tail()
        if t is not None:
            yield t


def _new_windows(batches, batch_size, drop_remainder):
    rb = _Rebatcher(batch_size)
    for b in batches:
        yield from rb.push(b)
    if not drop_remainder:
        w = rb.tail()
        if w is not None:
            yield w


def _new_collate(window: _Window):
    if window.fast:
        return window.collate()
    return _default_collate(window.to_table())


def _assert_same_pytree(got: dict, ref: dict):
    assert set(got) == set(ref)
    for name in ref:
        g, r = got[name], ref[name]
        assert g.dtype == r.dtype, (name, g.dtype, r.dtype)
        assert g.shape == r.shape, (name, g.shape, r.shape)
        if g.dtype == object:
            assert list(g) == list(r), name
        else:
            np.testing.assert_array_equal(g, r, err_msg=name)


def _roundtrip(batches, batch_size, drop_remainder=False):
    ref = [
        _default_collate(w)
        for w in _old_windows(batches, batch_size, drop_remainder)
    ]
    got = [
        _new_collate(w)
        for w in _new_windows(batches, batch_size, drop_remainder)
    ]
    assert len(got) == len(ref), (len(got), len(ref))
    for g, r in zip(got, ref):
        _assert_same_pytree(g, r)
    return got


# --------------------------------------------------------------------------
# byte identity across input shapes
# --------------------------------------------------------------------------


def _numeric_batches(n_batches=7, rows=300, seed=0):
    rng = np.random.default_rng(seed)
    out = []
    for i in range(n_batches):
        n = rows + (i * 37) % 100
        out.append(pa.record_batch({
            "id": pa.array(np.arange(i * 1000, i * 1000 + n, dtype=np.int64)),
            "f32": pa.array(rng.normal(size=n).astype(np.float32)),
            "f64": pa.array(rng.normal(size=n)),
            "i32": pa.array(rng.integers(-50, 50, n).astype(np.int32)),
        }))
    return out


class TestByteIdentity:
    def test_numeric_fast_path_matches_old(self):
        batches = _numeric_batches()
        got = _roundtrip(batches, 256)
        # sanity: these windows take the fused path
        ws = list(_new_windows(_numeric_batches(), 256, False))
        assert all(w.fast for w in ws)
        assert got, "no windows emitted"

    def test_window_not_aligned_to_batches(self):
        # window size coprime to batch lengths: every window spans parts
        _roundtrip(_numeric_batches(), 211)
        _roundtrip(_numeric_batches(), 997)

    def test_chunked_table_input(self):
        t = pa.Table.from_batches(_numeric_batches(4))
        assert t.column("id").num_chunks > 1
        _roundtrip([t], 123)

    def test_sliced_batches_nonzero_offset(self):
        sliced = [b.slice(17, len(b) - 40) for b in _numeric_batches()]
        assert all(len(b) for b in sliced)
        _roundtrip(sliced, 201)

    def test_null_bearing_columns_fall_back_identically(self):
        rng = np.random.default_rng(1)
        batches = []
        for i in range(5):
            n = 200
            vals = rng.normal(size=n)
            mask = rng.random(n) < 0.2
            batches.append(pa.record_batch({
                "id": pa.array(np.arange(n, dtype=np.int64)),
                "v": pa.array([None if m else float(x) for m, x in zip(mask, vals)],
                              type=pa.float64()),
            }))
        ws = list(_new_windows(batches, 128, False))
        assert not all(w.fast for w in ws)  # nulls force the fallback
        _roundtrip(batches, 128)

    def test_fixed_size_list_tensor_columns(self):
        rng = np.random.default_rng(2)
        batches = []
        for i in range(4):
            n = 150 + i
            batches.append(pa.record_batch({
                "id": pa.array(np.arange(n, dtype=np.int64)),
                "emb": pa.FixedSizeListArray.from_arrays(
                    rng.normal(size=n * 8).astype(np.float32), 8
                ),
            }))
        got = _roundtrip(batches, 97)
        assert got[0]["emb"].shape[1] == 8

    def test_sliced_fixed_size_list(self):
        rng = np.random.default_rng(3)
        n = 400
        b = pa.record_batch({
            "emb": pa.FixedSizeListArray.from_arrays(
                rng.normal(size=n * 4).astype(np.float32), 4
            ),
            "id": pa.array(np.arange(n, dtype=np.int64)),
        })
        _roundtrip([b.slice(33, 300), b.slice(5, 111)], 64)

    def test_strings_and_bools_fall_back_identically(self):
        batches = []
        for i in range(3):
            n = 120
            batches.append(pa.record_batch({
                "id": pa.array(np.arange(n, dtype=np.int64)),
                "name": pa.array([f"r{i}_{j}" for j in range(n)]),
                "flag": pa.array([j % 3 == 0 for j in range(n)]),
            }))
        out = _roundtrip(batches, 77)
        assert out[0]["name"].dtype == object
        assert out[0]["flag"].dtype == np.bool_

    def test_timestamp_columns_fast_path(self):
        batches = []
        for i in range(3):
            n = 90
            batches.append(pa.record_batch({
                "ts": pa.array(
                    (np.arange(n) + i * 1000).astype("datetime64[us]")
                ),
                "id": pa.array(np.arange(n, dtype=np.int64)),
            }))
        ws = list(_new_windows(batches, 50, False))
        assert all(w.fast for w in ws)
        _roundtrip(batches, 50)

    def test_drop_remainder_boundary(self):
        batches = _numeric_batches(3, rows=100)
        _roundtrip(batches, 100, drop_remainder=True)
        _roundtrip(batches, 10_000, drop_remainder=False)  # single tail window


# --------------------------------------------------------------------------
# degeneracy: no-PK / compacted scans are raw-decode plans
# --------------------------------------------------------------------------


def _stage_delta(before_s, before_c):
    after_s, after_c = stage_seconds(), stage_counts()
    return (
        {k: after_s[k] - before_s[k] for k in after_s},
        {k: after_c[k] - before_c[k] for k in after_c},
    )


class TestDegeneracy:
    def _build(self, tmp_warehouse, name, *, primary_keys=None, rows=200_000,
               budget=None):
        from lakesoul_tpu import LakeSoulCatalog

        catalog = LakeSoulCatalog(str(tmp_warehouse))
        props = {}
        if budget:
            props["lakesoul.memory_budget_bytes"] = str(budget)
        schema = pa.schema([
            ("id", pa.int64()), ("v", pa.float64()), ("f0", pa.float32()),
        ])
        t = catalog.create_table(
            name, schema, primary_keys=primary_keys or [],
            hash_bucket_num=1, properties=props,
        )
        rng = np.random.default_rng(0)
        per = rows // 4
        for i in range(4):
            ids = np.arange(i * per, (i + 1) * per, dtype=np.int64)
            t.write_arrow(pa.table({
                "id": ids,
                "v": rng.normal(size=per),
                "f0": rng.normal(size=per).astype(np.float32),
            }, schema=schema))
        return t

    def _scan_all(self, t):
        rows = 0
        for b in t.scan().batch_size(8192).to_batches():
            rows += len(b)
        return rows

    def test_no_pk_stream_merge_fill_near_zero(self, tmp_warehouse):
        # a small budget forces the bounded STREAMING branch
        t = self._build(tmp_warehouse, "nopk", budget=1 << 20)
        before = stage_seconds(), stage_counts()
        rows = self._scan_all(t)
        ds, dc = _stage_delta(*before)
        assert rows == 200_000
        assert dc["merge"] == 0, dc
        assert ds["decode"] > 0, ds
        # fill may be touched by identity-exit probes; it must stay noise
        assert ds["merge"] + ds["fill"] <= max(0.10 * ds["decode"], 0.005), ds

    def test_no_pk_materialize_merge_fill_near_zero(self, tmp_warehouse):
        t = self._build(tmp_warehouse, "nopk_mat")  # default budget: hybrid materialize
        before = stage_seconds(), stage_counts()
        rows = self._scan_all(t)
        ds, dc = _stage_delta(*before)
        assert rows == 200_000
        assert dc["merge"] == 0, dc
        assert ds["merge"] + ds["fill"] <= max(0.10 * ds["decode"], 0.005), ds

    def test_compacted_pk_scan_merge_near_decode_zero(self, tmp_warehouse):
        t = self._build(tmp_warehouse, "pk", primary_keys=["id"])
        t.compact()
        before = stage_seconds(), stage_counts()
        rows = self._scan_all(t)
        ds, dc = _stage_delta(*before)
        assert rows == 200_000
        # a compacted PK unit still passes through the merge entry point,
        # but the strictly-increasing fast exit reduces it to one O(n)
        # compare — a small fraction of decode
        assert ds["merge"] + ds["fill"] <= max(0.25 * ds["decode"], 0.01), ds

    def test_mor_scan_populates_breakdown(self, tmp_warehouse):
        t = self._build(tmp_warehouse, "mor", primary_keys=["id"])
        # overlapping upsert wave → real merge work
        rng = np.random.default_rng(1)
        ids = rng.choice(200_000, 50_000, replace=False).astype(np.int64)
        t.upsert(pa.table({
            "id": ids,
            "v": rng.normal(size=len(ids)),
            "f0": rng.normal(size=len(ids)).astype(np.float32),
        }))
        before = stage_seconds(), stage_counts()
        batches = list(t.scan().batch_size(4096).to_jax_iter(
            device_put=False, drop_remainder=False
        ))
        ds, dc = _stage_delta(*before)
        rows = sum(len(b["id"]) for b in batches)
        assert rows == 200_000  # upsert overwrote, no new keys
        for stage in ("decode", "merge", "rebatch", "collate", "queue"):
            assert dc[stage] > 0, (stage, dc)
        assert ds["decode"] > 0 and ds["merge"] > 0, ds
