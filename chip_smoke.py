#!/usr/bin/env python
"""First-run proof on a TPU: drive the main path once through the entry
points users call, at the full width of BERT-base, and check what comes out.

    python chip_smoke.py        # one process per chip; no options

Stages, each of which fails the run if it fails (nothing is caught):

- ``trainer``: a primary-key table (4 hash buckets, pre-tokenized
  ``fixed_size_list<int32, 128>`` rows from a seed, one 25% upsert wave so
  merge-on-read does work) → ``scan().batch_size(32).to_jax_iter(...)`` →
  ``make_bert_train_state(BertConfig.base())`` / ``make_bert_train_step``
  on a one-device mesh.  The loader runs with ``sharding=None``, with
  ``plan.sharding("dp", "sp")`` and through a ``cache="device"`` replay
  epoch; every delivered leaf, the params and every loss must be on a TPU
  device, every loss finite, the first loss within a tolerance of a
  float32 ``jnp`` reference, and the step compiled exactly once.
- ``ann_server``: a plane built from a seed by ``ShardedAnnBuilder``,
  opened with default arguments, served by a ``ShardedAnnEndpoint`` to
  several threads with mixed ``nprobe``; then one shard's
  ``IvfRabitqIndex.batch_search`` on its device-resident path.  Recall@10
  against ``vector/oracle.py`` must hold the floor the CPU tests use.
- ``kernels``: the smoke register (``lakesoul_tpu/tensorplane/smoke.py``):
  every Pallas kernel compiled (``interpret=False``) at a deployed
  size, d = 128 and 768, against its ``jnp`` twin, plus the delivery
  and replay cases.
- ``multichip`` (four or more devices): the trainer again over
  ``make_mesh(jax.devices()[:4])`` with batch 64 under ``P("dp", "sp")``,
  then the register's multichip cases (cross-chip top-k, and the
  dp×tp×sp / dp×pp dryrun).  On fewer devices the JSON says
  ``"not run: N device(s)"``, which is not a pass.

The script has no CPU mode: it exits 2 at once, printing no result, unless
``jax.devices()[0].platform`` is ``tpu``, and it never sets
``JAX_PLATFORMS``.  The stage functions take explicit sizes and an explicit
``interpret`` so that tier-1 can run them tiny on the CPU
(``tests/test_chip_smoke.py``).  The last stdout line is the result,
``{"ok": true, "device": {"platform", "kind", "count"}}`` as JAX reports the
device; the line before it is the report: one JSON object with each stage's
status and seconds, the compile-cache directory and ``"claim": null``.  The
timings in it are smoke timings, not a metric.
"""

from __future__ import annotations

import dataclasses
import functools
import json
import os
import statistics
import sys
import tempfile
import time
from concurrent.futures import ThreadPoolExecutor

import numpy as np

REPO = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, REPO)

MASK_ID = 103          # [MASK] in the BERT vocabulary
UPSERT_FRACTION = 0.25
RECALL_FLOOR = 0.9     # tests/test_annplane.py, 1-bit plane
REFERENCE_LOSS_TOL = 0.05
_LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


class _LoweringCounter:
    """Counts top-level lowerings: each is one executable built (or fetched
    from the persistent cache) for a jitted function."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, _seconds, **_kwargs):
        if name == _LOWERING_EVENT:
            self.count += 1


def _platforms(tree) -> set[str]:
    import jax

    return {
        d.platform
        for leaf in jax.tree_util.tree_leaves(tree)
        for d in leaf.devices()
    }


def _require(cond: bool, message: str) -> None:
    if not cond:
        raise RuntimeError(message)


# ------------------------------------------------------------------ trainer


def build_token_table(warehouse: str, *, rows: int, seq: int, vocab: int, seed: int):
    """The smoke table: ``rows`` pre-tokenized sequences under a primary
    key, 4 hash buckets, then an upsert wave over a quarter of the keys so
    the scan merges on read."""
    import pyarrow as pa

    from lakesoul_tpu import LakeSoulCatalog
    from lakesoul_tpu.tensorplane import tensor_field

    schema = pa.schema([("id", pa.int64()), tensor_field("tokens", (seq,), "int32")])
    rng = np.random.default_rng(seed)

    def token_rows(ids: np.ndarray) -> pa.Table:
        tokens = rng.integers(1000, vocab, (len(ids), seq), dtype=np.int32)
        return pa.table({
            "id": ids,
            "tokens": pa.FixedSizeListArray.from_arrays(
                pa.array(tokens.ravel()), seq
            ).cast(schema.field("tokens").type),
        }, schema=schema)

    table = LakeSoulCatalog(warehouse).create_table(
        "smoke_tokens", schema, primary_keys=["id"], hash_bucket_num=4
    )
    table.write_arrow(token_rows(np.arange(rows, dtype=np.int64)))
    wave = rng.choice(rows, int(rows * UPSERT_FRACTION), replace=False)
    table.upsert(token_rows(np.sort(wave).astype(np.int64)))
    return table


def mlm_collate(seed: int):
    """Host transform: token rows → (ids, labels, mask) with 15% of the
    positions masked; labels are -100 everywhere else."""
    rng = np.random.default_rng(seed)

    def collate(batch: dict) -> dict:
        tokens = batch["tokens"]
        masked = rng.random(tokens.shape) < 0.15
        return {
            "ids": np.where(masked, np.int32(MASK_ID), tokens),
            "labels": np.where(masked, tokens, np.int32(-100)),
            "mask": np.ones(tokens.shape, np.bool_),
        }

    return collate


def stage_trainer(devices, *, cfg, batch: int, seq: int, steps: int,
                  loader_modes: tuple[str, ...]) -> dict:
    """Table → loader → BERT train step over ``make_mesh(devices)``.

    ``loader_modes`` picks from ``default`` (``sharding=None``),
    ``sharded`` (``plan.sharding("dp", "sp")``) and ``replay`` (a
    ``cache="device"`` loader: one filling epoch, one replayed).  Every
    mode takes ``steps`` optimizer steps per epoch with the same step
    function, which must compile exactly once over all of them."""
    import jax

    from lakesoul_tpu.models.bert import bert_mlm_loss
    from lakesoul_tpu.models.train import make_bert_train_state, make_bert_train_step
    from lakesoul_tpu.parallel.mesh import make_mesh

    want_platform = devices[0].platform
    plan = make_mesh(devices)
    params, opt_state, tx, shardings = make_bert_train_state(cfg, plan)
    step = make_bert_train_step(cfg, plan, tx, shardings)
    _require(_platforms(params) == {want_platform},
             f"params on {_platforms(params)}, expected {want_platform}")
    batch_sharding = plan.sharding("dp", "sp")
    lowerings = _LoweringCounter()
    out: dict = {"mesh": {"dp": plan.dp, "tp": plan.tp, "sp": plan.sp}, "modes": {}}
    step_seconds: list[float] = []
    losses: list[float] = []

    def check_delivery(delivered, mode: str) -> None:
        _require(_platforms(delivered) == {want_platform},
                 f"{mode}: batch on {_platforms(delivered)}, expected {want_platform}")
        for leaf in jax.tree_util.tree_leaves(delivered):
            _require(leaf.shape == (batch, seq), f"{mode}: leaf shape {leaf.shape}")
            if mode != "default":
                _require(leaf.sharding.device_set == set(devices),
                         f"{mode}: batch spans {len(leaf.sharding.device_set)} devices")
                if plan.dp > 1:
                    # nothing landed whole on one device
                    _require(
                        all(s.data.shape[0] == batch // plan.dp
                            for s in leaf.addressable_shards),
                        f"{mode}: a device holds the whole batch",
                    )

    def run_epoch(loader, mode: str) -> None:
        nonlocal params, opt_state
        n = 0
        for delivered in loader:
            check_delivery(delivered, mode)
            if not losses:
                out["reference_loss"] = reference_loss(delivered)
            lowered_before = lowerings.count
            t0 = time.perf_counter()
            params, opt_state, loss = step(
                params, opt_state, delivered["ids"], delivered["labels"], delivered["mask"]
            )
            loss.block_until_ready()
            step_seconds.append(time.perf_counter() - t0)
            out["modes"][mode] += lowerings.count - lowered_before
            _require(_platforms(loss) == {want_platform}, f"{mode}: loss on {_platforms(loss)}")
            losses.append(float(loss))
            n += 1
        _require(n == steps, f"{mode}: {n} steps, expected {steps}")

    def reference_loss(delivered) -> float:
        """The same loss in plain float32 at full matmul precision, from
        the params the first step is about to consume."""
        ref_cfg = dataclasses.replace(cfg, dtype="float32")
        with jax.default_matmul_precision("highest"):
            return float(jax.jit(functools.partial(bert_mlm_loss, cfg=ref_cfg))(
                params, delivered["ids"], delivered["labels"], delivered["mask"]
            ))

    with tempfile.TemporaryDirectory(prefix="chip_smoke_wh_") as warehouse:
        table = build_token_table(
            warehouse, rows=batch * steps, seq=seq, vocab=cfg.vocab_size, seed=0
        )
        _require(
            any(len(u.data_files) > 1 for u in table.scan().scan_plan()),
            "the upsert wave left nothing to merge on read",
        )
        scan = table.scan().batch_size(batch)
        for mode in loader_modes:
            out["modes"][mode] = 0  # lowerings its step calls caused
            if mode == "default":
                run_epoch(scan.to_jax_iter(transform=mlm_collate(1)), mode)
            elif mode == "sharded":
                run_epoch(
                    scan.to_jax_iter(transform=mlm_collate(2), sharding=batch_sharding),
                    mode,
                )
            elif mode == "replay":
                loader = scan.to_jax_iter(
                    transform=mlm_collate(3), sharding=batch_sharding, cache="device"
                )
                run_epoch(loader, mode)  # streams, and pins each batch
                replay = loader.stats()["replay"]
                _require(replay["ready"] and not replay["spilled"], f"replay cache: {replay}")
                run_epoch(loader, mode)  # served from device memory
                _require(loader.stats()["replay"]["resident_batches"] == steps,
                         f"replay cache: {loader.stats()['replay']}")
            else:
                raise ValueError(f"unknown loader mode {mode!r}")

    _require(all(np.isfinite(losses)), f"non-finite loss in {losses}")
    step_lowerings = sum(out["modes"].values())
    _require(step_lowerings == 1,
             f"the train step was lowered {step_lowerings} times, expected exactly 1:"
             f" {out['modes']}")
    gap = abs(losses[0] - out["reference_loss"])
    _require(gap <= REFERENCE_LOSS_TOL,
             f"first loss {losses[0]} vs float32 reference {out['reference_loss']}")
    tp_leaf = params["layers"]["w1"]
    tp_devices = {s.device for s in tp_leaf.addressable_shards}
    if plan.tp > 1:
        _require(len(tp_devices) > 1, "a tp-sharded parameter sits on one device")
        _require(
            all(s.data.shape[-1] == cfg.ff // plan.tp for s in tp_leaf.addressable_shards),
            "a tp-sharded parameter was not split",
        )
    out.update(
        optimizer_steps=len(losses),
        first_loss=round(losses[0], 4),
        last_loss=round(losses[-1], 4),
        reference_gap=round(gap, 5),
        first_step_seconds=round(step_seconds[0], 3),
        median_step_seconds=round(statistics.median(step_seconds[1:]), 4),
        train_step_lowerings=step_lowerings,
        tp_param_devices=len(tp_devices),
    )
    return out


# --------------------------------------------------------------- ANN server


def stage_ann_server(*, rows: int, dim: int, nlist: int, queries: int,
                     nprobes: tuple[int, ...], rerank_depth: int,
                     interpret: bool) -> dict:
    """Build a two-shard plane from a seed, serve it, and check recall.

    ``interpret=False`` opens the plane with default arguments, which on a
    TPU must select the compiled ragged kernel; ``interpret=True`` (the CPU
    dry run) asks for the same kernel in the Pallas interpreter."""
    from lakesoul_tpu.annplane import (
        AnnPlane,
        AnnPlaneConfig,
        ShardedAnnBuilder,
        ShardedAnnEndpoint,
    )
    from lakesoul_tpu.annplane.build import shard_root
    from lakesoul_tpu.vector.config import VectorIndexConfig
    from lakesoul_tpu.vector.index import SearchParams
    from lakesoul_tpu.vector.manifest import ManifestStore
    from lakesoul_tpu.vector.oracle import exact_topk, recall_at_k

    rng = np.random.default_rng(0)
    centers = rng.standard_normal((64, dim), dtype=np.float32) * 3.0
    vectors = centers[rng.integers(0, 64, rows)] + rng.standard_normal((rows, dim), dtype=np.float32)
    ids = np.arange(rows, dtype=np.uint64)
    probes = centers[rng.integers(0, 64, queries)] + rng.standard_normal((queries, dim), dtype=np.float32)

    index_cfg = VectorIndexConfig(column="emb", dim=dim, nlist=nlist)
    per_row = AnnPlaneConfig(index=index_cfg, shard_budget_bytes=1 << 30).bytes_per_vector()
    shard_rows = -(-rows // 2)
    plane_cfg = AnnPlaneConfig(
        index=index_cfg, shard_budget_bytes=shard_rows * per_row, keep_raw=True
    )
    params = SearchParams(top_k=10, nprobe=nprobes[0], rerank_depth=rerank_depth)
    out: dict = {"rows": rows, "dim": dim}
    with tempfile.TemporaryDirectory(prefix="chip_smoke_ann_") as root:
        t0 = time.perf_counter()
        manifest = ShardedAnnBuilder(root, plane_cfg).build(
            (vectors[lo:lo + 16384], ids[lo:lo + 16384]) for lo in range(0, rows, 16384)
        )
        out["build_seconds"] = round(time.perf_counter() - t0, 2)
        if interpret:
            plane = AnnPlane.open(root, use_pallas=True, pallas_interpret=True)
        else:
            plane = AnnPlane.open(root)
            _require(plane.use_pallas is True and plane.pallas_interpret is False,
                     f"default plane: use_pallas={plane.use_pallas}"
                     f" pallas_interpret={plane.pallas_interpret}")
        _require(len(plane.shards) == 2, f"{len(plane.shards)} shards, expected 2")

        # requests from several threads, each with its own probe depth
        def client(start: int) -> list:
            futures = [
                (i, endpoint.submit(probes[i], nprobe=nprobes[i % len(nprobes)]))
                for i in range(start, queries, 4)
            ]
            return [(i, fut.result(timeout=600)) for i, fut in futures]

        with ShardedAnnEndpoint(plane, params, max_wait_ms=2.0) as endpoint, \
                ThreadPoolExecutor(max_workers=4) as clients:
            t0 = time.perf_counter()
            answered = dict(
                pair for served in clients.map(client, range(4)) for pair in served
            )
            out["serve_seconds"] = round(time.perf_counter() - t0, 2)
            out["endpoint_batches"] = endpoint.stats()["batches"]
        _require(len(answered) == queries, f"{len(answered)} of {queries} requests answered")
        truth = exact_topk(vectors, ids, probes, 10)
        out["plane_recall_at_10"] = round(
            recall_at_k(truth, [answered[i][0] for i in range(queries)]), 4
        )
        _require(out["plane_recall_at_10"] >= RECALL_FLOOR,
                 f"plane recall@10 {out['plane_recall_at_10']} < {RECALL_FLOOR}")

        # one shard's index on its device-resident path: 1-bit codes, so the
        # batch search runs packed_dot_batch when the device is a TPU
        entry = manifest["shards"][0]
        index = ManifestStore(shard_root(root, entry["shard"])).read_at(entry["generation"])
        index.enable_device_cache()
        resident = index._get_device_bundle()
        _require(resident is not None, "the shard index has no device-resident bundle")
        lo, hi = entry["row_start"], entry["row_end"]
        got, _ = index.batch_search(probes, params)
        shard_truth = exact_topk(vectors[lo:hi], ids[lo:hi], probes, 10)
        out["index_recall_at_10"] = round(recall_at_k(shard_truth, got), 4)
        out["index_codes_platform"] = sorted(_platforms(resident["codes"]))
        _require(out["index_recall_at_10"] >= RECALL_FLOOR,
                 f"index recall@10 {out['index_recall_at_10']} < {RECALL_FLOOR}")
    return out


# ------------------------------------------------------------------ kernels


def _by_name(report: dict) -> dict:
    return {
        c["name"]: {"status": c["status"], "seconds": c.get("seconds"), **c.get("detail", {})}
        for c in report["cases"]
    }


def stage_kernels(*, dims: tuple[int, ...], sizes_for, interpret: bool) -> dict:
    """The smoke register's Pallas cases at ``sizes_for(d)`` for each
    width, then its delivery and replay cases once."""
    from lakesoul_tpu.tensorplane.smoke import run_smoke

    out = {
        f"d{d}": _by_name(run_smoke(interpret=interpret, sizes=sizes_for(d), kinds=("pallas",)))
        for d in dims
    }
    out["tensorplane"] = _by_name(
        run_smoke(interpret=interpret, sizes=sizes_for(dims[0]), kinds=("tensorplane",))
    )
    return out


# ---------------------------------------------------------------- multichip


def stage_multichip(devices, *, cfg, batch: int, seq: int, steps: int) -> dict:
    """The trainer over a four-device mesh with the batch delivered under
    ``P("dp", "sp")``, then the register's collective shapes."""
    from lakesoul_tpu.tensorplane.smoke import TINY, run_smoke

    out = {
        "trainer": stage_trainer(
            devices, cfg=cfg, batch=batch, seq=seq, steps=steps, loader_modes=("sharded",)
        )
    }
    out["collectives"] = _by_name(run_smoke(interpret=False, sizes=TINY, kinds=("multichip",)))
    for name, case in out["collectives"].items():
        _require(case["status"] == "pass", f"{name}: {case['status']}")
    return out


# --------------------------------------------------------------------- main


def main() -> int:
    import jax

    from lakesoul_tpu import native
    from lakesoul_tpu.models.bert import BertConfig
    from lakesoul_tpu.tensorplane.smoke import deployed
    from lakesoul_tpu.utils.compile_cache import configure_compile_cache

    cache_dir = configure_compile_cache()
    devices = jax.devices()
    device = {
        "platform": devices[0].platform,
        "kind": devices[0].device_kind,
        "count": len(devices),
    }
    print(f"chip_smoke: jax {jax.__version__} sees {device}", file=sys.stderr, flush=True)
    if device["platform"] != "tpu":
        print(
            f"chip_smoke: platform is {device['platform']!r}, not 'tpu' — this"
            " script has no CPU mode (tier-1 runs its stages tiny:"
            " tests/test_chip_smoke.py)", file=sys.stderr,
        )
        return 2
    if not native.available():
        print(
            "chip_smoke: the native library did not build or load; the host"
            " stages would be the numpy fallbacks", file=sys.stderr,
        )
        return 3

    stages: dict = {}

    def run(name: str, fn) -> None:
        t0 = time.perf_counter()
        detail = fn()
        stages[name] = {
            "status": "pass", "seconds": round(time.perf_counter() - t0, 2), **detail,
        }
        print(f"chip_smoke: {name} pass in {stages[name]['seconds']} s",
              file=sys.stderr, flush=True)

    cfg = BertConfig.base()
    run("trainer", lambda: stage_trainer(
        devices[:1], cfg=cfg, batch=32, seq=128, steps=8,
        loader_modes=("default", "sharded", "replay"),
    ))
    run("ann_server", lambda: stage_ann_server(
        rows=100_000, dim=128, nlist=64, queries=64,
        # 1-bit codes in 128 dimensions need a deep shortlist: recall@10 on
        # the host path is 0.83 at depth 80 and 0.99 at depth 400
        nprobes=(32, 48, 64, 96), rerank_depth=400, interpret=False,
    ))
    run("kernels", lambda: stage_kernels(
        dims=(128, 768), sizes_for=deployed, interpret=False,
    ))
    if len(devices) >= 4:
        run("multichip", lambda: stage_multichip(
            devices[:4], cfg=cfg, batch=64, seq=128, steps=8,
        ))
        multichip = "pass"
    else:
        multichip = f"not run: {len(devices)} device(s)"

    # the report, then the result: the last stdout line carries exactly
    # "ok" and "device", which is all the driver's check reads
    print(json.dumps({"report": {
        "jax": jax.__version__,
        "compile_cache_dir": cache_dir,
        "native_built": True,
        "multichip": multichip,
        "stages": stages,
        "note": "seconds are smoke timings, not a metric",
        "claim": None,
    }}))
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
