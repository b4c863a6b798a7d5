"""``python -m lakesoul_tpu.fleet`` — the fleet-plane process entries.

Two roles (the fleet chaos suite runs THESE as the children it SIGKILLs —
what is tested is what deploys):

- ``autoscale``: the leased worker controller.  Watches one spool's
  backlog (plus the obs fleet's merged SLO view when armed) and sizes a
  scanplane worker fleet between ``--min/--max``.  Every action is one
  JSON line on stdout (``{"event": "spawn", "pid": ...}``) so a parent —
  chaos test, operator tooling — can watch spawns, takeovers and
  backfills without scraping logs.
- ``train``: one emulated training host.  Resolves its position on the
  data axis (``LAKESOUL_FLEET_PROCESS_INDEX``/``_COUNT``, else jax's
  view), consumes its shard through ``to_jax_iter(multihost=True)`` —
  optionally via a scanplane gateway — and prints ``{rows, batches,
  sha256, ...}`` hashed over the collated host arrays, the per-rank
  identity oracle tests/test_fleet_chaos.py compares against single-process
  shard scans.
"""

from __future__ import annotations

import argparse
import hashlib
import json
import logging
import time


def _cmd_autoscale(args) -> int:
    from lakesoul_tpu import LakeSoulCatalog
    from lakesoul_tpu.obs import fleet
    from lakesoul_tpu.fleet.autoscale import (
        WorkerAutoscaler,
        WorkerSpawner,
        emit_jsonl,
    )

    catalog = LakeSoulCatalog(args.warehouse, db_path=args.db_path)
    spawner = WorkerSpawner(
        args.warehouse,
        args.spool,
        db_path=args.db_path,
        lease_ttl_s=args.worker_lease_ttl_s,
        poll_s=args.worker_poll_s,
    )
    controller = WorkerAutoscaler(
        catalog.client.store,
        spawner,
        spool_dir=args.spool,
        min_workers=args.min_workers,
        max_workers=args.max_workers,
        controller_id=args.controller_id,
        lease_ttl_s=args.lease_ttl_s,
    )
    fleet.arm("fleet-autoscaler", service_id=controller.controller_id)
    emit_jsonl({
        "event": "autoscaler",
        "controller": controller.controller_id,
        "spool": args.spool,
        "min": controller.policy.min_workers,
        "max": controller.policy.max_workers,
    })
    try:
        controller.run_forever(poll_s=args.poll_s, on_event=emit_jsonl)
    except KeyboardInterrupt:
        pass
    finally:
        controller.stop()
    return 0


def _cmd_train(args) -> int:
    from lakesoul_tpu import LakeSoulCatalog
    from lakesoul_tpu.obs import fleet
    from lakesoul_tpu.obs.tracing import span
    from lakesoul_tpu.fleet.multihost import digest_batch, process_axis

    index, count = process_axis()
    fleet.arm("fleet-train", service_id=f"rank{index}")
    catalog = LakeSoulCatalog(args.warehouse, db_path=args.db_path)
    scan = catalog.scan(args.table, args.namespace).batch_size(args.batch_size)
    if args.location:
        scan = scan.via_scanplane(args.location)
    # JAX is touched only by a rank that will deliver to a device: a host-
    # array rank must neither claim the chip nor pay the backend start-up
    local_devices = None
    if args.device_put:
        import jax

        from lakesoul_tpu.utils.compile_cache import configure_compile_cache

        configure_compile_cache()
        local_devices = jax.local_device_count()
    digest = hashlib.sha256()
    rows = 0
    batches = 0
    start = time.perf_counter()
    with span("fleet.train.consume", table=args.table, rank=index):
        it = scan.to_jax_iter(
            multihost=True,
            device_put=args.device_put,
            drop_remainder=False,
        )
        for batch in it:
            # hash the collated HOST arrays key-by-key: deterministic for
            # equal contents regardless of device placement or process, so
            # the same loop over a single-process scan.shard(rank, world)
            # is the byte-identity oracle
            rows += digest_batch(digest, batch)
            batches += 1
    elapsed = time.perf_counter() - start
    print(json.dumps({
        "rows": rows,
        "batches": batches,
        "sha256": digest.hexdigest(),
        "elapsed_s": round(elapsed, 4),
        "process_index": index,
        "process_count": count,
        "local_devices": local_devices,
    }), flush=True)
    return 0


def main(argv=None) -> int:
    p = argparse.ArgumentParser(
        "lakesoul-fleet",
        description="fleet plane: worker autoscaling + multi-host trainers",
    )
    sub = p.add_subparsers(dest="role")

    pa_ = sub.add_parser("autoscale", help="leased scanplane worker controller")
    pa_.add_argument("--warehouse", required=True)
    pa_.add_argument("--db-path", default=None)
    pa_.add_argument("--spool", required=True)
    pa_.add_argument("--min-workers", type=int, default=None,
                     help="floor (default LAKESOUL_FLEET_MIN_WORKERS or 1)")
    pa_.add_argument("--max-workers", type=int, default=None,
                     help="ceiling (default LAKESOUL_FLEET_MAX_WORKERS or 8)")
    pa_.add_argument("--lease-ttl-s", type=float, default=10.0,
                     help="controller lease TTL (fail-over bound)")
    pa_.add_argument("--poll-s", type=float, default=1.0)
    pa_.add_argument("--controller-id", default=None)
    pa_.add_argument("--worker-lease-ttl-s", type=float, default=None)
    pa_.add_argument("--worker-poll-s", type=float, default=None)
    pa_.set_defaults(fn=_cmd_autoscale)

    pt = sub.add_parser("train", help="one emulated training host (rows + sha256)")
    pt.add_argument("--warehouse", required=True)
    pt.add_argument("--db-path", default=None)
    pt.add_argument("--table", required=True)
    pt.add_argument("--namespace", default="default")
    pt.add_argument("--batch-size", type=int, default=8192)
    pt.add_argument("--location", default=None,
                    help="scanplane gateway; omit to decode in-process")
    pt.add_argument("--device-put", action="store_true",
                    help="move batches to device (default: host arrays)")
    pt.set_defaults(fn=_cmd_train)

    args = p.parse_args(argv)
    if args.role is None:
        p.error("choose a role: autoscale | train")
    logging.basicConfig(level=logging.INFO)
    return args.fn(args)


if __name__ == "__main__":
    raise SystemExit(main())
