"""Online feature pipeline e2e (BASELINE.json config 5).

Debezium-style CDC events stream into a feature table with exactly-once
checkpoints; a resumable follow() consumer turns each new commit into
device-resident feature updates — the Flink-CDC → online-features loop of
the reference, on the TPU stack.

Run: python examples/online_features.py [--warehouse DIR]
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile
import threading

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--warehouse", default=None)
    args = ap.parse_args()
    wh = args.warehouse or tempfile.mkdtemp(prefix="lakesoul_feat_")

    import jax.numpy as jnp

    from lakesoul_tpu import LakeSoulCatalog
    from lakesoul_tpu.meta.client import (
        follow_cursors_from_json,
        follow_cursors_to_json,
    )
    from lakesoul_tpu.meta.entity import now_millis
    from lakesoul_tpu.streaming import DebeziumJsonConsumer
    from lakesoul_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    catalog = LakeSoulCatalog(wh)
    consumer = DebeziumJsonConsumer(catalog, primary_keys={"user_features": ["uid"]})

    def ev(op, row):
        return {"op": op, "after": row, "source": {"table": "user_features"}}

    # epoch 1: initial facts
    rng = np.random.default_rng(0)
    for uid in range(32):
        consumer.consume(
            ev("c", {"uid": uid, "clicks": int(rng.integers(0, 50)),
                     "spend": round(float(rng.gamma(2.0, 5.0)), 2)})
        )
    consumer.checkpoint(1)

    table = catalog.table("user_features")
    cursors = catalog.client.init_follow_cursors("user_features", now_millis())
    feature_bank = jnp.zeros((32, 2))  # device-resident feature matrix

    stop = threading.Event()
    updates = {"rows": 0}

    cdc_col = table.info.cdc_column

    def serve():
        nonlocal feature_bank
        # with_cdc_deletes: consume row KINDS, not just surviving rows — a
        # delete must CLEAR its uid's features, not leave them stale
        for batch in table.scan().with_cdc_deletes().follow(
            poll_interval=0.05, stop_event=stop, cursors=cursors
        ):
            uids = np.asarray(batch.column("uid"))
            kinds = np.asarray(batch.column(cdc_col).to_pylist(), dtype=object)
            feats = np.stack(
                [
                    np.asarray(batch.column("clicks"), dtype=np.float32),
                    np.asarray(batch.column("spend"), dtype=np.float32),
                ],
                axis=1,
            )
            # grow the bank for new uids (jax .at[] would silently clamp
            # out-of-range indices onto the last row)
            top = int(uids.max()) + 1
            if top > feature_bank.shape[0]:
                pad = jnp.zeros((top - feature_bank.shape[0], 2))
                feature_bank = jnp.concatenate([feature_bank, pad])
            live = kinds != "delete"
            if live.any():
                feature_bank = feature_bank.at[uids[live]].set(jnp.asarray(feats[live]))
            if (~live).any():
                feature_bank = feature_bank.at[uids[~live]].set(0.0)
            updates["rows"] += len(uids)
            if updates["rows"] >= 9:
                stop.set()

    t = threading.Thread(target=serve, daemon=True)
    t.start()

    # epoch 2: live updates + a delete arrive while the consumer runs
    for uid in (3, 7, 11, 19):
        consumer.consume(ev("u", {"uid": uid, "clicks": 999, "spend": 123.45}))
    for uid in (40, 41, 42, 43):
        consumer.consume(ev("c", {"uid": uid, "clicks": 1, "spend": 1.0}))
    consumer.consume(
        {"op": "d", "before": {"uid": 5, "clicks": 0, "spend": 0.0},
         "source": {"table": "user_features"}}
    )
    consumer.checkpoint(2)
    t.join(timeout=20)
    stop.set()

    # the stream position survives restarts alongside any app checkpoint
    state = follow_cursors_to_json(cursors)
    assert follow_cursors_from_json(state).keys() == cursors.keys()

    hot = float(feature_bank[3, 0])
    gone = float(feature_bank[5, 0])
    print(f"online features updated: {updates['rows']} rows streamed,"
          f" uid=3 clicks={hot:.0f}, deleted uid=5 clicks={gone:.0f}")
    assert hot == 999.0, "live update did not reach the feature bank"
    assert gone == 0.0, "delete did not clear the feature bank"


if __name__ == "__main__":
    main()
