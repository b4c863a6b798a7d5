"""Titanic-style e2e example (BASELINE.json config 1): hash-partitioned
table → LakeSoulScan → to_jax_iter → 2-layer MLP train loop.

Run: python examples/titanic_mlp.py [--warehouse DIR]
"""

from __future__ import annotations

import argparse
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import numpy as np
import pyarrow as pa


def make_synthetic_titanic(n: int = 2000, seed: int = 0) -> pa.Table:
    """Synthetic passengers with a survival rule the MLP can learn."""
    rng = np.random.default_rng(seed)
    pclass = rng.integers(1, 4, n).astype(np.int32)
    age = np.clip(rng.normal(30, 14, n), 1, 80).astype(np.float32)
    fare = (rng.gamma(2.0, 15.0, n) * (4 - pclass)).astype(np.float32)
    sex = rng.integers(0, 2, n).astype(np.int32)  # 1 = female
    logits = 1.8 * sex - 0.9 * (pclass - 2) - 0.02 * (age - 30) + 0.01 * fare
    survived = (rng.random(n) < 1 / (1 + np.exp(-logits))).astype(np.int32)
    return pa.table(
        {
            "passenger_id": np.arange(n, dtype=np.int64),
            "pclass": pclass,
            "age": age,
            "fare": fare,
            "sex": sex,
            "survived": survived,
        }
    )


def main() -> None:
    parser = argparse.ArgumentParser()
    parser.add_argument("--warehouse", default=None)
    parser.add_argument("--epochs", type=int, default=5)
    args = parser.parse_args()

    import jax
    import optax

    from lakesoul_tpu import LakeSoulCatalog
    from lakesoul_tpu.models.mlp import init_mlp_params, mlp_forward
    from lakesoul_tpu.models.train import make_mlp_train_step
    from lakesoul_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    warehouse = args.warehouse or tempfile.mkdtemp(prefix="lakesoul_titanic_")
    catalog = LakeSoulCatalog(warehouse)

    data = make_synthetic_titanic()
    if not catalog.table_exists("titanic"):
        t = catalog.create_table(
            "titanic", data.schema, primary_keys=["passenger_id"], hash_bucket_num=4
        )
        t.write_arrow(data)
        # a later correction wave exercises merge-on-read, like re-ingests do
        t.upsert(data.slice(0, 200))
    else:
        t = catalog.table("titanic")

    feature_cols = ["pclass", "age", "fare", "sex"]

    def transform(b):
        x = np.stack([b[c].astype(np.float32) for c in feature_cols], axis=1)
        x = (x - x.mean(0)) / (x.std(0) + 1e-6)
        return {"x": x, "y": b["survived"].astype(np.int32)}

    params = init_mlp_params(jax.random.key(0), len(feature_cols), hidden=64)
    tx = optax.adam(1e-2)
    opt_state = tx.init(params)
    step, _ = make_mlp_train_step(tx)

    for epoch in range(args.epochs):
        losses = []
        scan = t.scan().batch_size(256).auto_shard()
        for batch in scan.to_jax_iter(transform=transform, drop_remainder=False):
            params, opt_state, loss = step(params, opt_state, batch["x"], batch["y"])
            losses.append(float(loss))
        print(f"epoch {epoch}: loss={np.mean(losses):.4f}")

    # final train accuracy
    full = transform(
        {c: data.column(c).to_numpy(zero_copy_only=False) for c in feature_cols + ["survived"]}
    )
    import jax.numpy as jnp

    preds = np.asarray(jnp.argmax(mlp_forward(params, jnp.asarray(full["x"])), axis=1))
    acc = (preds == full["y"]).mean()
    print(f"train accuracy: {acc:.3f}")
    assert acc > 0.7, "model failed to learn"


if __name__ == "__main__":
    main()
