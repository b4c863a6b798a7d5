"""BERT MLM training fed from a lakehouse text table (BASELINE.json config 3
in miniature): tokenized C4-style rows stored in a hash-bucketed table,
streamed through the sharded data plane into a dp/tp/sp-parallel train step
with ring attention.

Run (CPU mesh):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/bert_mlm_from_table.py
"""

from __future__ import annotations

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np
import pyarrow as pa


def main() -> None:
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from lakesoul_tpu import LakeSoulCatalog
    from lakesoul_tpu.models.bert import BertConfig
    from lakesoul_tpu.models.train import make_bert_train_state, make_bert_train_step
    from lakesoul_tpu.parallel.mesh import make_mesh
    from lakesoul_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    plan = make_mesh(jax.devices())
    print(f"mesh: dp={plan.dp} tp={plan.tp} sp={plan.sp}")

    cfg = BertConfig(
        vocab_size=512,
        hidden=64 * plan.tp,
        layers=2,
        heads=2 * plan.tp,
        ff=128 * plan.tp,
        max_len=32 * max(plan.sp, 1),
    )
    T = cfg.max_len
    B = 2 * plan.dp

    # "C4" rows: pre-tokenized sequences in a PK table
    catalog = LakeSoulCatalog(tempfile.mkdtemp(prefix="lakesoul_c4_"))
    rng = np.random.default_rng(0)
    n_docs = 64
    tokens = rng.integers(4, cfg.vocab_size, (n_docs, T)).astype(np.int32)
    schema = pa.schema(
        [("doc_id", pa.int64()), ("tokens", pa.list_(pa.int32(), T))]
    )
    t = catalog.create_table("c4", schema, primary_keys=["doc_id"], hash_bucket_num=4)
    t.write_arrow(
        pa.table(
            {
                "doc_id": np.arange(n_docs),
                "tokens": pa.FixedSizeListArray.from_arrays(tokens.reshape(-1), T),
            },
            schema=schema,
        )
    )

    params, opt_state, tx, shardings = make_bert_train_state(cfg, plan, lr=1e-3)
    step = make_bert_train_step(cfg, plan, tx, shardings)
    batch_sharding = NamedSharding(plan.mesh, P("dp", "sp"))

    def transform(b):
        ids = np.stack(b["tokens"])  # [rows, T]
        labels = np.full_like(ids, -100)
        mask_pos = rng.random(ids.shape) < 0.15
        labels[mask_pos] = ids[mask_pos]
        masked = ids.copy()
        masked[mask_pos] = 3  # [MASK]
        return {
            "ids": masked.astype(np.int32),
            "labels": labels.astype(np.int32),
            "mask": np.ones_like(ids, dtype=bool),
        }

    it = t.scan().batch_size(B).to_jax_iter(transform=transform, sharding=batch_sharding)
    losses = []
    for i, batch in enumerate(it):
        params, opt_state, loss = step(
            params, opt_state, batch["ids"], batch["labels"], batch["mask"]
        )
        losses.append(float(loss))
    print(f"{len(losses)} steps, loss {losses[0]:.3f} -> {losses[-1]:.3f}")


if __name__ == "__main__":
    main()
