"""ResNet training fed from an image table (BASELINE.json config 2 in
miniature): encoded image tensors stored in a hash-bucketed lakehouse table,
sharded over the data-parallel axis and streamed into a jitted ResNet train
step.

Run (CPU mesh):
  JAX_PLATFORMS=cpu XLA_FLAGS=--xla_force_host_platform_device_count=8 \
    python examples/resnet_from_table.py
"""

from __future__ import annotations

import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
import numpy as np
import pyarrow as pa

IMG = 32  # miniature "ImageNet" resolution
NUM_CLASSES = 10


def main() -> None:
    import jax
    import optax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from lakesoul_tpu import LakeSoulCatalog
    from lakesoul_tpu.models.resnet import ResNetConfig, init_resnet_params
    from lakesoul_tpu.models.train import make_resnet_train_step
    from lakesoul_tpu.parallel.mesh import make_mesh
    from lakesoul_tpu.utils.compile_cache import configure_compile_cache

    configure_compile_cache()
    plan = make_mesh(jax.devices())
    B = 4 * plan.dp  # data-parallel batch

    # image table: uint8-encoded pixels as fixed-size lists + labels
    catalog = LakeSoulCatalog(tempfile.mkdtemp(prefix="lakesoul_imgs_"))
    rng = np.random.default_rng(0)
    n = 128
    pixels = rng.integers(0, 256, (n, IMG * IMG * 3), dtype=np.uint8)
    schema = pa.schema(
        [
            ("image_id", pa.int64()),
            ("pixels", pa.list_(pa.uint8(), IMG * IMG * 3)),
            ("label", pa.int32()),
        ]
    )
    t = catalog.create_table("imagenet_mini", schema, primary_keys=["image_id"],
                             hash_bucket_num=4)
    t.write_arrow(
        pa.table(
            {
                "image_id": np.arange(n),
                "pixels": pa.FixedSizeListArray.from_arrays(pixels.reshape(-1), IMG * IMG * 3),
                "label": rng.integers(0, NUM_CLASSES, n).astype(np.int32),
            },
            schema=schema,
        )
    )

    cfg = ResNetConfig(num_classes=NUM_CLASSES, width=8, dtype="float32")
    params = init_resnet_params(cfg, jax.random.key(0))
    tx = optax.sgd(0.05)
    opt_state = tx.init(params)
    step = make_resnet_train_step(cfg, tx, plan)
    data_sharding = NamedSharding(plan.mesh, P("dp"))

    def transform(b):
        imgs = np.stack(b["pixels"]).reshape(-1, IMG, IMG, 3).astype(np.float32) / 255.0
        return {"x": imgs, "y": b["label"].astype(np.int32)}

    losses = []
    # auto_shard: on a multi-host pod each process reads only its scan units
    it = (
        t.scan().auto_shard().batch_size(B)
        .to_jax_iter(transform=transform, sharding=data_sharding)
    )
    for batch in it:
        params, opt_state, loss = step(params, opt_state, batch["x"], batch["y"])
        losses.append(float(loss))
    print(f"{len(losses)} steps, loss {losses[0]:.3f} -> {losses[-1]:.3f}")


if __name__ == "__main__":
    main()
