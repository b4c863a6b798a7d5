"""The batch of ``ouro_2_6b_clm_pk.seq8k_mor_stream`` is checkable: XLA's
memory analysis of the cell's whole train step, at the published widths and
the held cut (six layers run four times), compiled for a described v5e (no
chip: the TPU's compiler is installed here).

The configuration file's rule (``assumed.per_chip_batch``, PR 36's): the
largest of 2, 1 rows of 8,192 tokens that leaves at least 0.5 GB of a v5e's
15.75.  One row reads 14.49 GB and is taken; two read 18.86 and do not fit the
chip at all (15.02 and 19.32 until PR 47, while a tile of the loss held the
float32 logits ``[2736, 49152]`` and the log-softmax written out beside them:
``models/loss_tile.py`` writes a bfloat16 cotangent and no log-softmax).  The
compile also holds the attention kernels at 16 key-value heads of one query
head at head 128, the operand kernels WITHOUT head norms and the loss tile's
kernel at a vocabulary of 49,152 to Mosaic's rules, the first two inside the
pass loop's body.  A file of its own: the
suite runs ``--dist loadfile`` and each case compiles for most of a minute.
"""

from __future__ import annotations

import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks", "chip")
CHIP_GB, FREE_GB = 15.75, 0.5  # a v5e's usable memory; what the rule leaves free


def _bench_file(folder: str, name: str) -> dict:
    with open(os.path.join(BENCH, folder, name + ".json")) as f:
        return json.load(f)


def _step_gb(rows: int) -> dict:
    """XLA's memory analysis, in GB, of the step ``make_lm_train_step`` jits
    (``_adamw_step`` over ``cfg.loss``, state donated) for one v5e."""
    import optax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from lakesoul_tpu.models import ouro, train
    from lakesoul_tpu.utils import platform

    config = _bench_file("configs", "ouro_2_6b_clm_pk")
    m = config["model"]
    cfg = ouro.OuroConfig.from_published(m, dtype=m["compute_dtype"])
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    one_chip = SingleDeviceSharding(topo.devices[0])
    tx = optax.adamw(config["learning_rate"])

    def init(seed):
        params = cfg.init(jax.random.key(seed))
        return params, tx.init(params)

    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(init, np.uint32(0)),
    )
    ids = jax.ShapeDtypeStruct((rows, config["table"]["seq"]), jnp.int32, sharding=one_chip)
    adamw_step = train._adamw_step(cfg.loss, tx)

    def step(params, opt_state, ids, labels):
        # ``_CountedStep`` reads the integer counts and drops the float terms the comparison reads and the
        # Python integers it adds on the host (the layer passes, the kernels' grid steps: no operation of the step)
        params, opt_state, loss, counts = adamw_step(params, opt_state, ids, labels)
        return params, opt_state, loss, {k: v for k, v in counts.items() if getattr(v, "dtype", None) == jnp.int32}

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(platform, "on_tpu", lambda: True)  # the branch the chip takes
        compiled = jax.jit(step, donate_argnums=(0, 1)).lower(*state, ids, ids).compile()
    found = compiled.memory_analysis()
    gb = {
        "arguments": found.argument_size_in_bytes / 1e9,
        "scratch": found.temp_size_in_bytes / 1e9,
        "code": found.generated_code_size_in_bytes / 1e9,
        "outputs_not_aliased": (found.output_size_in_bytes - found.alias_size_in_bytes) / 1e9,
    }
    gb["total"] = sum(gb.values())
    return gb


@pytest.mark.parametrize("rows", [1, 2])
def test_the_cells_batch_is_the_largest_that_leaves_half_a_gigabyte(rows):
    """One row fits with room (14.49 GB: 6.116 of arguments, 8.211 of scratch,
    0.158 of code: 1.26 GB free); two do not fit the chip (18.86: 12.70 of
    scratch).  The cell runs the batch the rule gives."""
    cell = _bench_file("workloads", "ouro_2_6b_clm_pk.seq8k_mor_stream")
    gb = _step_gb(rows)
    assert gb["arguments"] == pytest.approx(6.116, abs=0.005)  # 509.7 M parameters x 12 B and the counts
    assert gb["outputs_not_aliased"] < 0.001                    # the state is donated
    fits = gb["total"] <= CHIP_GB - FREE_GB
    if rows == 1:
        assert gb["total"] == pytest.approx(14.49, abs=0.15) and fits, gb
    else:
        assert gb["total"] == pytest.approx(18.86, abs=0.3) and CHIP_GB < gb["total"] and not fits, gb
    assert (rows <= cell["per_chip_batch"]) == fits
