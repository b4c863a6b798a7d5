"""The batch of ``trinity_mini_clm_pk.seq8k_mor_stream`` is checkable: XLA's
memory analysis of the cell's whole train step, at the published widths and
the held cut, compiled for a described v5e (no chip: the TPU's compiler is
installed here).

The configuration file's rule (``assumed.per_chip_batch``, PR 36's): the
largest of 4, 3, 2, 1 rows of 8,192 tokens that leaves at least 0.5 GB of a
v5e's 15.75.  Two rows read 14.22 GB and are taken; three read 15.88 and are
refused (they do not fit the chip at all).  14.43 and 15.83 while the experts'
backward loop held 64 tiles of row buffers and a tile's float32 products
(``parallel/moe.py``: a segment of the grouped kernels is 16 tiles, beside the
float32 stagings of ``x`` and ``dy``, 0.13 GB each a row).  Before the operand kernels
(``attention.py: _operand_tiles``) the two read 14.86 and 16.01: the float32
``[8192, 32, 128]`` temporaries of the head norms and the rotary went, and
nothing new is kept across the mixers' checkpoint.  14.26 while the gate's
product ran over ``[.., heads, D]`` arrays: over ``[.., heads x D]``, the
layout the flash kernels write the output in, XLA's schedule holds 0.18 GB
more of the gate's temporaries at once (the chip's own peak, the benchmark's
``peak_hbm_gb``, fell by 6 MB with the same change).  The compile also holds
both attention kernels, with and without the window, and both operand kernels, with
and without positions, to Mosaic's rules at the cell's shapes (groups of 8 at
head 128, 8,192 tokens).  A file of its own: the
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

    from lakesoul_tpu.models import afmoe, train
    from lakesoul_tpu.utils import platform

    config = _bench_file("configs", "trinity_mini_clm_pk")
    m = config["model"]
    cfg = afmoe.AfmoeConfig.from_published(
        m, experts_held=(m["first_expert_held"], m["num_experts_held"]), dtype=m["compute_dtype"]
    )
    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    one_chip = SingleDeviceSharding(topo.devices[0])
    tx = optax.adamw(config["learning_rate"])

    def init(seed):
        params = cfg.init(jax.random.key(seed))
        return params, tx.init(train._split_buffers(params)[0])

    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(init, np.uint32(0)),
    )
    ids = jax.ShapeDtypeStruct((rows, config["table"]["seq"]), jnp.int32, sharding=one_chip)
    adamw_step = train._adamw_step(cfg.loss, tx)

    def step(params, opt_state, ids, labels):
        # ``_CountedStep`` reads the integer counts and drops the two loss terms the comparison reads and the
        # Python integers it adds on the host (the attention kernels' grid steps: no operation of the step)
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


@pytest.mark.parametrize("rows", [2, 3])
def test_the_cells_batch_is_the_largest_that_leaves_half_a_gigabyte(rows):
    """Two rows fit with room (14.22 GB: 8.466 of arguments, 5.514 of scratch,
    0.239 of code: 1.53 GB free); three do not fit the chip (15.88: 7.172 of
    scratch).  The cell runs the batch the rule gives."""
    cell = _bench_file("workloads", "trinity_mini_clm_pk.seq8k_mor_stream")
    gb = _step_gb(rows)
    assert gb["arguments"] == pytest.approx(8.466, abs=0.005)  # 705.5 M parameters x 12 B, the biases, the counts
    assert gb["outputs_not_aliased"] < 0.001                    # the state is donated
    fits = gb["total"] <= CHIP_GB - FREE_GB
    if rows == 2:
        assert gb["total"] == pytest.approx(14.22, abs=0.15) and fits, gb
    else:
        assert gb["total"] == pytest.approx(15.88, abs=0.15) and CHIP_GB < gb["total"] and not fits, gb
    assert (rows <= cell["per_chip_batch"]) == fits
