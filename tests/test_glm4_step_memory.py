"""The batch of ``glm47_flash_clm_pk.seq8k_mor_stream`` is checkable: XLA's
memory analysis of the cell's whole train step, at the published widths and
the held cut, compiled for a described v5e (no chip: the TPU's compiler is
installed here).

The configuration file's rule (``assumed.per_chip_batch``): the largest of 4,
3, 2, 1 rows of 8,192 tokens that leaves at least 0.5 GB of a v5e's 15.75.
One row reads 13.33 GB and is taken (13.20 before the flash kernels wrote the
attention output token-major, 12.98 while the experts ran a tile a loop turn:
the grouped kernels read ``x`` and ``dy`` from float32 stagings, 0.07 GB each
at one row, and every pass holds a segment's operands); two read 15.55 and are
refused (15.28 while the experts' backward loop held row buffers for
``parallel/moe.py: expert_dw`` only where an expert's rows filled two tiles;
15.52 before that).  The other two causal-LM cells' steps are held to their
analyses beside it: each pass of the experts holds its stagings and a
segment's operands, 16 tiles sized by the shapes (``parallel/moe.py:
_segment``), and no step may pass 15.0 GB.  A file of its own: the suite runs ``--dist loadfile`` and
each case compiles for most of a minute.
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


def _step_gb(rows: int, configuration: str = "glm47_flash_clm_pk") -> dict:
    """XLA's memory analysis, in GB, of the step ``make_lm_train_step`` jits
    (``_adamw_step`` over ``cfg.loss``, state donated) for one v5e."""
    import optax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from lakesoul_tpu.models import glm4_moe_lite, lfm2_moe, qwen3_next, train
    from lakesoul_tpu.utils import platform

    config = _bench_file("configs", configuration)
    m = config["model"]
    family = {
        "glm47_flash_clm_pk": glm4_moe_lite.Glm4MoeLiteConfig, "lfm2_8b_a1b_clm_pk": lfm2_moe.Lfm2MoeConfig,
        "qwen3_next_a3b_clm_pk": qwen3_next.Qwen3NextConfig,
    }[configuration]
    cfg = family.from_published(
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
        found = jax.jit(step, donate_argnums=(0, 1)).lower(*state, ids, ids).compile().memory_analysis()
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
    """One row fits with room (13.33 GB: 8.478 of arguments, 4.575 of
    scratch, 0.280 of code; 13.20 with 4.487 of scratch while the attention
    output left the kernels heads first and copies laid it out for ``w_o``);
    two leave 0.20 GB (15.55: 6.745 of scratch, 0.330 of code), under the
    rule's 0.5.  The cell runs the batch the rule gives."""
    cell = _bench_file("workloads", "glm47_flash_clm_pk.seq8k_mor_stream")
    gb = _step_gb(rows)
    assert gb["arguments"] == pytest.approx(8.478, abs=0.005)  # 706.5 M parameters x 12 B, the biases, the counts
    assert gb["outputs_not_aliased"] < 0.001                    # the state is donated
    fits = gb["total"] <= CHIP_GB - FREE_GB
    if rows == 1:
        assert gb["total"] == pytest.approx(13.33, abs=0.15) and fits, gb
    else:
        assert gb["total"] == pytest.approx(15.55, abs=0.15) and not fits, gb
    assert (rows <= cell["per_chip_batch"]) == fits


@pytest.mark.parametrize("configuration, total", [("qwen3_next_a3b_clm_pk", 13.82), ("lfm2_8b_a1b_clm_pk", 12.03)])
def test_the_other_causal_lm_steps_stay_where_their_analyses_stand(configuration, total):
    """The Qwen3-Next step at its cell's 2 rows (7.508 GB of arguments, 6.098
    of scratch: the fullest of the three) and the LFM2 step at its 4 (6.094
    and 5.734: its experts' backward pass holds the float32 stagings of ``x``
    and ``dy``, 0.27 GB each, and a segment's operands, 0.16 GB; 12.00 with 64
    tiles of row buffers, 0.62 GB, and a tile's float32 products), under the
    15.0 GB no step may pass."""
    cell = _bench_file("workloads", configuration + ".seq8k_mor_stream")
    gb = _step_gb(cell["per_chip_batch"], configuration)
    assert gb["outputs_not_aliased"] < 0.001
    assert gb["total"] == pytest.approx(total, abs=0.15) and gb["total"] < 15.0, gb
