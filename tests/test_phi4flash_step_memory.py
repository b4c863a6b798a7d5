"""The batch of ``phi4_mini_flash_clm_pk.seq8k_mor_stream`` is checkable:
XLA's memory analysis of the cell's whole train step, at the published widths
and the held cut, compiled for a described v5e (no chip: the TPU's compiler is
installed here).

The configuration file's rule (``assumed.per_chip_batch``, PR 36's): the
largest of 4, 3, 2, 1 rows of 8,192 tokens that leaves at least 0.5 GB of a
v5e's 15.75.  When the cell was admitted (PR 50) one row read 13.11 GB and
was taken; two read 16.35 and did not fit the chip at all.  Since PR 51 (the
paired score maps computed once: two key-value heads a pair beside a value of
128, where four stood beside one of 64) one row reads 13.32 GB and two read
14.92: the second row costs 1.6 GB where it cost 3.2, and by this count alone
the rule would now take it, with 0.33 GB to spare over the half it leaves.
The cell keeps its one row: its file is the benchmark's, this count is one
program's and not what else the process holds on the chip, and no run on the
chip has tried two.

The compile also holds the scan kernels, the attention kernels at a head of
64 beside a value of 128 in groups of 2 with and without the 512 window, and
the loss tile's kernel to Mosaic's rules at the cell's shapes, and shows that no ``[T, E, N]`` tensor
of a row is a buffer of the compiled step.  A file of its own: the suite runs
``--dist loadfile`` and each case compiles for most of a minute.
"""

from __future__ import annotations

import json
import os
import re

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


def _compiled_step(rows: int):
    """The step ``make_lm_train_step`` jits (``_adamw_step`` over
    ``cfg.loss``, state donated), compiled for one v5e → (XLA's memory
    analysis in GB, the compiled module's text)."""
    import optax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from lakesoul_tpu.models import phi4flash, train
    from lakesoul_tpu.utils import platform

    config = _bench_file("configs", "phi4_mini_flash_clm_pk")
    m = config["model"]
    cfg = phi4flash.Phi4FlashConfig.from_published(m, dtype=m["compute_dtype"])
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
        # Python integers it adds on the host (the kernels' grid steps, the scan's rows: no operation of the step)
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
    return gb, compiled.as_text()


@pytest.mark.parametrize("rows", [1, 2])
def test_the_cells_batch_is_the_largest_that_leaves_half_a_gigabyte(rows):
    """One row fits with room (13.32 GB: 8.365 of arguments, 4.839 of scratch,
    0.111 of code: 2.43 GB free); two read 14.92 (6.422 of scratch): on the
    chip, and since PR 51 inside the rule's half gigabyte by this count, which
    the cell, at the one row it was admitted with, has not tried.  No array
    of the compiled step has the scan's ``T x E x N`` elements a row, and
    every attention kernel's call is a pair's two maps beside a value of 128."""
    cell = _bench_file("workloads", "phi4_mini_flash_clm_pk.seq8k_mor_stream")
    gb, text = _compiled_step(rows)
    assert gb["arguments"] == pytest.approx(8.366, abs=0.005)  # 697.1 M parameters x 12 B, lambda_init, the counts
    assert gb["outputs_not_aliased"] < 0.001                    # the state is donated
    fits = gb["total"] <= CHIP_GB - FREE_GB
    assert gb["total"] == pytest.approx({1: 13.32, 2: 14.92}[rows], abs=0.05) and fits, gb
    assert cell["per_chip_batch"] == 1
    # the largest array of the step: a row's discretised [T, E, N] tensor would be 8192 x 5120 x 16 elements
    seq, channels, states = 8192, 5120, 16
    largest = max(
        (np.prod([int(d) for d in dims.split(",") if d], dtype=np.int64) for dims in re.findall(r"(?:f32|bf16)\[([\d,]+)\]", text)),
    )
    assert largest < seq * channels * states // 2, largest
    kernels = set(re.findall(r"(selective_scan_fwd|selective_scan_bwd|flash_attention_fwd|flash_attention_bwd|loss_tile)", text))
    assert {"selective_scan_fwd", "selective_scan_bwd", "flash_attention_fwd", "flash_attention_bwd"} <= kernels
    flash = re.findall(r"%flash_attention_(fwd|bwd)\.\d+ = \((\w+\[[\d,]+\])", text)  # a call's first result: o, or dQ
    assert sorted(flash) == [("bwd", "bf16[20,2,8192,64]")] * 3 + [("fwd", "bf16[20,2,8192,128]")] * 3, flash
