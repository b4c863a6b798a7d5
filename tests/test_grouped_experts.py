"""The held experts' grouped kernels: ``parallel/moe.py: experts_fwd`` and
``experts_bwd`` (Pallas kernels whose grid walks a segment's tiles, gather
their own rows and add their rows to the sums, here in the interpreter)
against the tile loop, their ``jnp`` twin, on shapes of whole 128-lane tiles:
the forward pass and the six gradients (``dx``, the routing weights', the
three matrices'; the plan's is none) bit for bit; and the rule that says which
shapes take them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lakesoul_tpu.parallel import moe

TILE = 128
H = F = 128
COUNT = 3  # held experts, of N_EXPERTS, from FIRST on
FIRST = 1


def _routing(loads, n: int, k: int, seed: int):
    """``top_e`` [n, k] in which held expert ``j`` has ``loads[j]`` tokens and no
    token takes an expert twice: expert ``j`` has column ``j`` to itself, and
    every other slot names an expert that is not held."""
    assert len(loads) == COUNT <= k and max(loads) <= n
    rng = np.random.default_rng(seed)
    top_e = np.tile(FIRST + COUNT + np.arange(k, dtype=np.int32), (n, 1))
    for j, load in enumerate(loads):
        top_e[rng.permutation(n)[:load], j] = FIRST + j
    return jnp.asarray(top_e)


def _layer(loads, n, k, dtype, tile=TILE, seed=0):
    keys = jax.random.split(jax.random.key(seed + sum(loads)), 6)
    x = jax.random.normal(keys[0], (n, H)).astype(dtype)
    w = jax.nn.softmax(jax.random.normal(keys[1], (n, k)))
    wg, wu, wd = (0.1 * jax.random.normal(key, (COUNT, *shape)) for key, shape in
                  zip(keys[2:5], ((H, F), (H, F), (F, H))))
    top_e = _routing(loads, n, k, seed)
    local = top_e.reshape(-1) - FIRST
    local = jnp.where((local >= 0) & (local < COUNT), local, COUNT)
    return x, w, moe._tile_plan(local, COUNT, tile), wg, wu, wd, jax.random.normal(keys[5], (n, H))


def _passes(operands, weigh, span, grouped, tile=TILE):
    """Forward result and the gradients through the kernels at ``span`` tiles a
    segment (``grouped``), or through the tile loop with its weight-gradient
    sums in the loop (``span`` 0) or through ``expert_dw`` a segment."""
    def program(x, w, wg, wu, wd):
        y = moe._held_experts(x, w, operands[2], wg, wu, wd, tile, span, grouped)
        return jnp.sum(weigh * y.astype(jnp.float32)), y

    x, w, _, wg, wu, wd = operands
    (_, y), grads = jax.jit(jax.value_and_grad(program, argnums=(0, 1, 2, 3, 4), has_aux=True))(x, w, wg, wu, wd)
    return y, grads


def _assert_same_bits(got, want):
    np.testing.assert_array_equal(got[0], want[0], err_msg="y")
    for name, a, b in zip(("dx", "dw", "dw_gate", "dw_up", "dw_down"), got[1], want[1], strict=True):
        np.testing.assert_array_equal(a, b, err_msg=name)


CASES = {  # rows of each held expert, top_k, tiles a segment
    "an-expert-with-no-row": ([130, 0, 128], 4, 4),
    "an-expert-whose-rows-end-on-a-tile": ([128, 256, 100], 4, 4),
    "a-last-tile-of-one-row": ([129, 1, 257], 4, 4),
    "an-expert-split-by-a-segments-end": ([384, 256, 60], 4, 2),
    "every-assignment-on-one-held-expert": ([0, 512, 0], 4, 3),
    "no-assignment-held": ([0, 0, 0], 4, 4),
    "a-run-that-ends-with-its-segment": ([256, 128, 128], 4, 2),
    "one-tile-a-segment": ([100, 129, 7], 4, 1),
    "top-8": ([300, 17, 129], 8, 4),
    "top-10": ([511, 1, 256], 10, 4),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16", "float32"])
@pytest.mark.parametrize("case", CASES)
def test_the_grouped_kernels_equal_the_tile_loop_bit_for_bit(case, dtype):
    """Both of the loop's ways with the weight-gradient sums (``_dw_span``: a
    tile's products added in place, or ``expert_dw`` over a segment's row
    buffers) are the kernels' twin."""
    loads, k, span = CASES[case]
    *operands, weigh = _layer(loads, 512, k, dtype)
    assert moe._grouped(H, F, TILE, jnp.dtype(dtype).itemsize)
    with jax.default_matmul_precision("highest"):
        y, grads = _passes(operands, weigh, span, True)
        for dw_span in (0, 3):  # the sums in the loop; by expert_dw, three tiles a segment
            _assert_same_bits((y, grads), _passes(operands, weigh, dw_span, False))
    if not sum(loads):
        assert not float(jnp.abs(y).max()) and all(not float(jnp.abs(g).max()) for g in grads)
    else:
        assert all(float(jnp.abs(g.astype(jnp.float32)).max()) > 0 for g in grads)


@pytest.mark.parametrize("tile, rows", [(256, 128), (512, 256), (512, 128), (384, 128)],
                         ids=lambda v: str(v))
def test_blocks_past_an_experts_last_row_are_skipped_and_leave_zeros(monkeypatch, tile, rows):
    """A tile goes a block of rows a grid step; the blocks past its expert's
    last row are not multiplied, and the layer is the loop's bit for bit: the
    backward kernel leaves zeros there for ``expert_dw``, which multiplies
    whole tiles."""
    monkeypatch.setattr(moe, "GROUP_ROWS", rows)
    loads, k = [tile + 1, 0, 2 * tile - rows], 4  # a last tile of one row; a last tile short of its last block
    *operands, weigh = _layer(loads, 2 * tile, k, jnp.bfloat16, tile=tile)
    assert moe._group_rows(tile) == 128 if tile % rows else rows
    with jax.default_matmul_precision("highest"):
        _assert_same_bits(_passes(operands, weigh, 3, True, tile), _passes(operands, weigh, 0, False, tile))
    # the kernel alone: the operands of a block that does not run are zeros, what lies past the run is not written
    block = moe._group_rows(tile)
    keys = jax.random.split(jax.random.key(tile), 4)
    n = 64 + rows  # its own shapes: the jitted kernels read GROUP_ROWS when they are traced
    (x32, dy32), zeros = moe.stage_rows(tuple(jax.random.normal(key, (n, H)) for key in keys[:2]), interpret=True)
    assert x32.shape == zeros.shape == (n, 1, H) and not float(jnp.abs(zeros).max())
    wg, wu, wd = operands[3:6]
    counts = jnp.asarray([1, block + 1, 0], jnp.int32)
    holds = jnp.arange(3 * tile) % tile < jnp.repeat(counts, tile)
    tok = jnp.arange(3 * tile) % n  # a slot without an assignment names some token, as the plan's clipped index does
    wt = jnp.where(holds, 0.5, 0.0)
    dx, *held, dw = moe.experts_bwd(zeros, x32, dy32, tok, wt, wg, wu, wd, jnp.asarray([0, 2, COUNT], jnp.int32), counts, interpret=True)
    for m in held:
        assert not float(jnp.abs(m[block:tile]).max())
        assert 2 * block == tile or not float(jnp.abs(m[tile + 2 * block:2 * tile]).max())
        assert float(jnp.abs(m[:block].astype(jnp.float32)).max()) > 0
    assert int(jnp.sum(jnp.any(dx != 0, axis=(1, 2)))) <= 1 + block + 1  # only the rows that hold an assignment join the sums


@pytest.mark.parametrize("shapes, span", [
    ((4 * 8192 * 4, 8, (2048, 1792), 512), 16),   # LFM2 cell
    ((2 * 8192 * 10, 32, (2048, 512), 512), 16),  # Qwen3-Next cell
    ((8192 * 4, 8, (2048, 1536), 512), 16),       # GLM-4.7-Flash cell
    ((2 * 8192 * 8, 16, (2048, 1024), 512), 16),  # Trinity-Mini cell
    ((1024, 3, (128, 128), 128), 11),             # no more tiles than the assignments can fill
    ((2048, 3, (128, 16), 128), 0), ((2048, 3, (96, 128), 128), 0), ((2048, 3, (128, 128), 16), 0),  # not whole lane tiles
    ((1 << 17, 8, (4096, 4096), 512), 0),         # an expert's matrices twice: 201 MB, no VMEM for them
    ((1 << 17, 8, (2048, 1792, 4), 512), 0),      # float32 experts of the LFM2 cell's widths: 88 MB
], ids=["lfm2", "qwen3-next", "glm-4.7-flash", "trinity-mini", "short", "narrow-f", "narrow-h", "narrow-tile",
        "wide-experts", "float32-experts"])
def test_the_kernels_take_whole_lane_tiles_and_experts_that_fit_vmem_twice(shapes, span):
    assignments, count, (h, f, *itemsize), tile = shapes
    assert moe._segment(assignments, count, (h, f), tile, *itemsize) == span
    if span:
        assert 2 * 3 * h * f * 2 <= moe.GROUP_WEIGHT_BYTES < moe.GROUP_VMEM_LIMIT < 128 * 2**20
        assert moe._dw_blocks(h, f, tile) is not None  # the sums of whatever the kernels take go through expert_dw


def test_counters_of_a_layer_the_kernels_take_and_of_one_they_do_not(monkeypatch):
    """``moe_grouped``: the held assignments where the layer's shapes go to the
    kernels, 0 where the loop runs; ``moe_tile_rows`` counts whole tiles in
    the loop and the row blocks that run in the kernels."""
    monkeypatch.setattr(moe, "GROUP_ROWS", 128)
    loads = [130, 0, 257]
    x, w, _, wg, wu, wd, _ = _layer(loads, 512, 4, jnp.float32)
    top_e = _routing(loads, 512, 4, 0)
    p = {"w_gate": wg, "w_up": wu, "w_down": wd}
    _, counts = moe.held_experts(x, top_e, w, p, n_experts=16, held=(FIRST, COUNT), tile=256)
    assert int(counts["moe_tile_rows"]) == 5 * 128 and int(counts["moe_grouped"]) == int(counts["moe_held"]) == sum(loads)
    assert int(counts["moe_dw_writes"]) == 2
    narrow = {name: m[:, :64, :64] for name, m in p.items()}
    _, counts = moe.held_experts(x[:, :64], top_e, w, narrow, n_experts=16, held=(FIRST, COUNT), tile=256)
    assert int(counts["moe_tile_rows"]) == 3 * 256 and int(counts["moe_grouped"]) == 0
    assert int(counts["moe_dw_writes"]) == 3


def test_the_kernels_run_a_shard_of_the_batch_under_shard_map():
    """Two shards of the tokens, each through its own plan and its own calls
    of the kernels: the layer's output and gradients as on one device (an
    expert's rows fall into other tiles, so sums differ in their last bits),
    the counters summed over the shards."""
    from jax.sharding import Mesh, NamedSharding
    from jax.sharding import PartitionSpec as P

    loads, k = [300, 17, 129], 4
    x, w, _, wg, wu, wd, weigh = _layer(loads, 512, k, jnp.float32)
    top_e = _routing(loads, 512, k, 0)
    p = {"w_gate": wg, "w_up": wu, "w_down": wd}
    sharding = NamedSharding(Mesh(np.asarray(jax.devices()[:2]), ("dp",)), P("dp"))

    def program(x, w, p, batch_sharding):
        y, counts = moe.held_experts(x, top_e, w, p, n_experts=16, held=(FIRST, COUNT), tile=TILE, batch_sharding=batch_sharding)
        return jnp.sum(weigh * y), counts

    with jax.default_matmul_precision("highest"):
        (_, counts), grads = jax.jit(jax.value_and_grad(program, argnums=(0, 1, 2), has_aux=True), static_argnums=3)(x, w, p, sharding)
        (_, alone), want = jax.jit(jax.value_and_grad(program, argnums=(0, 1, 2), has_aux=True), static_argnums=3)(x, w, p, None)
    for got, ref in zip(jax.tree.leaves(grads), jax.tree.leaves(want), strict=True):
        assert float(jnp.linalg.norm(got - ref) / jnp.linalg.norm(ref)) < 1e-5
    per_shard = [np.bincount(np.asarray(top_e[rows]).ravel(), minlength=16)[FIRST:FIRST + COUNT] for rows in (slice(0, 256), slice(256, 512))]
    tiles = sum(-(-int(load) // TILE) for shard in per_shard for load in shard)
    assert int(counts["moe_tile_rows"]) == tiles * TILE
    assert int(counts["moe_grouped"]) == int(counts["moe_held"]) == int(alone["moe_held"]) == sum(loads)
