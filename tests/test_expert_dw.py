"""The held experts' weight-gradient sums by expert: ``parallel/moe.py:
expert_dw`` (a Pallas kernel, here in the interpreter) against its ``jnp``
twin and a dense sum over each expert's rows, on shapes of whole 128-lane
tiles; and both passes of ``held_experts`` through the grouped kernels and it
against a dense layer expert by expert (``tests/test_grouped_experts.py``
holds the kernels to the tile loop bit for bit).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lakesoul_tpu.parallel import moe

TILE = 128


def assert_close(got, want, tol=2e-4):
    """Every leaf within ``tol`` of the reference by relative norm."""
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        err = float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))
        assert err < tol, f"{jax.tree_util.keystr(path)}: {err}"


def _rows_in_tiles(loads, a, b, dtype):
    """Operand rows as the backward loop leaves them: each expert's ``loads``
    rows in whole tiles, the right operand zeros in the slots past an expert's
    last row (the left one is not: a padded slot holds some token's row).
    → (lhs, rhs, the expert of each tile, which rows hold an assignment)."""
    tiles = [e for e, load in enumerate(loads) for _ in range(-(-load // TILE))]
    valid = np.concatenate([np.arange(-(-load // TILE) * TILE) < load for load in loads])
    keys = jax.random.split(jax.random.key(sum(loads)), 2)
    lhs = jax.random.normal(keys[0], (len(tiles) * TILE, a)).astype(dtype)
    rhs = (jax.random.normal(keys[1], (len(tiles) * TILE, b)) * valid[:, None]).astype(dtype)
    return lhs, rhs, np.asarray(tiles, np.int32), valid


def _by_segments(add, sums, lhs, rhs, tiles, span):
    """``add`` over segments of ``span`` tiles, as ``_held_experts_bwd`` calls
    it: the buffers hold ``span`` tiles, the last segment's run is shorter and
    what the buffers hold past it (here: the rows of the segment before) is
    not to be read."""
    count = sums.shape[0]
    for t0 in range(0, len(tiles), span):
        n = min(span, len(tiles) - t0)
        rows = slice(t0 * TILE, (t0 + span) * TILE)
        stale = span * TILE - lhs[rows].shape[0]
        held = [jnp.concatenate([m[rows], m[:stale] + 1]) for m in (lhs, rhs)]
        experts = np.concatenate([tiles[t0:t0 + n], np.full(span - n, count, np.int32)])
        sums = add(sums, *held, jnp.asarray(experts), jnp.int32(n))
    return sums


CASES = {  # rows of each held expert, tiles a segment
    "an-expert-with-no-row": ([130, 0, 128], 4),
    "an-expert-of-exactly-one-tile": ([128, 256], 4),
    "a-last-tile-part-padding": ([100, 129], 4),
    "every-assignment-on-one-expert": ([0, 512, 0], 4),
    "an-expert-split-across-two-segments": ([384, 256], 2),
    "one-expert-held": ([300], 2),
    "a-segment-that-ends-with-an-expert": ([256, 128, 128], 2),
}


@pytest.mark.parametrize("dtype", [jnp.bfloat16, jnp.float32], ids=["bfloat16", "float32"])
@pytest.mark.parametrize("case", CASES)
def test_expert_dw_equals_its_twin_bit_for_bit_and_the_sum_over_an_experts_rows(case, dtype):
    loads, span = CASES[case]
    a, b = 256, 128
    lhs, rhs, tiles, valid = _rows_in_tiles(loads, a, b, dtype)
    zeros = jnp.zeros((len(loads), a, b), jnp.float32)
    assert moe._dw_blocks(a, b, TILE) is not None

    def kernel(*operands):
        return moe.expert_dw(*operands, interpret=True)

    got = _by_segments(kernel, zeros, lhs, rhs, tiles, span)
    np.testing.assert_array_equal(got, _by_segments(moe._expert_dw_twin, zeros, lhs, rhs, tiles, span))
    # a segment's end adds an expert's tiles in the same order as no segment at all
    np.testing.assert_array_equal(got, _by_segments(moe._expert_dw_twin, zeros, lhs, rhs, tiles, len(tiles)))
    of_row = np.repeat(tiles, TILE)
    for e, load in enumerate(loads):
        rows = (of_row == e) & valid
        assert rows.sum() == load
        want = lhs[rows].astype(jnp.float32).T @ rhs[rows].astype(jnp.float32)
        if load == 0:
            np.testing.assert_array_equal(got[e], 0.0)
        else:
            assert_close(got[e], want, tol=1e-5)


def test_expert_dw_adds_to_the_first_experts_sum_and_writes_the_others():
    """The sum a segment's first expert brings is kept (the segment before may
    have begun it); an expert no tile names keeps what it holds."""
    lhs, rhs, tiles, _ = _rows_in_tiles([128, 256, 128], 128, 128, jnp.bfloat16)
    sums = jax.random.normal(jax.random.key(3), (5, 128, 128)).at[2:4].set(0.0)
    got = moe.expert_dw(sums, lhs, rhs, jnp.asarray(tiles + 1), jnp.int32(len(tiles)), interpret=True)
    np.testing.assert_array_equal(got, moe._expert_dw_twin(sums, lhs, rhs, jnp.asarray(tiles + 1), jnp.int32(len(tiles))))
    np.testing.assert_array_equal(got[jnp.asarray([0, 4])], sums[jnp.asarray([0, 4])])
    assert float(jnp.abs(got[1] - sums[1]).max()) > 1.0
    # no tile run: nothing moves
    np.testing.assert_array_equal(moe.expert_dw(sums, lhs, rhs, jnp.asarray(tiles + 1), jnp.int32(0), interpret=True), sums)


@pytest.mark.parametrize("at", [0, 1, 3], ids=["the-first-tile", "a-middle-tile", "the-last-tile"])
def test_put_tiles_writes_a_tile_of_every_buffer_and_nothing_else(at):
    widths, keys = (256, 128, 128), jax.random.split(jax.random.key(at), 6)
    buffers = tuple(jax.random.normal(key, (4 * TILE, w)).astype(jnp.bfloat16) for key, w in zip(keys[:3], widths))
    tiles = tuple(jax.random.normal(key, (TILE, w)).astype(jnp.bfloat16) for key, w in zip(keys[3:], widths))
    got = moe.put_tiles(buffers, tiles, jnp.int32(at * TILE), interpret=True)
    want = tuple(jax.lax.dynamic_update_slice(b, t, (at * TILE, 0)) for b, t in zip(buffers, tiles))
    assert len(got) == len(want) == 3
    for a, b, before in zip(got, want, buffers):
        np.testing.assert_array_equal(a, b)
        assert int(jnp.sum(jnp.any(a != before, axis=1))) <= TILE


@pytest.mark.parametrize("shape, blocks", [
    ((2048, 512, 512), (2048, 512)), ((512, 2048, 512), (512, 2048)),        # Qwen3-Next: w_gate and w_up, w_down
    ((2048, 1792, 512), (2048, 1792)), ((1792, 2048, 512), (1792, 2048)),    # LFM2
    ((2048, 1536, 512), (2048, 1536)), ((1536, 2048, 512), (1536, 2048)),    # GLM-4.7-Flash: whole experts, all three
    ((4096, 4096, 512), (2048, 2048)), ((8192, 2048, 512), (2048, 2048)),    # larger experts go in blocks
    ((256, 128, 128), (256, 128)), ((256, 16, 16), None), ((96, 128, 128), None), ((256, 128, 16), None),
], ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) and len(v) == 3 else None)
def test_blocks_follow_the_shapes_and_the_vmem_a_kernel_may_use(shape, blocks):
    assert moe._dw_blocks(*shape) == blocks
    if blocks is not None:
        (a, b, tile), (ba, bb) = shape, blocks
        assert a % ba == 0 and b % bb == 0 and ba % 128 == 0 and bb % 128 == 0
        held_twice = 2 * 4 * ba * bb + 2 * 2 * tile * (ba + bb)
        # the sum's block and the operands' tiles twice, and once more the product and the transposed rows
        assert held_twice <= moe.DW_VMEM_BYTES and held_twice + 4 * ba * bb + 2 * tile * ba <= moe.DW_VMEM_LIMIT < 128 * 2**20


@pytest.mark.parametrize("loads, span, writes", [
    ([130, 0, 128], 64, 2), ([384, 256], 2, 4), ([0, 0], 64, 0), ([300], 2, 2), ([128] * 5, 4, 5), ([1024, 128], 4, 3),
    ([384, 256], 0, 5), ([0, 0], 0, 0),
], ids=["an-empty-expert", "split-by-a-segment", "no-tile", "one-expert", "a-tile-each", "segment-and-expert-end-as-one",
        "sums-in-the-loop-a-write-a-tile", "sums-in-the-loop-no-tile"])
def test_dw_writes_counts_an_experts_tiles_inside_a_segment_once(loads, span, writes):
    local = jnp.asarray(np.repeat(np.arange(len(loads)), loads), jnp.int32)
    plan = moe._tile_plan(local, len(loads), TILE)
    most = sum(loads) // TILE + len(loads)
    assert int(moe._dw_writes(plan, span, most)) == writes


@pytest.mark.parametrize("shapes, span", [
    ((4 * 8192 * 4, 32, 8, (2048, 1792), 512), 64),   # LFM2 cell: 4,096 rows an expert, eight tiles
    ((2 * 8192 * 10, 512, 32, (2048, 512), 512), 0),  # Qwen3-Next cell: 320 rows an expert, one tile
    ((8192 * 4, 64, 8, (2048, 1536), 512), 0),        # GLM-4.7-Flash cell: 512, one tile
    ((2048, 6, 3, (128, 128), 128), 19),              # no more tiles than the assignments can fill
    ((2048, 6, 3, (128, 16), 128), 0), ((2048, 6, 3, (128, 128), 16), 0),  # not whole lane tiles
    ((1024, 6, 3, (128, 128), 128), 0),               # 170 rows an expert: under two tiles
], ids=["lfm2", "qwen3-next", "glm-4.7-flash", "short", "narrow-experts", "narrow-tile", "few-rows-an-expert"])
def test_the_kernels_take_the_sums_where_an_experts_rows_fill_two_tiles(shapes, span):
    assert moe._dw_span(*shapes) == span


def _dense(x, top_e, w, p, held):
    """The held experts' share, every held expert over every token."""
    first, count = held
    y = 0.0
    for e in range(count):
        mid = jax.nn.silu(x @ p["w_gate"][e]) * (x @ p["w_up"][e])
        y = y + (mid @ p["w_down"][e]) * jnp.sum(jnp.where(top_e == first + e, w, 0.0), axis=-1, keepdims=True)
    return y


@pytest.mark.parametrize("routing", ["even", "all-on-one-held", "none-held", "even-and-few-rows-an-expert"])
def test_held_experts_gradient_through_the_kernels_equals_the_dense_layer(monkeypatch, routing):
    n, h, f, k, held = (256 if routing.endswith("few-rows-an-expert") else 1024), 128, 128, 2, (1, 3)
    keys = jax.random.split(jax.random.key(5), 6)
    x = jax.random.normal(keys[0], (n, h))
    p = {name: jax.random.normal(key, shape) * 0.1 for name, key, shape in
         (("w_gate", keys[1], (3, h, f)), ("w_up", keys[2], (3, h, f)), ("w_down", keys[3], (3, f, h)))}
    if routing.startswith("even"):
        top_e = jax.vmap(lambda key: jax.random.permutation(key, 6)[:k])(jax.random.split(keys[4], n)).astype(jnp.int32)
    else:
        top_e = jnp.tile(jnp.asarray([[2, 0]] if routing == "all-on-one-held" else [[0, 4]], jnp.int32), (n, 1))
    w = jax.nn.softmax(jax.random.normal(keys[5], (n, k)))
    weigh = jax.random.normal(jax.random.key(6), (n, h))
    calls = {"expert_dw": [], "experts_fwd": [], "experts_bwd": [], "put_tiles": [], "take_rows": [], "put_rows": []}
    for name, seen in calls.items():
        monkeypatch.setattr(moe, name, lambda *a, _kernel=getattr(moe, name), _seen=seen, **kw: _seen.append(kw) or _kernel(*a, **kw))
    monkeypatch.setattr(moe, "GROUP_SEGMENT", 4)  # 2,048 assignments on three experts of six: segments split them

    def program(x, w, p):
        y, counts = moe.held_experts(x, top_e, w, p, n_experts=6, held=held, tile=TILE)
        return jnp.sum(weigh * y), counts

    with jax.default_matmul_precision("highest"):
        (_, counts), got = jax.value_and_grad(program, argnums=(0, 1, 2), has_aux=True)(x, w, p)
        want = jax.grad(lambda x, w, p: jnp.sum(weigh * _dense(x, top_e, w, p, held)), argnums=(0, 1, 2))(x, w, p)
    assert_close(got, want)
    loads = [int(jnp.sum(top_e == held[0] + e)) for e in range(held[1])]
    tiles = [e for e, load in enumerate(loads) for _ in range(-(-load // TILE))]
    # experts and a tile of whole lane tiles: both passes through the grouped kernels at 341 rows an expert and at 85,
    # traced once a pass and once a matrix, in the interpreter off a TPU; the kernels move their own rows
    assert calls == {"expert_dw": [{"interpret": True}] * 3, "experts_fwd": [{"interpret": True}], "experts_bwd": [{"interpret": True}],
                     "put_tiles": [], "take_rows": [], "put_rows": []}
    assert int(counts["moe_tile_rows"]) == len(tiles) * TILE  # a block of 128 rows a tile of 128
    assert int(counts["moe_grouped"]) == int(counts["moe_held"]) == sum(loads)
    assert int(counts["moe_dw_writes"]) == sum(1 for t, e in enumerate(tiles) if t % 4 == 0 or tiles[t - 1] != e)
    if routing == "none-held":
        assert all(float(jnp.abs(leaf).max()) == 0.0 for leaf in jax.tree.leaves(got[2]))
