"""Every Pallas kernel must lower for the ``tpu`` platform from the CPU.

Lowering runs Pallas' own checks of the kernel against Mosaic's rules —
block shapes whose last two dimensions are neither divisible by (8, 128)
nor equal to the array's, unsupported memory spaces — without a chip, so
that class of refusal is caught here and not on the chip budget.  It is not
a compile and says nothing about agreement; ``chip_smoke.py`` owns both.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import pytest

from lakesoul_tpu.annplane import ragged
from lakesoul_tpu.models import attention, loss_tile, qwen3_next, selective_scan
from lakesoul_tpu.parallel import moe
from lakesoul_tpu.tensorplane.smoke import enumerate_pallas_kernels
from lakesoul_tpu.vector import kernels

ROWS = 65_536


def _sds(shape, dtype=jnp.float32):
    return jax.ShapeDtypeStruct(shape, dtype)


def _packed_scan(d):
    return kernels.packed_scan_pallas.trace(
        _sds((ROWS, d // 8), jnp.uint8), _sds((ROWS,)), _sds((ROWS,)), _sds((d,)),
        d=d, interpret=False,
    )


def _packed_dot(d):
    return kernels.packed_dot_pallas.trace(
        _sds((ROWS, d // 8), jnp.uint8), _sds((d,)), interpret=False
    )


def _packed_dot_batch(d):
    return kernels.packed_dot_batch_pallas.trace(
        _sds((ROWS, d // 8), jnp.uint8), _sds((64, d)), interpret=False
    )


def _bruteforce(d):
    return kernels.bruteforce_distances_pallas.trace(
        _sds((ROWS, d)), _sds((d,)), interpret=False
    )


def _ragged_score(d):
    m, nq = 4096, 64
    return ragged._ragged_score_pallas_call.trace(
        _sds((m,), jnp.int32), _sds((m,), jnp.int32), _sds((m,)), _sds((m,)),
        _sds((nq, d)), _sds((ROWS, d)), _sds((ROWS,)), _sds((ROWS,)), _sds((ROWS,)),
        tile=ragged.TILE, interpret=False,
    )


def _unit_lower_inverse(d):
    # one DeltaNet layer's chunk systems for an 8,192-token row: d is not a shape of this kernel
    del d
    lead, c = (64, 1, 32), qwen3_next.GDN_CHUNK
    return jax.jit(
        lambda a, g: qwen3_next._unit_lower_inverse_pallas(a, jnp.bfloat16, g, interpret=False)
    ).trace(_sds((*lead, c, c)), _sds((*lead, c)))


def _delta_row(d):
    # one DeltaNet layer's row at the published shape: 16 key heads serving 32 value heads of 128, 64 chunks of 128
    del d
    t, hk, hv, dh, c = 8192, 16, 32, 128, qwen3_next.GDN_CHUNK
    bf16 = jnp.bfloat16
    tokens = _sds((1, t // c, hv, c))
    return (_sds((1, t, hk, dh), bf16), _sds((1, t, hk, dh), bf16), _sds((1, t, hv, dh), bf16), tokens, tokens,
            _sds((1, t // c, hv, c, c), bf16))


def _gated_delta_forward(d):
    return jax.jit(
        lambda *operands: qwen3_next._gated_delta_forward(*operands, eps=1e-6, interpret=False)
    ).trace(*_delta_row(d))


def _gated_delta_backward(d):
    q, k, v, gc, beta, inv = _delta_row(d)
    states = _sds((*inv.shape[:3], q.shape[-1], v.shape[-1]))
    return jax.jit(
        lambda *operands: qwen3_next._gated_delta_backward(*operands, eps=1e-6, interpret=False)
    ).trace(q, k, v, gc, beta, inv, states, _sds(v.shape))


def _expert_sums(d):
    # the float32 sums over a step's 16,384 tokens at the LM cell's width, a tile of slots
    del d
    return _sds((16_384, 1, 2048)), _sds((moe.EXPERT_TILE,), jnp.int32), _sds((), jnp.int32)


def _take_rows(d):
    return jax.jit(lambda acc, idx, n: moe.take_rows(acc, idx, n, interpret=False)).trace(*_expert_sums(d))


def _put_rows(d):
    return jax.jit(lambda acc, idx, n, rows: moe.put_rows(acc, idx, n, rows, interpret=False)).trace(
        *_expert_sums(d), _sds((moe.EXPERT_TILE, 1, 2048))
    )


EXPERT_CELLS = {  # an expert's h and f, the experts a chip holds, the tokens of a step
    "lfm2": (2048, 1792, 8, 32768), "trinity-mini": (2048, 1024, 16, 16384),
    "qwen3-next": (2048, 512, 32, 16384), "glm-4.7-flash": (2048, 1536, 8, 8192),
}


def _grouped_operands(cell):
    """A segment of the grouped kernels at ``cell``'s experts (the LFM2 cell's
    where the register asks: ``d`` of the vector kernels names none): the
    float32 sums and staged rows [N, 1, h], a segment's slots, the weights a
    chip holds, the tiles' experts and counts."""
    h, f, count, n = EXPERT_CELLS.get(cell, EXPERT_CELLS["lfm2"])
    slots, bf16 = moe.GROUP_SEGMENT * moe.EXPERT_TILE, jnp.bfloat16
    weights = (_sds((count, h, f), bf16), _sds((count, h, f), bf16), _sds((count, f, h), bf16))
    plan = (_sds((moe.GROUP_SEGMENT,), jnp.int32), _sds((moe.GROUP_SEGMENT,), jnp.int32))
    return _sds((n, 1, h)), (_sds((slots,), jnp.int32), _sds((slots,))), weights, plan


def _experts_forward(d):
    rows32, slots, weights, plan = _grouped_operands(d)
    return jax.jit(lambda *operands: moe.experts_fwd(*operands, interpret=False)).trace(rows32, rows32, *slots, *weights, *plan)


def _experts_backward(d):
    rows32, slots, weights, plan = _grouped_operands(d)
    return jax.jit(lambda *operands: moe.experts_bwd(*operands, interpret=False)).trace(
        rows32, rows32, rows32, *slots, *weights, *plan
    )


def _stage_rows(d):
    # a layer's tokens and their cotangent into the float32 rows a DMA takes one of, with the zeros of the sums
    del d
    x = _sds((8192, 2048), jnp.bfloat16)
    return jax.jit(lambda *arrays: moe.stage_rows(arrays, interpret=False)).trace(x, x)


def _unstage_rows(d):
    del d
    return jax.jit(lambda acc: moe.unstage_rows(acc, jnp.bfloat16, interpret=False)).trace(_sds((8192, 1, 2048)))


def _expert_dw(d):
    # a segment of the backward loop at the LFM2 cell's experts: 64 tiles of rows into eight float32 sums of [2048, 1792]
    del d
    rows, (h, f) = moe.DW_SEGMENT * moe.EXPERT_TILE, (2048, 1792)
    return jax.jit(lambda *operands: moe.expert_dw(*operands, interpret=False)).trace(
        _sds((8, h, f)), _sds((rows, h), jnp.bfloat16), _sds((rows, f), jnp.bfloat16),
        _sds((moe.DW_SEGMENT,), jnp.int32), _sds((), jnp.int32),
    )


def _put_tiles(d):
    # a tile's five operands into the row buffers of the same segment
    del d
    rows, (h, f), bf16 = moe.DW_SEGMENT * moe.EXPERT_TILE, (2048, 1792), jnp.bfloat16
    widths = (h, f, h, f, f)
    return jax.jit(lambda *operands: moe.put_tiles(*operands, interpret=False)).trace(
        tuple(_sds((rows, w), bf16) for w in widths), tuple(_sds((moe.EXPERT_TILE, w), bf16) for w in widths),
        _sds((), jnp.int32),
    )


def _loss_tile(d):
    # the Ouro cell's tile of the head's logits: 2,736 stacked positions of 49,152, the cotangent in bfloat16, blocks of
    # 64 whole rows (the last ragged)
    del d
    return jax.jit(functools.partial(loss_tile.loss_tile.__wrapped__, dtype=jnp.bfloat16, interpret=False)).trace(
        _sds((2736, 49152)), _sds((2736,), jnp.int32), _sds((2736,))
    )


def _scan_row(d):
    # one Mamba-1 layer's row at the published shape: 5,120 channels of 16 states over 8,192 tokens, blocks of 512
    # channels; u bfloat16, Delta and the rest float32 (d is a vector width, not a shape of these kernels)
    del d
    t, e, n = 8192, 5120, 16
    return (_sds((1, t, e), jnp.bfloat16), _sds((1, t, e)), _sds((n, e)), _sds((1, t, n)), _sds((1, t, n)), _sds((e,))), \
        selective_scan.scan_takes(e, n)


def _scan_forward(d):
    row, eb = _scan_row(d)
    return jax.jit(lambda *operands: selective_scan._scan_forward(*operands, eb=eb, interpret=False)).trace(*row)


def _scan_backward(d):
    row, eb = _scan_row(d)
    u, _, at = row[:3]
    bounds = _sds((1, u.shape[1] // selective_scan.SCAN_TOKENS, *at.shape))
    return jax.jit(lambda *operands: selective_scan._scan_backward(*operands, eb=eb, interpret=False)).trace(*row, bounds, u)


ATTENTION_ROWS = {  # a row's attention in each causal-LM cell: key-value heads, query heads each serves, head size
    "lfm2": (8, 4, 64), "qwen3-next": (2, 8, 256), "glm-4.7-flash": (20, 1, 256), "trinity-mini": (4, 8, 128),
    "ouro": (16, 1, 128), "phi-4-mini-flash": (20, 2, 64),  # the paired maps: two key-value heads a key-value pair
}
VALUE_WIDTHS = {"phi-4-mini-flash": 128}  # where it is not the head's size: a pair's two values side by side


def _attention_row(d):
    # a row's attention at a published group and head size: the LFM2 cell's layer at head 64 wherever d is not the
    # Qwen cell's (d is a vector width, not a shape of these kernels), or a family of ATTENTION_ROWS by name; the
    # output (and its cotangent) token-major, [1, T, heads x D], where ``_token_major`` takes the shape: every
    # published head but LFM2's 64; the value as wide as the head but for Phi-4-mini-flash's pairs
    family = d if d in ATTENTION_ROWS else "qwen3-next" if d == 768 else "lfm2"
    hkv, groups, d = ATTENTION_ROWS[family]
    dv = VALUE_WIDTHS.get(family, d)
    t = 8192
    bf16 = jnp.bfloat16
    token_major = attention._token_major(t, groups, d, dv)
    o = _sds((1, t, hkv * groups * d), bf16) if token_major else _sds((hkv, groups, t, dv), bf16)
    tiles = dict(zip(("bq", "bk"), attention._flash_tiles(t, groups, d, dv)))
    return _sds((hkv, groups, t, d), bf16), _sds((hkv, t, d), bf16), _sds((hkv, t, dv), bf16), o, tiles


def _flash_forward(d, window=None):
    q, k, v, o, tiles = _attention_row(d)
    batch = None if o.ndim == 4 else 1
    return jax.jit(
        lambda q, k, v: attention._flash_forward(q, k, v, **tiles, window=window, batch=batch, interpret=False)
    ).trace(q, k, v)


def _flash_backward(d, window=None):
    q, k, v, o, tiles = _attention_row(d)
    lse = _sds((*q.shape[:2], 1, q.shape[2]))
    return jax.jit(
        lambda q, k, v, o, lse, do: attention._flash_backward(
            q, k, v, o, lse, do, **tiles, window=window, interpret=False
        )
    ).trace(q, k, v, o, lse, o)


def _operand_row(turned=True, family="trinity-mini", normed=True):
    # a row's mixer (d is a vector width, not a shape of these kernels), Trinity-Mini's wherever no family is named:
    # the raw q, k, v as the products leave them, the norm weights (none where the heads are not normed), the
    # position tables of a layer that sees positions (none on Trinity-Mini's full layer)
    hkv, groups, d = ATTENTION_ROWS[family]
    t, bf16 = 8192, jnp.bfloat16
    raw = [_sds((1, t, n * d), bf16) for n in (hkv * groups, hkv, hkv)]
    laid = [_sds((1, hkv, groups, t, d), bf16), _sds((1, hkv, t, d), bf16), _sds((1, hkv, t, d), bf16)]
    turn = (_sds((t, d)), _sds((t, d))) if turned else None
    weights = [_sds((d,)), _sds((d,))] if normed else [None, None]
    recipe = dict(d=d, eps=1e-5, bt=attention._operand_tiles(t, hkv * groups, hkv, d, d if turned else None), interpret=False)
    return raw, laid, [*weights, turn], recipe


def _operands_forward(d, turned=True, **row):
    raw, _, rest, recipe = _operand_row(turned, **row)
    return jax.jit(lambda *a: attention._operands_forward(*a, **recipe)).trace(*raw, *rest)


def _operands_backward(d, turned=True, **row):
    raw, laid, rest, recipe = _operand_row(turned, **row)
    return jax.jit(lambda *a: attention._operands_backward(*a, **recipe)).trace(*laid, *raw[:2], *rest)


# keyed by lakelint device-index qname, like the smoke register
TRACERS = {
    "lakesoul_tpu/vector/kernels.py::_packed_scan_kernel": _packed_scan,
    "lakesoul_tpu/vector/kernels.py::_packed_dot_kernel": _packed_dot,
    "lakesoul_tpu/vector/kernels.py::_packed_dot_batch_kernel": _packed_dot_batch,
    "lakesoul_tpu/vector/kernels.py::_bruteforce_kernel": _bruteforce,
    "lakesoul_tpu/annplane/ragged.py::_ragged_score_kernel": _ragged_score,
    "lakesoul_tpu/models/attention.py::_flash_fwd_kernel": _flash_forward,
    "lakesoul_tpu/models/attention.py::_flash_bwd_kernel": _flash_backward,
    "lakesoul_tpu/models/attention.py::_operands_fwd_kernel": _operands_forward,
    "lakesoul_tpu/models/attention.py::_operands_bwd_kernel": _operands_backward,
    "lakesoul_tpu/models/qwen3_next.py::_unit_lower_inverse_kernel": _unit_lower_inverse,
    "lakesoul_tpu/models/qwen3_next.py::_gated_delta_fwd_kernel": _gated_delta_forward,
    "lakesoul_tpu/models/qwen3_next.py::_gated_delta_bwd_kernel": _gated_delta_backward,
    "lakesoul_tpu/models/loss_tile.py::_loss_tile_kernel": _loss_tile,
    "lakesoul_tpu/models/selective_scan.py::_scan_fwd_kernel": _scan_forward,
    "lakesoul_tpu/models/selective_scan.py::_scan_bwd_kernel": _scan_backward,
    "lakesoul_tpu/parallel/moe.py::_take_rows_kernel": _take_rows,
    "lakesoul_tpu/parallel/moe.py::_put_rows_kernel": _put_rows,
    "lakesoul_tpu/parallel/moe.py::_expert_dw_kernel": _expert_dw,
    "lakesoul_tpu/parallel/moe.py::_put_tiles_kernel": _put_tiles,
    "lakesoul_tpu/parallel/moe.py::_experts_fwd_kernel": _experts_forward,
    "lakesoul_tpu/parallel/moe.py::_experts_bwd_kernel": _experts_backward,
    "lakesoul_tpu/parallel/moe.py::_stage_rows_kernel": _stage_rows,
    "lakesoul_tpu/parallel/moe.py::_unstage_rows_kernel": _unstage_rows,
}


def test_every_enumerated_kernel_has_a_lowering_case():
    assert sorted(TRACERS) == enumerate_pallas_kernels()


@pytest.mark.parametrize("d", [128, 768])
@pytest.mark.parametrize("kernel", sorted(TRACERS))
def test_kernel_lowers_for_tpu(kernel, d):
    lowered = TRACERS[kernel](d).lower(lowering_platforms=("tpu",))
    assert "tpu_custom_call" in lowered.as_text()


@pytest.mark.parametrize("cell", sorted(EXPERT_CELLS))
@pytest.mark.parametrize("kernel", ["_experts_fwd_kernel", "_experts_bwd_kernel"])
def test_grouped_expert_kernels_lower_at_every_cells_experts(kernel, cell):
    """The four routed cells' experts: a segment of 16 tiles of 512 rows, a
    grid step 128 rows of a tile, an expert's three bfloat16 matrices whole as
    one block each, the float32 sums and the staged rows left in HBM for the
    kernel's own row copies; the backward kernel gives back the five operands
    ``expert_dw`` takes."""
    h, f, count, n = EXPERT_CELLS[cell]
    assert moe._segment(1 << 17, count, (h, f), moe.EXPERT_TILE) == moe.GROUP_SEGMENT == 16
    text = TRACERS["lakesoul_tpu/parallel/moe.py::" + kernel](cell).lower(lowering_platforms=("tpu",)).as_text()
    call = next(line for line in text.splitlines() if "tpu_custom_call" in line)
    operands, results = call.split(" -> ")
    slots = moe.GROUP_SEGMENT * moe.EXPERT_TILE
    assert f"tensor<{count}x{h}x{f}xbf16>" in operands and f"tensor<{count}x{f}x{h}xbf16>" in operands
    assert operands.count(f"tensor<{n}x1x{h}xf32>") == (2 if kernel == "_experts_fwd_kernel" else 3)  # the sums; x's rows, dy's
    assert f"tensor<{n}x1x{h}xf32>" in results  # the sums come back, written in place
    if kernel == "_experts_bwd_kernel":
        assert results.count(f"tensor<{slots}x{f}xbf16>") == 3 and results.count(f"tensor<{slots}x{h}xbf16>") == 2  # mid, dg, du; xs, dyw


@pytest.mark.parametrize("family", sorted(ATTENTION_ROWS))
@pytest.mark.parametrize("kernel", ["_flash_fwd_kernel", "_flash_bwd_kernel"])
def test_attention_kernels_lower_at_every_published_shape(kernel, family):
    """The five families' rows: groups of 4 at head 64, of 8 at head 256,
    latent attention's 20 key-value heads of one query head each at head 256
    (tiles of 512 queries x 512 keys), groups of 8 at head 128, and 16
    key-value heads of one query head each at head 128 (512 x 512 again:
    ``FLASH_ROWS`` is then queries alone); with the token-major output's
    block spec at the four heads of whole lane tiles, the heads-first one at
    head 64; and the sixth's paired maps, 20 key-value heads of two query
    heads each at head 64 beside a value of 128 (512 x 512)."""
    if family in ("glm-4.7-flash", "ouro"):
        assert attention._flash_tiles(8192, 1, ATTENTION_ROWS[family][2]) == (512, 512)
    lowered = TRACERS["lakesoul_tpu/models/attention.py::" + kernel](family).lower(lowering_platforms=("tpu",))
    call = next(line for line in lowered.as_text().splitlines() if "tpu_custom_call" in line)
    # where a head is whole lane tiles the call writes the output (reads its cotangent) token-major, a block of
    # ``bq`` tokens by a group's lanes; at LFM2's head of 64 heads first, as before
    hkv, groups, d = ATTENTION_ROWS[family]
    assert (f"tensor<1x8192x{hkv * groups * d}xbf16>" in call) == (d != 64)


@pytest.mark.parametrize("window", [None, 512], ids=["full", "window-512"])
@pytest.mark.parametrize("kernel", ["_flash_fwd_kernel", "_flash_bwd_kernel"])
def test_attention_kernels_lower_at_a_value_wider_than_its_key(kernel, window):
    """A Phi-4-mini-flash row's paired maps, 20 key-value heads of two query
    heads at head 64 beside a value of 128 (``paired_attention``), 8,192
    tokens in the head's tiles of 512 x 512, under the causal mask (136 steps
    a head) and under the window layers' 512 (31): the call takes q and k 64
    wide and the value 128, and gives the output (the forward call) or dV
    (the backward) 128 wide and dQ, dK 64."""
    assert attention._flash_tiles(8192, 2, 64, 128) == attention._flash_tiles(8192, 2, 64) == (512, 512)
    lowered = TRACERS["lakesoul_tpu/models/attention.py::" + kernel]("phi-4-mini-flash", window).lower(
        lowering_platforms=("tpu",)
    )
    text = lowered.as_text()
    call = next(line for line in text.splitlines() if "tpu_custom_call" in line)
    steps = attention.key_tile_steps(8192, 2, 64, window, 128)[0]
    assert steps == (31 if window else 136) and f"tensor<{steps}xi32>" in text  # the tables the call prefetches
    operands, results = call.split(" -> ")
    assert "tensor<20x2x8192x64xbf16>" in operands and "tensor<20x8192x64xbf16>" in operands and "tensor<20x8192x128xbf16>" in operands
    if kernel == "_flash_fwd_kernel":
        assert "tensor<20x2x8192x128xbf16>" in results
    else:
        assert "tensor<20x2x8192x128xbf16>" in operands  # the output's cotangent
        assert all(shape in results for shape in ("tensor<20x2x8192x64xbf16>", "tensor<20x8192x64xf32>", "tensor<20x8192x128xf32>"))


@pytest.mark.parametrize("window", [None, 2048], ids=["full", "window-2048"])
@pytest.mark.parametrize("kernel", ["_flash_fwd_kernel", "_flash_bwd_kernel"])
def test_attention_kernels_lower_with_and_without_the_window(kernel, window):
    """A Trinity-Mini row's two kinds of layer: 4 key-value heads of 8 query
    heads at head 128, 8,192 tokens in tiles of 128 queries x 512 keys, under
    the causal mask (544 steps a head) and under a window of 2,048 (280: the
    lower edge's mask and the grid's first key tile read off the step)."""
    assert attention._flash_tiles(8192, 8, 128) == (128, 512)
    lowered = TRACERS["lakesoul_tpu/models/attention.py::" + kernel]("trinity-mini", window).lower(
        lowering_platforms=("tpu",)
    )
    text = lowered.as_text()
    assert "tpu_custom_call" in text
    steps = attention.key_tile_steps(8192, 8, 128, window)[0]
    assert steps == (280 if window else 544) and f"tensor<{steps}xi32>" in text  # the tables the call prefetches


@pytest.mark.parametrize("turned", [True, False], ids=["window-turned", "full-unturned"])
@pytest.mark.parametrize("kernel", ["_operands_fwd_kernel", "_operands_bwd_kernel"])
def test_operand_kernels_lower_with_and_without_positions(kernel, turned):
    """A Trinity-Mini row's two kinds of layer, 32 heads over 4 key-value
    heads at head 128: blocks of 512 tokens of a key-value head's group, a
    grid of 16 token blocks by 4 heads; the window layer's call reads the two
    position tables, the full layer's has none."""
    assert attention._operand_tiles(8192, 32, 4, 128, 128 if turned else None) == 512
    traced = TRACERS["lakesoul_tpu/models/attention.py::" + kernel](128, turned)
    text = traced.lower(lowering_platforms=("tpu",)).as_text()
    assert "tpu_custom_call" in text
    assert ("tensor<8192x128xf32>" in text) == turned  # the tables


@pytest.mark.parametrize("kernel", ["_operands_fwd_kernel", "_operands_bwd_kernel"])
def test_operand_kernels_lower_without_head_norms(kernel):
    """An Ouro row's mixer, 16 heads on 16 key-value heads at head 128,
    positions over the whole head and NO norm over a head: blocks of 512
    tokens of one head, a grid of 16 token blocks by 16 heads; the call reads
    the two position tables and no norm weight, and the backward call neither
    the raw query nor the raw key (turning back needs neither) and writes no
    weight gradient's share."""
    assert attention._operand_tiles(8192, 16, 16, 128, 128) == 512
    traced = TRACERS["lakesoul_tpu/models/attention.py::" + kernel](128, family="ouro", normed=False)
    text = traced.lower(lowering_platforms=("tpu",)).as_text()
    call = next(line for line in text.splitlines() if "tpu_custom_call" in line)
    assert "tensor<8192x128xf32>" in call and "tensor<1x128xf32>" not in call  # the tables, no norm weight
    operands = call[call.index("tpu_custom_call(") : call.index(")")].count("%")
    assert operands == 5  # three arrays and the two tables, in either direction
    assert ("x8x128xf32>" in call) is False  # no share of a weight gradient
    normed = TRACERS["lakesoul_tpu/models/attention.py::" + kernel](128).lower(lowering_platforms=("tpu",)).as_text()
    assert "tensor<1x128xf32>" in next(line for line in normed.splitlines() if "tpu_custom_call" in line)
