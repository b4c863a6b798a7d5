"""Dropless top-k expert layer: one chip's share of an expert-parallel layer.

The router scores all ``n_experts``, every token keeps its ``top_k``, and this
chip computes the part of the result that the experts it holds give, for
however many assignments land on them (none to all).  Nothing has a capacity
and nothing is dropped; what the absent experts would add is left out.  On an
``ep > 1`` mesh the token exchange in front of it is not written yet.

The layer is three pieces and the layer stack composes them
(``models/causal_lm.py: lm_layer``): a routing rule (:func:`route_top_k`:
softmax; :func:`route_sigmoid_top_k`: sigmoid scores picked under a per-expert
bias), :func:`held_experts` and, where the family has one,
:func:`shared_expert`.  What each family passes: ``models/qwen3_next.py``
:func:`route_top_k` and a shared expert under its gate; ``models/lfm2_moe.py``
:func:`route_sigmoid_top_k` at ``scale`` 1 with the default ``eps`` 1e-6 and no
shared expert; ``models/glm4_moe_lite.py`` :func:`route_sigmoid_top_k` at
``scale`` 1.8 and ``eps`` 1e-20 and a shared expert with no gate (its weights
hold no ``"gate"``); ``models/afmoe.py`` the same rule at ``scale`` 2.826 and
``eps`` 1e-20 over 128 experts, top 8, and a shared expert with no gate, the
two summed before the layer's last norm.  They stay apart because the stack rematerialises the
first and the last with its norm and leaves the second outside (see
:func:`held_experts`).

Assignments are sorted by held expert and cut into fixed tiles of one
expert's rows, as many as the held assignments fill (:func:`_tile_plan`): the
work follows the routing while every shape stays static.  A pass whose length
depends on the data has no reverse-mode derivative, so the backward pass walks
the same tiles again under one custom_vjp.

How a pass runs.  Where the experts' matrices and the tile are whole 128-lane
tiles and an expert's three matrices wait twice in VMEM (:func:`_grouped`; all
four cells' experts), a pass is a loop over segments of :data:`GROUP_SEGMENT`
tiles and a segment one Pallas kernel whose grid walks the tiles, a block of
:data:`GROUP_ROWS` rows a step (:func:`experts_fwd`, :func:`experts_bwd`).
The slots' tokens, the tiles' experts and the rows each tile holds are
prefetched scalars; the three weight matrices are blocks by the tile's expert,
so an expert's consecutive tiles find them in VMEM and the next expert's
arrive while this one's tiles run; a block past its expert's last row is not
multiplied (the backward kernel leaves zeros there: padding adds nothing to a
sum), so a layer's time follows its rows and not its tiles.  The body of a
block is the tile loop's, function for function (:func:`_tile_mid`,
:func:`_tile_down`, :func:`_tile_operands`, :func:`_tile_dx`: the gate and up
products, the SwiGLU, the down product and the row's routing weight forward;
the recomputed products, ``<expert output, dy>``, ``dyw``, ``dmid``, ``dg``,
``du`` and ``dx``'s rows backward), every rounding where the loop has it.
Every other shape, as most tests use, keeps a loop of one turn a tile, which
is the kernels' twin: on a CPU, the kernels in the Pallas interpreter, both
give the same bits, forward and every gradient.

How a block's rows move.  The kernels move their own rows, one DMA a row,
between HBM and VMEM.  Mosaic refuses a one-row slice of a two-dimensional
array on a v5e (a slice of the second to last axis must be a multiple of its
tiling, 8 rows of 32-bit words, 16 of 16-bit ones), while XLA lays ``[N, 1,
h]`` float32 out a row a tile (``T(1,128)``), rows contiguous and unpadded,
and Mosaic reads ``[rows, 1, h]`` in VMEM as ``[rows, h]`` at no cost that a
profile shows.  So ``x`` (and ``dy``) are staged once a layer and pass as
float32 ``[N, 1, h]`` (:func:`stage_rows`, one Pallas pass at the memory's
speed, which also writes the zeros of the sums), the sums over the tokens live
in that layout, and :func:`unstage_rows` reads them back into ``[N, h]``.  A
block's rows of ``x`` are started a step ahead of their products
(:func:`_gathered`).  Its float32 result joins the sums inside the kernel
(:func:`_add_block`): the sums' rows come in while the block's last product
runs, are added, and go back; the block before may hold the same tokens
(another expert's), so its rows have landed before this block's come, and a
token's rows are summed in float32 in the order the loop adds them (ascending
held expert, from zero).  Only the slots that hold an assignment join the
sums; the slots past an expert's last row inside a block that runs read the
row of some token, as the plan's clipped index gives it: finite, and
multiplied by zeros.  A copy's start is 18 ns of the scalar unit's whatever
the row's bytes and whichever queue (PERF.md section 6, PR 53): seven a held
assignment, forward and backward, which the matrix unit waits for, since a
loop of starts shares no instruction with a product; XLA's own row movement
was dearer (its gather 40 ns a row standing alone, :func:`take_rows` and
:func:`put_rows` around a dense sum 35 us a tile, its scatter-add 134: PERF.md
section 6, PRs 31 and 52).  The tile loop keeps it (indexing for the reads,
the two row kernels for the float32 rows where a row is whole lane tiles,
``.at[].add`` for narrower ones).

How the weight gradients sum.  An expert's three float32 sums ([h, f], 14.7 MB
each for an expert of 2048 x 1792) cannot ride a loop in VMEM.  The backward
kernel leaves a segment's operands in row buffers, in the plan's sorted order
(``x``'s rows, the SwiGLU's output and the three gradients ``dyw``, ``dg``,
``du``, all in the operands' precision, zeros in the slots past an expert's
last row): they are the kernel's own output blocks, so nothing copies them.
One Pallas kernel a matrix, :func:`expert_dw`, multiplies them expert by
expert (``out[e] += lhs[e's rows].T @ rhs[e's rows]``, the transposed grouped
product): the tile is the grid's innermost axis and the output block follows
the tile's expert, so an expert's sum stays in VMEM through the expert's
consecutive tiles and crosses HBM once a segment.  An expert that a segment's
end splits is read back once, which is the only sum the kernel reads.  The
tile loop adds a tile's three products to the sums in place where an expert's
rows fill a tile or so, and where they fill two (:func:`_dw_span`) leaves its
operands in row buffers by :func:`put_tiles` for the same kernel, a segment of
:data:`DW_SEGMENT` tiles at a time.  The same bfloat16 products are added in
float32 in the same tile order on every path: the same bits
(:func:`_expert_dw_twin` is the kernel's ``jnp`` twin, for the tests and the
smoke register).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from lakesoul_tpu.parallel.mesh import spec_axes
from lakesoul_tpu.utils import platform

ROUTE_SCOPE = "lakesoul.lm.moe.route"
EXPERTS_SCOPE = "lakesoul.lm.moe.experts"
SHARED_SCOPE = "lakesoul.lm.moe.shared"
# rows of one expert a product takes at a time.  Two loads run it (PERF.md
# section 4): 320 assignments an expert under even routing (16,384 tokens,
# top-10 of 512), where with 512 most experts fill one tile whatever the seed
# and a step's time follows the routing less than with 256, at 1% more time a
# step (PERF.md section 6, PR 28); and about 2,000 (top-4 of 32), four tiles
# or so an expert of which the last is part padding
EXPERT_TILE = 512
# tiles of the backward loop whose operands wait for :func:`expert_dw` at a
# time: 32,768 rows of ``2h + 3f`` bfloat16 (0.62 GB in the LFM2 cell, where
# buffers for every assignment the shapes allow would be 2.6 GB)
DW_SEGMENT = 64
# the grouped kernels (:func:`experts_fwd`, :func:`experts_bwd`): tiles a call
# walks (its prefetched scalars, and in the backward pass the row buffers
# :func:`expert_dw` reads: 8,192 rows of ``2h + 3f`` bfloat16, 0.16 GB in the
# LFM2 cell), rows of a tile a grid step multiplies (the blocks past an
# expert's last row are skipped, so a step's rows are what padding costs), and
# what the kernels may hold in VMEM of a v5e's 128 MiB: an expert's three
# matrices twice (the pipeline fetches the next expert's while this one's
# tiles run) beside a step's gathered rows and float32 products
GROUP_SEGMENT = 16
GROUP_ROWS = 128
GROUP_WEIGHT_BYTES = 48 * 2**20
GROUP_VMEM_LIMIT = 100 * 2**20
# what :func:`expert_dw` may hold in VMEM of an expert's float32 sum and of its
# operands, each twice (the pipeline's two buffers), and what the kernel may
# use in all, a tile's product and transposed rows with them, of a v5e's 128
# MiB.  Whole experts of the three cells fit (37 MB at [2048, 1792]); under the
# 16 MB a kernel has unasked the sum went in four blocks and the products ran
# at 68% of the bf16 peak where they run at 81% (PERF.md section 6, PR 39)
DW_VMEM_BYTES = 40 * 2**20
DW_VMEM_LIMIT = 64 * 2**20


def _router_logits(x, router_w):
    """Every expert's logit, float32: the product at ``HIGHEST``, since which
    experts a token takes hangs on the last bits of its scores."""
    return jnp.einsum(
        "...h,he->...e", x.astype(jnp.float32), router_w.astype(jnp.float32),
        precision=jax.lax.Precision.HIGHEST,
    )


def route_top_k(x, router_w, *, top_k: int):
    """Tokens ``x`` [..., h] → (experts [..., k] int32, weights [..., k] f32):
    softmax over every expert in float32, the ``top_k`` largest, their
    weights divided by their sum."""
    with jax.named_scope(ROUTE_SCOPE):
        top_p, top_e = jax.lax.top_k(jax.nn.softmax(_router_logits(x, router_w), axis=-1), top_k)
        return top_e.astype(jnp.int32), top_p / jnp.sum(top_p, axis=-1, keepdims=True)


def route_sigmoid_top_k(x, router_w, bias, *, top_k: int, scale: float = 1.0, eps: float = 1e-6):
    """Tokens ``x`` [..., h] → (experts [..., k] int32, weights [..., k] f32,
    assignments the bias moved, int32): every expert's score is the sigmoid of
    its logit, float32; the ``top_k`` largest of ``score + bias`` are picked
    (``bias`` [experts] float32 steers the selection and carries no gradient),
    and their weights are the unbiased scores over their sum plus ``eps``,
    times ``scale``.  An assignment is moved where its expert is among the
    ``top_k`` of ``score + bias`` and not of ``score``."""
    with jax.named_scope(ROUTE_SCOPE):
        score = jax.nn.sigmoid(_router_logits(x, router_w))
        _, top_e = jax.lax.top_k(score + jax.lax.stop_gradient(bias.astype(jnp.float32)), top_k)
        picked = jnp.take_along_axis(score, top_e, axis=-1)
        w = picked / (jnp.sum(picked, axis=-1, keepdims=True) + eps) * scale
        unbiased, _ = jax.lax.top_k(score, top_k)
        moved = jnp.sum(picked < unbiased[..., -1:], dtype=jnp.int32)
        return top_e.astype(jnp.int32), w, moved


def _tile_plan(local, count: int, tile: int):
    """``local`` [A]: the held expert (0..count-1) of each assignment, or
    ``count`` where its expert is not held.  → (order, sizes, starts,
    tile_ends): assignments sorted by held expert, rows of each expert, where
    its rows start in ``order``, and the running count of tiles."""
    order = jnp.argsort(local, stable=True).astype(jnp.int32)
    # the experts' boundaries in the sorted order: no scatter to count them
    bounds = jnp.searchsorted(local[order], jnp.arange(count + 1, dtype=local.dtype)).astype(jnp.int32)
    starts, sizes = bounds[:-1], bounds[1:] - bounds[:-1]
    return order, sizes, starts, jnp.cumsum((sizes + tile - 1) // tile)


def _tile_experts(t, plan):
    """The held expert of tile ``t`` (or of each of an array of tiles);
    ``count`` from the last tile on."""
    # every boundary compared at once: a held expert's count is small, and a search by halves is a loop of its own
    return jnp.searchsorted(plan[3], t, side="right", method="compare_all").astype(jnp.int32)


def _tile_rows(t, plan, tile: int, k: int):
    """Tile ``t`` → (its expert, assignment of each row, token of each row,
    which rows hold an assignment: a prefix, first row in ``order``)."""
    order, sizes, starts, tile_ends = plan
    e = _tile_experts(t, plan)
    row0 = starts[e] + (t - (tile_ends[e] - (sizes[e] + tile - 1) // tile)) * tile
    rows = row0 + jnp.arange(tile, dtype=jnp.int32)
    valid = rows < starts[e] + sizes[e]
    a = order[jnp.minimum(rows, order.shape[0] - 1)]
    return e, a, a // k, valid, row0


# ---------------------------------------------------------- rows by index


def _copy_rows(n, copy):
    """Start ``copy(i)`` for every i < n, then wait for as many: the copies
    share one semaphore and move a row's bytes each, whichever row.  Plain
    loops: eight starts a pass took 3 us off a call of 300 rows (0.4% of the
    LM cell's step) and cost 0.6 s of tracing and lowering in every process
    that builds the step (PERF.md section 6, PR 31)."""
    jax.lax.fori_loop(0, n, lambda i, carry: (copy(i).start(), carry)[1], 0)
    jax.lax.fori_loop(0, n, lambda i, carry: (copy(0).wait(), carry)[1], 0)


def _take_rows_kernel(idx_ref, n_ref, src_ref, out_ref, sem):
    _copy_rows(n_ref[0], lambda i: pltpu.make_async_copy(
        src_ref.at[pl.ds(idx_ref[i], 1)], out_ref.at[pl.ds(i, 1)], sem))


def _put_rows_kernel(idx_ref, n_ref, dst_in_ref, rows_ref, dst_ref, sem):
    del dst_in_ref  # the same buffer as dst_ref
    _copy_rows(n_ref[0], lambda i: pltpu.make_async_copy(
        rows_ref.at[pl.ds(i, 1)], dst_ref.at[pl.ds(idx_ref[i], 1)], sem))


def _row_copy_grid(n_arrays: int):
    """One grid step with the indices and the count in SMEM and every array
    left where it is, in HBM, and one semaphore for all of a call's copies."""
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    return pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2, grid=(1,), in_specs=[anywhere] * n_arrays, out_specs=anywhere,
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )


@functools.partial(jax.jit, static_argnames="interpret")
def take_rows(src, idx, n, *, interpret: bool):
    """``src`` [N, 1, w] float32, ``idx`` [tile] int32, ``n`` a scalar →
    [tile, 1, w] whose first ``n`` rows are ``src[idx[:n]]``; the rest is
    whatever the buffer held.  One DMA a row from HBM to HBM, all in flight."""
    return pl.pallas_call(
        _take_rows_kernel,
        out_shape=jax.ShapeDtypeStruct((idx.shape[0], *src.shape[1:]), src.dtype),
        grid_spec=_row_copy_grid(1), name="take_rows", interpret=interpret,
    )(idx, jnp.reshape(n, (1,)).astype(jnp.int32), src)


@functools.partial(jax.jit, static_argnames="interpret")
def put_rows(dst, idx, n, rows, *, interpret: bool):
    """``dst`` with ``dst[idx[:n]] = rows[:n]``, written in place where the
    caller lets go of ``dst``; ``idx[:n]`` repeats no row.  The slots from
    ``n`` on are not written, whatever their index."""
    return pl.pallas_call(
        _put_rows_kernel,
        out_shape=jax.ShapeDtypeStruct(dst.shape, dst.dtype),
        grid_spec=_row_copy_grid(2), input_output_aliases={2: 0}, name="put_rows", interpret=interpret,
    )(idx, jnp.reshape(n, (1,)).astype(jnp.int32), dst, rows)


def _rows_by_dma(x) -> bool:
    """Whether a float32 row as wide as x's [N, h] is whole 128-lane tiles."""
    return x.shape[-1] % 128 == 0


def _row_accumulator(x):
    """Zeros for the float32 sums over rows shaped like x [N, h]: [N, 1, h]
    where :func:`_add_rows` moves rows by DMA, [N, h] where by indexing.  The
    rank tells the two apart from there on; ``.reshape(x.shape)`` ends both."""
    n, h = x.shape
    return jnp.zeros((n, 1, h) if _rows_by_dma(x) else (n, h), jnp.float32)


def _add_rows(acc, tok, valid, rows):
    """``acc`` of :func:`_row_accumulator` with ``rows`` [tile, h] added to its
    rows ``tok``, for the slots that are ``valid``: a prefix in which no token
    repeats.  ``rows`` are zeros in the other slots."""
    if acc.ndim == 2:
        return acc.at[tok].add(rows)
    interpret = not platform.on_tpu()
    n = jnp.sum(valid, dtype=jnp.int32)
    seen = take_rows(acc, tok, n, interpret=interpret)
    return put_rows(acc, tok, n, seen + rows[:, None, :], interpret=interpret)


# ------------------------------------------- weight-gradient sums by expert


def _dw_span(assignments: int, n_experts: int, count: int, matrix: tuple[int, int], tile: int) -> int:
    """Tiles a segment of the backward loop holds for :func:`expert_dw`, or 0
    where the weight-gradient sums ride the loop, a tile's products added to
    them in place.  The kernels take experts of whole lane tiles
    (:func:`_dw_blocks`) whose rows, ``assignments`` shared evenly among
    ``n_experts``, fill two tiles or more: under that an expert's sum crosses
    HBM once either way, and leaving a tile's operands for the kernel costs
    more than its products save (PERF.md section 6, PR 39).  A segment is
    :data:`DW_SEGMENT` tiles, or as many as ``assignments`` over ``count`` held
    experts can fill where that is less."""
    if _dw_blocks(*matrix, tile) is None or assignments // n_experts < 2 * tile:
        return 0
    return min(DW_SEGMENT, assignments // tile + count)


def _put_tiles_kernel(at_ref, *refs):
    """``refs``: the tiles, the buffers (the outputs' memory), the outputs and
    one semaphore a copy.  Every tile goes to its buffer's rows from
    ``at_ref[0]`` on, all copies in flight at once."""
    n = len(refs) // 3
    tiles, outs, sems = refs[:n], refs[2 * n:3 * n], refs[3 * n]
    at = pl.multiple_of(at_ref[0], tiles[0].shape[0])
    copies = [pltpu.make_async_copy(tile, out.at[pl.ds(at, tile.shape[0])], sems.at[i])
              for i, (tile, out) in enumerate(zip(tiles, outs, strict=True))]
    for copy in copies:
        copy.start()
    for copy in copies:
        copy.wait()


@functools.partial(jax.jit, static_argnames="interpret")
def put_tiles(buffers, tiles, at, *, interpret: bool):
    """``buffers`` (arrays [R, w]) with ``tiles`` (one [tile, w] for each) as
    their rows from ``at`` on, a multiple of ``tile``; written in place where
    the caller lets go of ``buffers``.  One DMA a tile, from where XLA holds
    it (in the backward loop: VMEM) to HBM: XLA's ``dynamic_update_slice`` of
    such a tile wrote 0.2 GB/ms, a quarter of what the memory takes (PERF.md
    section 6, PR 39)."""
    n = len(buffers)
    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    return tuple(pl.pallas_call(
        _put_tiles_kernel,
        out_shape=[jax.ShapeDtypeStruct(b.shape, b.dtype) for b in buffers],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1, grid=(1,), in_specs=[anywhere] * (2 * n), out_specs=[anywhere] * n,
            scratch_shapes=[pltpu.SemaphoreType.DMA((n,))],
        ),
        input_output_aliases={1 + n + i: i for i in range(n)}, name="put_tiles", interpret=interpret,
    )(jnp.reshape(at, (1,)).astype(jnp.int32), *tiles, *buffers))


def _dw_writes(plan, span: int, most: int):
    """Times the backward pass writes an expert's weight-gradient sum, for one
    matrix of a layer.  In segments of ``span`` tiles an expert's tiles inside
    one segment are one block :func:`expert_dw` writes, so an expert that a
    segment's end splits is two; with the sums in the loop (``span`` 0) every
    tile is one.  ``most``: the tiles the shapes allow."""
    tiles = plan[3][-1]
    if span == 0:
        return tiles
    t = jnp.arange(most, dtype=jnp.int32)
    e = _tile_experts(t, plan)
    opens = (t % span == 0) | (e != jnp.roll(e, 1))
    return jnp.sum(opens & (t < tiles), dtype=jnp.int32)


def _dw_blocks(a: int, b: int, tile: int, itemsize: int = 2):
    """(rows, columns) of the block of an expert's [a, b] float32 sum that
    :func:`expert_dw` keeps in VMEM through the expert's tiles, or None where
    the kernel does not take the shape: ``a``, ``b`` and the tile whole 128-lane
    tiles.  Of the blocks that fit :data:`DW_VMEM_BYTES` with their operands'
    tiles (``itemsize`` bytes an element) the one that fetches the least: the
    left operand comes once for every block of columns, the right one once for
    every block of rows."""
    if a % 128 or b % 128 or tile % 128:
        return None
    fits = [(ba, bb) for ba in range(128, a + 1, 128) for bb in range(128, b + 1, 128)
            if a % ba == 0 and b % bb == 0 and 8 * ba * bb + 2 * itemsize * tile * (ba + bb) <= DW_VMEM_BYTES]
    return min(fits, key=lambda block: (b // block[1] * a + a // block[0] * b, -block[1]))


def _expert_dw_kernel(experts_ref, n_ref, lhs_ref, rhs_ref, sums_ref, out_ref, sem):
    """One step: tile ``t``'s product into block (i, j) of its expert's sum.
    The block stays in VMEM while the expert stays the same and goes to HBM
    when the next expert's first tile comes (the pipeline's write of a block
    whose index changes).  The call's first expert may bring a sum: its block
    is read; every later expert starts from its first product."""
    i, j, t = (pl.program_id(axis) for axis in range(3))
    e = experts_ref[t]
    _, ba, bb = out_ref.shape

    @pl.when(t == 0)
    def _():
        held = pltpu.make_async_copy(sums_ref.at[pl.ds(e, 1), pl.ds(i * ba, ba), pl.ds(j * bb, bb)], out_ref, sem)
        held.start()
        held.wait()

    @pl.when(t < n_ref[0])
    def _():
        part = jax.lax.dot_general(lhs_ref[...], rhs_ref[...], (((0,), (0,)), ((), ())),
                                   preferred_element_type=jnp.float32)
        opens = (t > 0) & (e != experts_ref[jnp.maximum(t - 1, 0)])

        @pl.when(opens)
        def _():
            out_ref[0] = part

        @pl.when(jnp.logical_not(opens))
        def _():
            out_ref[0] += part


@functools.partial(jax.jit, static_argnames="interpret")
def expert_dw(sums, lhs, rhs, experts, n, *, interpret: bool):
    """``sums`` [count, a, b] float32 with ``lhs[rows of t].T @ rhs[rows of t]``
    added to ``sums[experts[t]]`` for every tile ``t < n``, in place where the
    caller lets go of ``sums``: ``lhs`` [R, a], ``rhs`` [R, b] hold ``experts``
    [R / tile] int32 tiles of rows, a tile's rows all of one expert and an
    expert's tiles next to each other.  Every expert but ``experts[0]`` must
    come with zeros: its sum is written, not added to.  The tiles from ``n``
    on are not fetched."""
    count, a, b = sums.shape
    tiles = experts.shape[0]
    tile = lhs.shape[0] // tiles
    ba, bb = _dw_blocks(a, b, tile, lhs.dtype.itemsize)
    n = jnp.reshape(n, (1,)).astype(jnp.int32)
    experts = jnp.minimum(experts, count - 1)

    def ran(t, n_ref):  # past the run the last tile's blocks stay: nothing moves
        return jnp.maximum(jnp.minimum(t, n_ref[0] - 1), 0)

    return pl.pallas_call(
        _expert_dw_kernel,
        out_shape=jax.ShapeDtypeStruct(sums.shape, sums.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(a // ba, b // bb, tiles),
            in_specs=[
                pl.BlockSpec((tile, ba), lambda i, j, t, experts_ref, n_ref: (ran(t, n_ref), i)),
                pl.BlockSpec((tile, bb), lambda i, j, t, experts_ref, n_ref: (ran(t, n_ref), j)),
                pl.BlockSpec(memory_space=pl.ANY),
            ],
            out_specs=pl.BlockSpec((1, ba, bb), lambda i, j, t, experts_ref, n_ref: (experts_ref[ran(t, n_ref)], i, j)),
            scratch_shapes=[pltpu.SemaphoreType.DMA(())],
        ),
        input_output_aliases={4: 0}, name="expert_dw", interpret=interpret,
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=DW_VMEM_LIMIT
        ),
    )(experts, n, lhs, rhs, sums)


def _expert_dw_twin(sums, lhs, rhs, experts, n):
    """:func:`expert_dw` by indexing: one product and one ``.at[].add`` a tile."""
    tile = lhs.shape[0] // experts.shape[0]

    def add(t, sums):
        left, right = (jax.lax.dynamic_slice_in_dim(m, t * tile, tile) for m in (lhs, rhs))
        return sums.at[experts[t]].add(jnp.dot(left.T, right, preferred_element_type=jnp.float32))

    return jax.lax.fori_loop(0, n, add, sums)


def _swiglu(xt, wg, wu):
    g = jnp.dot(xt, wg, preferred_element_type=jnp.float32)
    u = jnp.dot(xt, wu, preferred_element_type=jnp.float32)
    return g, u, jax.nn.silu(g) * u


def _across(a, b):
    """``a @ b.T``, float32: the second operand read as it lies."""
    return jax.lax.dot_general(a, b, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32)


def _tile_mid(xt, wg, wu):
    """The SwiGLU's output for a tile's rows, in their precision."""
    return _swiglu(xt, wg, wu)[2].astype(xt.dtype)


def _tile_down(mid, wt, wd):
    """A tile's weighted rows, float32: the down product times ``wt`` [rows, 1]."""
    return jnp.dot(mid, wd, preferred_element_type=jnp.float32) * wt


def _tile_outputs(xt, wt, wg, wu, wd):
    """``wt * expert(xt)``, float32."""
    return _tile_down(_tile_mid(xt, wg, wu), wt, wd)


def _tile_operands(xt, dyt, wt, wg, wu, wd):
    """A tile's part of the backward pass up to ``dx``'s rows → (the SwiGLU's
    output and the three gradients ``dyw``, ``dg``, ``du`` in the operands'
    precision: with ``xt`` the operands of the weight-gradient products, zeros
    where ``wt`` [rows, 1] is: padding adds nothing to a sum; each row's
    ``<expert output, dy>`` [rows, 1], the gradient of its routing weight)."""
    lo, f32 = xt.dtype, jnp.float32
    g, u, mid = _swiglu(xt, wg, wu)
    mid_lo = mid.astype(lo)
    yt = jnp.dot(mid_lo, wd, preferred_element_type=f32)
    dw_t = jnp.sum(yt * dyt.astype(f32), axis=-1, keepdims=True)
    dyw = (dyt.astype(f32) * wt).astype(lo)
    dmid = _across(dyw, wd)
    sig = jax.nn.sigmoid(g)
    dg = (dmid * u * sig * (1.0 + g * (1.0 - sig))).astype(lo)
    du = (dmid * g * sig).astype(lo)
    return mid_lo, dyw, dg, du, dw_t


def _tile_dx(dg, du, wg, wu):
    """``dx``'s rows of a tile, float32, from :func:`_tile_operands`' ``dg``, ``du``."""
    return _across(dg, wg) + _across(du, wu)


# ------------------------------------------------ a segment's tiles as one kernel


def _grouped(h: int, f: int, tile: int, itemsize: int = 2) -> bool:
    """Whether the grouped kernels take experts of [h, f] at this tile: ``h``,
    ``f`` and the tile whole 128-lane tiles, and a whole expert's three
    matrices twice within :data:`GROUP_WEIGHT_BYTES`."""
    return not (h % 128 or f % 128 or tile % 128) and 2 * 3 * h * f * itemsize <= GROUP_WEIGHT_BYTES


def _segment(assignments: int, count: int, matrix: tuple[int, int], tile: int, itemsize: int = 2) -> int:
    """Tiles a call of the grouped kernels walks, or 0 where the tile loop
    runs, a turn a tile (:func:`_grouped` says which shapes the kernels take):
    :data:`GROUP_SEGMENT`, or as many as ``assignments`` over ``count`` held
    experts can fill where that is less."""
    if not _grouped(*matrix, tile, itemsize):
        return 0
    return min(GROUP_SEGMENT, assignments // tile + count)


def _group_rows(tile: int) -> int:
    """Rows of a tile the grouped kernels take a grid step."""
    return GROUP_ROWS if tile % GROUP_ROWS == 0 else 128


def _by_lanes(wt):
    """A float32 a row as a row of 128 lanes, all the same: how it reaches a kernel's block of rows."""
    return jnp.broadcast_to(wt.astype(jnp.float32)[:, None], (wt.shape[0], 128))


def _block_held(counts_ref, i, rows: int, sub: int):
    """Rows of block ``i`` (``rows`` rows, ``sub`` blocks a tile) that hold an
    assignment: none where the block lies past its expert's last row or past
    the run.  ``counts_ref``: each tile's, and after them the run's last block."""
    return jnp.clip(counts_ref[i // sub] - (i % sub) * rows, 0, rows)


def _block_fetched(counts_ref, i, rows: int, sub: int):
    """The block whose rows and weights stand in VMEM at step ``i``: ``i``
    where it runs, else the last one that ran before it (its tile's, or the
    run's): a block index that does not change moves nothing."""
    tile, tiles = i // sub, counts_ref.shape[0] - 1
    mine = counts_ref[tile]
    before = jnp.where(mine > 0, tile * sub + (mine - 1) // rows, counts_ref[tiles])
    return jnp.where(mine > (i % sub) * rows, i, before)


class _Block:
    """Where a grid step of the grouped kernels stands: block ``i`` of
    ``rows`` rows, how many of its rows hold an assignment, whether it is
    padding (past its expert's last row inside a tile that runs: its outputs
    are zeros), whether it is the call's last, whether the next one runs."""

    def __init__(self, counts_ref, rows: int, sub: int):
        self.i, self.rows = pl.program_id(0), rows
        steps = pl.num_programs(0)
        self.held = _block_held(counts_ref, self.i, rows, sub)
        self.padding = (self.held == 0) & (counts_ref[self.i // sub] > 0)
        self.last = self.i == steps - 1
        self.next_runs = jnp.logical_not(self.last) & (_block_held(counts_ref, jnp.minimum(self.i + 1, steps - 1), rows, sub) > 0)


ROW_BURST = 8  # row copies a loop turn starts, and rows one wait counts


def _start_rows(n, copy):
    """Start ``copy(r)`` for every r < n, :data:`ROW_BURST` a loop turn: inside
    a kernel that multiplies, a turn's overhead is time the matrix unit waits."""
    def burst(j, carry):
        for r in range(ROW_BURST):
            copy(j * ROW_BURST + r).start()
        return carry

    jax.lax.fori_loop(0, n // ROW_BURST, burst, 0)
    jax.lax.fori_loop(n // ROW_BURST * ROW_BURST, n, lambda r, carry: (copy(r).start(), carry)[1], 0)


def _wait_rows(n, landed):
    """Wait until ``n`` of the row copies that share ``landed``'s semaphore have
    landed; ``landed(k)`` is a copy of ``k`` rows, whose wait counts as many bytes."""
    jax.lax.fori_loop(0, n // ROW_BURST, lambda j, carry: (landed(ROW_BURST).wait(), carry)[1], 0)
    jax.lax.fori_loop(0, n % ROW_BURST, lambda j, carry: (landed(1).wait(), carry)[1], 0)


def _gathered(tok_ref, block, src_ref, buf, sem):
    """→ ``take``: ``take()`` gives the float32 rows ``src[tok]`` of this step's
    block, [rows, width]; the next block's are started here, one DMA a row from
    HBM into one of ``buf``'s [2, rows, 1, width] (``src`` [N, 1, width]: what
    lets a DMA take one row, see the module's docstring), so that they land
    while this block's products run.  Every row of a block that runs comes,
    the slots past an expert's last row with the row of some token, as the
    plan's clipped index gives it: finite, and multiplied by zeros."""
    i, rows = block.i, block.rows

    def rows_of(step, slot):
        return lambda r: pltpu.make_async_copy(
            src_ref.at[pl.ds(tok_ref[step * rows + r], 1)], buf.at[slot, pl.ds(r, 1)], sem.at[slot])

    @pl.when((i == 0) & (block.held > 0))
    def _():
        _start_rows(rows, rows_of(0, 0))

    @pl.when(block.next_runs)
    def _():
        _start_rows(rows, rows_of(i + 1, (i + 1) % 2))

    def take():
        pltpu.make_async_copy(src_ref.at[pl.ds(0, rows)], buf.at[i % 2], sem.at[i % 2]).wait()  # all of a block's rows at once
        return buf[i % 2].reshape(rows, buf.shape[-1])

    return take


def _rows_back(acc_ref, buf, sem, k):
    """A copy of ``k`` rows on the semaphore of the rows :func:`_add_block` sends back."""
    return pltpu.make_async_copy(buf.at[pl.ds(0, k)], acc_ref.at[pl.ds(0, k)], sem.at[1])


def _add_block(tok_ref, block, acc_ref, buf, sem, pending, between):
    """``acc[tok] += rows`` for the block's first ``held`` rows, in which no
    token repeats: ``acc`` [N, 1, width] float32 stays in HBM, the rows it
    holds come into ``buf`` [rows, 1, width] a DMA a row, are added and go
    back a DMA a row.  ``rows = between()`` is computed while they come; the
    block before may hold the same tokens (another expert's), so its rows must
    have landed first: ``pending`` counts them."""
    i, rows, held = block.i, block.rows, block.held

    def seen(r):
        return pltpu.make_async_copy(acc_ref.at[pl.ds(tok_ref[i * rows + r], 1)], buf.at[pl.ds(r, 1)], sem.at[0])

    def back(r):
        return pltpu.make_async_copy(buf.at[pl.ds(r, 1)], acc_ref.at[pl.ds(tok_ref[i * rows + r], 1)], sem.at[1])

    _wait_rows(pending[0], lambda k: _rows_back(acc_ref, buf, sem, k))
    _start_rows(held, seen)
    added = between()
    _wait_rows(held, lambda k: pltpu.make_async_copy(acc_ref.at[pl.ds(0, k)], buf.at[pl.ds(0, k)], sem.at[0]))

    # a branch of its own (always taken: the caller's condition again) so that the rows are rounded before
    # the sum in the interpreter too: in one expression XLA's CPU backend contracts a product and a sum
    @pl.when(held > 0)
    def _():
        buf[...] = (buf[...].reshape(added.shape) + added).reshape(buf.shape)

    _start_rows(held, back)
    pending[0] = held


def _experts_fwd_kernel(tok_ref, experts_ref, counts_ref, x_ref, wt_ref, wg_ref, wu_ref, wd_ref, y_in_ref,
                        y_ref, xbuf, ybuf, xsem, ysem, pending, *, sub):
    del experts_ref, y_in_ref  # the block specs read the first; the second is y_ref's memory
    block = _Block(counts_ref, ybuf.shape[0], sub)
    lo = wg_ref.dtype

    @pl.when(block.i == 0)
    def _():
        pending[0] = 0

    take_x = _gathered(tok_ref, block, x_ref, xbuf, xsem)

    @pl.when(block.held > 0)
    def _():
        mid = _tile_mid(take_x().astype(lo), wg_ref[0], wu_ref[0])
        _add_block(tok_ref, block, y_ref, ybuf, ysem, pending, lambda: _tile_down(mid, wt_ref[:, :1], wd_ref[0]))

    @pl.when(block.last)
    def _():
        _wait_rows(pending[0], lambda k: _rows_back(y_ref, ybuf, ysem, k))


def _experts_bwd_kernel(tok_ref, experts_ref, counts_ref, x_ref, dy_ref, wt_ref, wg_ref, wu_ref, wd_ref, dx_in_ref,
                        dx_ref, xs_ref, mid_ref, dyw_ref, dg_ref, du_ref, dw_ref,
                        xbuf, dybuf, dxbuf, xsem, dysem, dxsem, pending, *, sub):
    del experts_ref, dx_in_ref
    block = _Block(counts_ref, dxbuf.shape[0], sub)
    lo = wg_ref.dtype
    operands = (xs_ref, mid_ref, dyw_ref, dg_ref, du_ref)

    @pl.when(block.i == 0)
    def _():
        pending[0] = 0

    take_x = _gathered(tok_ref, block, x_ref, xbuf, xsem)
    take_dy = _gathered(tok_ref, block, dy_ref, dybuf, dysem)

    @pl.when(block.held > 0)
    def _():
        xt, dyt = take_x().astype(lo), take_dy().astype(lo)
        wg, wu = wg_ref[0], wu_ref[0]
        *outs, dw_t = _tile_operands(xt, dyt, wt_ref[:, :1], wg, wu, wd_ref[0])
        for ref, out in zip(operands, (xt, *outs), strict=True):
            ref[...] = out
        dw_ref[...] = jnp.broadcast_to(dw_t, dw_ref.shape)
        _add_block(tok_ref, block, dx_ref, dxbuf, dxsem, pending, lambda: _tile_dx(outs[2], outs[3], wg, wu))

    @pl.when(block.padding)
    def _():
        for ref in operands:
            ref[...] = jnp.zeros(ref.shape, ref.dtype)

    @pl.when(block.last)
    def _():
        _wait_rows(pending[0], lambda k: _rows_back(dx_ref, dxbuf, dxsem, k))


def _segment_grid(acc, gathered, tok, wt, weights, experts, counts, outs):
    """The call of a grouped kernel over a segment's tiles → (blocks a tile,
    ``pallas_call``'s arguments, the call's operands).  ``acc`` [N, 1, h]
    float32 (the sums the kernel adds its rows to, in place where the caller
    lets go of them) and ``gathered`` (arrays [N, 1, h] float32 whose rows
    ``tok`` [tiles * tile] the kernel reads) stay in HBM; the rows' weights
    ``wt`` and ``outs`` ((width, dtype) of each array [tiles * tile, width]
    that comes back) go a block of rows a grid step, ``weights`` [count, ...]
    by the tile's expert: a block whose index does not change is not fetched
    again, so an expert's matrices come once for its consecutive tiles.
    ``counts`` [tiles]: the rows of each tile that hold an assignment, 0 from
    the run's last tile on.  A block without one is neither fetched nor
    multiplied; inside a tile that runs it is written, as zeros."""
    tiles = experts.shape[0]
    tile = tok.shape[0] // tiles
    rows = _group_rows(tile)
    sub = tile // rows
    experts = jnp.minimum(experts, weights[0].shape[0] - 1)
    counts = counts.astype(jnp.int32)
    run = jnp.sum(counts > 0)  # the tiles that run are the first ones; after their counts, the run's last block
    counts = jnp.append(counts, jnp.maximum((run - 1) * sub + (counts[jnp.maximum(run - 1, 0)] - 1) // rows, 0))

    def fetched(i, counts_ref):
        return _block_fetched(counts_ref, i, rows, sub)

    def written(i, counts_ref):  # as far as the run's last tile every block is written; past it the last one stays
        return jnp.where(counts_ref[i // sub] > 0, i, counts_ref[tiles])

    def by_rows(width, where):
        return pl.BlockSpec((rows, width), lambda i, tok_ref, experts_ref, counts_ref: (where(i, counts_ref), 0))

    def by_expert(m):
        return pl.BlockSpec((1, *m.shape[1:]),
                            lambda i, tok_ref, experts_ref, counts_ref: (experts_ref[fetched(i, counts_ref) // sub], 0, 0))

    anywhere = pl.BlockSpec(memory_space=pl.ANY)
    h = acc.shape[-1]
    row_buffers = [pltpu.VMEM((2, rows, 1, h), jnp.float32) for _ in gathered] + [pltpu.VMEM((rows, 1, h), jnp.float32)]
    semaphores = [pltpu.SemaphoreType.DMA((2,)) for _ in range(len(gathered) + 1)]
    call = dict(
        out_shape=[jax.ShapeDtypeStruct(acc.shape, acc.dtype)]
        + [jax.ShapeDtypeStruct((tiles * tile, width), dtype) for width, dtype in outs],
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3, grid=(tiles * sub,),
            in_specs=[anywhere] * len(gathered) + [by_rows(128, fetched)] + [by_expert(m) for m in weights] + [anywhere],
            out_specs=[anywhere] + [by_rows(width, written) for width, _ in outs],
            scratch_shapes=[*row_buffers, *semaphores, pltpu.SMEM((1,), jnp.int32)],
        ),
        input_output_aliases={3 + len(gathered) + 1 + len(weights): 0},
        compiler_params=pltpu.CompilerParams(dimension_semantics=("arbitrary",), vmem_limit_bytes=GROUP_VMEM_LIMIT),
    )
    return sub, call, (tok, experts, counts, *gathered, _by_lanes(wt), *weights, acc)


@functools.partial(jax.jit, static_argnames="interpret")
def experts_fwd(y, x32, tok, wt, wg, wu, wd, experts, counts, *, interpret: bool):
    """``y`` [N, 1, h] float32 with ``wt * expert(x32[tok])`` added to its rows
    ``tok``, for the slots that hold an assignment: ``tok``, ``wt`` [R] are
    ``experts`` [R / tile] int32 tiles of slots, a tile's all of one expert,
    the first ``counts`` [R / tile] of them holding an assignment (no token
    twice in a tile), an expert's tiles next to each other; ``x32`` [N, 1, h]
    float32 holds values of the weights' precision, the weights [count, ...].
    A tile's rows join ``y`` in the tiles' order."""
    sub, call, operands = _segment_grid(y, (x32,), tok, wt, (wg, wu, wd), experts, counts, [])
    (y,) = pl.pallas_call(functools.partial(_experts_fwd_kernel, sub=sub), name="experts_fwd", interpret=interpret, **call)(*operands)
    return y


@functools.partial(jax.jit, static_argnames="interpret")
def experts_bwd(dx, x32, dy32, tok, wt, wg, wu, wd, experts, counts, *, interpret: bool):
    """:func:`experts_fwd`'s operands, the cotangent ``dy32`` [N, 1, h] and the
    sums ``dx`` [N, 1, h] float32 → (``dx`` with the tiles' rows added; ``xs``
    [R, h], ``mid`` [R, f], ``dyw`` [R, h], ``dg`` and ``du`` [R, f] in the
    weights' precision: the operands :func:`expert_dw` takes, zeros in the
    slots of a tile past its expert's last block; the routing weights'
    gradients [R] float32), as :func:`_tile_operands` and :func:`_tile_dx`
    give a tile's.  What the arrays hold from the run's last tile on is not
    written."""
    h, f, lo = x32.shape[-1], wg.shape[2], wg.dtype
    sub, call, operands = _segment_grid(
        dx, (x32, dy32), tok, wt, (wg, wu, wd), experts, counts, [(h, lo), (f, lo), (h, lo), (f, lo), (f, lo), (128, jnp.float32)])
    dx, *held, dw = pl.pallas_call(
        functools.partial(_experts_bwd_kernel, sub=sub), name="experts_bwd", interpret=interpret, **call)(*operands)
    return dx, *held, dw[:, 0]


def _segment_rows(t0, plan, tile: int, k: int, span: int, w_flat):
    """Tiles ``t0`` to ``t0 + span`` → (expert of each, ``count`` from the run's
    last tile on; assignment of each slot [span, tile]; which slots hold one, a
    prefix of each tile and none past the run; the slots' routing weights,
    zeros in the other slots).  A slot's token is its assignment over ``k``."""
    order, sizes, starts, tile_ends = plan
    count = sizes.shape[0]
    t = t0 + jnp.arange(span, dtype=jnp.int32)
    e = _tile_experts(t, plan)
    held = jnp.minimum(e, count - 1)
    row0 = starts[held] + (t - (tile_ends[held] - (sizes[held] + tile - 1) // tile)) * tile
    rows = row0[:, None] + jnp.arange(tile, dtype=jnp.int32)
    valid = (rows < (starts + sizes)[held][:, None]) & (e < count)[:, None]
    a = order[jnp.clip(rows, 0, order.shape[0] - 1)]
    return e, a, valid, jnp.where(valid, w_flat[a], 0.0)


# a step's blocks twice (three float32 outputs of [256, 1, 2048] are 12 MB) pass the 16 MB a kernel has unasked
_STAGE_PARAMS = pltpu.CompilerParams(dimension_semantics=("parallel",), vmem_limit_bytes=48 * 2**20)


def _stage_block(n: int) -> int:
    """Rows a grid step of :func:`stage_rows` and :func:`unstage_rows` takes: the
    largest of 256 down to 8 that divides ``n``, or 0 where none does."""
    return next((rows for rows in (256, 128, 64, 32, 16, 8) if n % rows == 0), 0)


def _stage_rows_kernel(*refs):
    *pairs, zeros_ref = refs
    for src, dst in zip(pairs[: len(pairs) // 2], pairs[len(pairs) // 2:], strict=True):
        dst[...] = src[...].astype(jnp.float32).reshape(dst.shape)
    zeros_ref[...] = jnp.zeros(zeros_ref.shape, zeros_ref.dtype)


@functools.partial(jax.jit, static_argnames="interpret")
def stage_rows(arrays, *, interpret: bool):
    """``arrays`` (each [N, h]) → (each as float32 [N, 1, h], the layout a DMA
    takes one row of, and zeros of that shape: the sums the kernels add to).
    One pass at the memory's speed: XLA writes that layout at 0.35 TB/s (a
    convert of [32768, 2048] 1.15 ms, zeros 0.82; PERF.md section 6, PR 53)."""
    n, h = arrays[0].shape
    rows = _stage_block(n)
    staged = jax.ShapeDtypeStruct((n, 1, h), jnp.float32)
    if not rows:
        return tuple(m.astype(jnp.float32)[:, None, :] for m in arrays), jnp.zeros(staged.shape, staged.dtype)
    out = pl.BlockSpec((rows, 1, h), lambda i: (i, 0, 0))
    *staged, zeros = pl.pallas_call(
        _stage_rows_kernel, out_shape=[staged] * (len(arrays) + 1), grid=(n // rows,),
        in_specs=[pl.BlockSpec((rows, h), lambda i: (i, 0))] * len(arrays), out_specs=[out] * (len(arrays) + 1),
        name="stage_rows", interpret=interpret, compiler_params=_STAGE_PARAMS,
    )(*arrays)
    return tuple(staged), zeros


def _unstage_rows_kernel(acc_ref, out_ref):
    out_ref[...] = acc_ref[...].reshape(out_ref.shape).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def unstage_rows(acc, dtype, *, interpret: bool):
    """The sums ``acc`` [N, 1, h] float32 → [N, h] in ``dtype``, one pass."""
    n, _, h = acc.shape
    rows = _stage_block(n)
    if not rows:
        return acc.reshape(n, h).astype(dtype)
    return pl.pallas_call(
        _unstage_rows_kernel, out_shape=jax.ShapeDtypeStruct((n, h), dtype), grid=(n // rows,),
        in_specs=[pl.BlockSpec((rows, 1, h), lambda i: (i, 0, 0))], out_specs=pl.BlockSpec((rows, h), lambda i: (i, 0)),
        name="unstage_rows", interpret=interpret, compiler_params=_STAGE_PARAMS,
    )(acc)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _held_experts(x, w, plan, wg, wu, wd, tile, span, grouped):
    """``sum over held assignments of w * expert(x)``: x [N, h], w [N, k] f32,
    ``plan`` of :func:`_tile_plan` over the N x k assignments, weights
    [count, ...] → [N, h].  ``grouped``: whether a segment of ``span`` tiles
    (:func:`_segment`) is one call of :func:`experts_fwd`; else the tile loop,
    the kernels' twin, and ``span`` of :func:`_dw_span` is the backward
    pass's."""
    k = w.shape[1]
    w_flat = w.reshape(-1)
    wg, wu, wd = (m.astype(x.dtype) for m in (wg, wu, wd))
    tiles = plan[3][-1]

    def run_segment(carry):
        t0, y = carry
        e, a, valid, wt = _segment_rows(t0, plan, tile, k, span, w_flat)
        y = experts_fwd(y, x32, (a // k).reshape(-1), wt.reshape(-1), wg, wu, wd, e,
                        jnp.sum(valid, axis=1), interpret=interpret)
        return t0 + span, y

    def run_tile(carry):
        # a tile's rows join ``y`` a turn late: carried through the loop the
        # weighted rows are rounded before the sum on every backend (in one
        # expression XLA's CPU backend contracts product and sum into one
        # rounding, and the kernels would not equal their twin bit for bit)
        t, y, late = carry
        y = _add_rows(y, *late)
        e, a, tok, valid, _ = _tile_rows(t, plan, tile, k)
        yt = _tile_outputs(x[tok], jnp.where(valid, w_flat[a], 0.0)[:, None], wg[e], wu[e], wd[e])
        return t + 1, y, (tok, valid, yt)

    if grouped:
        interpret = not platform.on_tpu()
        (x32,), zeros = stage_rows((x,), interpret=interpret)
        _, y = jax.lax.while_loop(lambda c: c[0] < tiles, run_segment, (jnp.int32(0), zeros))
        return unstage_rows(y, x.dtype, interpret=interpret)
    nothing = (jnp.zeros(tile, jnp.int32), jnp.zeros(tile, bool), jnp.zeros((tile, x.shape[1]), jnp.float32))
    _, y, late = jax.lax.while_loop(lambda c: c[0] < tiles, run_tile, (jnp.int32(0), _row_accumulator(x), nothing))
    y = _add_rows(y, *late)
    return y.reshape(x.shape).astype(x.dtype)


def _held_experts_fwd(x, w, plan, wg, wu, wd, tile, span, grouped):
    return _held_experts(x, w, plan, wg, wu, wd, tile, span, grouped), (x, w, plan, wg, wu, wd)


def _held_experts_bwd(tile, span, grouped, saved, dy):
    x, w, plan, wg, wu, wd = saved
    n, k = w.shape
    w_flat = w.reshape(-1)
    lo = x.dtype
    wg_lo, wu_lo, wd_lo = (m.astype(lo) for m in (wg, wu, wd))
    dy = dy.astype(lo)
    f32 = jnp.float32
    tiles = plan[3][-1]
    interpret = not platform.on_tpu()
    sums = tuple(jnp.zeros(m.shape, f32) for m in (wg, wu, wd))

    def by_expert(sums, operands, experts, run):
        xs, mids, dyws, dgs, dus = operands
        return tuple(expert_dw(held, lhs, rhs, experts, run, interpret=interpret)
                     for held, lhs, rhs in zip(sums, (xs, xs, mids), (dgs, dus, dyws), strict=True))

    def run_grouped(carry):
        # the kernel leaves a segment's operands in row buffers for one :func:`expert_dw` a matrix
        t0, dx, (dw_slots, a_slots), sums = carry
        e, a, valid, wt = _segment_rows(t0, plan, tile, k, span, w_flat)
        dx, *operands, dws = experts_bwd(dx, x32, dy32, (a // k).reshape(-1), wt.reshape(-1), wg_lo, wu_lo, wd_lo, e,
                                         jnp.sum(valid, axis=1), interpret=interpret)
        # every slot's weight gradient and whose it is (none's: past the last assignment), for one scatter at the end
        dw_slots = jax.lax.dynamic_update_slice(dw_slots, dws, (t0 * tile,))
        a_slots = jax.lax.dynamic_update_slice(a_slots, jnp.where(valid, a, n * k).reshape(-1), (t0 * tile,))
        return t0 + span, dx, (dw_slots, a_slots), by_expert(sums, operands, e, jnp.minimum(tiles - t0, span))

    def tile_grads(t, dx, dw_rows):
        """Tile ``t`` → (its expert, ``dx`` and ``dw_rows`` with the tile's
        part, the operands of its three weight-gradient products)."""
        e, a, tok, valid, row0 = _tile_rows(t, plan, tile, k)
        xt = x[tok]
        wt = jnp.where(valid, w_flat[a], 0.0)[:, None]
        mid_lo, dyw, dg, du, dw_t = _tile_operands(xt, dy[tok], wt, wg_lo[e], wu_lo[e], wd_lo[e])
        seen = jax.lax.dynamic_slice(dw_rows, (row0,), (tile,))
        dw_rows = jax.lax.dynamic_update_slice(dw_rows, jnp.where(valid, dw_t[:, 0], seen), (row0,))
        dxt = _tile_dx(dg, du, wg_lo[e], wu_lo[e])
        return e, _add_rows(dx, tok, valid, dxt), dw_rows, (xt, mid_lo, dyw, dg, du)

    def run_tile(carry):
        t, dx, dw_rows, (dwg, dwu, dwd) = carry
        e, dx, dw_rows, (xt, mid_lo, dyw, dg, du) = tile_grads(t, dx, dw_rows)
        dwd = dwd.at[e].add(jnp.dot(mid_lo.T, dyw, preferred_element_type=f32))
        dwg = dwg.at[e].add(jnp.dot(xt.T, dg, preferred_element_type=f32))
        dwu = dwu.at[e].add(jnp.dot(xt.T, du, preferred_element_type=f32))
        return t + 1, dx, dw_rows, (dwg, dwu, dwd)

    def run_segment(carry):
        # a tile's operands wait in row buffers for one :func:`expert_dw` a matrix
        t0, dx, dw_rows, sums, held = carry
        t1 = jnp.minimum(t0 + span, tiles)

        def hold_tile(carry):
            t, dx, dw_rows, held = carry
            _, dx, dw_rows, operands = tile_grads(t, dx, dw_rows)
            return t + 1, dx, dw_rows, put_tiles(held, operands, (t - t0) * tile, interpret=interpret)

        _, dx, dw_rows, held = jax.lax.while_loop(lambda c: c[0] < t1, hold_tile, (t0, dx, dw_rows, held))
        experts = _tile_experts(t0 + jnp.arange(span, dtype=jnp.int32), plan)
        return t1, dx, dw_rows, by_expert(sums, held, experts, t1 - t0), held

    if grouped:
        (x32, dy32), zeros = stage_rows((x, dy), interpret=interpret)
        # a tile's slots, tile after tile; a segment may reach past the last tile the shapes allow
        most = -(-(n * k // tile + wg.shape[0]) // span) * span * tile
        slots = (jnp.zeros(most, f32), jnp.full(most, n * k, jnp.int32))
        _, dx, (dw_slots, a_slots), (dwg, dwu, dwd) = jax.lax.while_loop(
            lambda c: c[0] < tiles, run_grouped, (jnp.int32(0), zeros, slots, sums))
        dw = jnp.zeros(n * k, f32).at[a_slots].set(dw_slots, mode="drop").reshape(n, k)
        dx = unstage_rows(dx, x.dtype, interpret=interpret)
    else:
        init = (jnp.int32(0), _row_accumulator(x), jnp.zeros(n * k + tile, f32), sums)  # a tile may reach past the last row
        body = run_tile
        if span:
            # not written: the loop fills a tile's rows before a kernel reads them,
            # and none reads the tiles a segment stops short of
            h, f = wg.shape[1:]
            init = (*init, tuple(jax.lax.empty((span * tile, width), lo) for width in (h, f, h, f, f)))
            body = run_segment
        _, dx, dw_rows, (dwg, dwu, dwd), *_ = jax.lax.while_loop(lambda c: c[0] < tiles, body, init)
        dw = jnp.zeros(n * k, f32).at[plan[0]].set(dw_rows[: n * k]).reshape(n, k)
    return (dx.reshape(x.shape).astype(x.dtype), dw.astype(w.dtype), None,
            dwg.astype(wg.dtype), dwu.astype(wu.dtype), dwd.astype(wd.dtype))


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def _routed_share(x, top_e, w, wg, wu, wd, *, n_experts, held, tile, axes):
    """One shard's rows through the experts held here.  → (y, expert loads
    [count], the slots multiplied forward, the held assignments that went
    through the grouped kernels and the times the backward pass writes an
    expert's weight-gradient sum a matrix, all summed over ``axes``)."""
    first, count = held
    shape = x.shape
    k = top_e.shape[-1]
    local = top_e.reshape(-1) - first
    local = jnp.where((local >= 0) & (local < count), local, count)
    tile = min(tile, -(-local.shape[0] // 8) * 8)
    segment = _segment(local.shape[0], count, wg.shape[1:], tile, x.dtype.itemsize)
    span = segment or _dw_span(local.shape[0], n_experts, count, wg.shape[1:], tile)
    plan = _tile_plan(local, count, tile)
    y = _held_experts(x.reshape(-1, shape[-1]), w.reshape(-1, k), plan, wg, wu, wd, tile, span, bool(segment))
    loads = plan[1]
    # the tile loop multiplies every slot of a tile it runs; the kernels the blocks up to an expert's last row
    block = _group_rows(tile) if segment else tile
    slots = jnp.sum((loads + block - 1) // block) * block
    grouped = jnp.sum(loads) if segment else jnp.int32(0)
    writes = _dw_writes(plan, span, local.shape[0] // tile + count)
    if axes:
        loads, slots, grouped, writes = jax.lax.psum((loads, slots, grouped, writes), axes)
    return y.reshape(shape), loads, slots, grouped, writes


def shared_expert(x, p):
    """The expert every token takes: x [..., h] → [..., h]; under a sigmoid
    gate of its own where the weights hold one (``p["gate"]`` [h])."""
    dtype = x.dtype
    with jax.named_scope(SHARED_SCOPE):
        mid = jax.nn.silu(x @ p["w_gate"].astype(dtype)) * (x @ p["w_up"].astype(dtype))
        out = mid @ p["w_down"].astype(dtype)
        if "gate" not in p:
            return out
        gate = jax.nn.sigmoid(jnp.einsum("...h,h->...", x.astype(jnp.float32), p["gate"].astype(jnp.float32)))
        return (out * gate[..., None]).astype(dtype)


def held_experts(x, top_e, w, p, *, n_experts: int, held: tuple[int, int],
                 batch_sharding=None, tile: int | None = None):
    """The held experts' part of a routed layer: x [..., h], the routing
    ``top_e``, ``w`` [..., k] of a routing rule → (y [..., h], counts).
    Its backward pass needs ``x``, the routing and the weights and nothing it
    computed, so a caller that rematerialises its layer can leave this call
    outside: the tiles then run once forward, not twice."""
    first, count = held
    if not (0 <= first and first + count <= n_experts and p["w_gate"].shape[0] == count):
        raise ValueError(f"held={held} does not fit {n_experts} experts and {p['w_gate'].shape[0]} held weights")
    tile = tile or EXPERT_TILE
    weights = (p["w_gate"], p["w_up"], p["w_down"])
    with jax.named_scope(EXPERTS_SCOPE):
        if batch_sharding is None:
            y, loads, tile_rows, grouped, dw_writes = _routed_share(
                x, top_e, w, *weights, n_experts=n_experts, held=held, tile=tile, axes=())
        else:
            spec = batch_sharding.spec
            y, loads, tile_rows, grouped, dw_writes = jax.shard_map(
                functools.partial(_routed_share, n_experts=n_experts, held=held, tile=tile, axes=spec_axes(spec)),
                mesh=batch_sharding.mesh, in_specs=(spec, spec, spec, P(), P(), P()),
                out_specs=(spec, P(), P(), P(), P()), check_vma=False,
            )(x, top_e, w, *weights)
        counts = {
            "moe_all": jnp.int32(top_e.size),
            "moe_held": jnp.sum(loads),
            "moe_load_max": jnp.max(loads),
            "moe_tile_rows": tile_rows,  # slots moved and multiplied, forward: whole tiles in the loop, row blocks in the kernels
            "moe_grouped": grouped,  # held assignments whose products ran in the grouped kernels
            "moe_dw_writes": dw_writes,  # times an expert's weight-gradient sum is written a matrix, backward
        }
    return y, counts
