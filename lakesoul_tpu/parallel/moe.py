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

Assignments are sorted by held expert and the products run one fixed tile of
one expert's rows at a time, for as many tiles as the held assignments fill:
the work follows the routing while every shape stays static.  A loop whose
length depends on the data has no reverse-mode derivative, so the backward
pass is a second loop of the same tiles under one custom_vjp.

How a tile's rows move.  A tile reads the rows of its tokens out of ``x`` (and
``dy``) by indexing: XLA's row gather runs near the memory's speed on a v5e
(8.5 us for 512 rows of 4 KB).  Its scatter-add does not: adding a tile's rows
into the float32 sums over all tokens took 134 us a tile, half the loop's time
(PERF.md section 6, PR 31).  So where a row of the sums is whole lane tiles
(``h % 128 == 0``) the sums are carried as ``[N, 1, h]`` and a tile is added by
two Pallas kernels around a dense sum, :func:`take_rows` and :func:`put_rows`:
one DMA a row from HBM to HBM, the index read from SMEM, all of a tile's copies
in flight at once and only the slots that hold an assignment moved (22 us a
tile for the read, the sum and the write).  The middle axis is what lets a DMA take one row: Mosaic refuses a
one-row slice of a two-dimensional array on a v5e (a slice of the second to
last axis must be a multiple of its tiling, 8 rows of 32-bit words), while
XLA lays ``[N, 1, h]`` out a row a tile (``T(1,128)``), rows contiguous and
unpadded.  16-bit rows would have to travel as pairs in 32-bit words (their
tiling is 16 rows); nothing here moves any, the sums are float32.  Narrower
rows, as most tests use, keep ``.at[].add``, which stays as the kernels' twin:
on a CPU (the kernels in the Pallas interpreter) and on a v5e the two paths
give the same bits.

How the weight gradients sum.  An expert's three float32 sums ([h, f], 14.7 MB
each for an expert of 2048 x 1792) ride the backward loop where an expert's
rows fill a tile or so: a tile's three products are added to them in place,
one read and one write of a sum for one tile of rows.  Where an expert's rows
fill several tiles that is the same sum read and written several times over,
88 MB a tile where the products themselves are 11 GFLOP, and a loop's carry
cannot stay in VMEM (PERF.md section 6, PR 39).  There (:func:`_dw_span`:
experts of whole lane tiles whose rows, shared evenly, fill two tiles or more)
the loop only leaves a tile's five bfloat16 operands (``x``'s rows, the
SwiGLU's output, and the three gradients ``dyw``, ``dg``, ``du``, zeros in the
slots past an expert's last row) in row buffers, in the plan's sorted order
(:func:`put_tiles`, one DMA an operand), and after :data:`DW_SEGMENT` tiles
one Pallas kernel a matrix, :func:`expert_dw`, multiplies them expert by
expert (``out[e] += lhs[e's rows].T @ rhs[e's rows]``, the transposed grouped
product): the tile is the grid's innermost axis and the output block follows
the tile's expert, so an expert's sum stays in VMEM through the expert's
consecutive tiles and crosses HBM once a segment.  Segments bound the buffers
(64 tiles: 0.62 GB in the LFM2 cell, where buffers for every assignment the
shapes allow would be 2.6 GB); an expert that a segment's end splits is read
back once, which is the only sum the kernel reads.  The same bfloat16 products
are added in float32 in the same tile order on both paths: the same bits
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
    return jnp.searchsorted(plan[3], t, side="right").astype(jnp.int32)


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7))
def _held_experts(x, w, plan, wg, wu, wd, tile, span):
    """``sum over held assignments of w * expert(x)``: x [N, h], w [N, k] f32,
    ``plan`` of :func:`_tile_plan` over the N x k assignments, weights
    [count, ...] → [N, h].  ``span`` of :func:`_dw_span` is the backward
    pass's."""
    k = w.shape[1]
    w_flat = w.reshape(-1)
    wg, wu, wd = (m.astype(x.dtype) for m in (wg, wu, wd))

    def run_tile(carry):
        # a tile's rows join ``y`` a turn late: carried through the loop the
        # weighted rows are rounded before the sum on every backend (in one
        # expression XLA's CPU backend contracts product and sum into one
        # rounding, and the kernels would not equal their twin bit for bit)
        t, y, late = carry
        y = _add_rows(y, *late)
        e, a, tok, valid, _ = _tile_rows(t, plan, tile, k)
        _, _, mid = _swiglu(x[tok], wg[e], wu[e])
        yt = jnp.dot(mid.astype(x.dtype), wd[e], preferred_element_type=jnp.float32)
        yt = yt * jnp.where(valid, w_flat[a], 0.0)[:, None]
        return t + 1, y, (tok, valid, yt)

    tiles = plan[3][-1]
    nothing = (jnp.zeros(tile, jnp.int32), jnp.zeros(tile, bool), jnp.zeros((tile, x.shape[1]), jnp.float32))
    _, y, late = jax.lax.while_loop(
        lambda c: c[0] < tiles, run_tile, (jnp.int32(0), _row_accumulator(x), nothing)
    )
    y = _add_rows(y, *late)
    return y.reshape(x.shape).astype(x.dtype)


def _held_experts_fwd(x, w, plan, wg, wu, wd, tile, span):
    return _held_experts(x, w, plan, wg, wu, wd, tile, span), (x, w, plan, wg, wu, wd)


def _held_experts_bwd(tile, span, saved, dy):
    x, w, plan, wg, wu, wd = saved
    n, k = w.shape
    w_flat = w.reshape(-1)
    lo = x.dtype
    wg_lo, wu_lo, wd_lo = (m.astype(lo) for m in (wg, wu, wd))
    dy = dy.astype(lo)
    f32 = jnp.float32
    tiles = plan[3][-1]
    interpret = not platform.on_tpu()

    def tile_grads(t, dx, dw_rows):
        """Tile ``t`` → (its expert, ``dx`` and ``dw_rows`` with the tile's
        part, the operands of its three weight-gradient products)."""
        e, a, tok, valid, row0 = _tile_rows(t, plan, tile, k)
        xt = x[tok]
        g, u, mid = _swiglu(xt, wg_lo[e], wu_lo[e])
        mid_lo = mid.astype(lo)
        wt = jnp.where(valid, w_flat[a], 0.0)
        dyt = dy[tok]
        # the assignment's weight: <expert output, dy>
        yt = jnp.dot(mid_lo, wd_lo[e], preferred_element_type=f32)
        dw_t = jnp.sum(yt * dyt.astype(f32), axis=-1)
        seen = jax.lax.dynamic_slice(dw_rows, (row0,), (tile,))
        dw_rows = jax.lax.dynamic_update_slice(dw_rows, jnp.where(valid, dw_t, seen), (row0,))
        dyw = (dyt.astype(f32) * wt[:, None]).astype(lo)
        dmid = jnp.dot(dyw, wd_lo[e].T, preferred_element_type=f32)
        sig = jax.nn.sigmoid(g)
        # dyw, dg, du are zeros in the slots past the expert's last row (wt is): padding adds nothing to a sum
        dg = (dmid * u * sig * (1.0 + g * (1.0 - sig))).astype(lo)
        du = (dmid * g * sig).astype(lo)
        dxt = (jnp.dot(dg, wg_lo[e].T, preferred_element_type=f32)
               + jnp.dot(du, wu_lo[e].T, preferred_element_type=f32))
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
        t0, dx, dw_rows, (dwg, dwu, dwd), held = carry
        t1 = jnp.minimum(t0 + span, tiles)

        def hold_tile(carry):
            t, dx, dw_rows, held = carry
            _, dx, dw_rows, operands = tile_grads(t, dx, dw_rows)
            return t + 1, dx, dw_rows, put_tiles(held, operands, (t - t0) * tile, interpret=interpret)

        _, dx, dw_rows, held = jax.lax.while_loop(lambda c: c[0] < t1, hold_tile, (t0, dx, dw_rows, held))
        xs, mids, dyws, dgs, dus = held
        experts = _tile_experts(t0 + jnp.arange(span, dtype=jnp.int32), plan)
        dwg, dwu, dwd = (expert_dw(sums, lhs, rhs, experts, t1 - t0, interpret=interpret)
                         for sums, lhs, rhs in ((dwg, xs, dgs), (dwu, xs, dus), (dwd, mids, dyws)))
        return t1, dx, dw_rows, (dwg, dwu, dwd), held

    init = (
        jnp.int32(0), _row_accumulator(x),
        jnp.zeros(n * k + tile, f32),  # a tile may reach past the last row
        tuple(jnp.zeros(m.shape, f32) for m in (wg, wu, wd)),
    )
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
    [count], the slots of the tiles run and the times the backward pass writes
    an expert's weight-gradient sum a matrix, all summed over ``axes``)."""
    first, count = held
    shape = x.shape
    k = top_e.shape[-1]
    local = top_e.reshape(-1) - first
    local = jnp.where((local >= 0) & (local < count), local, count)
    tile = min(tile, -(-local.shape[0] // 8) * 8)
    span = _dw_span(local.shape[0], n_experts, count, wg.shape[1:], tile)
    plan = _tile_plan(local, count, tile)
    y = _held_experts(x.reshape(-1, shape[-1]), w.reshape(-1, k), plan, wg, wu, wd, tile, span)
    loads, tiles, writes = plan[1], plan[3][-1], _dw_writes(plan, span, local.shape[0] // tile + count)
    if axes:
        loads, tiles, writes = jax.lax.psum((loads, tiles, writes), axes)
    return y.reshape(shape), loads, tiles * tile, writes


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
    outside: the tile loop then runs once forward, not twice."""
    first, count = held
    if not (0 <= first and first + count <= n_experts and p["w_gate"].shape[0] == count):
        raise ValueError(f"held={held} does not fit {n_experts} experts and {p['w_gate'].shape[0]} held weights")
    tile = tile or EXPERT_TILE
    weights = (p["w_gate"], p["w_up"], p["w_down"])
    with jax.named_scope(EXPERTS_SCOPE):
        if batch_sharding is None:
            y, loads, tile_rows, dw_writes = _routed_share(
                x, top_e, w, *weights, n_experts=n_experts, held=held, tile=tile, axes=())
        else:
            spec = batch_sharding.spec
            y, loads, tile_rows, dw_writes = jax.shard_map(
                functools.partial(_routed_share, n_experts=n_experts, held=held, tile=tile, axes=spec_axes(spec)),
                mesh=batch_sharding.mesh, in_specs=(spec, spec, spec, P(), P(), P()),
                out_specs=(spec, P(), P(), P()), check_vma=False,
            )(x, top_e, w, *weights)
        counts = {
            "moe_all": jnp.int32(top_e.size),
            "moe_held": jnp.sum(loads),
            "moe_load_max": jnp.max(loads),
            "moe_tile_rows": tile_rows,  # slots the tile loop moved and multiplied, forward
            "moe_dw_writes": dw_writes,  # times an expert's weight-gradient sum is written a matrix, backward
        }
    return y, counts
