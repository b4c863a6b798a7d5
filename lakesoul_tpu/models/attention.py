"""Causal softmax attention over a ``(q, k, v)`` triple, and the triple's
making: everything of the causal LMs' step whose subject is one.  Nothing here
names a family, a layer or a loss; ``models/causal_lm.py``'s mixers call
:func:`attention_operands` and :func:`causal_attention`, its checkpoint keeps
:data:`ATTN_KEPT`, and its counts are :func:`mixer_counts`'.

Two Pallas kernel pairs, each under one ``custom_vjp``, compiled on a TPU and
run in the Pallas interpreter elsewhere, and three rules that say which shape
gets which (shapes alone decide; every other shape runs the ``jnp`` twin, which
is also what the kernels are held to):

- the flash pair (``flash_attention_fwd``, ``flash_attention_bwd``; the comment
  above :func:`_flash_tiles` and :func:`causal_attention` say what they do):
  :func:`_flash_tiles` takes a head of 64, 128 or 256 channels and a row of
  whole 128-key tiles (the six published models at 8,192 tokens: groups of 4
  at head 64, of 8 at 256, of 1 at 256 on 20 key-value heads, of 8 at 128, of
  1 at 128 on 16 key-value heads, and differential attention's pairs, groups
  of 2 at head 64 beside a value of 128: the value's width is read off ``v``);
  twin :func:`_blockwise_attention`;
- where the output lies: :func:`_token_major` takes, of those, a head of whole
  128-lane tiles (every published head but 64), and the kernels then write
  ``o`` and read ``do`` token-major through their own block specs; elsewhere
  heads first, and a ``jnp`` transpose inside :func:`causal_attention`;
- the operand pair (``attn_operands_fwd``, ``attn_operands_bwd``; the comment
  above :func:`_operand_tiles`): :func:`_operand_tiles` takes, of those again,
  positions over the whole head or none; twin :func:`_xla_operands`.
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lakesoul_tpu.models.norms import rms_norm
from lakesoul_tpu.parallel.ring_attention import block_attn
from lakesoul_tpu.utils import platform

ATTN_KEPT = ("attn_out", "attn_lse")  # ``checkpoint_name``s of what the flash kernels' backward pass keeps
ATTN_BAND = 1024   # blockwise: queries that share one static slice of the keys
ATTN_ROWS = 128    # blockwise: queries whose scores live at once
FLASH_HEADS = (64, 128, 256)  # head sizes the flash kernels take: half a lane tile, one, two
FLASH_KEYS = 512   # keys a tile holds, where the row has as many
FLASH_ROWS = 1024  # score rows a tile holds: a group's heads x queries, 128 queries at least
FLASH_ROW_ELEMENTS = 8192 * 256  # T x D at most: a row's float32 dK and dV are 8 MB each at that
FLASH_VMEM_BYTES = 96 * 2**20    # of a v5e's 128 MiB
OPERAND_ELEMENTS = 512 * 1024    # tokens x a group's channels a block of the operand kernels holds at most: 1 MB
OPERAND_VMEM_BYTES = 64 * 2**20
MASKED = -1e30
_NT = (((1,), (1,)), ((), ()))  # x y^T
_TN = (((0,), (0,)), ((), ()))  # x^T y


def _rotary_angles(positions, rotary_dim: int, theta: float):
    """The positions' angles [T, rotary_dim // 2], float32."""
    inv_freq = theta ** (-jnp.arange(rotary_dim // 2, dtype=jnp.float32) * 2.0 / rotary_dim)
    return positions.astype(jnp.float32)[:, None] * inv_freq


def _rotary(x, positions, rotary_dim: int, theta: float):
    """Rotate the first ``rotary_dim`` channels of x [B, T, H, D] (float32):
    halves ``[x1 | x2]`` → ``[x1 cos - x2 sin | x2 cos + x1 sin]``."""
    half = rotary_dim // 2
    angle = _rotary_angles(positions, rotary_dim, theta)
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def _blockwise_attention(q, k, v, band: int, rows: int, window: int | None = None):
    """:func:`causal_attention` as whole-array ``jnp`` operations: the queries
    go a band at a time against the keys up to the band's last position (a
    static slice, so the keys after it cost nothing; under a ``window`` from
    the first key the band's first query sees), and inside a band
    ``rows`` queries at a time, each block rematerialised: no more than
    ``rows`` rows of scores live at once, in either pass, and every one of
    them in HBM.  What a shape the kernel does not take runs, and the kernel's
    twin and reference; the value may be wider than the key, as the kernels'."""
    b, hkv, groups, t, d = q.shape
    dv = v.shape[-1]

    def block(q_blk, k_seen, v_seen, first, key0=0):
        """q_blk [B, Hkv, G, n, D] at positions first.. against the keys seen,
        which start at position ``key0``."""
        n = q_blk.shape[3]
        pos = jnp.tile(first + jnp.arange(n), groups)
        if window is None:
            mask = pos[:, None] >= jnp.arange(k_seen.shape[2])[None, :]
        else:
            back = pos[:, None] - (key0 + jnp.arange(k_seen.shape[2]))[None, :]
            mask = (back >= 0) & (back < window)
        _, l, o = block_attn(q_blk.reshape(b, hkv, groups * n, d), k_seen, v_seen, 1.0, mask)
        return (o / l[..., None]).astype(v.dtype).reshape(b, hkv, groups, n, dv)

    out = []
    for start in range(0, t, band):
        end = min(start + band, t)
        key0 = 0 if window is None else max(0, start - window + 1)
        q_band, k_seen, v_seen = q[:, :, :, start:end], k[:, :, key0:end], v[:, :, key0:end]
        band_block = jax.checkpoint(functools.partial(block, key0=key0))
        if (end - start) % rows or end - start == rows:
            out.append(band_block(q_band, k_seen, v_seen, start))
            continue
        blocks = (end - start) // rows
        q_rows = jnp.moveaxis(q_band.reshape(b, hkv, groups, blocks, rows, d), 3, 0)
        firsts = start + rows * jnp.arange(blocks)
        o = jax.lax.map(lambda xs: band_block(xs[0], k_seen, v_seen, xs[1]), (q_rows, firsts))
        out.append(jnp.moveaxis(o, 0, 3).reshape(b, hkv, groups, end - start, dv))
    return jnp.concatenate(out, axis=3)


# The flash kernels.  A tile is a key-value head's whole group: ``G`` query
# heads x ``bq`` queries as the rows of one score tile against ``bk`` keys, so
# K and V are fetched once a group and dK, dV sum over it inside the kernel.
# The grid walks the (query tile, key tile) pairs the mask lets anything
# through, listed in two tables in SMEM: a key tile wholly after a query tile,
# or under a window wholly before the first key the tile's first query sees,
# is not in the list, so it is neither fetched nor multiplied.  Only the tiles
# an edge of the mask crosses build one: a query tile's last key tile (the
# diagonal's) and, under a window, the key tiles that hold a key the tile's
# last query no longer sees (the first of the list; the first two where the
# window is no multiple of the query tile).  One tile may be both.
# q and k are ``D`` wide and the value ``Dv``, read off ``v``'s last axis (the
# head's size, but for differential attention's pairs: 2D,
# :func:`paired_attention`): the output, its cotangent, the forward's
# accumulator and dV are as wide as the value, the scores, the softmax, dQ and
# dK know nothing of it, and the tiles are the head's.


def _flash_tiles(t: int, groups: int, d: int, dv: int | None = None):
    """(queries, keys) a tile holds for rows of ``t`` tokens, or None where
    the kernels do not take the shape: a head of :data:`FLASH_HEADS` (and a
    value of ``dv`` channels, where it is not the head's size, of them too), a
    row that is whole tiles of 128 keys, and a row's float32 dK and dV held in
    VMEM through the backward kernel (twice: the pipeline's two buffers)."""
    dv = d if dv is None else dv
    if d not in FLASH_HEADS or dv not in FLASH_HEADS or t % 128 or t * max(d, dv) > FLASH_ROW_ELEMENTS:
        return None
    bk = next(n for n in (512, 256, 128) if n <= FLASH_KEYS and t % n == 0)
    return max(128, min(bk, FLASH_ROWS // groups)), bk


def _token_major(t: int, groups: int, d: int, dv: int | None = None) -> bool:
    """Whether the flash kernels write the output, and read its cotangent,
    token-major, [B, T, heads x D] as the gate and ``w_o`` read it: a shape
    they take (:func:`_flash_tiles`) whose head is whole 128-lane tiles and
    whose value is as wide as the head.  A block of that array, ``bq`` tokens
    by a key-value head's ``G x D`` lanes, is then a query tile of the group,
    each head at a lane-aligned column slice; two heads of 64 would share a
    lane tile."""
    return _flash_tiles(t, groups, d, dv) is not None and d % 128 == 0 and dv in (None, d)


def _first_key_tile(i, bq: int, bk: int, window: int | None):
    """The first key tile of query tile ``i`` (a Python or a traced integer):
    the one that holds the first key the tile's first query sees."""
    if window is None:
        return 0
    seen_from = i * bq - (window - 1)
    return (max(seen_from, 0) if isinstance(i, int) else jnp.maximum(seen_from, 0)) // bk


def _flash_pairs(t: int, bq: int, bk: int, window: int | None = None) -> list[tuple[int, int]]:
    """The (query tile, key tile) pairs of the grid's second axis: for every
    query tile its key tiles in order, from :func:`_first_key_tile` to the one
    that holds the tile's diagonal."""
    return [(i, j) for i in range(t // bq)
            for j in range(_first_key_tile(i, bq, bk, window), (i * bq + bq - 1) // bk + 1)]


def _flash_steps(t: int, bq: int, bk: int, window: int | None = None):
    """:func:`_flash_pairs` as the two int32 tables the kernels prefetch →
    (query tile, key tile) of each step."""
    return tuple(jnp.asarray(a, jnp.int32) for a in zip(*_flash_pairs(t, bq, bk, window), strict=True))


def key_tile_steps(t: int, groups: int, d: int, window: int | None = None, dv: int | None = None) -> tuple[int, int]:
    """(steps the kernels' list holds for one key-value head of one row, steps
    a causal list alone would hold): host integers off :func:`_flash_pairs`;
    (0, 0) for a shape the kernels do not take."""
    tiles = _flash_tiles(t, groups, d, dv)
    if tiles is None:
        return 0, 0
    return len(_flash_pairs(t, *tiles, window)), len(_flash_pairs(t, *tiles))  # a window of t or more hides no tile


def _lanes(x, n: int):
    """x [rows, 128], every lane of a row the same, as [rows, n]."""
    return x[:, :n] if n <= 128 else jnp.tile(x, (1, n // 128))


def _seen(i, j, bq: int, bk: int, shape, *, queries: int, causal: bool = True, window: int | None = None):
    """Whether a score's key is at or before its query (``causal``) and, under
    a ``window``, among the query's own position and the ``window - 1`` before
    it, over a tile of ``shape`` whose axis ``queries`` runs over the group's
    rows (head-major: row ``r`` is query ``r % bq`` of the tile) and whose
    other axis over keys."""
    pos = i * bq + (jax.lax.broadcasted_iota(jnp.int32, shape, queries) & (bq - 1))
    key = j * bk + jax.lax.broadcasted_iota(jnp.int32, shape, 1 - queries)
    if window is None:
        return pos >= key
    inside = pos - key < window
    return (pos >= key) & inside if causal else inside


def _flash_step(qi_ref, kj_ref, bq: int, bk: int, window: int | None = None):
    """(query tile, key tile, the query tile's first and last key tiles) of
    this grid step."""
    i, j = qi_ref[pl.program_id(1)], kj_ref[pl.program_id(1)]
    return i, j, _first_key_tile(i, bq, bk, window), (i * bq + bq - 1) // bk


def _flash_tile_kinds(tile, i, j, last, bq: int, bk: int, window: int | None, finish):
    """Run ``tile(mask)`` for this step, ``mask(shape, queries)`` being None
    on a tile no edge of the mask crosses; after a query tile's last key tile
    ``finish()``.  Without a window: the last tile alone is masked."""
    def edge(causal):
        return lambda shape, queries: _seen(i, j, bq, bk, shape, queries=queries, causal=causal, window=window)

    if window is None:
        pl.when(j < last)(functools.partial(tile, None))
    else:
        hidden = j * bk < i * bq + bq - window  # the tile holds a key the tile's last query no longer sees
        pl.when((j < last) & jnp.logical_not(hidden))(functools.partial(tile, None))
        pl.when((j < last) & hidden)(functools.partial(tile, edge(False)))

    @pl.when(j == last)
    def _():
        tile(edge(True))
        finish()


def _group_rows(ref, groups: int, d: int):
    """A group's query tile as the kernels multiply it, [G*bq, D] head-major,
    from a token-major block [bq, G*D]: the heads' column slices, whole lane
    tiles each, one under the other."""
    return jnp.concatenate([ref[:, g * d:(g + 1) * d] for g in range(groups)], axis=0)


def _store_group_rows(ref, x, groups: int, d: int):
    """x [G*bq, D] head-major into a query tile's block, cast: heads first
    [G, bq, D], or token-major [bq, G*D] (:func:`_group_rows` the other way)."""
    if len(ref.shape) == 3:
        ref[...] = x.reshape(ref.shape).astype(ref.dtype)
        return
    bq = ref.shape[0]
    for g in range(groups):
        ref[:, g * d:(g + 1) * d] = x[g * bq:(g + 1) * bq].astype(ref.dtype)


def _flash_fwd_kernel(qi_ref, kj_ref, q_ref, k_ref, v_ref, o_ref, lse_ref, m_ref, l_ref, acc_ref, *, bq, bk,
                      window=None):
    """One step: a group's query tile [G, bq, D] against a key tile [bk, D]
    and its values [bk, Dv].  Running maximum and sum [G*bq, 128] (every lane
    the same) and the weighted values [G*bq, Dv] stay in VMEM over a query
    tile's steps; the last of them divides and writes the output (heads first
    [G, bq, Dv], or token-major where its block is:
    :func:`_store_group_rows`) and the log-sum-exp [G, 1, bq]."""
    i, j, first, last = _flash_step(qi_ref, kj_ref, bq, bk, window)
    groups, _, d = q_ref.shape
    dv = v_ref.shape[-1]
    rows = groups * bq
    f32 = jnp.float32

    @pl.when(j == first)
    def _():
        m_ref[...] = jnp.full_like(m_ref, MASKED)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def tile(mask):
        v = v_ref[...]
        s = jax.lax.dot_general(q_ref[...].reshape(rows, d), k_ref[...], _NT, preferred_element_type=f32)
        if mask is not None:
            s = jnp.where(mask(s.shape, 0), s, MASKED)
        m_prev = m_ref[...]
        m_next = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.exp(s - _lanes(m_next, bk))
        alpha = jnp.exp(m_prev - m_next)
        l_ref[...] = alpha * l_ref[...] + jnp.sum(p, axis=1, keepdims=True)
        m_ref[...] = m_next
        acc_ref[...] = acc_ref[...] * _lanes(alpha, dv) + jnp.dot(p.astype(v.dtype), v, preferred_element_type=f32)

    def finish():
        l = l_ref[...]
        _store_group_rows(o_ref, acc_ref[...] / _lanes(l, dv), groups, dv)
        lse = (m_ref[...] + jnp.log(l)).T[:1]  # [1, G*bq]: a row's queries along the lanes
        for g in range(groups):
            lse_ref[g] = lse[:, g * bq:(g + 1) * bq]

    _flash_tile_kinds(tile, i, j, last, bq, bk, window, finish)


def _flash_bwd_kernel(qi_ref, kj_ref, q_ref, k_ref, v_ref, do_ref, lse_ref, with_ref, dq_ref, dk_ref, dv_ref, dq_acc,
                      *assembled, bq, bk, window=None):
    """One step of the backward pass, on the forward kernel's grid: the scores
    of the tile again from q, k and the log-sum-exp, keys down the sublanes
    ([bk, G*bq]: the log-sum-exp and ``delta = sum(o * do)`` are rows, and dV
    and dK plain products), their share of dQ into VMEM until the query tile's
    last step, of dK and dV into the row's whole float32 dK [T, D] and dV
    [T, Dv], which stay in VMEM over all of a key-value head's steps.  One
    ``ds = p (dp - delta)``, ``dp`` and ``delta`` over the whole of the
    value's width, makes dK and dQ.

    Heads first, ``do_ref`` [G, bq, Dv] is the operand as it lies and
    ``with_ref`` holds ``delta`` [G, 1, bq], an XLA reduction.  Token-major
    (``assembled``: two more buffers in VMEM), ``do_ref`` and ``with_ref`` are
    the cotangent's and the kept output's blocks [bq, G*D], and a query tile's
    first step lays the cotangent's heads one under the other
    (:func:`_group_rows`) and sums ``delta`` from the two, once for all of the
    tile's steps."""
    i, j, first, last = _flash_step(qi_ref, kj_ref, bq, bk, window)
    groups, _, d = q_ref.shape
    rows = groups * bq
    f32 = jnp.float32

    @pl.when(pl.program_id(1) == 0)
    def _():
        dk_ref[...] = jnp.zeros_like(dk_ref)
        dv_ref[...] = jnp.zeros_like(dv_ref)

    @pl.when(j == first)
    def _():
        dq_acc[...] = jnp.zeros_like(dq_acc)
        if assembled:
            do_rows, delta_row = assembled
            do = _group_rows(do_ref, groups, d)
            do_rows[...] = do
            delta = jnp.sum(_group_rows(with_ref, groups, d).astype(f32) * do.astype(f32), axis=1, keepdims=True)
            delta_row[...] = jnp.broadcast_to(delta, (rows, 128)).T[:1]  # [1, G*bq]: a row's queries along the lanes

    def tile(mask):
        q = q_ref[...].reshape(rows, d)
        do = assembled[0][...] if assembled else do_ref[...].reshape(rows, v_ref.shape[-1])
        k, v = k_ref[...], v_ref[...]
        lse = jnp.concatenate([lse_ref[g] for g in range(groups)], axis=1)
        delta = assembled[1][...] if assembled else jnp.concatenate([with_ref[g] for g in range(groups)], axis=1)
        s = jax.lax.dot_general(k, q, _NT, preferred_element_type=f32)
        if mask is not None:
            s = jnp.where(mask(s.shape, 1), s, MASKED)
        p = jnp.exp(s - lse)
        dp = jax.lax.dot_general(v, do, _NT, preferred_element_type=f32)
        ds = (p * (dp - delta)).astype(q.dtype)
        keys = pl.ds(pl.multiple_of(j * bk, bk), bk)
        dv_ref[keys, :] += jnp.dot(p.astype(do.dtype), do, preferred_element_type=f32)
        dk_ref[keys, :] += jnp.dot(ds, q, preferred_element_type=f32)
        dq_acc[...] += jax.lax.dot_general(ds, k, _TN, preferred_element_type=f32)

    def finish():
        dq_ref[...] = dq_acc[...].reshape(groups, bq, d).astype(dq_ref.dtype)

    _flash_tile_kinds(tile, i, j, last, bq, bk, window, finish)


def _flash_grid(q, dv: int, bq: int, bk: int, window, *, in_specs, out_specs, scratch_shapes, batch: int | None = None):
    """What the two kernels' ``pallas_call``s share, over q's [N, G, T, D]
    and a value ``dv`` wide: the grid (key-value heads, steps of
    :func:`_flash_steps`) with the two tables in SMEM, and block specs by
    what a block follows: a query tile's [G, bq, D], its per-query floats
    [G, 1, bq], a key tile's [bk, D], a key-value head's whole [T, D], and
    those three as wide as the value (``query_out`` [G, bq, Dv], ``values``
    [bk, Dv], ``whole_values`` [T, Dv]: the output and its cotangent, v, dV);
    ``in_specs`` and ``out_specs`` name those.  With ``batch`` (the ``N`` key-value heads are those of ``batch`` rows)
    also ``tokens``, the query tile in a token-major array [batch, T, heads x
    D]: [bq, G*D] at the row's tokens and the key-value head's columns.
    Returns (the tables, the call's keyword arguments)."""
    n, groups, t, d = q.shape
    tables = _flash_steps(t, bq, bk, window)
    specs = {"per_query": pl.BlockSpec((None, groups, 1, bq), lambda h, s, qi, kj: (h, 0, 0, qi[s]))}
    for wide, query, keys, whole_row in ((d, "query", "keys", "whole_row"), (dv, "query_out", "values", "whole_values")):
        specs[query] = pl.BlockSpec((None, groups, bq, wide), lambda h, s, qi, kj: (h, 0, qi[s], 0))
        specs[keys] = pl.BlockSpec((None, bk, wide), lambda h, s, qi, kj: (h, kj[s], 0))
        specs[whole_row] = pl.BlockSpec((None, t, wide), lambda h, s, qi, kj: (h, 0, 0))
    if batch is not None:
        kv = n // batch
        specs["tokens"] = pl.BlockSpec((None, bq, groups * d), lambda h, s, qi, kj: (h // kv, qi[s], h % kv))
    return tables, dict(
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2, grid=(n, tables[0].shape[0]), scratch_shapes=scratch_shapes,
            in_specs=[specs[s] for s in in_specs], out_specs=[specs[s] for s in out_specs],
        ),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"), vmem_limit_bytes=FLASH_VMEM_BYTES
        ),
    )


@functools.partial(jax.jit, static_argnames=("bq", "bk", "window", "batch", "interpret"))
def _flash_forward(q, k, v, *, bq: int, bk: int, window: int | None = None, batch: int | None = None, interpret: bool):
    """q [N, G, T, D], k [N, T, D], v [N, T, Dv] → (o [N, G, T, Dv],
    log-sum-exp [N, G, 1, T] float32).  With ``batch`` (:func:`_token_major`
    shapes, ``Dv = D``: the ``N`` key-value heads are ``batch`` rows') o is
    written token-major, [batch, T, heads x D] with a key-value head's group
    side by side: the same values at the addresses the gate and ``w_o``
    read."""
    n, groups, t, d = q.shape
    dv = v.shape[-1]
    rows = groups * bq
    tables, grid = _flash_grid(
        q, dv, bq, bk, window, in_specs=("query", "keys", "values"), batch=batch,
        out_specs=("query_out" if batch is None else "tokens", "per_query"),
        scratch_shapes=[pltpu.VMEM((rows, 128), jnp.float32)] * 2 + [pltpu.VMEM((rows, dv), jnp.float32)],
    )
    o_shape = (n, groups, t, dv) if batch is None else (batch, t, n // batch * groups * d)
    return pl.pallas_call(
        functools.partial(_flash_fwd_kernel, bq=bq, bk=bk, window=window),
        out_shape=(jax.ShapeDtypeStruct(o_shape, v.dtype), jax.ShapeDtypeStruct((n, groups, 1, t), jnp.float32)),
        name="flash_attention_fwd", interpret=interpret, **grid,
    )(*tables, q, k, v)


@functools.partial(jax.jit, static_argnames=("bq", "bk", "window", "interpret"))
def _flash_backward(q, k, v, o, lse, do, *, bq: int, bk: int, window: int | None = None, interpret: bool):
    """The three gradients, dK and dV summed over the group (dV as wide as
    the value); ``o`` and ``do`` as :func:`_flash_forward` wrote ``o``.
    Heads first, [N, G, T, Dv]:
    ``delta = sum(o * do)`` is an XLA reduction and an operand of the kernel.
    Token-major, [B, T, heads x D]: the kernel reads both through the output's
    block spec and sums ``delta`` itself (as an XLA reduction over token-major
    arrays its [T, heads] result wants relaying into [N, G, 1, T], and XLA
    writes the float32 products out whole to do that)."""
    _, groups, _, d = q.shape
    rows = groups * bq
    scratch = [pltpu.VMEM((rows, d), jnp.float32)]
    if o.ndim == 3:
        batch, given, do_spec, given_spec = o.shape[0], o, "tokens", "tokens"
        scratch += [pltpu.VMEM((rows, d), do.dtype), pltpu.VMEM((1, rows), jnp.float32)]
    else:
        batch, do_spec, given_spec = None, "query_out", "per_query"
        given = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)[:, :, None, :]
    tables, grid = _flash_grid(
        q, v.shape[-1], bq, bk, window, batch=batch,
        in_specs=("query", "keys", "values", do_spec, "per_query", given_spec),
        out_specs=("query", "whole_row", "whole_values"), scratch_shapes=scratch,
    )
    dq, dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_kernel, bq=bq, bk=bk, window=window),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype), *(jax.ShapeDtypeStruct(a.shape, jnp.float32) for a in (k, v))),
        name="flash_attention_bwd", interpret=interpret, **grid,
    )(*tables, q, k, v, do, lse, given)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, bq, bk, window, batch):
    return _flash_attention_fwd(q, k, v, bq, bk, window, batch)[0]


def _flash_attention_fwd(q, k, v, bq, bk, window, batch):
    o, lse = _flash_forward(q, k, v, bq=bq, bk=bk, window=window, batch=batch, interpret=not platform.on_tpu())
    # a checkpoint around the caller may keep these two and run no second forward kernel
    o, lse = checkpoint_name(o, ATTN_KEPT[0]), checkpoint_name(lse, ATTN_KEPT[1])
    return o, (q, k, v, o, lse)


def _flash_attention_bwd(bq, bk, window, batch, kept, do):
    return _flash_backward(*kept, do, bq=bq, bk=bk, window=window, interpret=not platform.on_tpu())


_flash_attention.defvjp(_flash_attention_fwd, _flash_attention_bwd)


# The operand kernels.  Between the projections and the flash kernels a mixer
# norms each query and key head, turns it by its position, scales the query,
# casts, and lays heads before tokens.  Where :func:`_operand_tiles` takes the
# shape, one kernel does that in one pass over the projections' outputs and a
# second the transpose of it: a block is ``bt`` tokens of one key-value head's
# group, a head one row of whole lane tiles a token, and the permutation is
# the block specs' (no transpose inside).  Float32 inside, one rounding at the
# end, as the ``jnp`` lines they stand for (:func:`_xla_operands`, their twin).


def _operand_tiles(t: int, heads: int, kv_heads: int, d: int, rotary_dim: int | None):
    """Tokens a block of the operand kernels holds, or None where they do not
    take the shape: one the flash kernels take (:func:`_flash_tiles`), a head
    of whole 128-lane tiles, and positions over the whole head or none (a turn
    is then a roll by half a head)."""
    if heads % kv_heads or _flash_tiles(t, heads // kv_heads, d) is None or d % 128 or rotary_dim not in (None, d):
        return None
    width = heads // kv_heads * d
    return next(n for n in (512, 256, 128) if t % n == 0 and (n == 128 or n * width <= OPERAND_ELEMENTS))


def _head_operand(x, w, turn, eps: float):
    """x [n, D] float32, a head's raw channels a token → normed
    (:func:`rms_norm` by the weight as it multiplies; not where ``w`` is
    None: a mixer without head norms) and turned: ``[x1 cos -
    x2 sin | x2 cos + x1 sin]`` as ``y * [cos | cos] + roll(y) * [-sin | sin]``."""
    y = x if w is None else rms_norm(x, w, eps, centred=False)
    if turn is None:
        return y
    return y * turn[0] + pltpu.roll(y, y.shape[1] // 2, 1) * turn[1]


def _turned_back(dz, turn):
    """A turned head's cotangent [n, D] turned by the negative angle (as it is
    where the layer sees no positions)."""
    return dz if turn is None else dz * turn[0] - pltpu.roll(dz, dz.shape[1] // 2, 1) * turn[1]


def _head_operand_grads(x, w, turn, dz, eps: float):
    """:func:`_head_operand` of a normed head transposed: the cotangent ``dz``
    [n, D] → (the raw channels', the weight's summed over every eighth token:
    [8, D])."""
    r = jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    y = x * r
    dz = _turned_back(dz, turn)
    dy = dz * w
    dx = r * (dy - y * jnp.mean(dy * y, axis=-1, keepdims=True))
    return dx, jnp.sum((dz * y).reshape(-1, 8, x.shape[1]), axis=0)


def _operands_fwd_kernel(*refs, groups: int, eps: float, turned: bool, normed: bool = True):
    """One block: ``bt`` tokens of a key-value head's ``groups`` query heads
    [bt, G*D], its key and value [bt, D] → the query [G, bt, D] scaled, the
    key and the value [bt, D].  ``normed``: the two norm weights are among
    the operands (after the value) and the heads are normed by them."""
    q_ref, k_ref, v_ref, *given, qo_ref, ko_ref, vo_ref = refs
    wq_ref, wk_ref = given[:2] if normed else (None, None)
    d = k_ref.shape[1]
    f32 = jnp.float32
    turn = tuple(a[...] for a in given[2 * normed:]) if turned else None
    for g in range(groups):
        q = _head_operand(q_ref[:, g * d:(g + 1) * d].astype(f32), wq_ref[...] if normed else None, turn, eps)
        qo_ref[g] = (q * d**-0.5).astype(qo_ref.dtype)
    ko_ref[...] = _head_operand(k_ref[...].astype(f32), wk_ref[...] if normed else None, turn, eps).astype(ko_ref.dtype)
    vo_ref[...] = v_ref[...]


def _operands_bwd_kernel(*refs, groups: int, eps: float, turned: bool, normed: bool = True):
    """:func:`_operands_fwd_kernel` transposed, on its grid: the operands'
    cotangents and the raw query and key → the raw cotangents in the
    projections' layout and this block's share of the two norm weights'
    gradients [8, D] float32.  Not ``normed``: the cotangents alone in, the
    raw cotangents alone out (turning back needs neither the raw query nor
    the raw key)."""
    f32 = jnp.float32
    if not normed:
        dqo_ref, dko_ref, dvo_ref, *turn, dq_ref, dk_ref, dv_ref = refs
        d = dko_ref.shape[1]
        turn = tuple(a[...] for a in turn) if turned else None
        for g in range(groups):
            dq_ref[:, g * d:(g + 1) * d] = _turned_back(dqo_ref[g].astype(f32) * d**-0.5, turn).astype(dq_ref.dtype)
        dk_ref[...] = _turned_back(dko_ref[...].astype(f32), turn).astype(dk_ref.dtype)
        dv_ref[...] = dvo_ref[...]
        return
    dqo_ref, dko_ref, dvo_ref, q_ref, k_ref, wq_ref, wk_ref, *turn, dq_ref, dk_ref, dv_ref, dwq_ref, dwk_ref = refs
    d = k_ref.shape[1]
    turn = tuple(a[...] for a in turn) if turned else None
    dwq = jnp.zeros((8, d), f32)
    for g in range(groups):
        heads = slice(g * d, (g + 1) * d)
        dq, dw = _head_operand_grads(
            q_ref[:, heads].astype(f32), wq_ref[...], turn, dqo_ref[g].astype(f32) * d**-0.5, eps
        )
        dq_ref[:, heads] = dq.astype(dq_ref.dtype)
        dwq += dw
    dwq_ref[...] = dwq
    dk, dwk_ref[...] = _head_operand_grads(k_ref[...].astype(f32), wk_ref[...], turn, dko_ref[...].astype(f32), eps)
    dk_ref[...] = dk.astype(dk_ref.dtype)
    dv_ref[...] = dvo_ref[...]


def _operand_grid(q, k, d: int, bt: int, turned: bool, normed: bool, *, in_specs, out_specs):
    """What the two kernels' ``pallas_call``s share, over the raw q [B, T,
    heads*D] and k [B, T, kv*D]: the grid (rows, token blocks, key-value
    heads: the heads innermost, so a block of the position tables is fetched
    once) and block specs by what a block follows: the ``raw`` query's [bt,
    G*D] and key's or value's ``raw_kv`` [bt, D], the flash kernels' ``laid``
    [G, bt, D] and ``laid_kv`` [bt, D], a weight gradient's ``share`` [8, D];
    after ``in_specs`` come, where the heads are ``normed``, the two norm
    weights [1, D] and, where the layer turns, the two position tables' [bt, D].  Returns (key-value heads, query
    heads each serves, the call's keyword arguments)."""
    b, t, width = k.shape
    kv, groups = width // d, q.shape[2] // width
    specs = {
        "raw": pl.BlockSpec((None, bt, groups * d), lambda r, i, h: (r, i, h)),
        "raw_kv": pl.BlockSpec((None, bt, d), lambda r, i, h: (r, i, h)),
        "laid": pl.BlockSpec((None, None, groups, bt, d), lambda r, i, h: (r, h, 0, i, 0)),
        "laid_kv": pl.BlockSpec((None, None, bt, d), lambda r, i, h: (r, h, i, 0)),
        "share": pl.BlockSpec((None, None, None, 8, d), lambda r, i, h: (r, i, h, 0, 0)),
    }
    weight = pl.BlockSpec((1, d), lambda r, i, h: (0, 0))
    table = pl.BlockSpec((bt, d), lambda r, i, h: (i, 0))
    return kv, groups, dict(
        grid=(b, t // bt, kv),
        in_specs=[specs[s] for s in in_specs] + [weight] * (2 * normed) + [table] * (2 * turned),
        out_specs=[specs[s] for s in out_specs],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",) * 3, vmem_limit_bytes=OPERAND_VMEM_BYTES
        ),
    )


@functools.partial(jax.jit, static_argnames=("d", "eps", "bt", "interpret"))
def _operands_forward(q, k, v, wq, wk, turn, *, d: int, eps: float, bt: int, interpret: bool):
    """The raw q [B, T, heads*D], k, v [B, T, kv*D] as the products leave
    them, the norm weights [D] as they multiply (both None: heads that are not
    normed), ``turn`` None or the position
    tables ``([cos | cos], [-sin | sin])`` [T, D] float32 → the flash kernels'
    q [B, kv, G, T, D] (scaled), k, v [B, kv, T, D]."""
    b, t, _ = q.shape
    normed = wq is not None
    kv, groups, grid = _operand_grid(
        q, k, d, bt, turn is not None, normed, in_specs=("raw", "raw_kv", "raw_kv"),
        out_specs=("laid", "laid_kv", "laid_kv"),
    )
    return pl.pallas_call(
        functools.partial(_operands_fwd_kernel, groups=groups, eps=eps, turned=turn is not None, normed=normed),
        out_shape=(jax.ShapeDtypeStruct((b, kv, groups, t, d), q.dtype),
                   *(jax.ShapeDtypeStruct((b, kv, t, d), a.dtype) for a in (k, v))),
        name="attn_operands_fwd", interpret=interpret, **grid,
    )(q, k, v, *((wq[None], wk[None]) if normed else ()), *(turn or ()))


@functools.partial(jax.jit, static_argnames=("d", "eps", "bt", "interpret"))
def _operands_backward(dq, dk, dv, q, k, wq, wk, turn, *, d: int, eps: float, bt: int, interpret: bool):
    """The cotangents of :func:`_operands_forward`'s results, and its raw q
    and k again → the cotangents of the raw q, k, v and of the two norm
    weights (float32, summed here over the blocks' shares; None where the
    heads are not normed: the raw q and k then give their shapes alone)."""
    b, t, _ = q.shape
    normed = wq is not None
    kv, groups, grid = _operand_grid(
        q, k, d, bt, turn is not None, normed,
        in_specs=("laid", "laid_kv", "laid_kv", *(("raw", "raw_kv") if normed else ())),
        out_specs=("raw", "raw_kv", "raw_kv", *(("share", "share") if normed else ())),
    )
    share = jax.ShapeDtypeStruct((b, t // bt, kv, 8, d), jnp.float32)
    dq, dk, dv, *shares = pl.pallas_call(
        functools.partial(_operands_bwd_kernel, groups=groups, eps=eps, turned=turn is not None, normed=normed),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype), jax.ShapeDtypeStruct(k.shape, k.dtype),
                   jax.ShapeDtypeStruct(k.shape, dv.dtype), *([share, share] if normed else [])),
        name="attn_operands_bwd", interpret=interpret, **grid,
    )(dq, dk, dv, *((q, k, wq[None], wk[None]) if normed else ()), *(turn or ()))
    if not normed:
        return dq, dk, dv, None, None
    dwq, dwk = shares
    return dq, dk, dv, dwq.sum((0, 1, 2, 3)).astype(wq.dtype), dwk.sum((0, 1, 2, 3)).astype(wk.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(6, 7, 8))
def _attention_operands(q, k, v, wq, wk, turn, d, eps, bt):
    return _operands_forward(q, k, v, wq, wk, turn, d=d, eps=eps, bt=bt, interpret=not platform.on_tpu())


def _attention_operands_fwd(q, k, v, wq, wk, turn, d, eps, bt):
    # nothing new is kept: a checkpoint around the caller computes the raw q and k again, as it did
    return _attention_operands(q, k, v, wq, wk, turn, d, eps, bt), (q, k, wq, wk, turn)


def _attention_operands_bwd(d, eps, bt, kept, cotangents):
    q, k, wq, wk, turn = kept
    grads = _operands_backward(*cotangents, q, k, wq, wk, turn, d=d, eps=eps, bt=bt, interpret=not platform.on_tpu())
    return *grads, jax.tree.map(jnp.zeros_like, turn)  # the tables come from positions alone


_attention_operands.defvjp(_attention_operands_fwd, _attention_operands_bwd)


def _xla_operands(q, k, v, wq, wk, *, eps: float, centred: bool, rotary_dim: int | None, theta: float):
    """The raw q [B, T, heads, D], k, v [B, T, kv, D] and the two head norms'
    weights (both None: a mixer whose heads are not normed, and ``eps`` and
    ``centred`` are then not read) → the flash kernels' q [B, kv, G, T, D] (normed, turned over
    ``rotary_dim`` channels, scaled), k (normed, turned), v [B, kv, T, D], as
    whole-array ``jnp`` operations in float32: what a shape the operand
    kernels do not take runs, and the kernels' twin."""
    b, t, heads, d = q.shape
    kv_heads = k.shape[2]
    dtype = v.dtype
    positions = jnp.arange(t)

    def turned(a):
        return a if rotary_dim is None else _rotary(a, positions, rotary_dim, theta)

    def normed(a, w):
        return a.astype(jnp.float32) if w is None else rms_norm(a, w, eps, centred=centred)

    q = turned(normed(q, wq))
    k = turned(normed(k, wk))
    q = (q * d**-0.5).astype(dtype)
    # [B, T, heads, D] → [B, kv, heads // kv, T, D]: each key-value head serves a group
    q = q.reshape(b, t, kv_heads, heads // kv_heads, d).transpose(0, 2, 3, 1, 4)
    k, v = (a.transpose(0, 2, 1, 3) for a in (k.astype(dtype), v))
    return q, k, v


def _turn_tables(t: int, d: int, theta: float):
    """The operand kernels' position tables for a row of ``t`` tokens turned
    over a whole head: ``([cos | cos], [-sin | sin])`` [T, D] float32."""
    angle = _rotary_angles(jnp.arange(t), d, theta)
    cos, sin = jnp.cos(angle), jnp.sin(angle)
    return jnp.concatenate([cos, cos], axis=-1), jnp.concatenate([-sin, sin], axis=-1)


def _kernel_operands(q, k, v, wq, wk, bt: int, *, eps: float, centred: bool, rotary_dim: int | None, theta: float):
    """:func:`_xla_operands` by the operand kernels, ``bt`` tokens a block
    (:func:`_operand_tiles`): the same arguments, the same results."""
    b, t, _, d = q.shape
    turn = None if rotary_dim is None else _turn_tables(t, rotary_dim, theta)
    if wq is not None:
        wq, wk = (((1.0 + w) if centred else w).astype(jnp.float32) for w in (wq, wk))
    # the reshapes undo the caller's: the kernels read the products' own [B, T, heads x D]
    q, k, v = (a.reshape(b, t, -1) for a in (q, k, v))
    return _attention_operands(q, k, v, wq, wk, turn, d, eps, bt)


_traced_tiles = threading.local()  # ``.steps``, ``.pairs``, ``.operands``: what :func:`mixer_counts` collects while it traces a mixer


def mixer_counts(mixer, x, p) -> dict:
    """What ``mixer(x, p)``'s shapes make its attention run, as host integers
    from one abstract trace of the mixer (nothing runs), summed over every
    :func:`causal_attention` and :func:`attention_operands` it calls:
    ``attn_tiles_run`` and ``attn_tiles_causal``, the (query tile, key tile)
    steps the attention kernels' lists hold over its rows and key-value heads
    and the steps causal lists alone would hold (:func:`key_tile_steps`; 0 for
    a shape the kernels do not take); ``attn_out_tokens`` and
    ``attn_out_heads``, its rows by where the attention wrote its output:
    token-major through the kernels' block specs (:func:`_token_major`), or
    heads first and transposed after; ``attn_operands_kernel`` and
    ``attn_operands_xla``, its rows by what made the flash kernels' operands,
    the operand kernels or the ``jnp`` lines (:func:`_operand_tiles`);
    ``attn_pair_tiles_run`` and ``attn_pair_tiles``, of ``attn_tiles_run`` the
    steps of :func:`paired_attention`'s calls and the steps two score maps a
    head pair require (equal where every map is computed once; 0 for a mixer
    without pairs).  All 0 for a mixer without attention."""
    _traced_tiles.steps, _traced_tiles.pairs, _traced_tiles.operands = steps, pairs, operands = [], [], []
    try:
        jax.eval_shape(lambda x, p: mixer(x, p), x, p)  # a function of its own: a trace cached for ``mixer`` collects nothing
    finally:
        del _traced_tiles.steps, _traced_tiles.pairs, _traced_tiles.operands
    return {
        "attn_tiles_run": sum(run for run, *_ in steps), "attn_tiles_causal": sum(causal for _, causal, *_ in steps),
        "attn_pair_tiles_run": sum(run for run, _ in pairs), "attn_pair_tiles": sum(required for _, required in pairs),
        "attn_out_tokens": sum(rows for *_, rows, tokens in steps if tokens),
        "attn_out_heads": sum(rows for *_, rows, tokens in steps if not tokens),
        "attn_operands_kernel": sum(rows for rows, fused in operands if fused),
        "attn_operands_xla": sum(rows for rows, fused in operands if not fused),
    }


def causal_attention(q, k, v, window: int | None = None):
    """Causal softmax attention with grouped-query heads: q [B, Hkv, G, T, D]
    (scaled), k [B, Hkv, T, D], v [B, Hkv, T, Dv] → [B, T, heads, Dv], tokens
    before heads as the output projection reads it, a key-value head's group
    side by side (head ``kv * G + g``).  ``Dv`` is read off ``v``: the head's
    size ``D`` in every mixer but differential attention's, whose value is a
    pair's two (:func:`paired_attention`).  Two masks: key ``j`` is
    visible to query ``i`` iff ``j <= i`` and, under a ``window``,
    ``i - j < window`` (the query's own position and the ``window - 1`` before
    it); a window of the row's length or more is no window.

    Operands in their own type (bfloat16 in a model), scores, maximum, sum and
    accumulators in float32, the probabilities cast only as the second
    product's operand, the division by the sum after the accumulation.

    Where :func:`_flash_tiles` takes the shape (a head, and a value, of 64,
    128 or 256 channels, a row of whole 128-key tiles) two Pallas kernels
    under one ``custom_vjp`` do all of it, compiled on a TPU and in the Pallas
    interpreter elsewhere: no score leaves VMEM in either pass, and a key tile
    the mask hides whole is no step of the grid (:func:`_flash_pairs`).  The
    backward pass keeps the output and the log-sum-exp, named
    :data:`ATTN_KEPT` for a checkpoint around the caller, and computes the
    scores again from q, k and the log-sum-exp in float32.  Every other shape
    runs :func:`_blockwise_attention`.

    The operands come heads first and the three gradients go back so.  The
    output is the other way round: where :func:`_token_major` takes the shape
    (a head of whole 128-lane tiles and a value as wide) the forward kernel's
    output block spec writes it token-major and the backward kernel's reads
    its cotangent and the kept output there (and sums ``delta`` from them),
    so no layout copy stands between the kernels and ``w_o`` in either pass;
    a head of 64, a value wider than the head, and the blockwise path, write
    heads first and the transpose here is a copy."""
    b, hkv, groups, t, d = q.shape
    dv = v.shape[-1]
    if window is not None and window >= t:
        window = None
    tokens = _token_major(t, groups, d, dv)
    if hasattr(_traced_tiles, "steps"):  # :func:`mixer_counts` is tracing the caller
        _traced_tiles.steps.append((*(b * hkv * n for n in key_tile_steps(t, groups, d, window, dv)), b, tokens))
    tiles = _flash_tiles(t, groups, d, dv)
    if tiles is None:
        o = _blockwise_attention(q, k, v, ATTN_BAND, ATTN_ROWS, window)
    else:
        o = _flash_attention(
            q.reshape(b * hkv, groups, t, d), k.reshape(b * hkv, t, d), v.reshape(b * hkv, t, dv), *tiles, window,
            b if tokens else None,
        )
    if not tokens:  # heads first: laid out for the projection here, by copies
        o = o.reshape(b, hkv, groups, t, dv).transpose(0, 3, 1, 2, 4)
    return o.reshape(b, t, hkv * groups, dv)


def paired_attention(q, k, v, window: int | None = None):
    """The two score maps of differential attention's head pairs
    (arXiv:2410.05258) over ONE :func:`causal_attention`: q [B, T, heads, D]
    (scaled), k, v [B, T, kv heads, D] → (o1, o2), each [B, T, heads / 2, 2D].
    Adjacent heads pair: query pair ``p`` is heads ``2p`` (``q1``) and
    ``2p + 1`` (``q2``), key-value pair ``g`` keys ``2g`` (``k1``) and
    ``2g + 1`` (``k2``) and the value ``V = [v_2g ; v_2g+1]``, 2D wide; pair
    ``p`` reads ``g = p // r``, ``r`` the query pairs a key-value pair
    serves.  ``o1 = softmax(q1 k1^T) V`` and ``o2 = softmax(q2 k2^T) V``
    under the causal mask and ``window``.

    :func:`causal_attention` takes a value wider than its key, so each map is
    one key-value head and computed once: key-value head ``(g, a)`` is key
    ``2g + a`` beside the value ``V_g`` (``v``'s heads ``2g`` and ``2g + 1``
    as they lie side by side, once for each ``a``) and serves the ``r``
    queries ``2(g r + i) + a``: 12 D operations a query pair and visible key,
    the required ones.  ``o1`` is the heads ``a = 0`` and ``o2`` the heads
    ``a = 1``."""
    b, t, heads, d = q.shape
    pairs = k.shape[2] // 2
    r = heads // (2 * pairs)
    q = q.reshape(b, t, pairs, r, 2, d).transpose(0, 2, 4, 3, 1, 5)  # [B, g, a, i, T, D]
    k = k.reshape(b, t, pairs, 2, d).transpose(0, 2, 3, 1, 4)        # [B, g, a, T, D]
    v = jnp.broadcast_to(v.reshape(b, t, pairs, 1, 2 * d).transpose(0, 2, 3, 1, 4), (b, pairs, 2, t, 2 * d))
    o = causal_attention(
        q.reshape(b, 2 * pairs, r, t, d), k.reshape(b, 2 * pairs, t, d), v.reshape(b, 2 * pairs, t, 2 * d), window
    )
    if hasattr(_traced_tiles, "pairs"):  # :func:`mixer_counts` is tracing the caller: the call above is its last entry
        required = b * 2 * pairs * key_tile_steps(t, r, d, window, 2 * d)[0]
        _traced_tiles.pairs.append((_traced_tiles.steps[-1][0], required))
    o = o.reshape(b, t, pairs, 2, r, 2 * d)  # heads ((g, a), i)
    return o[:, :, :, 0].reshape(b, t, pairs * r, 2 * d), o[:, :, :, 1].reshape(b, t, pairs * r, 2 * d)


def attention_operands(q, k, v, wq, wk, *, eps: float, centred: bool, rotary_dim: int | None, theta: float):
    """A softmax-attention mixer's raw q, k, v and its two head norms' weights
    → :func:`causal_attention`'s operands (:func:`_xla_operands`' arguments
    and results): by the operand kernels in one pass where
    :func:`_operand_tiles` takes the shape, else by :func:`_xla_operands`.
    ``eps`` and ``centred`` are the family's RMS norm's (:func:`rms_norm`)."""
    b, t, heads, d = q.shape
    recipe = dict(eps=eps, centred=centred, rotary_dim=rotary_dim, theta=theta)
    bt = _operand_tiles(t, heads, k.shape[2], d, rotary_dim)
    if hasattr(_traced_tiles, "operands"):  # :func:`mixer_counts` is tracing the caller
        _traced_tiles.operands.append((b, bt is not None))
    if bt is None:
        return _xla_operands(q, k, v, wq, wk, **recipe)
    return _kernel_operands(q, k, v, wq, wk, bt, **recipe)
