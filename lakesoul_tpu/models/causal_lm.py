"""What the causal-LM families share: the layer stack, the head and the loss,
and the pieces more than one family's mixers are made of.

A family is a module with a configuration object (``models/qwen3_next.py``,
``models/lfm2_moe.py``, ``models/glm4_moe_lite.py``, ``models/afmoe.py``,
``models/ouro.py``); nothing here or in
``models/train.py`` names one.  The stack reads a layer's kinds from the
configuration and asks it for the rest:

- ``layer_kinds()``: each layer's mixer (``"gdn"``, ``"attn"``, ``"conv"``,
  ``"mla"``: latent attention, :func:`latent_attention`; ``"swa"``:
  :func:`softmax_attention` under a window), which is also the
  key of the mixer's weights in the layer;
- ``ffn_kinds()``: each layer's feed-forward, ``"dense"`` (SwiGLU, weights
  under ``"mlp"``) or ``"moe"`` (routed experts, under ``"moe"``);
- ``mixer(kind)`` → (``fn(y, p)`` on the normed input, the ``named_scope`` its
  device time is charged to);
- ``norm(x, w)``: the family's RMS norm, float32 out;
- ``route(y32, router, bias)`` → (experts, weights, assignments the bias
  moved): its routing rule over ``parallel/moe.py``;
- ``num_experts``, ``experts_held``, ``dtype``; and for ``models/train.py``
  ``init(key)`` and ``loss(params, ids, labels, batch_sharding=)``;
- ``mtp_loss_weight``, read only where the weights hold a prediction module;
  ``embed_scale``, where the family has one: what the embedding's output is
  multiplied by;
- ``loop_passes``, where the family has it: how many times the stack runs
  over its ONE set of weights (a looped model; :func:`loop_hidden`), with
  ``exit_beta``, the weight of the entropy term in its objective
  (:func:`exit_loss`); ``models/train.py`` reads ``loop_passes`` too, for the
  series a looped step feeds.  A family without it is walked once, by the
  plain Python loop (:func:`lm_hidden`), and its step's program does not
  change by what a looped family needs.

A looped family's step (:func:`loop_loss`): the walk through the layers
``loop_passes`` times as one ``lax.scan`` whose body ends in the final norm
(the normed state is what the next pass starts from and what the head reads),
the passes' states stacked; then ONE call of the head and the loss's tile loop
over the stacked states with a weight a position (``models/bert.py:
labelled_nll``'s weighted form: the exit distribution), the exit gate and the
expected loss under :data:`EXIT_SCOPE`.  ``params["exit"]`` holds the gate
(``w`` [h], ``b``): ordinary trained leaves.

The loss (:func:`lm_loss`) is the next-token cross-entropy, and where the
weights hold a multi-token-prediction module (``params["mtp"]``) that module's
loss times ``cfg.mtp_loss_weight`` on top (:func:`mtp_loss`): the final hidden
states and the next token's embedding through one more layer of the stack's
last kind, with its own weights, to the token after next, through the main
model's embedding and head matrix.  The head then runs twice a step.  Every
loss here goes through ``models/bert.py: labelled_nll``'s tile loop and hands
it :func:`fused_tile` as the tile's body: ``jax.vjp`` of the head, one Pallas
kernel from the float32 logits to each row's NLL and the logits' cotangent
(``models/loss_tile.py``), the head's pull-back; a tile smaller than any it
is measured at runs the loop's default body, the compiler's log-softmax and
autodiff, as the masked-LM loss always does (it passes no body, and ``models/bert.py`` imports
no Pallas).

A layer is ``h = x + mixer(norm1(x)); x' = h + ffn(norm2(h))``, and where its
weights hold ``norm1_out`` and ``norm2_out`` it has four norms, each
sublayer's output normed before it is added: ``h = x + norm1_out(mixer(
norm1(x))); x' = h + norm2_out(ffn(norm2(h)))``, the last over the routed
experts' share and the shared expert summed.  After the last layer
a final norm and the head: ``params["head"]`` [h, vocab] where there is one,
else the embedding (a tied head).  ``params["buffers"]``, where a family has
it, is state that no gradient and no optimizer touches (``models/train.py:
_adamw_step``); ``buffers["layers"][i]`` belongs to layer ``i`` and
``buffers["mtp"]`` to the prediction module's layer.

Softmax attention (:func:`causal_attention`, every family's) is two Pallas
kernels under one ``custom_vjp``, compiled on a TPU and in the Pallas
interpreter elsewhere, for the head sizes and row lengths :func:`_flash_tiles`
takes (a head of 64, 128 or 256 channels, a row of whole 128-key tiles: the
five published models at 8,192 tokens: groups of 4 at head 64, of 8 at 256, of
1 at 256 on 20 key-value heads, of 8 at 128, and of 1 at 128 on 16 key-value
heads); any other shape runs the blockwise ``jnp``
path, the kernels' twin.  It has two masks: causal, and under a ``window`` the
band of a query's own position and the ``window - 1`` before it; the kernels'
grid is the list of (query tile, key tile) pairs either mask lets anything
through (:func:`_flash_pairs`), so a key tile a window hides whole is neither
fetched nor multiplied, and :func:`key_tile_steps` counts the list for the
step's counters.  Of the scores nothing leaves VMEM in either pass;
the backward pass keeps the output and the log-sum-exp, which
:func:`_row_by_row`'s checkpoint holds on to by name (:data:`ATTN_KEPT`), so a
step runs the forward kernel once a row and the backward kernel once.
Between its projections and the kernels :func:`softmax_attention` norms each
head, turns it by its position, scales the query and lays heads before
tokens, by the operand kernels where :func:`_operand_tiles` takes the shape;
where the mixer's weights hold no ``q_norm`` and no ``k_norm`` (plain
attention) neither path norms, a static flag of the kernels as ``turned`` is.
The two sides of the flash pair speak two layouts: the operands arrive heads
first and the three gradients go back so, the output leaves token-major,
``[B, T, heads x D]`` as the gate and ``w_o`` read it.  Where
:func:`_token_major` takes the shape (a head of whole 128-lane tiles: every
published head but 64) that is the kernels' own block spec: the forward
kernel stores each head of a group at its column slice of a ``bq``-token
block, the backward kernel loads the cotangent and the kept output from
there (once a query tile, and sums ``delta = sum(o * do)`` from them then),
and no layout copy stands between the kernels and ``w_o`` in either pass;
elsewhere the output is written heads first, ``delta`` is XLA's, and the
output is transposed with ``jnp`` inside :func:`causal_attention`.

The model may be one chip's share of an expert-parallel job: ``experts_held``
says which of the ``num_experts`` live here (``parallel/moe.py: held_experts``)
and ``vocab_size`` is the slice of the vocabulary the embedding, the head and
the loss are over.  Parallelism: dp over rows; everything else is replicated.
"""

from __future__ import annotations

import functools
import math
import threading

import jax
import jax.numpy as jnp
from jax.ad_checkpoint import checkpoint_name
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu
from jax.sharding import PartitionSpec as P

from lakesoul_tpu.models.bert import head_tile, labelled_nll, tile_grads
from lakesoul_tpu.models.loss_tile import loss_tile, tile_takes
from lakesoul_tpu.parallel.mesh import spec_axes
from lakesoul_tpu.parallel.moe import EXPERTS_SCOPE, ROUTE_SCOPE, SHARED_SCOPE, held_experts, shared_expert
from lakesoul_tpu.parallel.ring_attention import block_attn
from lakesoul_tpu.vector.kernels import _on_tpu

ATTN_SCOPE = "lakesoul.lm.attn"
MLA_SCOPE = "lakesoul.lm.mla"  # inside ATTN_SCOPE: what latent attention adds around the kernels
MTP_SCOPE = "lakesoul.lm.mtp"  # the whole prediction module, its layer's and its head's scopes inside
MLP_SCOPE = "lakesoul.lm.mlp"
HEAD_SCOPE = "lakesoul.lm.head"
EMBED_SCOPE = "lakesoul.lm.embed"  # the token lookup and, through its transpose, the scatter-add of its gradient
EXIT_SCOPE = "lakesoul.lm.exit"    # a looped model's exit gate, its distribution over the passes and the expected loss
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
EXIT_MASS_UNIT = 1024  # :func:`exit_loss` counts the exit distribution's mass in this fraction of a position
_NT = (((1,), (1,)), ((), ()))  # x y^T
_TN = (((0,), (0,)), ((), ()))  # x^T y


def normal_init(key, *shape):
    """A weight matrix from a key: normal(0, 0.02), float32."""
    return (jax.random.normal(key, shape) * 0.02).astype(jnp.float32)


def _rms_norm(x, w, eps, *, centred: bool = True):
    """``x * rsqrt(mean(x^2) + eps) * (1 + w)`` in float32 (``* w`` where the
    weight is not zero-centred); float32 out."""
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return y * ((1.0 + w) if centred else w)


def causal_conv(x, w):
    """Depthwise causal convolution, no bias, no activation: x [B, T, C],
    w [C, K]; tap ``K-1`` sits on the current token, zeros left of the row."""
    taps = w.shape[1]
    t = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(xp[:, j:j + t].astype(jnp.float32) * w[:, j] for j in range(taps)).astype(x.dtype)


# ------------------------------------------------------ softmax attention


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
    twin and reference."""
    b, hkv, groups, t, d = q.shape

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
        return (o / l[..., None]).astype(v.dtype).reshape(b, hkv, groups, n, d)

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
        out.append(jnp.moveaxis(o, 0, 3).reshape(b, hkv, groups, end - start, d))
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


def _flash_tiles(t: int, groups: int, d: int):
    """(queries, keys) a tile holds for rows of ``t`` tokens, or None where
    the kernels do not take the shape: a head of :data:`FLASH_HEADS`, a row
    that is whole tiles of 128 keys, and a row's float32 dK and dV held in VMEM
    through the backward kernel (twice: the pipeline's two buffers)."""
    if d not in FLASH_HEADS or t % 128 or t * d > FLASH_ROW_ELEMENTS:
        return None
    bk = next(n for n in (512, 256, 128) if n <= FLASH_KEYS and t % n == 0)
    return max(128, min(bk, FLASH_ROWS // groups)), bk


def _token_major(t: int, groups: int, d: int) -> bool:
    """Whether the flash kernels write the output, and read its cotangent,
    token-major, [B, T, heads x D] as the gate and ``w_o`` read it: a shape
    they take (:func:`_flash_tiles`) whose head is whole 128-lane tiles.  A
    block of that array, ``bq`` tokens by a key-value head's ``G x D`` lanes,
    is then a query tile of the group, each head at a lane-aligned column
    slice; two heads of 64 would share a lane tile."""
    return _flash_tiles(t, groups, d) is not None and d % 128 == 0


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


def key_tile_steps(t: int, groups: int, d: int, window: int | None = None) -> tuple[int, int]:
    """(steps the kernels' list holds for one key-value head of one row, steps
    a causal list alone would hold): host integers off :func:`_flash_pairs`;
    (0, 0) for a shape the kernels do not take."""
    tiles = _flash_tiles(t, groups, d)
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
    """One step: a group's query tile [G, bq, D] against a key tile [bk, D].
    Running maximum and sum [G*bq, 128] (every lane the same) and the weighted
    values [G*bq, D] stay in VMEM over a query tile's steps; the last of them
    divides and writes the output (heads first, or token-major where its block
    is: :func:`_store_group_rows`) and the log-sum-exp [G, 1, bq]."""
    i, j, first, last = _flash_step(qi_ref, kj_ref, bq, bk, window)
    groups, _, d = q_ref.shape
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
        acc_ref[...] = acc_ref[...] * _lanes(alpha, d) + jnp.dot(p.astype(v.dtype), v, preferred_element_type=f32)

    def finish():
        l = l_ref[...]
        _store_group_rows(o_ref, acc_ref[...] / _lanes(l, d), groups, d)
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
    last step, of dK and dV into the row's whole float32 dK, dV [T, D], which
    stay in VMEM over all of a key-value head's steps.

    Heads first, ``do_ref`` [G, bq, D] is the operand as it lies and
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
        do = assembled[0][...] if assembled else do_ref[...].reshape(rows, d)
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


def _flash_grid(q, bq: int, bk: int, window, *, in_specs, out_specs, scratch_shapes, batch: int | None = None):
    """What the two kernels' ``pallas_call``s share, over q's [N, G, T, D]:
    the grid (key-value heads, steps of :func:`_flash_steps`) with the two
    tables in SMEM, and block specs by what a block follows: a query tile's
    [G, bq, D], its per-query floats [G, 1, bq], a key tile's [bk, D], a
    key-value head's whole [T, D]; ``in_specs`` and ``out_specs`` name those.
    With ``batch`` (the ``N`` key-value heads are those of ``batch`` rows)
    also ``tokens``, the query tile in a token-major array [batch, T, heads x
    D]: [bq, G*D] at the row's tokens and the key-value head's columns.
    Returns (the tables, the call's keyword arguments)."""
    n, groups, t, d = q.shape
    tables = _flash_steps(t, bq, bk, window)
    specs = {
        "query": pl.BlockSpec((None, groups, bq, d), lambda h, s, qi, kj: (h, 0, qi[s], 0)),
        "per_query": pl.BlockSpec((None, groups, 1, bq), lambda h, s, qi, kj: (h, 0, 0, qi[s])),
        "keys": pl.BlockSpec((None, bk, d), lambda h, s, qi, kj: (h, kj[s], 0)),
        "whole_row": pl.BlockSpec((None, t, d), lambda h, s, qi, kj: (h, 0, 0)),
    }
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
    """q [N, G, T, D], k, v [N, T, D] → (o [N, G, T, D], log-sum-exp
    [N, G, 1, T] float32).  With ``batch`` (:func:`_token_major` shapes: the
    ``N`` key-value heads are ``batch`` rows') o is written token-major,
    [batch, T, heads x D] with a key-value head's group side by side: the
    same values at the addresses the gate and ``w_o`` read."""
    n, groups, t, d = q.shape
    rows = groups * bq
    tables, grid = _flash_grid(
        q, bq, bk, window, in_specs=("query", "keys", "keys"), batch=batch,
        out_specs=("query" if batch is None else "tokens", "per_query"),
        scratch_shapes=[pltpu.VMEM((rows, 128), jnp.float32)] * 2 + [pltpu.VMEM((rows, d), jnp.float32)],
    )
    o_shape = q.shape if batch is None else (batch, t, n // batch * groups * d)
    return pl.pallas_call(
        functools.partial(_flash_fwd_kernel, bq=bq, bk=bk, window=window),
        out_shape=(jax.ShapeDtypeStruct(o_shape, v.dtype), jax.ShapeDtypeStruct((n, groups, 1, t), jnp.float32)),
        name="flash_attention_fwd", interpret=interpret, **grid,
    )(*tables, q, k, v)


@functools.partial(jax.jit, static_argnames=("bq", "bk", "window", "interpret"))
def _flash_backward(q, k, v, o, lse, do, *, bq: int, bk: int, window: int | None = None, interpret: bool):
    """The three gradients, dK and dV summed over the group; ``o`` and ``do``
    as :func:`_flash_forward` wrote ``o``.  Heads first, [N, G, T, D]:
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
        batch, do_spec, given_spec = None, "query", "per_query"
        given = jnp.sum(o.astype(jnp.float32) * do.astype(jnp.float32), axis=-1)[:, :, None, :]
    tables, grid = _flash_grid(
        q, bq, bk, window, batch=batch, in_specs=("query", "keys", "keys", do_spec, "per_query", given_spec),
        out_specs=("query", "whole_row", "whole_row"), scratch_shapes=scratch,
    )
    dq, dk, dv = pl.pallas_call(
        functools.partial(_flash_bwd_kernel, bq=bq, bk=bk, window=window),
        out_shape=(jax.ShapeDtypeStruct(q.shape, q.dtype), *[jax.ShapeDtypeStruct(k.shape, jnp.float32)] * 2),
        name="flash_attention_bwd", interpret=interpret, **grid,
    )(*tables, q, k, v, do, lse, given)
    return dq, dk.astype(k.dtype), dv.astype(v.dtype)


@functools.partial(jax.custom_vjp, nondiff_argnums=(3, 4, 5, 6))
def _flash_attention(q, k, v, bq, bk, window, batch):
    return _flash_attention_fwd(q, k, v, bq, bk, window, batch)[0]


def _flash_attention_fwd(q, k, v, bq, bk, window, batch):
    o, lse = _flash_forward(q, k, v, bq=bq, bk=bk, window=window, batch=batch, interpret=not _on_tpu())
    # a checkpoint around the caller may keep these two and run no second forward kernel
    o, lse = checkpoint_name(o, ATTN_KEPT[0]), checkpoint_name(lse, ATTN_KEPT[1])
    return o, (q, k, v, o, lse)


def _flash_attention_bwd(bq, bk, window, batch, kept, do):
    return _flash_backward(*kept, do, bq=bq, bk=bk, window=window, interpret=not _on_tpu())


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
    (:func:`_rms_norm` by the weight as it multiplies; not where ``w`` is
    None: a mixer without head norms) and turned: ``[x1 cos -
    x2 sin | x2 cos + x1 sin]`` as ``y * [cos | cos] + roll(y) * [-sin | sin]``."""
    y = x if w is None else _rms_norm(x, w, eps, centred=False)
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
    return _operands_forward(q, k, v, wq, wk, turn, d=d, eps=eps, bt=bt, interpret=not _on_tpu())


def _attention_operands_fwd(q, k, v, wq, wk, turn, d, eps, bt):
    # nothing new is kept: a checkpoint around the caller computes the raw q and k again, as it did
    return _attention_operands(q, k, v, wq, wk, turn, d, eps, bt), (q, k, wq, wk, turn)


def _attention_operands_bwd(d, eps, bt, kept, cotangents):
    q, k, wq, wk, turn = kept
    grads = _operands_backward(*cotangents, q, k, wq, wk, turn, d=d, eps=eps, bt=bt, interpret=not _on_tpu())
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
        return a.astype(jnp.float32) if w is None else _rms_norm(a, w, eps, centred=centred)

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


_traced_tiles = threading.local()  # ``.steps``, ``.operands``: what :func:`mixer_counts` collects while it traces a mixer


def mixer_counts(mixer, x, p) -> dict:
    """What ``mixer(x, p)``'s shapes make its attention run, as host integers
    from one abstract trace of the mixer (nothing runs), summed over every
    :func:`causal_attention` and :func:`softmax_attention` it calls:
    ``attn_tiles_run`` and ``attn_tiles_causal``, the (query tile, key tile)
    steps the attention kernels' lists hold over its rows and key-value heads
    and the steps causal lists alone would hold (:func:`key_tile_steps`; 0 for
    a shape the kernels do not take); ``attn_out_tokens`` and
    ``attn_out_heads``, its rows by where the attention wrote its output:
    token-major through the kernels' block specs (:func:`_token_major`), or
    heads first and transposed after; ``attn_operands_kernel`` and
    ``attn_operands_xla``, its rows by what made the flash kernels' operands,
    the operand kernels or the ``jnp`` lines (:func:`_operand_tiles`).  All 0
    for a mixer without attention."""
    _traced_tiles.steps, _traced_tiles.operands = steps, operands = [], []
    try:
        jax.eval_shape(lambda x, p: mixer(x, p), x, p)  # a function of its own: a trace cached for ``mixer`` collects nothing
    finally:
        del _traced_tiles.steps, _traced_tiles.operands
    return {
        "attn_tiles_run": sum(run for run, *_ in steps), "attn_tiles_causal": sum(causal for _, causal, *_ in steps),
        "attn_out_tokens": sum(rows for *_, rows, tokens in steps if tokens),
        "attn_out_heads": sum(rows for *_, rows, tokens in steps if not tokens),
        "attn_operands_kernel": sum(rows for rows, fused in operands if fused),
        "attn_operands_xla": sum(rows for rows, fused in operands if not fused),
    }


def causal_attention(q, k, v, window: int | None = None):
    """Causal softmax attention with grouped-query heads: q [B, Hkv, G, T, D]
    (scaled), k, v [B, Hkv, T, D] → [B, T, heads, D], tokens before heads as
    the output projection reads it, a key-value head's group side by side
    (head ``kv * G + g``).  Two masks: key ``j`` is
    visible to query ``i`` iff ``j <= i`` and, under a ``window``,
    ``i - j < window`` (the query's own position and the ``window - 1`` before
    it); a window of the row's length or more is no window.

    Operands in their own type (bfloat16 in a model), scores, maximum, sum and
    accumulators in float32, the probabilities cast only as the second
    product's operand, the division by the sum after the accumulation.

    Where :func:`_flash_tiles` takes the shape (a head of 64, 128 or 256
    channels, a row of whole 128-key tiles) two Pallas kernels under one
    ``custom_vjp`` do all of it, compiled on a TPU and in the Pallas
    interpreter elsewhere: no score leaves VMEM in either pass, and a key tile
    the mask hides whole is no step of the grid (:func:`_flash_pairs`).  The
    backward pass keeps the output and the log-sum-exp, named
    :data:`ATTN_KEPT` for a checkpoint around the caller, and computes the
    scores again from q, k and the log-sum-exp in float32.  Every other shape
    runs :func:`_blockwise_attention`.

    The operands come heads first and the three gradients go back so.  The
    output is the other way round: where :func:`_token_major` takes the shape
    (a head of whole 128-lane tiles) the forward kernel's output block spec
    writes it token-major and the backward kernel's reads its cotangent and
    the kept output there (and sums ``delta`` from them), so no layout copy
    stands between the kernels and ``w_o`` in either pass; a head of 64, and
    the blockwise path, write heads first and the transpose here is a copy."""
    b, hkv, groups, t, d = q.shape
    if window is not None and window >= t:
        window = None
    tokens = _token_major(t, groups, d)
    if hasattr(_traced_tiles, "steps"):  # :func:`mixer_counts` is tracing the caller
        _traced_tiles.steps.append((*(b * hkv * n for n in key_tile_steps(t, groups, d, window)), b, tokens))
    tiles = _flash_tiles(t, groups, d)
    if tiles is None:
        o = _blockwise_attention(q, k, v, ATTN_BAND, ATTN_ROWS, window)
    else:
        o = _flash_attention(
            q.reshape(b * hkv, groups, t, d), *(a.reshape(b * hkv, t, d) for a in (k, v)), *tiles, window,
            b if tokens else None,
        )
    if not tokens:  # heads first: laid out for the projection here, by copies
        o = o.reshape(q.shape).transpose(0, 3, 1, 2, 4)
    return o.reshape(b, t, hkv * groups, d)


def softmax_attention(x, p, *, heads: int, kv_heads: int, head_dim: int, rotary_dim: int | None,
                      theta: float, eps: float, centred: bool, gated: bool, window: int | None = None):
    """The grouped-query softmax-attention mixer: x [B, T, h] (normed) →
    [B, T, h].  ``eps`` and ``centred`` are the family's RMS norm's
    (:func:`_rms_norm`), here over a head's channels (``q_norm``, ``k_norm``;
    where the weights hold neither, the heads are not normed: plain attention);
    ``rotary_dim`` of them are rotated, none where it
    is None (a layer that sees no positions).  ``window``:
    :func:`causal_attention`'s.  A gate's sigmoid scales each head's output
    before ``w_o``: ``gated``: ``w_q`` holds per head the query, then the gate
    (the Qwen3-Next family); else a matrix of the gate's own where the weights
    hold one (``w_gate`` [h, heads x D]), else none.

    Between the projections and the kernels the query and the key are normed,
    turned, the query scaled, and all three laid out heads first: by the
    operand kernels in one pass where :func:`_operand_tiles` takes the shape
    (a head of whole lane tiles, positions over all of it or none), else by
    :func:`_xla_operands`."""
    dtype = x.dtype
    f32 = jnp.float32
    b, t, _ = x.shape
    d = head_dim
    q = (x @ p["w_q"].astype(dtype)).reshape(b, t, heads, (2 if gated else 1) * d)
    gate = None
    if gated:
        q, gate = q[..., :d], q[..., d:]
    elif "w_gate" in p:
        gate = (x @ p["w_gate"].astype(dtype)).reshape(b, t, heads, d)
    k = (x @ p["w_k"].astype(dtype)).reshape(b, t, kv_heads, d)
    v = (x @ p["w_v"].astype(dtype)).reshape(b, t, kv_heads, d)
    recipe = dict(eps=eps, centred=centred, rotary_dim=rotary_dim, theta=theta)
    bt = _operand_tiles(t, heads, kv_heads, d, rotary_dim)
    if hasattr(_traced_tiles, "operands"):  # :func:`mixer_counts` is tracing the caller
        _traced_tiles.operands.append((b, bt is not None))
    if bt is None:
        q, k, v = _xla_operands(q, k, v, p.get("q_norm"), p.get("k_norm"), **recipe)
    else:
        q, k, v = _kernel_operands(q, k, v, p.get("q_norm"), p.get("k_norm"), bt, **recipe)
    # the gate and ``w_o`` over [B, T, heads x D], no head axis: on [.., heads, D] arrays XLA lays the gate's
    # passes tokens-minor for the weight-gradient products and copies the kernels' row-major o and do across
    o = causal_attention(q, k, v, window).reshape(b, t, heads * d)
    if gate is not None:
        o = (o.astype(f32) * jax.nn.sigmoid(gate.reshape(b, t, heads * d).astype(f32))).astype(dtype)
    return o @ p["w_o"].astype(dtype)


def latent_attention(x, p, *, heads: int, nope_dim: int, rope_dim: int, theta: float, norm):
    """The latent-attention mixer (multi-head latent attention, unabsorbed: a
    training step's form, whose gradients reach the up-projections): x
    [B, T, h] (normed) → [B, T, h].

    ``c_q = norm(x W_dq)``, a head's query ``[q_nope | q_rope] = c_q W_uq``;
    ``[c_kv | k_r] = x W_dkv``, ``c_kv`` normed, a head's ``[k_nope | v] = c_kv
    W_ukv``; ``q_rope`` and ``k_r`` rotated over all their ``rope_dim``
    channels, and ``k_r`` is ONE head that every query head's key ends in.
    Scores over ``nope_dim + rope_dim`` channels, scaled by their root; values
    as wide (``w_ukv`` says how wide: :func:`causal_attention` is one head
    size).  No norm over a head, no gate.  ``norm(a, w)`` is the family's RMS
    norm (``q_norm``, ``kv_norm`` over the latents)."""
    dtype = x.dtype
    f32 = jnp.float32
    b, t, _ = x.shape
    d = nope_dim + rope_dim
    with jax.named_scope(MLA_SCOPE):
        c_q = norm(x @ p["w_dq"].astype(dtype), p["q_norm"]).astype(dtype)
        q = (c_q @ p["w_uq"].astype(dtype)).reshape(b, t, heads, d)
        down = x @ p["w_dkv"].astype(dtype)
        latent = p["kv_norm"].shape[0]
        c_kv = norm(down[..., :latent], p["kv_norm"]).astype(dtype)
        kv = (c_kv @ p["w_ukv"].astype(dtype)).reshape(b, t, heads, -1)
        k_nope, v = kv[..., :nope_dim], kv[..., nope_dim:]
        positions = jnp.arange(t)
        q = q.astype(f32)
        q = jnp.concatenate([q[..., :nope_dim], _rotary(q[..., nope_dim:], positions, rope_dim, theta)], axis=-1)
        q = (q * d**-0.5).astype(dtype)
        k_rope = _rotary(down[..., None, latent:].astype(f32), positions, rope_dim, theta).astype(dtype)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, t, heads, rope_dim))], axis=-1)
    # [B, T, heads, D] → [B, heads, 1, T, D]: every head has its own keys and values
    q = q.transpose(0, 2, 1, 3)[:, :, None]
    k, v = (a.transpose(0, 2, 1, 3) for a in (k, v))
    return causal_attention(q, k, v).reshape(b, t, -1) @ p["w_o"].astype(dtype)


# ------------------------------------------------------------- the stack


def _row_by_row(mixer, x, p, batch_sharding):
    """``mixer(x, p)`` one row of ``x`` [B, T, h] at a time, each row
    rematerialised: a mixer's intermediates at 8k tokens are gigabytes a row
    and no row needs another's.  Of a row the backward pass keeps its input
    and what the attention kernels name (:data:`ATTN_KEPT`: 34 MB a row at 32
    heads of 64).  On a mesh every device takes its own rows."""

    keep = jax.checkpoint_policies.save_only_these_names(*ATTN_KEPT)

    def local(x, p):
        return jax.lax.map(jax.checkpoint(lambda row: mixer(row[None], p)[0], policy=keep), x)

    if batch_sharding is None:
        return local(x, p)
    spec = batch_sharding.spec
    return jax.shard_map(
        local, mesh=batch_sharding.mesh, in_specs=(spec, P()), out_specs=spec, check_vma=False
    )(x, p)


def dense_mlp(x, p):
    """The dense SwiGLU feed-forward: x [..., h] (normed) → [..., h]."""
    dtype = x.dtype
    mid = jax.nn.silu(x @ p["w_gate"].astype(dtype)) * (x @ p["w_up"].astype(dtype))
    return mid @ p["w_down"].astype(dtype)


def lm_layer(x, lp, buffers, *, kind: str, ffn: str, cfg, batch_sharding=None):
    """One layer, its weights ``lp`` and its buffers (``None`` or a dict): x
    [B, T, h] → (x, the expert layer's counts; None after a dense
    feed-forward).  The mixer is rematerialised a row at a time; the
    dense feed-forward with its norm; of a routed layer norm, routing and the
    shared expert together, and the held experts' tile loop not (its backward
    pass needs its inputs alone).  What the backward pass keeps of a layer:
    its input, the mixer's output, and of a routed layer the experts' normed
    input and the routing.

    Where the weights hold ``norm1_out`` and ``norm2_out`` the layer has four
    norms: each sublayer's output is normed before it is added,
    ``h = x + norm(mixer(norm(x)))``, ``x' = h + norm(ffn(norm(h)))``, the
    second over the routed experts' share and the shared expert summed."""
    dtype = x.dtype
    mixer, scope = cfg.mixer(kind)

    def normed_out(out, w):
        return out if w is None else cfg.norm(out, w).astype(dtype)

    def mix(x, p):
        return x + normed_out(mixer(cfg.norm(x, p["norm"]).astype(dtype), p["mixer"]), p.get("out"))

    @jax.checkpoint
    def dense(x, norm, p, out):
        return normed_out(dense_mlp(cfg.norm(x, norm).astype(dtype), p), out)

    @jax.checkpoint
    def routed(x, norm, router, bias, shared):
        """Norm, routing and the shared expert: cheap to compute again."""
        y32 = cfg.norm(x, norm)
        top_e, w, moved = cfg.route(y32, router, bias)
        y = y32.astype(dtype)
        return y, top_e, w, moved, None if shared is None else shared_expert(y, shared)

    mixed = {"norm": lp["norm1"], "mixer": lp[kind]}
    if "norm1_out" in lp:
        mixed["out"] = lp["norm1_out"]
    # a scope stands around the call, not inside the checkpointed function: what the checkpoint itself
    # writes (the copies it keeps its inputs in) and each residual add are then the feed-forward's too
    with jax.named_scope(scope):
        x = _row_by_row(mix, x, mixed, batch_sharding)
    if ffn == "dense":
        with jax.named_scope(MLP_SCOPE):
            return x + dense(x, lp["norm2"], lp["mlp"], lp.get("norm2_out")), None
    p = lp["moe"]
    with jax.named_scope(ROUTE_SCOPE):  # the norm and the routing; the shared expert's scope is inside
        y, top_e, w, moved, shared = routed(
            x, lp["norm2"], p["router"], (buffers or {}).get("expert_bias"), p.get("shared")
        )
    out, counts = held_experts(
        y, top_e, w, p, n_experts=cfg.num_experts, held=cfg.experts_held, batch_sharding=batch_sharding
    )
    counts = dict(counts, moe_bias_moved=moved)
    if "norm2_out" in lp:  # the two shares meet before their norm
        with jax.named_scope(EXPERTS_SCOPE):
            return x + jax.checkpoint(normed_out)(out if shared is None else out + shared, lp["norm2_out"]), counts
    with jax.named_scope(EXPERTS_SCOPE):
        x = x + out
    if shared is not None:
        with jax.named_scope(SHARED_SCOPE):
            x = x + shared
    return x, counts


def layer_attention_counts(cfg, kind: str, x, p) -> dict:
    """:func:`mixer_counts` of one layer's mixer (its weights ``p``) over
    the batch ``x`` [B, T, h], as the counts a loss returns."""
    row = jax.ShapeDtypeStruct((1, *x.shape[1:]), x.dtype)
    return {key: x.shape[0] * n for key, n in mixer_counts(cfg.mixer(kind)[0], row, p).items()}


def _sum_counts(totals: dict | None, counts: dict | None) -> dict:
    """``totals`` with ``counts`` added, key by key."""
    totals = dict(totals or {})
    for key, n in (counts or {}).items():
        totals[key] = totals[key] + n if key in totals else n
    return totals


def _embedded(params, ids, *, cfg):
    """ids [B, T] → the stack's input [B, T, h] in ``cfg.dtype``: the
    embedding's rows, multiplied by ``cfg.embed_scale`` where the
    configuration has one."""
    with jax.named_scope(EMBED_SCOPE):
        x = params["embed"][ids]
        if getattr(cfg, "embed_scale", None) is not None:
            x = x * cfg.embed_scale
        return x.astype(jnp.dtype(cfg.dtype))


def _layers(params, x, *, cfg, batch_sharding=None):
    """One walk through ``params["layers"]``: x [B, T, h] → (x, the expert
    layers' counts summed over the routed layers, or None)."""
    kinds, ffns = cfg.layer_kinds(), cfg.ffn_kinds()
    buffers = params.get("buffers", {}).get("layers", [None] * len(kinds))
    totals = None
    for lp, held, kind, ffn in zip(params["layers"], buffers, kinds, ffns, strict=True):
        x, counts = lm_layer(x, lp, held, kind=kind, ffn=ffn, cfg=cfg, batch_sharding=batch_sharding)
        if counts is not None:
            with jax.named_scope(EXPERTS_SCOPE):
                totals = counts if totals is None else jax.tree.map(jnp.add, totals, counts)
    return x, totals


def _attention_counts(params, x, *, cfg) -> dict:
    """:func:`layer_attention_counts` summed over one walk through the layers
    for the batch ``x`` [B, T, h], traced once a kind (a kind's layers have
    one shape): Python integers, no operation of the program."""
    of_kind, tiles = {}, None
    for lp, kind in zip(params["layers"], cfg.layer_kinds(), strict=True):
        if kind not in of_kind:
            of_kind[kind] = layer_attention_counts(cfg, kind, x, lp[kind])
        tiles = _sum_counts(tiles, of_kind[kind])
    return tiles


def lm_hidden(params, ids, *, cfg, batch_sharding=None):
    """ids [B, T] → (final hidden states [B, T, h] before the final norm,
    counts: the expert layers' summed over the routed layers, and the
    attention kernels' grid steps and the rows by what made their operands
    over every layer, :func:`_attention_counts`).  The embedding's output is
    multiplied by ``cfg.embed_scale`` where the configuration has one."""
    x = _embedded(params, ids, cfg=cfg)
    tiles = _attention_counts(params, x, cfg=cfg)
    x, totals = _layers(params, x, cfg=cfg, batch_sharding=batch_sharding)
    return x, _sum_counts(totals, tiles)


def loop_hidden(params, ids, *, cfg, batch_sharding=None):
    """The stack run ``cfg.loop_passes`` times over ONE set of weights (a
    looped, weight-shared model): ids [B, T] → (the state after each pass
    [R, B, T, h], every one AFTER the final norm, which is applied between
    passes: pass ``t + 1`` starts from pass ``t``'s normed state, and that is
    also what the head and the exit gate read; counts as :func:`lm_hidden`'s,
    over all the passes, and ``loop_layers_run`` and ``loop_layers``: rows x
    layers x passes and rows x layers, Python integers).

    With more than one pass the passes are one ``lax.scan`` whose body is
    :func:`_layers` and the norm: the step program holds each layer's body
    once, the shared weights' gradients add up in the scan's transpose, and
    the backward pass keeps of each pass what it keeps of a stack
    (:func:`lm_layer`).  One pass is the plain walk."""
    passes = cfg.loop_passes
    dtype = jnp.dtype(cfg.dtype)

    def one_pass(x, _=None):
        x, totals = _layers(params, x, cfg=cfg, batch_sharding=batch_sharding)
        with jax.named_scope(HEAD_SCOPE):
            x = cfg.norm(x, params["final_norm"]).astype(dtype)
        return x, (x, totals)

    x = _embedded(params, ids, cfg=cfg)
    tiles = {key: passes * n for key, n in _attention_counts(params, x, cfg=cfg).items()}
    if passes == 1:
        _, (state, totals) = one_pass(x)
        states = state[None]
    else:
        _, (states, totals) = jax.lax.scan(one_pass, x, None, length=passes)
        with jax.named_scope(EXPERTS_SCOPE):
            totals = jax.tree.map(lambda n: jnp.sum(n, axis=0), totals)
    layer_rows = ids.shape[0] * len(params["layers"])
    return states, dict(_sum_counts(totals, tiles), loop_layers_run=passes * layer_rows, loop_layers=layer_rows)


def head_params(params) -> dict:
    """The leaves :func:`lm_head` reads: the final norm and the head, or the
    embedding where the head is tied to it."""
    return {k: params[k] for k in ("final_norm", "head" if "head" in params else "embed")}


def lm_head(head, x, *, cfg):
    """Logits over the held vocabulary, float32: x [..., h] → [..., vocab];
    the final norm first where ``head`` holds one."""
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope(HEAD_SCOPE):
        # a looped model's states come normed (:func:`loop_hidden`): its head holds no norm
        y = cfg.norm(x, head["final_norm"]).astype(dtype) if "final_norm" in head else x
        if "head" in head:
            return jnp.dot(y, head["head"].astype(dtype), preferred_element_type=jnp.float32)
        return jnp.einsum("...h,vh->...v", y, head["embed"].astype(dtype), preferred_element_type=jnp.float32)


def _tile_fused(head_fn, head, x) -> bool:
    """Whether a tile of rows ``x`` [tile, h] through ``head_fn`` makes logits
    the loss kernel takes (``models/loss_tile.py: tile_takes``; shapes alone,
    nothing runs)."""
    return tile_takes(*jax.eval_shape(head_fn, head, x).shape)


def fused_tile(head_fn, head, x, labels, scale, *weights):
    """The tile body the causal-LM losses hand :func:`labelled_nll`
    (``models/bert.py: tile_grads``'s arguments and results): ``jax.vjp`` of
    whatever head it is given, ONE kernel from the float32 logits to each
    row's NLL and the logits' cotangent (``models/loss_tile.py``), and the
    head's pull-back, whose two products stay the compiler's.  The row's
    coefficient is the loss's ``scale`` (the mean form) or its own weight
    where it has a label, 0 where it has none.

    The cotangent is written ONCE, in the rows' dtype: the dtype the head's
    two gradient products take it in.  The compiler's body hands them none:
    each product makes ``coef x (softmax - onehot)`` again in float32 from the
    logits inside its operand fusion, and the matrix unit rounds that float32
    operand to the other operand's bfloat16 at its input (default precision).
    Rounding the kernel's float32 value to bfloat16 as it is written is that
    same rounding, at that same place (``PERF.md`` section 6, PR 47: both
    gradients bit-equal between a float32 and a bfloat16 cotangent on a v5e);
    under float32 rows it stays float32.

    A tile smaller than any the kernel is measured at (:func:`_tile_fused`
    false: a tiny model's) runs the compiler's body, the program it had."""
    if not _tile_fused(head_fn, head, x):
        return tile_grads(head_fn, head, x, labels, scale, *weights)
    logits, pull = jax.vjp(head_fn, head, x)
    coef = jnp.where(labels >= 0, weights[0] if weights else scale, 0.0)
    nll, g = loss_tile(logits, labels, coef, dtype=x.dtype, interpret=not _on_tpu())
    part = jnp.sum(coef * nll)
    return ((part, nll) if weights else part), pull(g.astype(logits.dtype))


def _head_nll(head_fn, head, x, labels, batch_sharding, weights=None):
    """:func:`labelled_nll` with :func:`fused_tile` as its tile body → (what
    it returns, the rows it was handed by the body that runs their tiles:
    ``loss_rows_fused`` and ``loss_rows_compiler``, host integers known when
    the step is traced and no operation of it, by the rule the body itself
    reads at a shard's tile)."""
    axes = () if batch_sharding is None else spec_axes(batch_sharding.spec)
    shards = math.prod(batch_sharding.mesh.shape[axis] for axis in axes)
    tile = jax.ShapeDtypeStruct((head_tile(labels.size // shards), x.shape[-1]), x.dtype)
    fused = _tile_fused(head_fn, head, tile)
    rows = {"loss_rows_fused": labels.size if fused else 0, "loss_rows_compiler": 0 if fused else labels.size}
    return labelled_nll(head_fn, head, x, labels, batch_sharding, weights, fused_tile), rows


def lm_logits(params, ids, *, cfg):
    x, _ = lm_hidden(params, ids, cfg=cfg)
    return lm_head(head_params(params), x, cfg=cfg)


def mtp_hidden(params, x, labels, *, cfg, batch_sharding=None):
    """The multi-token-prediction module up to its head (one module, depth 1:
    DeepSeek-V3 report, arXiv:2412.19437, section 2.2): ``x`` [B, T, h] the
    main stack's final hidden states, ``labels`` the row's next tokens (-100
    where there is none) → (the module's hidden states [B, T, h] before its
    head norm, its expert layer's counts or None).

    ``h'_i = [norm_e(Emb(t_{i+1})) ; norm_h(final_norm(h_i))] W_eh``, then one
    layer of the stack's last kind with the module's own weights and buffers.
    The embedding is the main model's.  The next token is read off ``labels``;
    where a row has none (its last position) token 0 stands in, and that
    position has no label two ahead either."""
    p = params["mtp"]
    dtype = jnp.dtype(cfg.dtype)

    @jax.checkpoint
    def merged(x, embed, final_norm, p):
        e = cfg.norm(embed[jnp.maximum(labels, 0)].astype(dtype), p["enorm"])
        h = cfg.norm(cfg.norm(x, final_norm).astype(dtype), p["hnorm"])
        return jnp.concatenate([e, h], axis=-1).astype(dtype) @ p["eh_proj"].astype(dtype)

    h = merged(x, params["embed"], params["final_norm"], {k: p[k] for k in ("enorm", "hnorm", "eh_proj")})
    return lm_layer(
        h, p["layer"], params.get("buffers", {}).get("mtp"), kind=cfg.layer_kinds()[-1],
        ffn=cfg.ffn_kinds()[-1], cfg=cfg, batch_sharding=batch_sharding,
    )


def mtp_head_params(params) -> dict:
    """:func:`head_params` of the prediction module: its own norm and the
    main model's head matrix (one copy, gradients from both uses)."""
    return dict(head_params(params), final_norm=params["mtp"]["shared_head_norm"])


def mtp_loss(params, x, labels, *, cfg, batch_sharding=None):
    """The prediction module's loss: the mean cross-entropy of the token after
    next over the positions that have one → (loss, the module's layer's
    counts: its expert layer's, its attention kernels' grid steps; those
    positions' count)."""
    with jax.named_scope(MTP_SCOPE):
        after_next = jnp.concatenate([labels[:, 1:], jnp.full_like(labels[:, :1], -100)], axis=1)
        h, counts = mtp_hidden(params, x, labels, cfg=cfg, batch_sharding=batch_sharding)
        kind = cfg.layer_kinds()[-1]
        counts = _sum_counts(counts, layer_attention_counts(cfg, kind, x, params["mtp"]["layer"][kind]))
        (loss, _), rows = _head_nll(
            functools.partial(lm_head, cfg=cfg), mtp_head_params(params), h, after_next, batch_sharding
        )
        return loss, _sum_counts(counts, rows), jnp.sum(after_next >= 0, dtype=jnp.int32)


def lm_loss(params, ids, labels, *, cfg, batch_sharding=None):
    """Next-token cross-entropy, float32, mean over the positions with
    ``labels >= 0`` (-100 elsewhere), and where the weights hold a prediction
    module ``cfg.mtp_loss_weight`` times :func:`mtp_loss` on top → (loss,
    counts).  ``counts``: the expert layers' (summed over layers, the
    module's among them), ``tokens``, and the positions with a label,
    ``head_all`` over both losses and ``head_mtp`` the module's (0 without
    one), int32; ``loss_main`` and ``loss_mtp``, the two terms, float32; and
    six Python integers, known when the step is traced and no operation of it
    (:func:`mixer_counts`): ``attn_tiles_run`` and ``attn_tiles_causal`` (the
    attention kernels' grid steps and what causal lists alone would hold, over
    rows, layers and key-value heads: equal without a window),
    ``attn_out_tokens`` and ``attn_out_heads`` (attention layer-rows by where
    the output was written), ``attn_operands_kernel`` and
    ``attn_operands_xla`` (softmax-attention layer-rows by what made the
    kernels' operands), ``loss_rows_fused`` and ``loss_rows_compiler`` (the
    rows handed to the head's tile loop, over both losses, by the body that
    runs their tiles: :func:`_head_nll`)."""
    x, counts = lm_hidden(params, ids, cfg=cfg, batch_sharding=batch_sharding)
    with jax.named_scope(HEAD_SCOPE):  # the loss's own loop over tiles of positions around the head
        (loss, _), rows = _head_nll(functools.partial(lm_head, cfg=cfg), head_params(params), x, labels, batch_sharding)
        counts = _sum_counts(counts, rows)
        labelled = jnp.sum(labels >= 0, dtype=jnp.int32)
    terms = {"loss_main": loss, "loss_mtp": jnp.float32(0.0)}
    second = jnp.int32(0)
    if "mtp" in params:
        terms["loss_mtp"], more, second = mtp_loss(params, x, labels, cfg=cfg, batch_sharding=batch_sharding)
        with jax.named_scope(MTP_SCOPE):
            loss = loss + cfg.mtp_loss_weight * terms["loss_mtp"]
            labelled = labelled + second
            counts = _sum_counts(counts, more)
    return loss, dict(counts, **terms, tokens=jnp.int32(ids.size), head_all=labelled, head_mtp=second)


def exit_distribution(gate, states):
    """A looped model's exit gate over the passes' normed states [R, ..., h] →
    the distribution over the pass a position exits after, [R, ...] float32
    (Ouro, arXiv:2510.25741, section 3): ``lambda^(t) = sigmoid(z^(t) . w +
    b)``; the survival ``S^(0) = 1``, ``S^(t) = S^(t-1) (1 - lambda^(t))``;
    ``p(t) = lambda^(t) S^(t-1)`` before the last pass and ``p(R) = S^(R-1)``:
    what is left exits there, so the ``R`` terms sum to 1.  Float32, the
    product with ``w`` [h] a multiply and a sum (no matrix unit's rounding)."""
    z = states.astype(jnp.float32)
    lam = jax.nn.sigmoid(jnp.sum(z[:-1] * gate["w"], axis=-1) + gate["b"])
    ones = jnp.ones_like(z[:1, ..., 0])
    survived = jnp.concatenate([ones, jnp.cumprod(1.0 - lam, axis=0)])  # S^(0) .. S^(R-1)
    return jnp.concatenate([lam * survived[:-1], survived[-1:]])


def exit_loss(params, states, labels, *, cfg, batch_sharding=None):
    """A looped model's objective over its passes' normed states [R, B, T, h]
    (:func:`loop_hidden`) → (loss, counts): the expected next-token loss under
    the exit distribution less ``cfg.exit_beta`` times that distribution's
    entropy (a uniform prior), mean over the positions with a label,

        ``mean_i [ sum_t p_i(t) nll_i^(t) + beta sum_t p_i(t) log p_i(t) ]``

    with ``nll_i^(t)`` the float32 cross-entropy of pass ``t``'s logits
    ``z^(t) W_head`` (one head matrix, no second norm: the states come
    normed) and ``p`` :func:`exit_distribution` by ``params["exit"]``.

    The ``R`` passes go through the head and the loss's tile loop as ONE call
    over the stacked states (:func:`labelled_nll` with a weight a position:
    one float32 weight-gradient accumulator of the head, not ``R``), and the
    product rule gives both gradients from it: the weighted NLL with ``p``
    held constant carries the gradient into the head and the stack, ``p``
    times the returned NLL held constant the gradient into the gate (and
    through ``z`` into the stack).  ``counts``: ``head_all`` the labelled
    positions over all ``R`` losses, ``head_loop`` those of the passes
    before the last and ``head_mtp`` 0 (no prediction module), int32; ``loss_pass`` [R] each pass's mean NLL and
    ``exit_mass`` [R] the mean ``p(t)``, float32; ``exit_mass_<t>`` the summed
    ``p(t)`` over the labelled positions in 1,024ths, int32 (what the step's
    gauge counts); ``tokens``; ``loss_rows_fused`` and ``loss_rows_compiler``,
    the stacked rows by the body that runs their tiles (:func:`_head_nll`:
    Python integers)."""
    passes = states.shape[0]
    f32 = jnp.float32
    with jax.named_scope(EXIT_SCOPE):
        p = exit_distribution(params["exit"], states)
        labelled = (labels >= 0).astype(f32)
        n = jnp.maximum(jnp.sum(labelled), 1.0)
        log_p = jnp.log(jnp.maximum(p, jnp.finfo(f32).tiny))  # p log p → 0 as p → 0
        neg_entropy = jnp.sum(p * log_p * labelled) / n
        weights = jax.lax.stop_gradient(p) * (labelled / n)
    with jax.named_scope(HEAD_SCOPE):  # rows first: a mesh splits the stacked states by their rows
        (expected, _, nll), rows = _head_nll(
            functools.partial(lm_head, cfg=cfg), {k: v for k, v in head_params(params).items() if k != "final_norm"},
            jnp.moveaxis(states, 0, 1), jnp.broadcast_to(labels[:, None], (labels.shape[0], passes, labels.shape[1])),
            batch_sharding, jnp.moveaxis(weights, 0, 1),
        )
    with jax.named_scope(EXIT_SCOPE):
        nll = jax.lax.stop_gradient(jnp.moveaxis(nll, 1, 0))  # [R, B, T]
        through_gate = jnp.sum(p * nll * labelled) / n  # equal to ``expected``; its gradient is the gate's
        loss = expected + (through_gate - jax.lax.stop_gradient(through_gate)) + cfg.exit_beta * neg_entropy
        mass = jnp.sum(p * labelled, axis=(1, 2))
        count = jnp.sum(labels >= 0, dtype=jnp.int32)
        counts = {
            "loss_pass": jnp.sum(nll * labelled, axis=(1, 2)) / n, "exit_mass": mass / n,
            "head_all": passes * count, "head_loop": (passes - 1) * count, "head_mtp": jnp.int32(0),
            "tokens": jnp.int32(labels.size), **rows,
            **{f"exit_mass_{t}": jnp.round(mass[t] * EXIT_MASS_UNIT).astype(jnp.int32) for t in range(passes)},
        }
    return loss, counts


def loop_loss(params, ids, labels, *, cfg, batch_sharding=None):
    """The loss of a looped model (``cfg.loop_passes`` passes of the stack
    over one set of weights, a loss after every pass through one head, an exit
    gate): :func:`exit_loss` over :func:`loop_hidden` → (loss, counts: both
    functions')."""
    states, counts = loop_hidden(params, ids, cfg=cfg, batch_sharding=batch_sharding)
    loss, more = exit_loss(params, states, labels, cfg=cfg, batch_sharding=batch_sharding)
    return loss, dict(counts, **more)
