"""The softmax of one tile of a causal LM's head-and-loss in one pass.

Between a head's float32 logits ``[rows, vocab]`` and the two products that
carry the loss's gradient into the head's weights and its rows stand a
log-softmax, the picked logit and the transpose of both.  Written with
``jax.nn.log_softmax`` and autodiff (``models/head_loss.py: _tile_nll`` under
``jax.value_and_grad``, the tile loop's default body) they are three
stand-alone passes over ``[rows, vocab]`` in HBM, and each of the two products
makes the cotangent again from the logits in its operand fusion.
:func:`loss_tile` is one Pallas kernel that reads the logits once, holds a
block of whole rows in VMEM, takes the maximum, the sum of exponentials and
the picked logit there, and writes each row's NLL and the cotangent of the
logits, ``coef x (softmax - onehot(label))``, once.

The arithmetic is ``jax.nn.log_softmax``'s and its transpose's, float32
throughout: shift by the row maximum, ``lse = log(sum(exp(shifted)))``,
``nll = lse - shifted[label]``, ``g = exp(shifted) x (coef / sum) - coef x
onehot``, and ONE rounding at the end, to the dtype the caller names: the one
its two products take the cotangent in (:func:`fused_tile` says which and
why).  The kernel knows nothing of what made the logits or what a coefficient
means; :func:`fused_tile` is the kernel as a tile body of ``models/head_loss.py:
labelled_nll``'s loop, between ``jax.vjp`` of whatever head it is given and the
head's pull-back.  Only ``models/causal_lm.py`` imports this module: the
masked-LM loss keeps the compiler's body and its process imports no Pallas.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lakesoul_tpu.models.head_loss import tile_grads
from lakesoul_tpu.utils import platform

MIN_TILE_BYTES = 48 * 2**20        # the smallest tile of float32 logits the kernel takes: it is measured to gain at 53 MB and above
LOSS_TILE_VMEM_BYTES = 64 * 2**20  # of a v5e core's 128 MiB: what the kernel may be given
_BLOCK_BYTES = 40 * 2**20          # of that, the logits' and the cotangent's blocks, two buffers each
_MAX_BLOCK_ROWS = 256              # of a narrow vocabulary: the block's loop over rows stays short


def _lanes(vocab: int) -> int:
    """``vocab`` rounded up to whole 128-lane tiles: what a row takes in VMEM."""
    return -(-vocab // 128) * 128


def tile_takes(rows: int, vocab: int) -> bool:
    """Whether a tile of ``rows`` x ``vocab`` float32 logits goes through the
    kernel: one of :data:`MIN_TILE_BYTES` or more.  The head alone on a v5e
    (``PERF.md`` section 6, PR 47) gains at every deployed tile, from the GLM
    step's 688 x 19,360 (53 MB: 5%) to the Ouro step's 2,736 x 49,152 (538 MB:
    23%), also where XLA keeps the tile in VMEM between the head's products;
    below the smallest of them nothing is measured and a tile keeps the
    compiler's body (a tiny model's, a test's)."""
    return rows * _lanes(vocab) * 4 >= MIN_TILE_BYTES


def _sublanes(dtype) -> int:
    """Rows worked on at a time, whole: a sublane tile of the cotangent's
    dtype (8 float32 rows, 16 bfloat16 ones)."""
    return 32 // jnp.dtype(dtype).itemsize


def block_rows(rows: int, vocab: int, dtype) -> int:
    """Rows of a block of whole rows of ``vocab`` float32 logits in and their
    cotangent of ``dtype`` out: what :data:`_BLOCK_BYTES` holds of them twice
    each (the pipeline's two buffers), in whole sublane tiles, no more than
    the tile has."""
    sub = _sublanes(dtype)
    fit = _BLOCK_BYTES // (_lanes(vocab) * (4 + jnp.dtype(dtype).itemsize) * 2) // sub * sub
    return max(sub, min(fit, _MAX_BLOCK_ROWS, -(-rows // sub) * sub))


def _loss_tile_kernel(logits_ref, labels_ref, coef_ref, nll_ref, g_ref, *, vocab: int):
    """One block of whole rows: ``logits_ref`` [rows, lanes] (``lanes`` is
    ``vocab`` rounded up to whole lane tiles; what lies past ``vocab`` is
    unspecified and masked), ``labels_ref`` and ``coef_ref`` [rows, 1] →
    ``nll_ref`` [rows, 1], ``g_ref`` [rows, lanes].  A block's rows past the
    tile's end (a ragged last block) hold anything and are written nowhere;
    no row reads another.

    A sublane tile of whole rows at a time, all in VMEM, in three sweeps: the
    maximum; the sum of exponentials and the picked logit; the cotangent.  A
    row's lanes are read at static offsets (a loop over lane chunks at offsets
    known only when it runs read a third as fast on a v5e: ``PERF.md`` section
    6, PR 46), in two pieces where the vocabulary is not whole lane tiles: the
    tiles inside it, and the one that holds its end, masked."""
    rows, lanes = logits_ref.shape
    sub = _sublanes(g_ref.dtype)
    inside = vocab // 128 * 128
    pieces = [(a, b) for a, b in ((0, inside), (inside, lanes)) if b > a]

    def sub_rows(r, _):
        at = pl.ds(pl.multiple_of(r * sub, sub), sub)
        label, coef = labels_ref[at, :], coef_ref[at, :]

        def logits(a, b):
            z = logits_ref[at, a:b]
            if b > vocab:
                z = jnp.where(jax.lax.broadcasted_iota(jnp.int32, z.shape, 1) < vocab - a, z, -jnp.inf)
            return z

        def at_label(a, b):  # where a lane is its row's label
            return jax.lax.broadcasted_iota(jnp.int32, (sub, b - a), 1) == label - a

        top = functools.reduce(jnp.maximum, [jnp.max(logits(a, b), axis=-1, keepdims=True) for a, b in pieces])
        total, picked = 0.0, 0.0
        for a, b in pieces:
            shifted = logits(a, b) - top
            total += jnp.sum(jnp.exp(shifted), axis=-1, keepdims=True)
            picked += jnp.sum(jnp.where(at_label(a, b), shifted, 0.0), axis=-1, keepdims=True)
        nll_ref[at, :] = jnp.where(label >= 0, jnp.log(total) - picked, 0.0)
        share = coef / total
        for a, b in pieces:
            g = jnp.exp(logits(a, b) - top) * share - jnp.where(at_label(a, b), coef, 0.0)
            g_ref[at, a:b] = g.astype(g_ref.dtype)
        return 0

    jax.lax.fori_loop(0, rows // sub, sub_rows, 0)


@functools.partial(jax.jit, static_argnames=("dtype", "interpret"))
def loss_tile(logits, labels, coef, *, dtype, interpret: bool):
    """Float32 ``logits`` [rows, vocab], ``labels`` [rows] int32 (below 0: no
    label) and ``coef`` [rows] float32 (0 where a row has no label) → (each
    row's NLL [rows] float32, 0 without a label; the cotangent of the logits
    under ``sum(coef x nll)``, [rows, vocab] of ``dtype``: ``coef x (softmax -
    onehot(label))``, float32 until it is written).  One kernel, ``loss_tile``
    in a trace; the logits are read from HBM once."""
    rows, vocab = logits.shape
    block = block_rows(rows, vocab, dtype)
    per_row = pl.BlockSpec((block, 1), lambda i: (i, 0))
    whole_rows = pl.BlockSpec((block, _lanes(vocab)), lambda i: (i, 0))
    nll, g = pl.pallas_call(
        functools.partial(_loss_tile_kernel, vocab=vocab),
        grid=(pl.cdiv(rows, block),),
        in_specs=[whole_rows, per_row, per_row], out_specs=[per_row, whole_rows],
        out_shape=(jax.ShapeDtypeStruct((rows, 1), jnp.float32), jax.ShapeDtypeStruct(logits.shape, dtype)),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel",), vmem_limit_bytes=LOSS_TILE_VMEM_BYTES
        ),
        name="loss_tile", interpret=interpret,
    )(logits, labels[:, None], coef[:, None])
    return nll[:, 0], g


def fused_tile(head_fn, head, x, labels, scale, *weights):
    """The tile body the causal-LM losses hand ``models/head_loss.py:
    labelled_nll`` (``tile_grads``'s arguments and results): ``jax.vjp`` of
    whatever head it is given, ONE kernel from the float32 logits to each
    row's NLL and the logits' cotangent (:func:`loss_tile`), and the
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

    A tile smaller than any the kernel is measured at (:func:`tile_takes`
    false of its logits' shape: a tiny model's) runs the compiler's body, the
    program it had."""
    if not tile_takes(*jax.eval_shape(head_fn, head, x).shape):
        return tile_grads(head_fn, head, x, labels, scale, *weights)
    logits, pull = jax.vjp(head_fn, head, x)
    coef = jnp.where(labels >= 0, weights[0] if weights else scale, 0.0)
    nll, g = loss_tile(logits, labels, coef, dtype=x.dtype, interpret=not platform.on_tpu())
    part = jnp.sum(coef * nll)
    return ((part, nll) if weights else part), pull(g.astype(logits.dtype))
