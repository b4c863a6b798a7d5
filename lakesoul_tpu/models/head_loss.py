"""The head and the loss at the labelled positions only: the one tile loop of
every head-and-loss of ``models/`` (:func:`labelled_nll`).

A loss needs logits only where ``labels >= 0`` (15% of the positions of a
masked-LM batch, every position of a causal LM's), so the head runs over those
rows only: each shard of the batch moves its labelled rows to the front and
runs the head one fixed-size tile of them at a time, for as many tiles as its
labels fill, the gradients taken inside the loop (:func:`labelled_nll` says
why and how).

Nothing here knows what made the hidden states or what head it is handed
(``head_fn(head, rows)`` → float32 logits), and nothing here is a kernel: a
tile's loss and gradients are the caller's ``tile_body``, by default the
compiler's (:func:`tile_grads`).  The masked-LM loss (``models/bert.py:
mlm_head_loss``) passes none, and its process imports no Pallas; the causal
LMs' losses (``models/causal_lm.py: _head_nll``) pass ``models/loss_tile.py:
fused_tile``.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from lakesoul_tpu.parallel.mesh import spec_axes


def head_tile(n: int) -> int:
    """Rows the head runs at a time for a shard of ``n`` positions: a twelfth
    of them, rounded up to a multiple of 8.  MLM labels 15%, so two tiles
    are the usual case, and twelve cover every position with next to none
    over.  (Measured on a v5e at 8,192 positions: PERF.md section 6, PR 26.)"""
    return min(n, -(-n // 96) * 8)


def _tile_nll(head_fn, head, x, labels, scale, weights=None):
    """``scale`` x the summed NLL of one tile's labelled rows, in float32;
    with ``weights`` [rows] → (the sum of weight x NLL over them, each row's
    NLL: 0 where it has no label)."""
    logp = jax.nn.log_softmax(head_fn(head, x), axis=-1)
    picked = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[:, None], axis=-1)[:, 0]
    if weights is None:
        return -scale * jnp.sum(jnp.where(labels >= 0, picked, 0.0))
    nll = -jnp.where(labels >= 0, picked, 0.0)
    return jnp.sum(weights * nll), nll


def tile_grads(head_fn, head, x, labels, scale, *weights):
    """:func:`_tile_nll` and its gradients into ``head`` and ``x`` by autodiff
    → (what it returns, (g_head, g_x)): the compiler's body of a tile, and the
    tile loop's default."""
    return jax.value_and_grad(
        functools.partial(_tile_nll, head_fn), argnums=(0, 1), has_aux=bool(weights)
    )(head, x, labels, scale, *weights)


def _head_over_labelled(head_fn, head, x, labels, axes, with_grads: bool, weights=None, tile_body=tile_grads):
    """One shard's share of the loss: ``x`` [..., h] and ``labels`` [...] are
    the rows this device holds, ``axes`` the mesh axes the batch is split
    over, ``head_fn(head, x)`` the float32 logits of rows ``x``.  → (loss, positions the head ran at), both summed over ``axes``,
    and with ``with_grads`` the loss's gradients (head summed over ``axes``,
    x for this shard's rows).  With ``weights`` [...] (float32, a position's
    own) the loss is the sum of weight x NLL over the labelled positions, not
    their mean, and each position's NLL [...] (float32, 0 without a label)
    comes third: → (loss, positions, nll) and then the gradients.
    ``tile_body`` makes one tile's loss and gradients, called as
    :func:`tile_grads` is and returning what it returns."""
    x2, lab = x.reshape(-1, x.shape[-1]), labels.reshape(-1)
    n = lab.shape[0]
    tile = head_tile(n)
    slots = -(-n // tile) * tile
    order = jnp.argsort(lab < 0, stable=True)  # labelled rows first
    # slots past n read row 0 and carry no label
    rows = jnp.pad(order, (0, slots - n))
    row_labels = jnp.pad(lab[order], (0, slots - n), constant_values=-100)
    count = jnp.sum(lab >= 0)
    total = jax.lax.psum(count, axes) if axes else count
    scale = 1.0 / jnp.maximum(total, 1).astype(jnp.float32)
    weighted = weights is not None
    if weighted:
        row_weights = jnp.pad(weights.reshape(-1).astype(jnp.float32)[order], (0, slots - n))

    def run_tile(carry):
        k, loss, grads, nlls = carry
        at = k * tile
        xt = x2[jax.lax.dynamic_slice(rows, (at,), (tile,))]
        lt = jax.lax.dynamic_slice(row_labels, (at,), (tile,))
        wt = (jax.lax.dynamic_slice(row_weights, (at,), (tile,)),) if weighted else ()
        if with_grads:
            part, (g_head, g_x) = tile_body(head_fn, head, xt, lt, scale, *wt)
            acc_head, acc_x = grads
            grads = (
                jax.tree.map(jnp.add, acc_head, g_head),
                jax.lax.dynamic_update_slice(acc_x, g_x, (at, 0)),
            )
        else:
            part = _tile_nll(head_fn, head, xt, lt, scale, *wt)
        if weighted:
            part, tile_nll = part
            nlls = jax.lax.dynamic_update_slice(nlls, tile_nll, (at,))
        return k + 1, loss + part, grads, nlls

    grads = (
        (jax.tree.map(jnp.zeros_like, head), jnp.zeros((slots, x2.shape[1]), x2.dtype))
        if with_grads else ()
    )
    tiles = (count + tile - 1) // tile
    _, loss, grads, nlls = jax.lax.while_loop(
        lambda carry: carry[0] < tiles, run_tile,
        (jnp.int32(0), jnp.float32(0.0), grads, jnp.zeros((slots,), jnp.float32) if weighted else ()),
    )
    positions = tiles * tile
    if axes:
        loss, positions = jax.lax.psum((loss, positions), axes)
    out = (loss, positions)
    if weighted:  # back from labelled-first order, as the rows' gradients below
        out += (nlls[jnp.argsort(order)].reshape(labels.shape),)
    if not with_grads:
        return out
    g_head, g_rows = grads
    if axes:
        g_head = jax.lax.psum(g_head, axes)
    # back from labelled-first order; rows of tiles that never ran are zero
    g_x = g_rows[jnp.argsort(order)].reshape(x.shape)
    return *out, g_head, g_x


def _sharded_head(head_fn, head, x, labels, batch_sharding, with_grads: bool, weights=None, tile_body=tile_grads):
    if batch_sharding is None:
        return _head_over_labelled(head_fn, head, x, labels, (), with_grads, weights, tile_body)
    spec = batch_sharding.spec
    axes = spec_axes(spec)
    per_position = () if weights is None else (spec,)
    out_specs = (P(), P(), *per_position, P(), spec) if with_grads else (P(), P(), *per_position)
    # every device gathers among its own rows; only sums cross the mesh
    return jax.shard_map(
        lambda head, x, labels, *weights: _head_over_labelled(
            head_fn, head, x, labels, axes, with_grads, *weights, tile_body=tile_body
        ),
        mesh=batch_sharding.mesh, in_specs=(P(), spec, spec, *per_position), out_specs=out_specs,
        check_vma=False,
    )(head, x, labels, *(() if weights is None else (weights,)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 4, 6))
def labelled_nll(head_fn, head, x, labels, batch_sharding=None, weights=None, tile_body=tile_grads):
    """Hidden states ``x`` [..., h] and ``labels`` [...] → (mean NLL over the
    positions with labels >= 0, positions the head ran at), the logits of rows
    being ``head_fn(head, rows)`` in float32.  The one tile loop of every
    head-and-loss here: masked-LM runs it over the 15% it labels, a causal LM
    over every position.

    It has two forms.  Without ``weights`` (the masked-LM loss,
    ``models/bert.py: mlm_head_loss``; ``models/causal_lm.py: lm_loss`` and
    ``mtp_loss``): the mean, as above.  With ``weights`` [...] float32, a position's own
    (``models/causal_lm.py: exit_loss``, whose weights are a looped model's
    exit distribution over the stacked passes): → (the SUM of weight x NLL
    over the labelled positions, positions, each position's NLL [...] float32:
    0 where it has no label).  A mean is then the caller's to fold into the
    weights; the weights and the per-position NLL carry no gradient (the
    caller who wants one through a weight takes it from the returned NLL by
    the product rule).  ``positions`` counts the rows the tile loop ran the
    head over in either form: whole tiles of :func:`head_tile` rows covering
    the labelled positions, so at least their number, and with weights over
    every stacked pass.

    The gradients are made in the FORWARD pass, tile by tile inside the loop
    (a loop whose length follows the data has no reverse-mode derivative), for
    a scalar cotangent: the backward pass only scales them by the loss's
    cotangent (:func:`_labelled_nll_bwd`), so nothing but the loss itself may
    be differentiated through, and no [positions, vocab] array exists in either
    pass.  What makes a tile's loss and gradients there is ``tile_body``: by
    default :func:`tile_grads`, the compiler's (a float32 log-softmax, a
    gather and autodiff: the masked-LM loss passes nothing); a causal LM's
    losses pass ``models/loss_tile.py: fused_tile``.  Without gradients
    (evaluation) a tile is :func:`_tile_nll` whatever the body."""
    return _sharded_head(head_fn, head, x, labels, batch_sharding, False, weights)


def _labelled_nll_fwd(head_fn, head, x, labels, batch_sharding, weights=None, tile_body=tile_grads):
    *out, g_head, g_x = _sharded_head(head_fn, head, x, labels, batch_sharding, True, weights, tile_body)
    return tuple(out), (g_head, g_x)


def _labelled_nll_bwd(head_fn, batch_sharding, tile_body, grads, cotangents):
    g_head, g_x = grads
    ct = cotangents[0]
    return jax.tree.map(lambda g: ct * g, g_head), ct.astype(g_x.dtype) * g_x, None, None


labelled_nll.defvjp(_labelled_nll_fwd, _labelled_nll_bwd)
