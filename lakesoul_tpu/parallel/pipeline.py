"""Pipeline parallelism over a ``pp`` mesh axis (GPipe schedule, SPMD form).

The layer stack is split into ``pp`` stages; each device holds one stage's
parameters (the stacked-layer pytree's leading axis sharded over ``pp``).
Microbatches stream through the ring: every scan step each device applies its
stage to its current microbatch and ``lax.ppermute``s the activation to the
next stage — after ``n_micro + pp - 1`` steps every microbatch has crossed
every stage.  The backward pass needs no hand-written schedule: autodiff
through scan+ppermute *is* the reverse pipeline (ppermute's transpose is the
reverse rotation).

This is the canonical TPU formulation (collective pipelining over ICI
neighbours, one hop per step) rather than a port of GPU pipeline runtimes:
bubbles cost ``(pp-1)/(n_micro+pp-1)`` of the steps, all communication is
nearest-neighbour, and XLA overlaps the permute with the next stage compute.

The activation travelling the ring is a *pytree*, so per-microbatch side
inputs (attention masks, segment ids) ride along with the hidden state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def _index_pytree(tree, i, n):
    """tree leaves [M, ...] → leaves [...] at clamped index i."""
    return jax.tree.map(
        lambda a: lax.dynamic_index_in_dim(a, jnp.clip(i, 0, n - 1), axis=0,
                                           keepdims=False),
        tree,
    )


def pipeline_apply(stage_params, micro, *, stage_fn, axis_name: str = "pp"):
    """Run the pipeline on one device's stage (call under shard_map).

    stage_params: this stage's params (leading stage axis already sliced off).
    micro: pytree with leading [M, ...] microbatch axis, replicated on every
    device.  Returns the same pytree shape holding the LAST stage's outputs
    (zeros elsewhere — the caller psums over the pp axis)."""
    idx = lax.axis_index(axis_name)
    pp = lax.axis_size(axis_name)
    M = jax.tree.leaves(micro)[0].shape[0]
    perm = [(j, (j + 1) % pp) for j in range(pp)]

    state = _index_pytree(micro, jnp.int32(0), M)  # shape/dtype template
    state = jax.tree.map(jnp.zeros_like, state)
    outputs = jax.tree.map(jnp.zeros_like, micro)

    def body(carry, t):
        state, outputs = carry
        fed = _index_pytree(micro, t, M)
        # stage 0 ingests microbatch t (bubble steps feed a clamped repeat
        # that is never recorded); later stages consume the rotated state
        inp = jax.tree.map(
            lambda new, held: jnp.where(idx == 0, new, held), fed, state
        )
        out = stage_fn(stage_params, inp)
        # the last stage finishes microbatch t-(pp-1) at step t; bubble
        # writes land zeros on slot 0 BEFORE its first valid write (t=pp-1),
        # so nothing real is ever overwritten
        mb = t - (pp - 1)
        valid = (idx == pp - 1) & (mb >= 0)
        outputs = jax.tree.map(
            lambda os, o: lax.dynamic_update_index_in_dim(
                os, jnp.where(valid, o, jnp.zeros_like(o)),
                jnp.clip(mb, 0, M - 1), axis=0,
            ),
            outputs, out,
        )
        state = jax.tree.map(lambda a: lax.ppermute(a, axis_name, perm), out)
        return (state, outputs), None

    (_, outputs), _ = lax.scan(body, (state, outputs), jnp.arange(M + pp - 1))
    return outputs


def make_pipeline(mesh, stage_fn, *, axis_name: str = "pp", micro_spec: P = P()):
    """Build f(stacked_params, micro) → last-stage outputs, jit/GSPMD-ready.

    stacked_params: pytree whose leaves carry a leading stage axis of size
    ``pp`` (sharded over the pp mesh axis).  micro: pytree with leading
    microbatch axis [M, ...], laid out per ``micro_spec`` (e.g.
    P(None, 'dp') to keep the microbatch batch-dim data-parallel).  Leaves
    must be numeric (masks as ints, not bools: the last-stage collection
    psums over the pp axis).  Output: micro-shaped pytree, same spec."""

    def _stage(stage_params, inp):
        # shard_map hands each device a leading stage axis of length 1
        local = jax.tree.map(lambda a: a[0], stage_params)
        return stage_fn(local, inp)

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(P(axis_name), micro_spec),
        out_specs=micro_spec,
        check_vma=False,
    )
    def _run(stacked_params, micro):
        outs = pipeline_apply(stacked_params, micro, stage_fn=_stage,
                              axis_name=axis_name)
        # non-last stages contributed zeros; psum replicates the real values
        return jax.tree.map(lambda a: lax.psum(a, axis_name), outs)

    return _run


def split_stages(stacked_layers, pp: int):
    """Reshape a stacked-layer pytree [L, ...] → [pp, L/pp, ...] stages."""
    L = jax.tree.leaves(stacked_layers)[0].shape[0]
    if L % pp:
        raise ValueError(f"{L} layers do not split into {pp} pipeline stages")
    return jax.tree.map(
        lambda a: a.reshape((pp, L // pp) + a.shape[1:]), stacked_layers
    )


def merge_microbatches(tree, batch: int):
    """[M, mb, ...] pytree → [M·mb, ...] (undo split_microbatches)."""
    return jax.tree.map(
        lambda a: a.reshape((batch,) + a.shape[2:]), tree
    )


def split_microbatches(tree, n_micro: int):
    """[B, ...] pytree → [M, B/M, ...]."""
    def f(a):
        B = a.shape[0]
        if B % n_micro:
            raise ValueError(f"batch {B} does not split into {n_micro} microbatches")
        return a.reshape((n_micro, B // n_micro) + a.shape[1:])

    return jax.tree.map(f, tree)
