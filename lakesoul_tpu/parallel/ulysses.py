"""Ulysses-style all-to-all sequence parallelism.

The second first-class long-context strategy next to ring attention
(parallel/ring_attention.py).  Instead of rotating K/V blocks around a ring,
each device swaps its SEQUENCE shard for a HEAD shard with one
``lax.all_to_all`` before attention and swaps back after:

    in :  q/k/v sharded [B, H,       T/sp, D]   (sequence-parallel)
    a2a:  q/k/v sharded [B, H/sp,    T,    D]   (head-parallel)
    attn: plain full-sequence attention per head group (one MXU-friendly
          block — no online-softmax loop, no per-step collectives)
    a2a:  out back to    [B, H,      T/sp, D]

Trade-off vs ring (why both exist): Ulysses does 2 collectives total and
keeps attention as one large fused matmul pair (better MXU utilization,
simpler kernel), but requires ``sp`` to divide the head count and holds the
full T×T score tile per head group; ring never materializes full T but pays
``sp-1`` ppermute steps and runs the online-softmax update serially.  Short
sequences / many heads → Ulysses; extreme T → ring.  (DeepSpeed-Ulysses is
the public origin of the layout; the implementation here is jax-native
shard_map + lax.all_to_all.)
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def _full_attention(q, k, v, scale, kv_mask=None):
    """Plain softmax attention: q/k/v [B, h, T, D] → [B, h, T, D]."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if kv_mask is not None:
        s = jnp.where(kv_mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1)
    return jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v,
                      preferred_element_type=jnp.float32).astype(q.dtype)


def ulysses_attention(q, k, v, *, axis_name: str = "sp", kv_mask=None):
    """All-to-all sequence-parallel attention (per-device view).

    q/k/v: [B, H, T_local, D] with T_local = T/sp; H must be divisible by
    sp.  kv_mask: [B, T_local] bool (True = attend).  Returns
    [B, H, T_local, D]."""
    sp = lax.axis_size(axis_name)
    scale = 1.0 / (q.shape[-1] ** 0.5)
    if sp == 1:
        return _full_attention(q, k, v, scale, kv_mask)
    B, H, Tl, D = q.shape
    if H % sp != 0:
        raise ValueError(f"ulysses needs heads ({H}) divisible by sp ({sp})")

    def seq_to_head(x):
        # [B, H, T/sp, D] → all_to_all over the head axis → [B, H/sp, T, D]
        # split_axis=1 scatters head groups; concat_axis=2 gathers sequence
        return lax.all_to_all(x, axis_name, split_axis=1, concat_axis=2, tiled=True)

    def head_to_seq(x):
        return lax.all_to_all(x, axis_name, split_axis=2, concat_axis=1, tiled=True)

    qh = seq_to_head(q)  # [B, H/sp, T, D]
    kh = seq_to_head(k)
    vh = seq_to_head(v)
    full_mask = None
    if kv_mask is not None:
        # sequence shards of the mask gather to the full [B, T] mask
        full_mask = lax.all_gather(kv_mask, axis_name, axis=1, tiled=True)
    out = _full_attention(qh, kh, vh, scale, full_mask)
    return head_to_seq(out)  # back to [B, H, T/sp, D]


def make_ulysses_attention(mesh, *, axis_name: str = "sp"):
    """shard_map wrapper with the same calling convention as
    make_ring_attention — the two strategies are drop-in interchangeable in
    the trainer (models/train.py attention_fn)."""

    @functools.partial(
        jax.shard_map,
        mesh=mesh,
        in_specs=(
            P("dp", "tp", "sp", None),
            P("dp", "tp", "sp", None),
            P("dp", "tp", "sp", None),
            P("dp", "sp"),
        ),
        out_specs=P("dp", "tp", "sp", None),
        check_vma=False,
    )
    def _sharded(q, k, v, mask):
        return ulysses_attention(q, k, v, axis_name=axis_name, kv_mask=mask)

    return _sharded
