"""Ring attention: sequence-parallel exact attention over an ``sp`` mesh axis.

Each device holds a sequence shard of Q, K, V.  K/V blocks rotate around the
ring via ``lax.ppermute`` while every device accumulates flash-attention-style
online-softmax statistics (running max ``m``, normalizer ``l``, weighted sum
``o``) against its local Q block — after ``sp`` steps every Q row has seen
every K/V block with O(seq/sp) memory per device and all communication on ICI
overlapping compute.

The reference has no attention (it's a data framework); this exists because
the framework's north-star consumers (BERT-base MLM on long C4 rows,
BASELINE.json config 3) need sequence parallelism as a first-class axis.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax import lax
from jax.sharding import PartitionSpec as P


def block_attn(q, k, v, scale, mask=None):
    """One Q-block × K-block attention contribution.
    q: [B, H, Tq, D], k/v: [B, H, Tk, D] → (scores-max, exp-sum, weighted-V)."""
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32) * scale
    if mask is not None:
        s = jnp.where(mask, s, -1e30)
    m = jnp.max(s, axis=-1)  # [B, H, Tq]
    p = jnp.exp(s - m[..., None])
    l = jnp.sum(p, axis=-1)
    o = jnp.einsum("bhqk,bhkd->bhqd", p.astype(v.dtype), v, preferred_element_type=jnp.float32)
    return m, l, o


def ring_attention(q, k, v, *, axis_name: str = "sp", kv_mask=None):
    """Exact attention with K/V rotating over ``axis_name``.

    Shapes (per device): q/k/v [B, H, T_local, D]; kv_mask [B, T_local] bool
    (True = attend) travels with K/V around the ring.  Returns [B, H, T_local, D]
    in q's dtype."""
    sp = lax.axis_size(axis_name)
    scale = 1.0 / (q.shape[-1] ** 0.5)

    def mask_for(blk_mask):
        if blk_mask is None:
            return None
        return blk_mask[:, None, None, :]  # [B,1,1,Tk]

    m, l, o = block_attn(q, k, v, scale, mask_for(kv_mask))

    def body(i, carry):
        m, l, o, k, v, kv_mask = carry
        perm = [(j, (j + 1) % sp) for j in range(sp)]
        k = lax.ppermute(k, axis_name, perm)
        v = lax.ppermute(v, axis_name, perm)
        if kv_mask is not None:
            kv_mask = lax.ppermute(kv_mask, axis_name, perm)
        m_new, l_new, o_new = block_attn(q, k, v, scale, mask_for(kv_mask))
        m_tot = jnp.maximum(m, m_new)
        a = jnp.exp(m - m_tot)
        b = jnp.exp(m_new - m_tot)
        l = l * a + l_new * b
        o = o * a[..., None] + o_new * b[..., None]
        return m_tot, l, o, k, v, kv_mask

    if sp > 1:
        m, l, o, *_ = lax.fori_loop(0, sp - 1, body, (m, l, o, k, v, kv_mask))
    out = o / jnp.maximum(l[..., None], 1e-30)
    return out.astype(q.dtype)


def make_ring_attention(mesh, *, axis_name: str = "sp"):
    """Wrap ring_attention in shard_map over the mesh so it can be called from
    inside a jitted, GSPMD-partitioned train step.

    Inputs are [B, H, T, D] arrays logically sharded P('dp', 'tp', 'sp', None)
    (batch over dp, heads over tp, sequence over sp)."""

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
        return ring_attention(q, k, v, axis_name=axis_name, kv_mask=mask)

    return _sharded
