"""Dropless top-k expert layer: one chip's share of an expert-parallel layer.

The router scores all ``n_experts``, every token keeps its ``top_k``, and this
chip computes the part of the result that the experts it holds give, for
however many assignments land on them (none to all).  Nothing has a capacity
and nothing is dropped; what the absent experts would add is left out.  On an
``ep > 1`` mesh the token exchange in front of it is not written yet.

The layer is three pieces and the model composes them
(``models/qwen3_next.py: lm_layer``): :func:`route_top_k`,
:func:`held_experts` and :func:`shared_expert`.  They stay apart because the
model rematerialises the first and the last with its norm and leaves the
second outside (see :func:`held_experts`).

Assignments are sorted by held expert and the products run one fixed tile of
one expert's rows at a time, for as many tiles as the held assignments fill:
the work follows the routing while every shape stays static.  A loop whose
length depends on the data has no reverse-mode derivative, so the backward
pass is a second loop of the same tiles under one custom_vjp.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from lakesoul_tpu.parallel.mesh import spec_axes

ROUTE_SCOPE = "lakesoul.lm.moe.route"
EXPERTS_SCOPE = "lakesoul.lm.moe.experts"
SHARED_SCOPE = "lakesoul.lm.moe.shared"
# rows of one expert a product takes at a time.  An expert under even routing
# sees 320 assignments at 16,384 tokens, top-10 of 512: with 512 most experts
# fill one tile whatever the seed, so a step's time follows the routing less
# than with 256 (PERF.md section 6, PR 28), at 1% more time a step
EXPERT_TILE = 512


def route_top_k(x, router_w, *, top_k: int):
    """Tokens ``x`` [..., h] → (experts [..., k] int32, weights [..., k] f32):
    softmax over every expert in float32, the ``top_k`` largest, their
    weights divided by their sum."""
    with jax.named_scope(ROUTE_SCOPE):
        logits = jnp.einsum(
            "...h,he->...e", x.astype(jnp.float32), router_w.astype(jnp.float32),
            precision=jax.lax.Precision.HIGHEST,
        )
        top_p, top_e = jax.lax.top_k(jax.nn.softmax(logits, axis=-1), top_k)
        return top_e.astype(jnp.int32), top_p / jnp.sum(top_p, axis=-1, keepdims=True)


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


def _tile_rows(t, plan, tile: int, k: int):
    """Tile ``t`` → (its expert, assignment of each row, token of each row,
    which rows hold an assignment, first row in ``order``)."""
    order, sizes, starts, tile_ends = plan
    e = jnp.searchsorted(tile_ends, t, side="right").astype(jnp.int32)
    row0 = starts[e] + (t - (tile_ends[e] - (sizes[e] + tile - 1) // tile)) * tile
    rows = row0 + jnp.arange(tile, dtype=jnp.int32)
    valid = rows < starts[e] + sizes[e]
    a = order[jnp.minimum(rows, order.shape[0] - 1)]
    return e, a, a // k, valid, row0


def _swiglu(xt, wg, wu):
    g = jnp.dot(xt, wg, preferred_element_type=jnp.float32)
    u = jnp.dot(xt, wu, preferred_element_type=jnp.float32)
    return g, u, jax.nn.silu(g) * u


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _held_experts(x, w, plan, wg, wu, wd, tile):
    """``sum over held assignments of w * expert(x)``: x [N, h], w [N, k] f32,
    ``plan`` of :func:`_tile_plan` over the N x k assignments, weights
    [count, ...] → [N, h]."""
    k = w.shape[1]
    w_flat = w.reshape(-1)
    wg, wu, wd = (m.astype(x.dtype) for m in (wg, wu, wd))

    def run_tile(carry):
        t, y = carry
        e, a, tok, valid, _ = _tile_rows(t, plan, tile, k)
        _, _, mid = _swiglu(x[tok], wg[e], wu[e])
        yt = jnp.dot(mid.astype(x.dtype), wd[e], preferred_element_type=jnp.float32)
        yt = yt * jnp.where(valid, w_flat[a], 0.0)[:, None]
        return t + 1, y.at[tok].add(yt)

    tiles = plan[3][-1]
    _, y = jax.lax.while_loop(
        lambda c: c[0] < tiles, run_tile, (jnp.int32(0), jnp.zeros(x.shape, jnp.float32))
    )
    return y.astype(x.dtype)


def _held_experts_fwd(x, w, plan, wg, wu, wd, tile):
    return _held_experts(x, w, plan, wg, wu, wd, tile), (x, w, plan, wg, wu, wd)


def _held_experts_bwd(tile, saved, dy):
    x, w, plan, wg, wu, wd = saved
    n, k = w.shape
    w_flat = w.reshape(-1)
    lo = x.dtype
    wg_lo, wu_lo, wd_lo = (m.astype(lo) for m in (wg, wu, wd))
    dy = dy.astype(lo)
    f32 = jnp.float32

    def run_tile(carry):
        t, dx, dwg, dwu, dwd, dw_rows = carry
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
        dg = (dmid * u * sig * (1.0 + g * (1.0 - sig))).astype(lo)
        du = (dmid * g * sig).astype(lo)
        dwd = dwd.at[e].add(jnp.dot(mid_lo.T, dyw, preferred_element_type=f32))
        dwg = dwg.at[e].add(jnp.dot(xt.T, dg, preferred_element_type=f32))
        dwu = dwu.at[e].add(jnp.dot(xt.T, du, preferred_element_type=f32))
        dxt = (jnp.dot(dg, wg_lo[e].T, preferred_element_type=f32)
               + jnp.dot(du, wu_lo[e].T, preferred_element_type=f32))
        return t + 1, dx.at[tok].add(dxt), dwg, dwu, dwd, dw_rows

    init = (
        jnp.int32(0), jnp.zeros(x.shape, f32),
        jnp.zeros(wg.shape, f32), jnp.zeros(wu.shape, f32), jnp.zeros(wd.shape, f32),
        jnp.zeros(n * k + tile, f32),  # a tile may reach past the last row
    )
    tiles = plan[3][-1]
    _, dx, dwg, dwu, dwd, dw_rows = jax.lax.while_loop(lambda c: c[0] < tiles, run_tile, init)
    dw = jnp.zeros(n * k, f32).at[plan[0]].set(dw_rows[: n * k]).reshape(n, k)
    return (dx.astype(x.dtype), dw.astype(w.dtype), None,
            dwg.astype(wg.dtype), dwu.astype(wu.dtype), dwd.astype(wd.dtype))


_held_experts.defvjp(_held_experts_fwd, _held_experts_bwd)


def _routed_share(x, top_e, w, wg, wu, wd, *, held, tile, axes):
    """One shard's rows through the experts held here.  → (y, expert loads
    [count] summed over ``axes``)."""
    first, count = held
    shape = x.shape
    k = top_e.shape[-1]
    local = top_e.reshape(-1) - first
    local = jnp.where((local >= 0) & (local < count), local, count)
    tile = min(tile, -(-local.shape[0] // 8) * 8)
    plan = _tile_plan(local, count, tile)
    y = _held_experts(x.reshape(-1, shape[-1]), w.reshape(-1, k), plan, wg, wu, wd, tile)
    loads = plan[1]
    if axes:
        loads = jax.lax.psum(loads, axes)
    return y.reshape(shape), loads


def shared_expert(x, p):
    """The expert every token takes, under its sigmoid gate: x [..., h]."""
    dtype = x.dtype
    with jax.named_scope(SHARED_SCOPE):
        mid = jax.nn.silu(x @ p["w_gate"].astype(dtype)) * (x @ p["w_up"].astype(dtype))
        out = mid @ p["w_down"].astype(dtype)
        gate = jax.nn.sigmoid(jnp.einsum("...h,h->...", x.astype(jnp.float32), p["gate"].astype(jnp.float32)))
        return (out * gate[..., None]).astype(dtype)


def held_experts(x, top_e, w, p, *, n_experts: int, held: tuple[int, int],
                 batch_sharding=None, tile: int | None = None):
    """The held experts' part of a routed layer: x [..., h], the routing
    ``top_e``, ``w`` [..., k] of :func:`route_top_k` → (y [..., h], counts).
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
            y, loads = _routed_share(x, top_e, w, *weights, held=held, tile=tile, axes=())
        else:
            spec = batch_sharding.spec
            y, loads = jax.shard_map(
                functools.partial(_routed_share, held=held, tile=tile, axes=spec_axes(spec)),
                mesh=batch_sharding.mesh, in_specs=(spec, spec, spec, P(), P(), P()),
                out_specs=(spec, P()), check_vma=False,
            )(x, top_e, w, *weights)
    counts = {
        "moe_all": jnp.int32(top_e.size),
        "moe_held": jnp.sum(loads),
        "moe_load_max": jnp.max(loads),
    }
    return y, counts
