"""Mixture-of-Experts FFN with expert parallelism over an ``ep`` mesh axis.

Switch-Transformer-style top-1 routing expressed entirely as dense einsums
(one-hot dispatch/combine tensors) — the TPU-native formulation: routing
becomes MXU matmuls with static shapes, and GSPMD inserts the token
all-to-all from the sharding constraints alone (expert axis of the dispatched
tensors sharded over ``ep``), the same way the dp/tp collectives appear in
models/train.py.  No data-dependent gathers, no ragged shapes.

The reference has no model-side MoE (it's a data framework); this exists
because the task's parallelism inventory makes expert parallelism a
first-class axis alongside dp/tp/sp/pp, and the framework's delivery path
must feed models sharded this way.

Capacity semantics follow the Switch paper: each expert processes at most
``capacity = ceil(tokens/experts · capacity_factor)`` tokens; overflow tokens
are dropped from the expert path (their residual stream passes through) —
load balancing is encouraged by the standard auxiliary loss returned next to
the output.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from lakesoul_tpu.parallel.mesh import spec_axes


def moe_capacity(n_tokens: int, n_experts: int, capacity_factor: float) -> int:
    return max(1, math.ceil(n_tokens / n_experts * capacity_factor))


def moe_ffn(
    x: jax.Array,
    gate_w: jax.Array,
    w1: jax.Array,
    b1: jax.Array,
    w2: jax.Array,
    b2: jax.Array,
    *,
    capacity_factor: float = 1.25,
    ep_sharding=None,
) -> tuple[jax.Array, jax.Array]:
    """Top-1 MoE FFN over flattened tokens.

    Shapes: x [N, h]; gate_w [h, E]; w1 [E, h, f]; b1 [E, f]; w2 [E, f, h];
    b2 [E, h].  Returns (out [N, h], aux_loss scalar).

    ``ep_sharding`` is a ``NamedSharding`` (e.g. ``NamedSharding(mesh,
    P("ep", None, None))``) constraining the expert axis of the dispatched
    [E, C, h] activations; None skips the constraints (single-device tests /
    CPU reference)."""
    N, h = x.shape
    E = gate_w.shape[1]
    C = moe_capacity(N, E, capacity_factor)

    # ---- router (f32: tiny, and argmax/softmax stability matters)
    logits = x.astype(jnp.float32) @ gate_w.astype(jnp.float32)  # [N, E]
    probs = jax.nn.softmax(logits, axis=-1)
    expert = jnp.argmax(probs, axis=-1)  # [N]
    gate = jnp.max(probs, axis=-1)  # [N]

    onehot_i = jax.nn.one_hot(expert, E, dtype=jnp.int32)  # [N, E]
    # rank of each token within its expert (0-based), in token order —
    # deterministic tie-breaking, like the reference Switch implementation.
    # int32 cumsum: a float32 cumsum loses integer exactness past ~2^24
    # tokens routed to one expert, silently corrupting keep/drop decisions
    # (ADVICE r2); exact up to 2^31 here, cast to float only for the einsum.
    pos_i = jnp.cumsum(onehot_i, axis=0) * onehot_i - onehot_i  # [N, E]
    onehot = onehot_i.astype(jnp.float32)
    keep = (pos_i < C).astype(jnp.float32) * onehot  # beyond capacity drops
    pos_c = jax.nn.one_hot(jnp.sum(pos_i * onehot_i, axis=-1), C,
                           dtype=jnp.float32)  # [N, C]
    dispatch = keep[:, :, None] * pos_c[:, None, :]  # [N, E, C] 0/1

    # ---- dispatch: [N, h] → [E, C, h]; sharding the E axis over ep makes
    # GSPMD materialize this einsum as the token all-to-all over ICI
    xin = jnp.einsum("nec,nh->ech", dispatch, x.astype(jnp.float32))
    if ep_sharding is not None:
        xin = jax.lax.with_sharding_constraint(xin, ep_sharding)
    xin = xin.astype(x.dtype)

    # ---- expert FFN (batched over the ep-sharded expert axis: each device
    # runs only its local experts)
    hdn = jax.nn.gelu(
        jnp.einsum("ech,ehf->ecf", xin, w1.astype(x.dtype)) + b1[:, None, :].astype(x.dtype)
    )
    out_e = jnp.einsum("ecf,efh->ech", hdn, w2.astype(x.dtype)) + b2[:, None, :].astype(x.dtype)
    if ep_sharding is not None:
        out_e = jax.lax.with_sharding_constraint(out_e, ep_sharding)

    # ---- combine: weighted return all-to-all back to token order
    combine = dispatch * gate[:, None, None]  # [N, E, C]
    out = jnp.einsum("nec,ech->nh", combine, out_e.astype(jnp.float32))

    # ---- Switch aux loss: E · Σ_e (token fraction_e · mean router prob_e)
    frac_tokens = jnp.mean(onehot, axis=0)  # [E]
    frac_probs = jnp.mean(probs, axis=0)  # [E]
    aux = E * jnp.sum(frac_tokens * frac_probs)
    return out.astype(x.dtype), aux


def init_moe_ffn_params(key, n_layers: int, hidden: int, ff: int, n_experts: int,
                        std: float = 0.02) -> dict:
    """Stacked-per-layer MoE FFN params (the lax.scan layout bert.py uses)."""
    ks = jax.random.split(key, 3)
    L, E = n_layers, n_experts

    def norm(k, shape):
        return (jax.random.normal(k, shape) * std).astype(jnp.float32)

    return {
        "gate_w": norm(ks[0], (L, hidden, E)),
        "w1": norm(ks[1], (L, E, hidden, ff)),
        "b1": jnp.zeros((L, E, ff)),
        "w2": norm(ks[2], (L, E, ff, hidden)),
        "b2": jnp.zeros((L, E, hidden)),
    }


def moe_param_rules() -> dict:
    """PartitionSpecs for the stacked MoE params: experts sharded over ep
    (weights live where their tokens are dispatched to)."""
    return {
        "gate_w": P(),
        "w1": P(None, "ep", None, None),
        "b1": P(None, "ep", None),
        "w2": P(None, "ep", None, None),
        "b2": P(None, "ep", None),
    }


# ----------------------------------------------------- dropless top-k layer
# One chip's share of an expert-parallel layer: the router scores all
# ``n_experts``, every token keeps its ``top_k``, and this chip computes the
# part of the result that the experts it holds give, for however many
# assignments land on them (none to all).  Nothing has a capacity and nothing
# is dropped; what the absent experts would add is left out.  On an ``ep > 1``
# mesh the token exchange in front of it is not written yet.
#
# The layer is three pieces and the model composes them
# (``models/qwen3_next.py: lm_layer``): :func:`route_top_k`,
# :func:`held_experts` and :func:`shared_expert`.  They stay apart because the
# model rematerialises the first and the last with its norm and leaves the
# second outside (see :func:`held_experts`).
#
# Assignments are sorted by held expert and the products run one fixed tile of
# one expert's rows at a time, for as many tiles as the held assignments fill:
# the work follows the routing while every shape stays static.  A loop whose
# length depends on the data has no reverse-mode derivative, so the backward
# pass is a second loop of the same tiles under one custom_vjp.

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
