"""What the causal-LM families share: the layer stack, the head and the loss,
and the pieces more than one family's mixers are made of.

A family is a module with a configuration object (``models/qwen3_next.py``,
``models/lfm2_moe.py``); nothing here or in ``models/train.py`` names one.  The
stack reads a layer's kinds from the configuration and asks it for the rest:

- ``layer_kinds()``: each layer's mixer (``"gdn"``, ``"attn"``, ``"conv"``),
  which is also the key of the mixer's weights in the layer;
- ``ffn_kinds()``: each layer's feed-forward, ``"dense"`` (SwiGLU, weights
  under ``"mlp"``) or ``"moe"`` (routed experts, under ``"moe"``);
- ``mixer(kind)`` → (``fn(y, p)`` on the normed input, the ``named_scope`` its
  device time is charged to);
- ``norm(x, w)``: the family's RMS norm, float32 out;
- ``route(y32, router, bias)`` → (experts, weights, assignments the bias
  moved): its routing rule over ``parallel/moe.py``;
- ``num_experts``, ``experts_held``, ``dtype``; and for ``models/train.py``
  ``init(key)`` and ``loss(params, ids, labels, batch_sharding=)``.

A layer is ``h = x + mixer(norm1(x)); x' = h + ffn(norm2(h))``; after the last
a final norm and the head: ``params["head"]`` [h, vocab] where there is one,
else the embedding (a tied head).  ``params["buffers"]``, where a family has
it, is state that no gradient and no optimizer touches (``models/train.py:
_adamw_step``); ``buffers["layers"][i]`` belongs to layer ``i``.

The model may be one chip's share of an expert-parallel job: ``experts_held``
says which of the ``num_experts`` live here (``parallel/moe.py: held_experts``)
and ``vocab_size`` is the slice of the vocabulary the embedding, the head and
the loss are over.  Parallelism: dp over rows; everything else is replicated.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from lakesoul_tpu.models.bert import labelled_nll
from lakesoul_tpu.parallel.moe import ROUTE_SCOPE, held_experts, shared_expert
from lakesoul_tpu.parallel.ring_attention import block_attn

ATTN_SCOPE = "lakesoul.lm.attn"
MLP_SCOPE = "lakesoul.lm.mlp"
HEAD_SCOPE = "lakesoul.lm.head"
ATTN_BAND = 1024   # queries that share one static slice of the keys
ATTN_ROWS = 128    # queries whose scores live at once


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


def _rotary(x, positions, rotary_dim: int, theta: float):
    """Rotate the first ``rotary_dim`` channels of x [B, T, H, D] (float32):
    halves ``[x1 | x2]`` → ``[x1 cos - x2 sin | x2 cos + x1 sin]``."""
    half = rotary_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rotary_dim)
    angle = positions.astype(jnp.float32)[:, None] * inv_freq  # [T, half]
    cos, sin = jnp.cos(angle)[:, None, :], jnp.sin(angle)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def causal_attention(q, k, v, *, band: int | None = None, rows: int | None = None):
    """Causal softmax attention with grouped-query heads: q [B, Hkv, G, T, D]
    (scaled), k, v [B, Hkv, T, D] → [B, Hkv, G, T, D].

    The queries go a band at a time against the keys up to the band's last
    position (a static slice, so the keys after it cost nothing), and inside a
    band ``rows`` queries at a time, each block rematerialised: no more than
    ``rows`` rows of scores live at once, in either pass."""
    band, rows = band or ATTN_BAND, rows or ATTN_ROWS
    b, hkv, groups, t, d = q.shape

    def block(q_blk, k_seen, v_seen, first):
        """q_blk [B, Hkv, G, n, D] at positions first.. against the keys seen."""
        n = q_blk.shape[3]
        pos = jnp.tile(first + jnp.arange(n), groups)
        mask = pos[:, None] >= jnp.arange(k_seen.shape[2])[None, :]
        _, l, o = block_attn(q_blk.reshape(b, hkv, groups * n, d), k_seen, v_seen, 1.0, mask)
        return (o / l[..., None]).astype(v.dtype).reshape(b, hkv, groups, n, d)

    out = []
    for start in range(0, t, band):
        end = min(start + band, t)
        q_band, k_seen, v_seen = q[:, :, :, start:end], k[:, :, :end], v[:, :, :end]
        if (end - start) % rows or end - start == rows:
            out.append(jax.checkpoint(block)(q_band, k_seen, v_seen, start))
            continue
        blocks = (end - start) // rows
        q_rows = jnp.moveaxis(q_band.reshape(b, hkv, groups, blocks, rows, d), 3, 0)
        firsts = start + rows * jnp.arange(blocks)
        o = jax.lax.map(
            lambda xs: jax.checkpoint(block)(xs[0], k_seen, v_seen, xs[1]), (q_rows, firsts)
        )
        out.append(jnp.moveaxis(o, 0, 3).reshape(b, hkv, groups, end - start, d))
    return jnp.concatenate(out, axis=3)


def softmax_attention(x, p, *, heads: int, kv_heads: int, head_dim: int, rotary_dim: int,
                      theta: float, norm, gated: bool):
    """The grouped-query softmax-attention mixer: x [B, T, h] (normed) →
    [B, T, h].  ``norm(a, w)`` is the family's RMS norm over a head's channels
    (``q_norm``, ``k_norm``); ``rotary_dim`` of them are rotated.  ``gated``:
    ``w_q`` holds per head the query, then a gate whose sigmoid scales the
    head's output (the Qwen3-Next family); else the query alone."""
    dtype = x.dtype
    f32 = jnp.float32
    b, t, _ = x.shape
    d = head_dim
    q = (x @ p["w_q"].astype(dtype)).reshape(b, t, heads, (2 if gated else 1) * d)
    if gated:
        q, gate = q[..., :d], q[..., d:]
    k = (x @ p["w_k"].astype(dtype)).reshape(b, t, kv_heads, d)
    v = (x @ p["w_v"].astype(dtype)).reshape(b, t, kv_heads, d)
    positions = jnp.arange(t)
    q = _rotary(norm(q, p["q_norm"]), positions, rotary_dim, theta)
    k = _rotary(norm(k, p["k_norm"]), positions, rotary_dim, theta)
    q = (q * d**-0.5).astype(dtype)
    # [B, T, heads, D] → [B, kv, heads // kv, T, D]: each key-value head serves a group
    q = q.reshape(b, t, kv_heads, heads // kv_heads, d).transpose(0, 2, 3, 1, 4)
    k, v = (a.transpose(0, 2, 1, 3) for a in (k.astype(dtype), v))
    o = causal_attention(q, k, v)
    o = o.transpose(0, 3, 1, 2, 4).reshape(b, t, heads, d)
    if gated:
        o = (o.astype(f32) * jax.nn.sigmoid(gate.astype(f32))).astype(dtype)
    return o.reshape(b, t, heads * d) @ p["w_o"].astype(dtype)


# ------------------------------------------------------------- the stack


def _row_by_row(mixer, x, p, batch_sharding):
    """``mixer(x, p)`` one row of ``x`` [B, T, h] at a time, each row
    rematerialised: a mixer's intermediates at 8k tokens are gigabytes a row
    and no row needs another's.  On a mesh every device takes its own rows."""

    def local(x, p):
        return jax.lax.map(jax.checkpoint(lambda row: mixer(row[None], p)[0]), x)

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
    input and the routing."""
    dtype = x.dtype
    mixer, scope = cfg.mixer(kind)

    def mix(x, p):
        return x + mixer(cfg.norm(x, p["norm"]).astype(dtype), p["mixer"])

    @jax.checkpoint
    def dense(x, norm, p):
        with jax.named_scope(MLP_SCOPE):
            return dense_mlp(cfg.norm(x, norm).astype(dtype), p)

    @jax.checkpoint
    def routed(x, norm, router, bias, shared):
        """Norm, routing and the shared expert: cheap to compute again."""
        with jax.named_scope(ROUTE_SCOPE):
            y32 = cfg.norm(x, norm)
        top_e, w, moved = cfg.route(y32, router, bias)
        y = y32.astype(dtype)
        return y, top_e, w, moved, None if shared is None else shared_expert(y, shared)

    with jax.named_scope(scope):
        x = _row_by_row(mix, x, {"norm": lp["norm1"], "mixer": lp[kind]}, batch_sharding)
    if ffn == "dense":
        return x + dense(x, lp["norm2"], lp["mlp"]), None
    p = lp["moe"]
    y, top_e, w, moved, shared = routed(
        x, lp["norm2"], p["router"], (buffers or {}).get("expert_bias"), p.get("shared")
    )
    out, counts = held_experts(
        y, top_e, w, p, n_experts=cfg.num_experts, held=cfg.experts_held, batch_sharding=batch_sharding
    )
    x = x + out
    return (x if shared is None else x + shared), dict(counts, moe_bias_moved=moved)


def lm_hidden(params, ids, *, cfg, batch_sharding=None):
    """ids [B, T] → (final hidden states [B, T, h] before the final norm,
    counts summed over the routed layers)."""
    x = params["embed"][ids].astype(jnp.dtype(cfg.dtype))
    kinds, ffns = cfg.layer_kinds(), cfg.ffn_kinds()
    buffers = params.get("buffers", {}).get("layers", [None] * len(kinds))
    totals = None
    for lp, held, kind, ffn in zip(params["layers"], buffers, kinds, ffns, strict=True):
        x, counts = lm_layer(x, lp, held, kind=kind, ffn=ffn, cfg=cfg, batch_sharding=batch_sharding)
        if counts is not None:
            totals = counts if totals is None else jax.tree.map(jnp.add, totals, counts)
    return x, totals


def head_params(params) -> dict:
    """The leaves :func:`lm_head` reads: the final norm and the head, or the
    embedding where the head is tied to it."""
    return {k: params[k] for k in ("final_norm", "head" if "head" in params else "embed")}


def lm_head(head, x, *, cfg):
    """Logits over the held vocabulary, float32: x [..., h] → [..., vocab]."""
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope(HEAD_SCOPE):
        y = cfg.norm(x, head["final_norm"]).astype(dtype)
        if "head" in head:
            return jnp.dot(y, head["head"].astype(dtype), preferred_element_type=jnp.float32)
        return jnp.einsum("...h,vh->...v", y, head["embed"].astype(dtype), preferred_element_type=jnp.float32)


def lm_logits(params, ids, *, cfg):
    x, _ = lm_hidden(params, ids, cfg=cfg)
    return lm_head(head_params(params), x, cfg=cfg)


def lm_loss(params, ids, labels, *, cfg, batch_sharding=None):
    """Next-token cross-entropy, float32, mean over the positions with
    ``labels >= 0`` (-100 elsewhere) → (loss, counts).  ``counts``: the expert
    layers' (summed over layers) and ``tokens``, int32."""
    x, counts = lm_hidden(params, ids, cfg=cfg, batch_sharding=batch_sharding)
    loss, _ = labelled_nll(functools.partial(lm_head, cfg=cfg), head_params(params), x, labels, batch_sharding)
    return loss, dict(counts, tokens=jnp.int32(ids.size))
