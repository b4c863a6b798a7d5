"""LFM2-MoE-style hybrid causal LM in plain float32 ``jax.numpy``: the reference
for ``lakesoul_tpu/models/lfm2_moe.py``, and the one copy of it (the tests
load this file by path).

Written from the published ``config.json`` of LFM2-8B-A1B and the family's
public modelling code, over the parameter tree the program trains
(``init_lm_params``).  It imports nothing from ``lakesoul_tpu``.  The caller
runs it under ``jax.default_matmul_precision("highest")``; on a TPU a float32
product is otherwise rounded to bfloat16.

- **Gated short convolution**: ``[B | C | X] = y W_in``, ``u = B * X``,
  ``c[t] = sum_j k[:, j] u[t - (L-1) + j]`` with zeros left of the row,
  ``out = (C * c) W_out``: the shifted sums written out, no convolution call.
- **Attention**: RMS norm over each query and key head, rotary positions over
  the whole head (rotate-half), then the full ``[T, T]`` masked softmax of each
  head, a block of query rows at a time so that 8,192 tokens fit; every
  query head has its own copy of its group's keys and values.
- **Experts**: a Python loop over the held experts, each applied to every
  token and weighted by the routing (0 where the token is not routed to it).
  Routing: sigmoid scores, the top k of ``score + expert_bias``, weights the
  unbiased scores over their sum plus 1e-6, times ``routed_scaling_factor``.
  No shared expert.  Layers below ``num_dense_layers`` take a dense SwiGLU.
- Head tied to the embedding; loss: next-token cross-entropy over the held
  vocabulary, mean over the positions with ``labels >= 0``; gradients by
  ``jax.grad`` of that.  ``expert_bias`` (``params["buffers"]``) is a buffer:
  :func:`adamw_step` is handed the trained leaves only.

``held = (first, count)`` is the share of the experts the weights hold; what
the other experts would add is left out, as in the program.  ``dtype`` exists
to show what a lower precision does to the numbers (the precision control
computes all of this in ``bfloat16``).

Departures.  From the published model, shared with the program: the expert
bias has no update rule (the published config and code give none), no router
auxiliary loss, no document boundaries.  From the issue that asked for this
file ("no remat"): each layer and each block of query rows is rematerialised
(``jax.checkpoint``), which changes no arithmetic; without it the softmax
weights of one row alone are 8.6 GB in the backward pass and the comparison
could not run beside a training state at the published widths.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
KINDS = {"conv": "conv", "full_attention": "attn"}  # published layer type → the weights' key in a layer


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def swiglu(x, w_gate, w_up, w_down):
    return (silu(x @ w_gate) * (x @ w_up)) @ w_down


# ---------------------------------------------------------------- mixers


def short_conv(x, p):
    """x [B, T, h] (normed) → [B, T, h]."""
    t = x.shape[1]
    b, c, xs = jnp.split(x @ p["w_in"], 3, axis=-1)
    u = b * xs
    taps = p["conv"].shape[1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    conv = sum(padded[:, j:j + t] * p["conv"][:, j] for j in range(taps))
    return (c * conv) @ p["w_out"]


def rotary(x, theta):
    """x [B, T, H, D]; rotate-half over all D channels."""
    t, d = x.shape[1], x.shape[-1]
    half = d // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / d)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.cos(angle).astype(x.dtype)[:, None, :]
    sin = jnp.sin(angle).astype(x.dtype)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(x, p, cfg):
    """x [B, T, h] (normed) → [B, T, h]."""
    b, t, h = x.shape
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = h // heads
    q = (x @ p["w_q"]).reshape(b, t, heads, d)
    k = (x @ p["w_k"]).reshape(b, t, kv, d)
    v = (x @ p["w_v"]).reshape(b, t, kv, d)
    q = rotary(rms_norm(q, p["q_norm"], cfg["norm_eps"]), cfg["rope_theta"])
    k = rotary(rms_norm(k, p["k_norm"], cfg["norm_eps"]), cfg["rope_theta"])
    k, v = jnp.repeat(k, heads // kv, axis=2), jnp.repeat(v, heads // kv, axis=2)

    @jax.checkpoint
    def block(q_blk, first):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) / math.sqrt(d)
        pos = first + jnp.arange(q_blk.shape[1])
        scores = jnp.where(pos[:, None] >= jnp.arange(t)[None, :], scores, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)

    out = [block(q[:, first:first + QUERY_BLOCK], first) for first in range(0, t, QUERY_BLOCK)]
    return jnp.concatenate(out, axis=1).reshape(b, t, heads * d) @ p["w_o"]


# --------------------------------------------------------------- experts


def scores(x, router):
    """Every expert's unbiased score: x [N, h] → [N, experts]."""
    return jax.nn.sigmoid(x @ router)


def route(x, router, bias, cfg):
    """→ (experts [N, k], weights [N, k])."""
    s = scores(x, router)
    _, top_e = jax.lax.top_k(s + bias, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, top_e, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-6)
    return top_e, weights * cfg.get("routed_scaling_factor", 1.0)


def moe(x, p, bias, cfg, held):
    """The held experts' part: x [B, T, h] (normed) → [B, T, h]."""
    first, count = held
    flat = x.reshape(-1, x.shape[-1])
    top_e, w = route(flat, p["router"], bias, cfg)
    y = jnp.zeros_like(flat)
    for e in range(count):
        weight = jnp.sum(jnp.where(top_e == first + e, w, 0.0), axis=-1)  # 0 where not routed here
        y = y + weight[:, None].astype(flat.dtype) * swiglu(flat, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
    return y.reshape(x.shape)


# ----------------------------------------------------------------- model


def layer(x, lp, buffers, kind, dense, cfg, held):
    y = rms_norm(x, lp["norm1"], cfg["norm_eps"])
    x = x + (short_conv(y, lp["conv"]) if kind == "conv" else attention(y, lp["attn"], cfg))
    y = rms_norm(x, lp["norm2"], cfg["norm_eps"])
    if dense:
        return x + swiglu(y, lp["mlp"]["w_gate"], lp["mlp"]["w_up"], lp["mlp"]["w_down"])
    return x + moe(y, lp["moe"], buffers["expert_bias"], cfg, held)


def lm_logits(params, ids, *, cfg: dict, held, dtype=jnp.float32):
    """ids [B, T] → logits [B, T, vocab held]."""
    params = jax.tree.map(lambda a: jnp.asarray(a, dtype), params)
    x = params["embed"][ids]
    for i, (lp, buffers, kind) in enumerate(zip(params["layers"], params["buffers"]["layers"], cfg["layer_types"])):
        dense = i < cfg["num_dense_layers"]
        x = jax.checkpoint(
            lambda x, lp, buffers, kind=KINDS[kind], dense=dense: layer(x, lp, buffers, kind, dense, cfg, held)
        )(x, lp, buffers)
    return rms_norm(x, params["final_norm"], cfg["norm_eps"]) @ params["embed"].T


def lm_loss(params, ids, labels, *, cfg: dict, held, dtype=jnp.float32, logits_at=None):
    """Mean negative log-likelihood over the positions with ``labels >= 0``;
    with ``logits_at`` (positions along T) → (loss, logits [B, len, vocab])."""
    logits = lm_logits(params, ids, cfg=cfg, held=held, dtype=dtype)
    valid = labels >= 0
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
    loss = -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.maximum(jnp.sum(valid), 1)
    return loss if logits_at is None else (loss, logits[:, logits_at])


def adamw_step(params, grads, mu, nu, count, *, lr, b1=0.9, b2=0.999, eps=1e-8, weight_decay=1e-4):
    """One AdamW step (Loshchilov and Hutter 2019) with bias correction, the
    decay added to the update, over the trained leaves (no buffers): →
    (params, mu, nu)."""
    count = count + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)

    def update(p, m, v):
        m_hat, v_hat = m / (1 - b1**count), v / (1 - b2**count)
        return p - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + weight_decay * p)

    return jax.tree.map(update, params, mu, nu), mu, nu
