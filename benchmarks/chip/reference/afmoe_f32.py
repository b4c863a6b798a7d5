"""``afmoe`` (Trinity-Mini) causal LM in plain float32 ``jax.numpy``: the
reference for ``lakesoul_tpu/models/afmoe.py``, and the one copy of it (the
tests load this file by path).

Written from the published ``config.json`` of Trinity-Mini and, where that is
silent, the family's public modelling code (each such reading is marked
*assumed* here and listed under ``assumed`` in
``configs/trinity_mini_clm_pk.json``), over the parameter tree the program
trains (``init_lm_params``).  It imports nothing from ``lakesoul_tpu``.  The
caller runs it under ``jax.default_matmul_precision("highest")``; on a TPU a
float32 product is otherwise rounded to bfloat16.

- **Embedding**: ``x0 = Emb[ids] * sqrt(hidden_size)`` where ``mup_enabled``
  (*assumed*: the key is published, its equation is not).
- **Layer**, four plain RMS norms (*assumed*): ``h = x + N2(Mix(N1(x)))``,
  ``x' = h + N4(FFN(N3(h)))``.
- **Attention**, y the layer's normed input: ``q = y W_q`` (32 heads of 128),
  ``k = y W_k``, ``v = y W_v`` (4 heads of 128), ``g = y W_g`` (32 x 128;
  *assumed*); RMS norm over each query and key head (*assumed*).  A
  ``sliding_attention`` layer rotates q and k over the whole head
  (rotate-half, ``rope_theta``) and lets query ``i`` see key ``j`` iff
  ``0 <= i - j < sliding_window`` (*assumed*: the window counts the query's
  own position); a ``full_attention`` layer rotates nothing (*assumed*) and
  sees ``j <= i``.  The mask is one whole ``[T, T]`` comparison of positions,
  taken a block of query rows at a time so that 8,192 tokens fit; every query
  head has its own copy of its group's keys and values; scores over
  ``sqrt(128)``, softmax, ``((P v) * sigmoid(g)) W_o``.
- **Experts**: a Python loop over the held experts, each applied to every
  token and weighted by the routing (0 where the token is not routed to it).
  Routing: ``s = sigmoid(y W_r)``, the top k of ``s + expert_bias``, weights
  ``s_picked / (sum(s_picked) + 1e-20) x route_scale``.  Beside them the
  shared expert, a SwiGLU with no gate, on every token; the two are summed
  before ``N4``.  Layers below ``num_dense_layers`` take a dense SwiGLU.
- **Head and loss**: a final norm and an untied head over the held
  vocabulary; next-token cross-entropy, mean over the positions with
  ``labels >= 0``; gradients by ``jax.grad`` of that.  ``expert_bias``
  (``params["buffers"]``) is a buffer: :func:`adamw_step` is handed the
  trained leaves only.

``held = (first, count)`` is the share of the experts the weights hold; what
the other experts would add is left out, as in the program.  ``dtype`` exists
to show what a lower precision does to the numbers (the precision control
computes all of this in ``bfloat16``).

Departures.  From the published model, shared with the program: the expert
bias has no update rule (``load_balance_coeff`` is a published rate; the rule
it scales is not in the config); no auxiliary loss; no document boundaries;
the rotary pairing is rotate-half (a fixed permutation of ``W_q``'s and
``W_k``'s columns under seeded weights).  From a literal "no remat"
reference: each layer and each block of query rows is rematerialised
(``jax.checkpoint``), which changes no arithmetic; without it the softmax
weights of one row alone are 8.6 GB a layer in the backward pass.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
KINDS = {"sliding_attention": "swa", "full_attention": "attn"}  # published layer type → the weights' key in a layer


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def swiglu(x, w_gate, w_up, w_down):
    return (silu(x @ w_gate) * (x @ w_up)) @ w_down


# --------------------------------------------------------------- attention


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


def visible(t: int, window: int | None):
    """[T, T] bool: whether query ``i`` (rows) sees key ``j`` (columns)."""
    back = jnp.arange(t)[:, None] - jnp.arange(t)[None, :]
    return (back >= 0) if window is None else (back >= 0) & (back < window)


def attention(x, p, cfg, kind):
    """x [B, T, h] (normed) → [B, T, h]; ``kind`` the published layer type."""
    b, t, _ = x.shape
    heads, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    local = kind == "sliding_attention"
    q = rms_norm((x @ p["w_q"]).reshape(b, t, heads, d), p["q_norm"], cfg["rms_norm_eps"])
    k = rms_norm((x @ p["w_k"]).reshape(b, t, kv, d), p["k_norm"], cfg["rms_norm_eps"])
    v = (x @ p["w_v"]).reshape(b, t, kv, d)
    gate = jax.nn.sigmoid(x @ p["w_gate"])
    if local:
        q, k = rotary(q, cfg["rope_theta"]), rotary(k, cfg["rope_theta"])
    k, v = jnp.repeat(k, heads // kv, axis=2), jnp.repeat(v, heads // kv, axis=2)
    mask = visible(t, cfg["sliding_window"] if local else None)

    @jax.checkpoint
    def block(q_blk, mask_blk):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) / math.sqrt(d)
        scores = jnp.where(mask_blk, scores, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)

    out = [block(q[:, a:a + QUERY_BLOCK], mask[a:a + QUERY_BLOCK]) for a in range(0, t, QUERY_BLOCK)]
    return (jnp.concatenate(out, axis=1).reshape(b, t, heads * d) * gate) @ p["w_o"]


# --------------------------------------------------------------- experts


def scores(x, router):
    """Every expert's unbiased score: x [N, h] → [N, experts]."""
    return jax.nn.sigmoid(x @ router)


def route(x, router, bias, cfg):
    """→ (experts [N, k], weights [N, k])."""
    s = scores(x, router)
    _, top_e = jax.lax.top_k(s + bias, cfg["num_experts_per_tok"])
    picked = jnp.take_along_axis(s, top_e, axis=-1)
    weights = picked / (jnp.sum(picked, axis=-1, keepdims=True) + 1e-20)
    return top_e, weights * cfg["route_scale"]


def routed(x, p, bias, cfg, held):
    """The held experts' part: x [B, T, h] (normed) → [B, T, h]."""
    first, count = held
    flat = x.reshape(-1, x.shape[-1])
    top_e, w = route(flat, p["router"], bias, cfg)
    y = jnp.zeros_like(flat)
    for e in range(count):
        weight = jnp.sum(jnp.where(top_e == first + e, w, 0.0), axis=-1)  # 0 where not routed here
        y = y + weight[:, None].astype(flat.dtype) * swiglu(flat, p["w_gate"][e], p["w_up"][e], p["w_down"][e])
    return y.reshape(x.shape)


def shared(x, p):
    """The shared expert: on every token, no gate."""
    return swiglu(x, p["w_gate"], p["w_up"], p["w_down"])


def moe(x, p, bias, cfg, held):
    """The sparse feed-forward before its output norm: x [B, T, h] (normed)."""
    return routed(x, p, bias, cfg, held) + shared(x, p["shared"])


# ----------------------------------------------------------------- model


def layer(x, lp, buffers, kind, cfg, held):
    """``kind`` the published layer type; a layer whose weights hold ``mlp``
    is dense."""
    eps = cfg["rms_norm_eps"]
    mixed = attention(rms_norm(x, lp["norm1"], eps), lp[KINDS[kind]], cfg, kind)
    x = x + rms_norm(mixed, lp["norm1_out"], eps)
    y = rms_norm(x, lp["norm2"], eps)
    if "mlp" in lp:
        out = swiglu(y, lp["mlp"]["w_gate"], lp["mlp"]["w_up"], lp["mlp"]["w_down"])
    else:
        out = moe(y, lp["moe"], buffers["expert_bias"], cfg, held)
    return x + rms_norm(out, lp["norm2_out"], eps)


def lm_logits(params, ids, *, cfg: dict, held, dtype=jnp.float32):
    """ids [B, T] → logits [B, T, vocab held]."""
    params = jax.tree.map(lambda a: jnp.asarray(a, dtype), params)
    x = params["embed"][ids]
    if cfg["mup_enabled"]:
        x = x * jnp.asarray(math.sqrt(cfg["hidden_size"]), dtype)
    for lp, buffers, kind in zip(params["layers"], params["buffers"]["layers"], cfg["layer_types"], strict=True):
        x = jax.checkpoint(lambda x, lp, buffers, kind=kind: layer(x, lp, buffers, kind, cfg, held))(x, lp, buffers)
    return rms_norm(x, params["final_norm"], cfg["rms_norm_eps"]) @ params["head"]


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
