"""``glm4_moe_lite`` (GLM-4.7-Flash) causal LM in plain float32 ``jax.numpy``:
the reference for ``lakesoul_tpu/models/glm4_moe_lite.py``, and the one copy of
it (the tests load this file by path).

Written from the published ``config.json`` of GLM-4.7-Flash and, for the
prediction module, the DeepSeek-V3 report (arXiv:2412.19437, section 2.2),
which the family follows, over the parameter tree the program trains
(``init_lm_params``).  It imports nothing from ``lakesoul_tpu``.  The caller
runs it under ``jax.default_matmul_precision("highest")``; on a TPU a float32
product is otherwise rounded to bfloat16.

- **Latent attention**, x the layer's normed input: ``c_q = RMSNorm(x W_dq)``;
  per head ``[q_nope | q_rope] = c_q W_uq``; ``[c_kv | k_r] = x W_dkv``, ``c_kv
  = RMSNorm(c_kv)``; per head ``[k_nope | v] = c_kv W_ukv``; ``q_rope`` and
  ``k_r`` rotated over all their channels (rotate-half), ``k_r`` one head that
  every head's key ends in; scores ``q_h k_h^T / sqrt(nope + rope)``, the full
  masked softmax of each head, a block of query rows at a time so that 8,192
  tokens fit; ``concat_h(P v_h) W_o``.  Unabsorbed; no head norms, no gate.
- **Experts**: a Python loop over the held experts, each applied to every
  token and weighted by the routing (0 where the token is not routed to it).
  Routing: ``s = sigmoid(y W_r)``, the top k of ``s + expert_bias``, weights
  ``s_picked / (sum(s_picked) + 1e-20) x routed_scaling_factor``.  Beside them
  the shared expert, a SwiGLU with no gate, on every token.  Layers below
  ``first_k_dense_replace`` take a dense SwiGLU.
- **Head and loss**: a final norm and an untied head over the held
  vocabulary; next-token cross-entropy, mean over the positions with
  ``labels >= 0``.
- **Multi-token prediction** (one module): ``h'_i = [RMSNorm_e(Emb(t_{i+1})) ;
  RMSNorm_h(RMSNorm_final(h_i))] W_eh``, one whole sparse layer with the
  module's own weights and bias, the module's own norm and the main head
  matrix; its logits at position i predict ``t_{i+2}``.  ``Emb(t_{i+1})`` reads
  ``labels`` (token 0 where a row has none: that position has no label two
  ahead either).  Loss = ``L_main + mtp_loss_weight x L_mtp``, ``L_mtp`` the
  mean over the positions that have a token two ahead.
- Gradients by ``jax.grad`` of that.  ``expert_bias`` (``params["buffers"]``)
  is a buffer: :func:`adamw_step` is handed the trained leaves only.

``held = (first, count)`` is the share of the experts the weights hold; what
the other experts would add is left out, as in the program.  ``dtype`` exists
to show what a lower precision does to the numbers (the precision control
computes all of this in ``bfloat16``).

Departures.  From the published model, shared with the program: the expert
bias has no update rule (the published config gives none); ``mtp_loss_weight``
is no published key (0.3, the first pre-training phase's value in the
DeepSeek-V3 and GLM-4.5 reports) and has no schedule; the hidden state that
feeds ``RMSNorm_h`` is taken after the main final norm, as the public
inference implementations feed it; inside the concatenation the embedding
comes first (a permutation of ``W_eh``'s rows under seeded weights); the
rotary pairing is rotate-half (a fixed permutation of ``W_uq``'s and
``W_dkv``'s columns under seeded weights); no document boundaries.  From a
literal "no remat" reference: each layer and each block of query rows is
rematerialised (``jax.checkpoint``), which changes no arithmetic; without it
the softmax weights of one row alone are 5.4 GB a layer in the backward pass.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512


def rms_norm(x, w, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * w


def silu(x):
    return x * jax.nn.sigmoid(x)


def swiglu(x, w_gate, w_up, w_down):
    return (silu(x @ w_gate) * (x @ w_up)) @ w_down


# ------------------------------------------------------- latent attention


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


def latent_qkv(x, p, cfg):
    """x [B, T, h] (normed) → per-head (q [B, T, H, nope + rope], k the same
    shape, v [B, T, H, v_head_dim]), rotated, unscaled."""
    b, t, _ = x.shape
    heads, nope, rope = cfg["num_attention_heads"], cfg["qk_nope_head_dim"], cfg["qk_rope_head_dim"]
    latent, eps, theta = cfg["kv_lora_rank"], cfg["rms_norm_eps"], cfg["rope_theta"]
    c_q = rms_norm(x @ p["w_dq"], p["q_norm"], eps)
    q = (c_q @ p["w_uq"]).reshape(b, t, heads, nope + rope)
    down = x @ p["w_dkv"]
    c_kv = rms_norm(down[..., :latent], p["kv_norm"], eps)
    kv = (c_kv @ p["w_ukv"]).reshape(b, t, heads, nope + cfg["v_head_dim"])
    k_r = rotary(down[:, :, None, latent:], theta)  # one head
    q = jnp.concatenate([q[..., :nope], rotary(q[..., nope:], theta)], axis=-1)
    k = jnp.concatenate([kv[..., :nope], jnp.repeat(k_r, heads, axis=2)], axis=-1)
    return q, k, kv[..., nope:]


def attention(x, p, cfg):
    """x [B, T, h] (normed) → [B, T, h]."""
    b, t, _ = x.shape
    q, k, v = latent_qkv(x, p, cfg)
    d = q.shape[-1]

    @jax.checkpoint
    def block(q_blk, first):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) / math.sqrt(d)
        pos = first + jnp.arange(q_blk.shape[1])
        scores = jnp.where(pos[:, None] >= jnp.arange(t)[None, :], scores, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)

    out = [block(q[:, first:first + QUERY_BLOCK], first) for first in range(0, t, QUERY_BLOCK)]
    return jnp.concatenate(out, axis=1).reshape(b, t, -1) @ p["w_o"]


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
    return top_e, weights * cfg["routed_scaling_factor"]


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
    """The expert every token takes; no gate."""
    return swiglu(x, p["w_gate"], p["w_up"], p["w_down"])


def moe(x, p, bias, cfg, held):
    return routed(x, p, bias, cfg, held) + shared(x, p["shared"])


# ----------------------------------------------------------------- model


def layer(x, lp, buffers, cfg, held):
    eps = cfg["rms_norm_eps"]
    x = x + attention(rms_norm(x, lp["norm1"], eps), lp["mla"], cfg)
    y = rms_norm(x, lp["norm2"], eps)
    if "mlp" in lp:
        return x + swiglu(y, lp["mlp"]["w_gate"], lp["mlp"]["w_up"], lp["mlp"]["w_down"])
    return x + moe(y, lp["moe"], buffers["expert_bias"], cfg, held)


def _layer(x, lp, buffers, cfg, held):
    return jax.checkpoint(lambda x, lp, buffers: layer(x, lp, buffers, cfg, held))(x, lp, buffers)


def lm_hidden(params, ids, *, cfg: dict, held):
    """ids [B, T] → the main stack's hidden states before the final norm."""
    x = params["embed"][ids]
    for lp, buffers in zip(params["layers"], params["buffers"]["layers"], strict=True):
        x = _layer(x, lp, buffers, cfg, held)
    return x


def mtp_hidden(params, x, labels, *, cfg: dict, held):
    """The prediction module's hidden states before its head norm: ``x`` the
    main stack's (before the final norm), ``labels`` the next tokens."""
    p, eps = params["mtp"], cfg["rms_norm_eps"]
    e = rms_norm(params["embed"][jnp.maximum(labels, 0)], p["enorm"], eps)
    h = rms_norm(rms_norm(x, params["final_norm"], eps), p["hnorm"], eps)
    return _layer(jnp.concatenate([e, h], axis=-1) @ p["eh_proj"], p["layer"], params["buffers"]["mtp"], cfg, held)


def _nll(logits, labels):
    valid = labels >= 0
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
    return -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.maximum(jnp.sum(valid), 1)


def lm_logits(params, ids, labels=None, *, cfg: dict, held, dtype=jnp.float32):
    """ids [B, T] → the main head's logits [B, T, vocab held]; with ``labels``
    (the next tokens) and a module in ``params`` → (main, the module's)."""
    params = jax.tree.map(lambda a: jnp.asarray(a, dtype), params)
    eps = cfg["rms_norm_eps"]
    x = lm_hidden(params, ids, cfg=cfg, held=held)
    main = rms_norm(x, params["final_norm"], eps) @ params["head"]
    if labels is None or "mtp" not in params:
        return main
    h = mtp_hidden(params, x, labels, cfg=cfg, held=held)
    return main, rms_norm(h, params["mtp"]["shared_head_norm"], eps) @ params["head"]


def lm_loss(params, ids, labels, *, cfg: dict, held, dtype=jnp.float32, logits_at=None):
    """``L_main + mtp_loss_weight x L_mtp`` (``L_main`` alone without a
    module); with ``logits_at`` (positions along T) → (loss, {"loss_main",
    "loss_mtp", "logits", "logits_mtp"}), the logits [B, len, vocab]."""
    out = lm_logits(params, ids, labels, cfg=cfg, held=held, dtype=dtype)
    if "mtp" in params:
        main, second = out
        after_next = jnp.concatenate([labels[:, 1:], jnp.full_like(labels[:, :1], -100)], axis=1)
        terms = {"loss_main": _nll(main, labels), "loss_mtp": _nll(second, after_next)}
        loss = terms["loss_main"] + cfg["mtp_loss_weight"] * terms["loss_mtp"]
    else:
        main, second = out, None
        loss = _nll(main, labels)
        terms = {"loss_main": loss}
    if logits_at is None:
        return loss
    terms["logits"] = main[:, logits_at]
    if second is not None:
        terms["logits_mtp"] = second[:, logits_at]
    return loss, terms


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
