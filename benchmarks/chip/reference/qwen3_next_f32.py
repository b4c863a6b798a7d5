"""Qwen3-Next-style hybrid causal LM in plain float32 ``jax.numpy``: the
reference for ``lakesoul_tpu/models/qwen3_next.py``.

Written from the published ``config.json`` of Qwen3-Next-80B-A3B-Instruct and
the family's public modelling code, over the parameter tree the program trains
(``init_lm_params``).  It imports nothing from ``lakesoul_tpu``.  The caller
runs it under ``jax.default_matmul_precision("highest")``; on a TPU a float32
product is otherwise rounded to bfloat16.

- **Gated DeltaNet**: the recurrence token by token under ``lax.scan``, no
  chunks: ``S' = exp(g_t) S``, ``u_t = beta_t (v_t - S'^T k_t)``,
  ``S = S' + k_t u_t^T``, ``o_t = S^T q_t``.
- **Gated attention**: one masked softmax per block of queries over the keys
  up to the block's last position.
- **Experts**: a loop over the held experts, each applied to every token and
  masked by the routing; the shared expert under its sigmoid gate.
- Loss: next-token cross-entropy over the held vocabulary, mean over the
  positions with ``labels >= 0``; gradients by ``jax.grad`` of that.

``held = (first, count)`` is the share of the experts the weights hold; what
the other experts would add is left out, as in the program.  So that the
gradients fit beside a training state at the published widths, each layer,
each block of queries and each block of 128 tokens of the recurrence is
rematerialised (``jax.checkpoint``); that changes no arithmetic.  ``dtype``
exists to show what a lower precision does to the numbers (the tolerances of
the chip benchmark were set between float32 and ``bfloat16`` readings).

Departures from the published model, shared with the program: no multi-token
prediction, no router auxiliary loss, no document boundaries; ``in_proj_qkvz``
columns are ``[q | k | v | z]`` by kind, not interleaved per key head.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
TOKEN_BLOCK = 128


def layer_kinds(cfg: dict) -> list[str]:
    return ["attn" if (i + 1) % cfg["full_attention_interval"] == 0 else "gdn"
            for i in range(cfg["num_hidden_layers"])]


def rms_norm(x, w, eps, centred=True):
    y = x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps)
    return y * ((1.0 + w) if centred else w)


def silu(x):
    return x * jax.nn.sigmoid(x)


# ------------------------------------------------------------ Gated DeltaNet


def delta_rule(q, k, v, g, beta):
    """Token by token.  q, k [T, dk], v [T, dv], g, beta [T] → o [T, dv]."""
    t = q.shape[0]
    block = math.gcd(t, TOKEN_BLOCK)

    def token(state, xs):
        # products written out as sums: elementwise float32 on any backend, and
        # no matrix unit in a loop of 8,192 dependent steps
        q_t, k_t, v_t, g_t, b_t = xs
        state = jnp.exp(g_t) * state
        u_t = b_t * (v_t - jnp.sum(state * k_t[:, None], axis=0))
        state = state + k_t[:, None] * u_t[None, :]
        return state, jnp.sum(state * q_t[:, None], axis=0)

    @jax.checkpoint
    def tokens(state, xs):
        return jax.lax.scan(token, state, xs)

    xs = jax.tree.map(lambda a: a.reshape(t // block, block, *a.shape[1:]), (q, k, v, g, beta))
    _, o = jax.lax.scan(tokens, jnp.zeros((q.shape[1], v.shape[1]), q.dtype), xs)
    return o.reshape(t, -1)


def gated_delta_net(x, p, cfg):
    """x [B, T, h] (normed) → [B, T, h]."""
    b, t, _ = x.shape
    hk, hv = cfg["linear_num_key_heads"], cfg["linear_num_value_heads"]
    dk, dv = cfg["linear_key_head_dim"], cfg["linear_value_head_dim"]
    key_dim, value_dim = hk * dk, hv * dv
    qkvz = x @ p["w_qkvz"]
    ba = x @ p["w_ba"]
    conv_in = qkvz[..., : 2 * key_dim + value_dim]
    taps = p["conv"].shape[1]
    padded = jnp.pad(conv_in, ((0, 0), (taps - 1, 0), (0, 0)))
    qkv = silu(sum(padded[:, j:j + t] * p["conv"][:, j] for j in range(taps)))
    z = qkvz[..., 2 * key_dim + value_dim:].reshape(b, t, hv, dv)
    q = qkv[..., :key_dim].reshape(b, t, hk, dk)
    k = qkv[..., key_dim: 2 * key_dim].reshape(b, t, hk, dk)
    v = qkv[..., 2 * key_dim:].reshape(b, t, hv, dv)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) / math.sqrt(dk)
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    q, k = jnp.repeat(q, hv // hk, axis=2), jnp.repeat(k, hv // hk, axis=2)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["A_log"]) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])
    per_head = jax.vmap(jax.vmap(delta_rule, in_axes=1, out_axes=1))  # over rows, then heads
    o = per_head(q, k, v, g, beta)  # [B, T, hv, dv]
    o = rms_norm(o, p["norm"], cfg["rms_norm_eps"], centred=False) * silu(z)
    return o.reshape(b, t, value_dim) @ p["w_o"]


# ----------------------------------------------------------- gated attention


def rotary(x, rotary_dim, theta):
    """x [B, T, H, D]; rotate-half on the first ``rotary_dim`` channels."""
    t = x.shape[1]
    half = rotary_dim // 2
    inv_freq = theta ** (-jnp.arange(half, dtype=jnp.float32) * 2.0 / rotary_dim)
    angle = jnp.arange(t, dtype=jnp.float32)[:, None] * inv_freq
    cos = jnp.cos(angle).astype(x.dtype)[:, None, :]
    sin = jnp.sin(angle).astype(x.dtype)[:, None, :]
    x1, x2, rest = x[..., :half], x[..., half:rotary_dim], x[..., rotary_dim:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin, rest], axis=-1)


def gated_attention(x, p, cfg):
    """x [B, T, h] (normed) → [B, T, h]."""
    b, t, _ = x.shape
    heads, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    qg = (x @ p["w_q"]).reshape(b, t, heads, 2 * d)
    q, gate = qg[..., :d], qg[..., d:]
    k = (x @ p["w_k"]).reshape(b, t, kv, d)
    v = (x @ p["w_v"]).reshape(b, t, kv, d)
    rotary_dim = int(d * cfg["partial_rotary_factor"])
    q = rotary(rms_norm(q, p["q_norm"], cfg["rms_norm_eps"]), rotary_dim, cfg["rope_theta"])
    k = rotary(rms_norm(k, p["k_norm"], cfg["rms_norm_eps"]), rotary_dim, cfg["rope_theta"])
    k, v = jnp.repeat(k, heads // kv, axis=2), jnp.repeat(v, heads // kv, axis=2)

    @jax.checkpoint
    def block(q_blk, k_seen, v_seen, first):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k_seen) / math.sqrt(d)
        pos = first + jnp.arange(q_blk.shape[1])
        scores = jnp.where(pos[:, None] >= jnp.arange(k_seen.shape[1])[None, :], scores, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v_seen)

    out = []
    for first in range(0, t, QUERY_BLOCK):
        last = min(first + QUERY_BLOCK, t)
        out.append(block(q[:, first:last], k[:, :last], v[:, :last], first))
    attn = jnp.concatenate(out, axis=1) * jax.nn.sigmoid(gate)
    return attn.reshape(b, t, heads * d) @ p["w_o"]


# ------------------------------------------------------------------- experts


def route(x, router, top_k):
    probs = jax.nn.softmax(x @ router, axis=-1)
    top_p, top_e = jax.lax.top_k(probs, top_k)
    return top_e, top_p / jnp.sum(top_p, axis=-1, keepdims=True)


def swiglu(x, w_gate, w_up, w_down):
    return (silu(x @ w_gate) * (x @ w_up)) @ w_down


def routed_experts(x, p, cfg, held):
    """The held experts' part: x [N, h] → [N, h]."""
    first, _ = held
    top_e, w = route(x, p["router"], cfg["num_experts_per_tok"])

    def one(y, xs):
        e, w_gate, w_up, w_down = xs
        weight = jnp.sum(jnp.where(top_e == first + e, w, 0.0), axis=-1)  # 0 where not routed here
        return y + weight[:, None] * swiglu(x, w_gate, w_up, w_down), None

    experts = jnp.arange(p["w_gate"].shape[0])
    y, _ = jax.lax.scan(one, jnp.zeros_like(x), (experts, p["w_gate"], p["w_up"], p["w_down"]))
    return y


def shared_expert(x, p):
    return jax.nn.sigmoid(x @ p["gate"])[..., None] * swiglu(x, p["w_gate"], p["w_up"], p["w_down"])


def moe(x, p, cfg, held):
    """x [B, T, h] (normed) → [B, T, h]."""
    flat = x.reshape(-1, x.shape[-1])
    return (routed_experts(flat, p, cfg, held) + shared_expert(flat, p["shared"])).reshape(x.shape)


# --------------------------------------------------------------------- model


def layer(x, lp, kind, cfg, held):
    y = rms_norm(x, lp["norm1"], cfg["rms_norm_eps"])
    x = x + (gated_delta_net(y, lp["gdn"], cfg) if kind == "gdn" else gated_attention(y, lp["attn"], cfg))
    return x + moe(rms_norm(x, lp["norm2"], cfg["rms_norm_eps"]), lp["moe"], cfg, held)


def lm_logits(params, ids, *, cfg: dict, held, dtype=jnp.float32):
    """ids [B, T] → logits [B, T, vocab held]."""
    params = jax.tree.map(lambda a: jnp.asarray(a, dtype), params)
    x = params["embed"][ids]
    for lp, kind in zip(params["layers"], layer_kinds(cfg)):
        x = jax.checkpoint(lambda x, lp, kind=kind: layer(x, lp, kind, cfg, held))(x, lp)
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
    decay added to the update: → (params, mu, nu)."""
    count = count + 1
    mu = jax.tree.map(lambda m, g: b1 * m + (1 - b1) * g, mu, grads)
    nu = jax.tree.map(lambda v, g: b2 * v + (1 - b2) * g * g, nu, grads)
    def update(p, m, v):
        m_hat, v_hat = m / (1 - b1**count), v / (1 - b2**count)
        return p - lr * (m_hat / (jnp.sqrt(v_hat) + eps) + weight_decay * p)
    return jax.tree.map(update, params, mu, nu), mu, nu
