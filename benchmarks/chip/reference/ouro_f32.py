"""``ouro`` (Ouro-2.6B) looped causal LM in plain float32 ``jax.numpy``: the
reference for ``lakesoul_tpu/models/ouro.py``, and the one copy of it (the
tests load this file by path).

Written from the published ``config.json`` of Ouro-2.6B and the paper
("Scaling Latent Reasoning via Looped Language Models", arXiv:2510.25741) and,
where both are silent, the family's convention (each such reading is marked
*assumed* here, is a function of its own below, and is listed under
``assumed`` in ``configs/ouro_2_6b_clm_pk.json``), over the parameter tree the
program trains (``init_lm_params``).  It imports nothing from
``lakesoul_tpu``.  The caller runs it under
``jax.default_matmul_precision("highest")``; on a TPU a float32 product is
otherwise rounded to bfloat16.

With ``R = total_ut_steps`` and ``N`` a plain RMS norm (``rms_norm_eps``):

- **Embedding**: ``x^(0) = Emb[ids]``, no scale.
- **One pass**: a plain Python loop over the layers, the SAME weights in every
  pass; a layer has four norms (*assumed*, :func:`layer`): ``h = x +
  N2(Attn(N1(x)))``, ``x' = h + N4(FFN(N3(h)))``.
- **Attention**, y the layer's normed input: ``q, k, v = y W_q, y W_k, y W_v``
  as heads of ``head_dim``; no norm over a head (*assumed*,
  :func:`head_operand`); q and k rotated over the whole head (rotate-half,
  ``rope_theta``); every query head has its own copy of its group's keys and
  values; scores over ``sqrt(head_dim)``, the causal mask one whole ``[T, T]``
  comparison of positions taken a block of query rows at a time, softmax,
  ``W_o``.  No gate, no bias.
- **Feed-forward**: ``(silu(y W_gate) * (y W_up)) W_down``.
- **Between passes**: ``z^(t) = N_f(x after pass t)``, one ``final_norm``;
  pass ``t + 1`` starts from ``z^(t)`` (*assumed*, :func:`carried`), and the
  head reads it: ``logits^(t) = z^(t) W_head``.
- **Exit gate** (*assumed*: the paper's section 3, :func:`exit_distribution`):
  ``lambda^(t) = sigmoid(z^(t) . w_exit + b_exit)``; ``S^(0) = 1``, ``S^(t) =
  S^(t-1) (1 - lambda^(t))``; ``p(t) = lambda^(t) S^(t-1)`` for ``t < R`` and
  ``p(R) = S^(R-1)``.
- **Loss** (:func:`objective`): ``mean_i [ sum_t p_i(t) nll_i^(t) - beta
  H(p_i) ]`` with ``H(p) = -sum_t p(t) log p(t)``, over the positions with
  ``labels >= 0``; ``beta`` 0.05 (*assumed*); gradients by ``jax.grad`` of
  that.

``untied``, for the tests: ``R`` dicts ``{"layers", "final_norm"}``, pass
``t``'s own copies in place of the shared weights (the gradient of a shared
leaf is the sum of its copies' gradients).  ``dtype`` exists to show what a
lower precision does to the numbers (the precision control computes all of
this in ``bfloat16``).

Departures.  From the published model, shared with the program: the gate's
second training stage (the stack frozen) and inference-time exit at
``early_exit_threshold`` are not modelled; no document boundaries; the rotary
pairing is rotate-half (a fixed permutation of ``W_q``'s and ``W_k``'s columns
under seeded weights).  From a literal "no remat" reference: each layer of
each pass, each block of query rows and each pass's head are rematerialised
(``jax.checkpoint``), which changes no arithmetic; without it the softmax
weights of one 8,192-token row alone are 4.3 GB a layer and pass in the
backward pass, and the four passes' logits 6.4 GB.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
BETA = 0.05  # the entropy term's weight: *assumed*, not in the published config


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


def head_operand(a, cfg):
    """A query's or a key's heads [B, T, H, D] before the rotary: as the
    product leaves them.  *Assumed*: no norm over a head (the config names
    none)."""
    del cfg
    return a


def attention(x, p, cfg):
    """x [B, T, h] (normed) → [B, T, h]."""
    b, t, _ = x.shape
    heads, kv, d = cfg["num_attention_heads"], cfg["num_key_value_heads"], cfg["head_dim"]
    q = rotary(head_operand((x @ p["w_q"]).reshape(b, t, heads, d), cfg), cfg["rope_theta"])
    k = rotary(head_operand((x @ p["w_k"]).reshape(b, t, kv, d), cfg), cfg["rope_theta"])
    v = (x @ p["w_v"]).reshape(b, t, kv, d)
    k, v = jnp.repeat(k, heads // kv, axis=2), jnp.repeat(v, heads // kv, axis=2)
    mask = jnp.arange(t)[:, None] >= jnp.arange(t)[None, :]  # [T, T]: query i (rows) sees key j (columns)

    @jax.checkpoint
    def block(q_blk, mask_blk):
        scores = jnp.einsum("bqhd,bkhd->bhqk", q_blk, k) / math.sqrt(d)
        scores = jnp.where(mask_blk, scores, -1e30)
        return jnp.einsum("bhqk,bkhd->bqhd", jax.nn.softmax(scores, axis=-1), v)

    out = [block(q[:, a:a + QUERY_BLOCK], mask[a:a + QUERY_BLOCK]) for a in range(0, t, QUERY_BLOCK)]
    return jnp.concatenate(out, axis=1).reshape(b, t, heads * d) @ p["w_o"]


# ----------------------------------------------------------------- model


def layer(x, lp, cfg):
    """*Assumed*: four norms a layer, each sublayer's input and output."""
    eps = cfg["rms_norm_eps"]
    x = x + rms_norm(attention(rms_norm(x, lp["norm1"], eps), lp["attn"], cfg), lp["norm1_out"], eps)
    mlp = lp["mlp"]
    out = swiglu(rms_norm(x, lp["norm2"], eps), mlp["w_gate"], mlp["w_up"], mlp["w_down"])
    return x + rms_norm(out, lp["norm2_out"], eps)


def carried(x, z):
    """What the next pass starts from, of a pass's output ``x`` and its normed
    form ``z``.  *Assumed*: the normed state (the norm is inside the loop)."""
    del x
    return z


def lm_states(params, ids, *, cfg: dict, untied=None):
    """ids [B, T] → the ``R`` normed states ``z^(t)`` [B, T, h], a list."""
    x = params["embed"][ids]
    states = []
    for t in range(cfg["total_ut_steps"]):
        own = params if untied is None else untied[t]
        for lp in own["layers"]:
            x = jax.checkpoint(lambda x, lp: layer(x, lp, cfg))(x, lp)
        z = rms_norm(x, own["final_norm"], cfg["rms_norm_eps"])
        states.append(z)
        x = carried(x, z)
    return states


def exit_distribution(lam):
    """The gate's ``lambda`` of the passes before the last, a list of ``R -
    1`` arrays [...] → ``p(t)``, a list of ``R``: ``lambda^(t) S^(t-1)``, and
    what survives every gate exits after the last pass."""
    survived = jnp.ones_like(lam[0]) if lam else 1.0
    p = []
    for gate in lam:
        p.append(gate * survived)
        survived = survived * (1.0 - gate)
    return p + [survived]


def objective(p, nll, valid, beta):
    """``p``, ``nll``: ``R`` arrays [B, T] each → the loss: the expected NLL
    less ``beta`` times the entropy of ``p``, mean over ``valid``."""
    expected = sum(p_t * nll_t for p_t, nll_t in zip(p, nll, strict=True))
    entropy = -sum(p_t * jnp.log(jnp.maximum(p_t, jnp.finfo(jnp.float32).tiny)) for p_t in p)
    return jnp.sum(jnp.where(valid, expected - beta * entropy, 0.0)) / jnp.maximum(jnp.sum(valid), 1)


def lm_loss(params, ids, labels, *, cfg: dict, beta: float = BETA, dtype=jnp.float32, logits_at=None, untied=None):
    """The looped objective; with ``logits_at`` (positions along T) → (loss,
    ``{"loss_pass": [R], "exit_mass": [R], "logits": [R, B, len, vocab]}``:
    each pass's mean NLL, the mean ``p(t)``, every pass's logits there)."""
    params = jax.tree.map(lambda a: jnp.asarray(a, dtype), params)
    if untied is not None:
        untied = jax.tree.map(lambda a: jnp.asarray(a, dtype), untied)
    states = lm_states(params, ids, cfg=cfg, untied=untied)
    valid = labels >= 0
    count = jnp.maximum(jnp.sum(valid), 1)

    @jax.checkpoint
    def head(z, w):
        logits = z @ w
        logp = jax.nn.log_softmax(logits, axis=-1)
        picked = jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
        return -picked, (None if logits_at is None else logits[:, logits_at])

    nll, logits = zip(*(head(z, params["head"]) for z in states), strict=True)
    gate = params["exit"]
    p = exit_distribution([jax.nn.sigmoid(jnp.sum(z * gate["w"], axis=-1) + gate["b"]) for z in states[:-1]])
    loss = objective(p, nll, valid, beta)
    if logits_at is None:
        return loss
    mean = lambda a: jnp.sum(jnp.where(valid, a, 0.0)) / count  # noqa: E731
    return loss, {
        "loss_pass": jnp.stack([mean(a) for a in nll]), "exit_mass": jnp.stack([mean(a) for a in p]),
        "logits": jnp.stack(logits),
    }


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
