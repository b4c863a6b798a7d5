"""Phi-4-mini-flash-style decoder-hybrid-decoder causal LM (SambaY,
arXiv:2507.06607, with differential attention, arXiv:2410.05258) in plain
float32 ``jax.numpy``: the reference for ``lakesoul_tpu/models/phi4flash.py``,
and the one copy of it (the tests load this file by path).

Written from the published ``config.json`` of Phi-4-mini-flash-reasoning
(``model_type`` ``phi4flash``), the two papers and the Mamba paper
(arXiv:2312.00752), over the parameter tree the program trains
(``init_lm_params``).  It imports nothing from ``lakesoul_tpu``.  The caller
runs it under ``jax.default_matmul_precision("highest")``; on a TPU a float32
product is otherwise rounded to bfloat16.

By published layer index ``i`` of ``n = num_hidden_layers`` (``cfg
["layers_held"]`` lists the indices the weights hold, in order):

- ``i < n/2``: even Mamba-1, odd differential attention under
  ``sliding_window`` (a query sees its own position and the ``window - 1``
  before it);
- ``i = n/2``: Mamba-1, whose scan output ``y`` (before the gate) is kept as
  the memory ``m``; ``i = n/2 + 1``: differential attention, full causal,
  whose ``k``, ``v`` are kept;
- ``i >= n/2 + 2``: even a gated memory unit over ``m``, odd differential
  cross-attention (its own ``q`` alone) over the kept ``k``, ``v``, full
  causal.

``h = x + mixer(LN1(x)); x' = h + MLP(LN2(h))``, LayerNorm with weight and
bias; a final LayerNorm; logits through the embedding (tied).

- **Mamba-1**: ``[u, z] = x W_in``; ``u = silu(conv(u) + b_conv)``, the
  depthwise causal convolution written out as its shifted sums; ``[delta, B,
  C] = u W_x``; ``Delta = softplus(delta W_dt + b_dt)``; ``A = -exp(A_log)``;
  ``s_t = exp(Delta_t A) s_{t-1} + (Delta_t u_t) B_t``, ``y_t = s_t C_t + D
  u_t``, a ``lax.scan`` over tokens with the state [B, E, N]; ``out = (y
  silu(z)) W_out``.
- **GMU**: ``(m silu(x W_1)) W_2``.
- **Differential attention**: ``q = x W_q + b_q``, ``k``, ``v`` likewise; no
  positions, no head norm.  Query pair ``p`` is heads ``2p, 2p+1``; key-value
  pair ``g`` keys ``2g, 2g+1`` and ``V = [v_2g ; v_2g+1]``; pair ``p`` reads
  ``g = p // (pairs a key-value pair)``.  Whole ``[T, T]`` masked softmaxes
  ``A1``, ``A2``, a block of query rows at a time; ``o_p = (A1 - lambda A2)
  V``; ``RMSNorm_2d(o_p; g_sub, 1e-5) (1 - lambda_init)``; ``W_o``, ``b_o``.
  ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``, ``lambda_init =
  0.8 - 0.6 exp(-0.3 i)`` by the PUBLISHED index, computed here from the
  index (the program's buffer is not read).
- **MLP**: ``(silu(x W_gate) (x W_up)) W_down``, ``fc1``'s two halves.
- Loss: next-token cross-entropy over the held vocabulary, mean over the
  positions with ``labels >= 0``; gradients by ``jax.grad`` of that.

``dtype`` exists to show what a lower precision does to the numbers (the
precision control computes all of this in ``bfloat16``).

Departures.  From the published model, shared with the program: no document
boundaries (neither the scan's state nor a mask restarts inside a row); no
serving form (prefill that skips the second decoder, decoding against one
layer's cache); ``fc1`` held as two matrices.  From the issue that asked for
this file ("no checkpoint"): each layer, each block of query rows and each
block of 128 tokens of the scan is rematerialised (``jax.checkpoint``), which
changes no arithmetic; without it one Mamba layer's states alone are 2.7 GB a
row in the backward pass and the softmax weights of one attention layer
10.7 GB, and the comparison could not run beside the weights at the
published widths.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

QUERY_BLOCK = 512
SCAN_BLOCK = 128
SUBLN_EPS = 1e-5


def layer_norm(x, p, eps):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * p["w"] + p["b"]


def silu(x):
    return x * jax.nn.sigmoid(x)


def swiglu(x, p):
    return (silu(x @ p["w_gate"]) * (x @ p["w_up"])) @ p["w_down"]


def layer_kind(i: int, n: int) -> str:
    """The mixer of published layer ``i`` of ``n``."""
    if i % 2 == 0:
        return "mamba" if i < n // 2 else "memory_source" if i == n // 2 else "gmu"
    return "window" if i < n // 2 else "kv_source" if i == n // 2 + 1 else "cross"


# ---------------------------------------------------------------- mixers


def mamba(x, p, cfg):
    """x [B, T, h] (normed) → (out [B, T, h], the scan's output y [B, T, E])."""
    bsz, t, _ = x.shape
    n, r = cfg["mamba_d_state"], cfg["mamba_dt_rank"]
    u, z = jnp.split(x @ p["w_in"], 2, axis=-1)
    taps = p["conv"].shape[1]
    padded = jnp.pad(u, ((0, 0), (taps - 1, 0), (0, 0)))
    u = silu(sum(padded[:, j:j + t] * p["conv"][:, j] for j in range(taps)) + p["b_conv"])
    dbc = u @ p["w_x"]
    delta = jax.nn.softplus(dbc[..., :r] @ p["w_dt"] + p["b_dt"])
    b_in, c_out = dbc[..., r:r + n], dbc[..., r + n:]
    a = -jnp.exp(p["A_log"])

    def token(s, xs):
        u_t, dt, b_t, c_t = xs
        s = jnp.exp(dt[..., None] * a) * s + (dt * u_t)[..., None] * b_t[:, None, :]
        return s, jnp.einsum("ben,bn->be", s, c_t) + p["D"] * u_t

    @jax.checkpoint
    def block(s, xs):
        return jax.lax.scan(token, s, xs)

    pad = -t % SCAN_BLOCK  # tokens of Delta = 0 leave the state as it is
    xs = tuple(
        jnp.moveaxis(jnp.pad(a_, ((0, 0), (0, pad), (0, 0))), 1, 0).reshape(-1, SCAN_BLOCK, bsz, a_.shape[-1])
        for a_ in (u, delta, b_in, c_out)
    )
    _, y = jax.lax.scan(block, jnp.zeros((bsz, *a.shape), x.dtype), xs)
    y = jnp.moveaxis(y.reshape(-1, bsz, u.shape[-1]), 0, 1)[:, :t]
    return (y * silu(z)) @ p["w_out"], y


def gmu(x, p, m):
    return (m * silu(x @ p["w_1"])) @ p["w_2"]


def keys_values(x, p):
    return x @ p["w_k"] + p["b_k"], x @ p["w_v"] + p["b_v"]


def differential_attention(x, p, kv, index: int, cfg, window=None):
    """x [B, T, h] (normed), ``kv`` the raw keys and values [B, T, kv heads x
    d] (this layer's or the source's) → [B, T, h]."""
    b, t, h = x.shape
    heads, kv_heads = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    d = h // heads
    pairs, kv_pairs = heads // 2, kv_heads // 2
    q = (x @ p["w_q"] + p["b_q"]).reshape(b, t, pairs, 2, d)
    k = kv[0].reshape(b, t, kv_pairs, 2, d)
    v = kv[1].reshape(b, t, kv_pairs, 2 * d)  # [v_2g ; v_2g+1]
    # every query pair its own copy of its key-value pair
    k, v = jnp.repeat(k, pairs // kv_pairs, axis=2), jnp.repeat(v, pairs // kv_pairs, axis=2)
    lam_init = 0.8 - 0.6 * math.exp(-0.3 * index)
    lam = jnp.exp(jnp.sum(p["lq1"] * p["lk1"])) - jnp.exp(jnp.sum(p["lq2"] * p["lk2"])) + lam_init

    @jax.checkpoint
    def block(q_blk, first):
        pos = first + jnp.arange(q_blk.shape[1])
        back = pos[:, None] - jnp.arange(t)[None, :]
        seen = back >= 0 if window is None else (back >= 0) & (back < window)
        maps = []
        for which in (0, 1):
            scores = jnp.einsum("bqpd,bkpd->bpqk", q_blk[:, :, :, which], k[:, :, :, which]) / math.sqrt(d)
            maps.append(jax.nn.softmax(jnp.where(seen, scores, -1e30), axis=-1))
        return jnp.einsum("bpqk,bkpe->bqpe", maps[0] - lam.astype(x.dtype) * maps[1], v)

    o = jnp.concatenate([block(q[:, first:first + QUERY_BLOCK], first) for first in range(0, t, QUERY_BLOCK)], axis=1)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + SUBLN_EPS) * p["g_sub"] * (1.0 - lam_init)
    return o.reshape(b, t, heads * d) @ p["w_o"] + p["b_o"]


# ----------------------------------------------------------------- model


def lm_logits(params, ids, *, cfg: dict, dtype=jnp.float32):
    """ids [B, T] → logits [B, T, vocab held]."""
    params = jax.tree.map(lambda a: jnp.asarray(a, dtype), {k: v for k, v in params.items() if k != "buffers"})
    eps, n = cfg["layer_norm_eps"], cfg["num_hidden_layers"]
    x = params["embed"][ids]
    memory = kept_kv = None
    for index, lp in zip(cfg["layers_held"], params["layers"], strict=True):
        kind = layer_kind(index, n)

        def mixed(x, lp, memory, kept_kv, kind=kind, index=index):
            y = layer_norm(x, lp["norm1"], eps)
            made = None
            if kind in ("mamba", "memory_source"):
                out, made = mamba(y, lp["ssm"], cfg)
            elif kind == "gmu":
                out = gmu(y, lp["gmu"], memory)
            elif kind == "window":
                out = differential_attention(y, lp["swa"], keys_values(y, lp["swa"]), index, cfg, cfg["sliding_window"])
            elif kind == "kv_source":
                made = keys_values(y, lp["attn"])
                out = differential_attention(y, lp["attn"], made, index, cfg)
            else:
                out = differential_attention(y, lp["xattn"], kept_kv, index, cfg)
            h = x + out
            return h + swiglu(layer_norm(h, lp["norm2"], eps), lp["mlp"]), made

        x, made = jax.checkpoint(mixed)(x, lp, memory, kept_kv)
        if kind == "memory_source":
            memory = made
        elif kind == "kv_source":
            kept_kv = made
    return layer_norm(x, params["final_norm"], eps) @ params["embed"].T


def lm_loss(params, ids, labels, *, cfg: dict, dtype=jnp.float32, logits_at=None):
    """Mean negative log-likelihood over the positions with ``labels >= 0``;
    with ``logits_at`` (positions along T) → (loss, logits [B, len, vocab])."""
    logits = lm_logits(params, ids, cfg=cfg, dtype=dtype)
    valid = labels >= 0
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
    loss = -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.maximum(jnp.sum(valid), 1)
    return loss if logits_at is None else (loss, logits[:, logits_at])
