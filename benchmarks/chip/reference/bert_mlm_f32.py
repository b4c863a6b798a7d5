"""BERT masked-LM loss in plain float32 ``jax.numpy``: the trainer cells'
model reference.

Written from Devlin et al. 2018 (arXiv:1810.04805, section 3 and appendix A)
and Vaswani et al. 2017 for the encoder block, over the parameter tree the
program trains (``lakesoul_tpu/models/bert.py: init_bert_params``).  No
``lax.scan``, no bfloat16, no sharding, no kernels: a Python loop over the
layers and explicit matrix products.  The caller runs it under
``jax.default_matmul_precision("highest")``; on a TPU a float32 product is
otherwise rounded to bfloat16.

The program departs from the published model, and the reference follows it,
because the comparison is of arithmetic, not of architecture:

- layer norm comes before each sub-layer (pre-LN), not after it, with one more
  layer norm in front of the output head; epsilon is 1e-6, not 1e-12;
- no segment (token type) embedding, since the rows are single sequences;
- no bias on the query, key, value and output projections;
- the head is the tied embedding matrix and a bias, without BERT's dense
  transform and GELU in front;
- GELU is the tanh approximation (``jax.nn.gelu``'s default).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp


def _layer_norm(x, scale, bias, eps=1e-6):
    mean = jnp.mean(x, axis=-1, keepdims=True)
    var = jnp.mean((x - mean) ** 2, axis=-1, keepdims=True)
    return (x - mean) / jnp.sqrt(var + eps) * scale + bias


def _gelu_tanh(x):
    return 0.5 * x * (1.0 + jnp.tanh(math.sqrt(2.0 / math.pi) * (x + 0.044715 * x**3)))


def mlm_loss(params: dict, ids, labels, mask, *, heads: int) -> jax.Array:
    """Mean negative log-likelihood over the positions with ``labels >= 0``."""
    f32 = lambda t: jnp.asarray(t, jnp.float32)  # noqa: E731
    batch, seq = ids.shape
    hidden = params["tok_emb"].shape[1]
    head_dim = hidden // heads
    x = f32(params["tok_emb"])[ids] + f32(params["pos_emb"])[:seq][None]
    x = _layer_norm(x, f32(params["emb_ln"]["scale"]), f32(params["emb_ln"]["bias"]))
    key_ok = jnp.asarray(mask, bool)[:, None, None, :]
    layers = params["layers"]
    for i in range(layers["wq"].shape[0]):
        lp = jax.tree.map(lambda t: f32(t[i]), layers)
        y = _layer_norm(x, lp["ln1"]["scale"], lp["ln1"]["bias"])

        def split(t):
            return t.reshape(batch, seq, heads, head_dim).transpose(0, 2, 1, 3)

        q, k, v = split(y @ lp["wq"]), split(y @ lp["wk"]), split(y @ lp["wv"])
        scores = q @ k.transpose(0, 1, 3, 2) / math.sqrt(head_dim)
        scores = jnp.where(key_ok, scores, -1e30)
        attn = jax.nn.softmax(scores, axis=-1) @ v
        x = x + attn.transpose(0, 2, 1, 3).reshape(batch, seq, hidden) @ lp["wo"]
        y = _layer_norm(x, lp["ln2"]["scale"], lp["ln2"]["bias"])
        x = x + _gelu_tanh(y @ lp["w1"] + lp["b1"]) @ lp["w2"] + lp["b2"]
    x = _layer_norm(x, f32(params["mlm_ln"]["scale"]), f32(params["mlm_ln"]["bias"]))
    logits = x @ f32(params["tok_emb"]).T + f32(params["mlm_bias"])
    valid = labels >= 0
    logp = jax.nn.log_softmax(logits, axis=-1)
    picked = jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
    return -jnp.sum(jnp.where(valid, picked, 0.0)) / jnp.maximum(jnp.sum(valid), 1)
