"""BERT-style encoder for MLM training, written functionally (param pytrees +
pure apply fns) so sharding is explicit and pjit/GSPMD-friendly.

This is the flagship model the data plane feeds (BASELINE.json config 3:
C4 → BERT-base MLM).  Parallelism:

- dp: batch dimension
- tp: attention heads and FFN hidden sharded (Megatron-style column/row split;
  XLA inserts the psum for the row-parallel matmuls from sharding constraints)
- sp: sequence dimension via ring attention (lakesoul_tpu.parallel.ring_attention)

All matmuls run in bfloat16 with float32 accumulation (MXU-native).
"""

from __future__ import annotations

from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from lakesoul_tpu.models.head_loss import labelled_nll


@dataclass(frozen=True)
class BertConfig:
    vocab_size: int = 30522
    hidden: int = 768
    layers: int = 12
    heads: int = 12
    ff: int = 3072
    max_len: int = 512
    dtype: str = "bfloat16"

    @staticmethod
    def base() -> "BertConfig":
        return BertConfig()

    @staticmethod
    def tiny(vocab_size: int = 1024, max_len: int = 128) -> "BertConfig":
        return BertConfig(
            vocab_size=vocab_size, hidden=128, layers=2, heads=4, ff=256, max_len=max_len
        )

    @property
    def head_dim(self) -> int:
        return self.hidden // self.heads


def init_bert_params(cfg: BertConfig, key: jax.Array) -> dict:
    """Initialize a parameter pytree.  Layers are stacked on a leading axis so
    the encoder runs as one lax.scan (fast compile, XLA-friendly)."""
    k_emb, k_pos, k_layers, k_head = jax.random.split(key, 4)
    h, f, L = cfg.hidden, cfg.ff, cfg.layers
    std = 0.02

    def norm(key, shape):
        return (jax.random.normal(key, shape) * std).astype(jnp.float32)

    ks = jax.random.split(k_layers, 8)
    layers = {
        "wq": norm(ks[0], (L, h, h)),
        "wk": norm(ks[1], (L, h, h)),
        "wv": norm(ks[2], (L, h, h)),
        "wo": norm(ks[3], (L, h, h)),
        "ln1": {"scale": jnp.ones((L, h)), "bias": jnp.zeros((L, h))},
        "ln2": {"scale": jnp.ones((L, h)), "bias": jnp.zeros((L, h))},
        "w1": norm(ks[4], (L, h, f)),
        "w2": norm(ks[5], (L, f, h)),
        "b1": jnp.zeros((L, f)),
        "b2": jnp.zeros((L, h)),
    }
    params = {
        "tok_emb": norm(k_emb, (cfg.vocab_size, h)),
        "pos_emb": norm(k_pos, (cfg.max_len, h)),
        "emb_ln": {"scale": jnp.ones((h,)), "bias": jnp.zeros((h,))},
        "layers": layers,
        "mlm_ln": {"scale": jnp.ones((h,)), "bias": jnp.zeros((h,))},
        "mlm_bias": jnp.zeros((cfg.vocab_size,)),
    }
    return params


def param_sharding_rules(plan) -> dict:
    """PartitionSpecs per parameter path for a MeshPlan: FFN and QKV/out
    projections tensor-sharded over 'tp' (Megatron column/row split),
    embeddings replicated."""
    layers = {
        "wq": P(None, None, "tp"),
        "wk": P(None, None, "tp"),
        "wv": P(None, None, "tp"),
        "wo": P(None, "tp", None),
        "ln1": {"scale": P(), "bias": P()},
        "ln2": {"scale": P(), "bias": P()},
        "w1": P(None, None, "tp"),
        "w2": P(None, "tp", None),
        "b1": P(None, "tp"),
        "b2": P(None, None),
    }
    rules = {
        "tok_emb": P(),
        "pos_emb": P(),
        "emb_ln": {"scale": P(), "bias": P()},
        "layers": layers,
        "mlm_ln": {"scale": P(), "bias": P()},
        "mlm_bias": P(),
    }
    return rules


def _layer_norm(x, scale, bias, eps=1e-6):
    x32 = x.astype(jnp.float32)
    mu = jnp.mean(x32, axis=-1, keepdims=True)
    var = jnp.var(x32, axis=-1, keepdims=True)
    y = (x32 - mu) * jax.lax.rsqrt(var + eps)
    return (y * scale + bias).astype(x.dtype)


def default_attention(q, k, v, mask):
    """Plain full attention [B, H, T, D] (single-device sequence)."""
    D = q.shape[-1]
    s = jnp.einsum("bhqd,bhkd->bhqk", q, k, preferred_element_type=jnp.float32)
    s = s / np.sqrt(D)
    s = jnp.where(mask[:, None, None, :], s, -1e30)
    p = jax.nn.softmax(s, axis=-1).astype(v.dtype)
    return jnp.einsum("bhqk,bhkd->bhqd", p, v, preferred_element_type=jnp.float32).astype(v.dtype)


def bert_layer(x, lp, attn_mask, *, cfg: BertConfig, attention_fn=None):
    """One pre-LN transformer block: x [B, T, h] → x.

    Module-level (not a closure) so the pipeline-parallel path
    (parallel/pipeline.py stages) applies the same block the lax.scan
    encoder does."""
    dtype = jnp.dtype(cfg.dtype)
    B, T = x.shape[0], x.shape[1]
    H, D = cfg.heads, cfg.head_dim
    if attention_fn is None:
        attention_fn = default_attention
    y = _layer_norm(x, lp["ln1"]["scale"], lp["ln1"]["bias"])
    q = (y @ lp["wq"].astype(dtype)).reshape(B, T, H, D).transpose(0, 2, 1, 3)
    k = (y @ lp["wk"].astype(dtype)).reshape(B, T, H, D).transpose(0, 2, 1, 3)
    v = (y @ lp["wv"].astype(dtype)).reshape(B, T, H, D).transpose(0, 2, 1, 3)
    a = attention_fn(q, k, v, attn_mask)
    a = a.transpose(0, 2, 1, 3).reshape(B, T, cfg.hidden)
    x = x + (a @ lp["wo"].astype(dtype))
    y = _layer_norm(x, lp["ln2"]["scale"], lp["ln2"]["bias"])
    hdn = jax.nn.gelu(y @ lp["w1"].astype(dtype) + lp["b1"].astype(dtype))
    return x + (hdn @ lp["w2"].astype(dtype) + lp["b2"].astype(dtype))


def bert_embed(params, input_ids, *, cfg: BertConfig) -> jax.Array:
    T = input_ids.shape[1]
    x = params["tok_emb"][input_ids] + params["pos_emb"][:T][None, :, :]
    x = _layer_norm(x, params["emb_ln"]["scale"], params["emb_ln"]["bias"])
    return x.astype(jnp.dtype(cfg.dtype))


def bert_head(params, x) -> jax.Array:
    """MLM logits of hidden states ``[..., h]`` → ``[..., vocab]``."""
    x = _layer_norm(x, params["mlm_ln"]["scale"], params["mlm_ln"]["bias"])
    # weight-tied MLM head
    return jnp.einsum(
        "...h,vh->...v", x.astype(jnp.float32), params["tok_emb"], preferred_element_type=jnp.float32
    ) + params["mlm_bias"]


def bert_encode(
    params: dict,
    input_ids: jax.Array,
    attn_mask: jax.Array | None = None,
    *,
    cfg: BertConfig,
    attention_fn=None,
):
    """Encoder forward → final hidden states [B, T, h]."""
    B, T = input_ids.shape
    if attn_mask is None:
        attn_mask = jnp.ones((B, T), dtype=bool)
    else:
        attn_mask = attn_mask.astype(bool)

    x = bert_embed(params, input_ids, cfg=cfg)

    def layer(x, lp):
        return bert_layer(x, lp, attn_mask, cfg=cfg, attention_fn=attention_fn), None

    x, _ = jax.lax.scan(layer, x, params["layers"])
    return x


def bert_forward(
    params: dict,
    input_ids: jax.Array,
    attn_mask: jax.Array | None = None,
    *,
    cfg: BertConfig,
    attention_fn=None,
):
    """Encoder forward → MLM logits [B, T, vocab].

    ``attention_fn(q, k, v, mask)`` defaults to plain full attention;
    pass ``make_ring_attention(mesh)`` for sequence parallelism."""
    x = bert_encode(params, input_ids, attn_mask, cfg=cfg, attention_fn=attention_fn)
    return bert_head(params, x)


# ---------------------------------------------------------------- MLM loss


def mlm_head_loss(params, x, labels, *, batch_sharding=None):
    """Final hidden states [B, T, h] and labels [B, T] → (mean NLL over the
    positions with labels >= 0 (-100 = ignore), positions the head ran at).

    The one head-and-loss of the scan-encoder loss and the pipelined loss, so
    the two can never drift.  Per labelled position it is ``bert_head``'s
    arithmetic and a float32 log-softmax, whatever the number of labels;
    what depends on ``labels`` is how many tiles run.  ``batch_sharding`` is
    the ``NamedSharding`` of ``labels`` inside a sharded step, so that no
    hidden state leaves its device."""
    head = {k: params[k] for k in ("mlm_ln", "tok_emb", "mlm_bias")}
    return labelled_nll(bert_head, head, x, labels, batch_sharding)


def bert_mlm_loss(
    params: dict,
    input_ids: jax.Array,
    labels: jax.Array,
    attn_mask: jax.Array | None = None,
    *,
    cfg: BertConfig,
    attention_fn=None,
    batch_sharding=None,
    with_head_positions: bool = False,
):
    """Masked-LM loss: labels == -100 are ignored.  With
    ``with_head_positions`` → (loss, positions the head ran at)."""
    x = bert_encode(params, input_ids, attn_mask, cfg=cfg, attention_fn=attention_fn)
    loss, positions = mlm_head_loss(params, x, labels, batch_sharding=batch_sharding)
    return (loss, positions) if with_head_positions else loss
