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

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import PartitionSpec as P

from lakesoul_tpu.parallel.mesh import spec_axes


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
# The loss needs logits only where labels >= 0 (15% of positions in MLM), so
# the head runs over those rows only: each shard of the batch moves its
# labelled rows to the front and runs the head one fixed-size tile of them at
# a time, for as many tiles as its labels fill.  A loop whose length depends
# on the data has no reverse-mode derivative, so each tile's gradients are
# taken inside the loop and handed to autodiff through one custom_vjp: no
# [B*T, vocab] array exists in either pass.


def head_tile(n: int) -> int:
    """Rows the head runs at a time for a shard of ``n`` positions: a twelfth
    of them, rounded up to a multiple of 8.  MLM labels 15%, so two tiles
    are the usual case, and twelve cover every position with next to none
    over.  (Measured on a v5e at 8,192 positions: PERF.md section 6, PR 26.)"""
    return min(n, -(-n // 96) * 8)


def _tile_nll(head_fn, head, x, labels, scale, weights=None):
    """``scale`` x the summed NLL of one tile's labelled rows, in float32;
    with ``weights`` [rows] → (the sum of weight x NLL over them, each row's
    NLL: 0 where it has no label)."""
    logp = jax.nn.log_softmax(head_fn(head, x), axis=-1)
    picked = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[:, None], axis=-1)[:, 0]
    if weights is None:
        return -scale * jnp.sum(jnp.where(labels >= 0, picked, 0.0))
    nll = -jnp.where(labels >= 0, picked, 0.0)
    return jnp.sum(weights * nll), nll


def tile_grads(head_fn, head, x, labels, scale, *weights):
    """:func:`_tile_nll` and its gradients into ``head`` and ``x`` by autodiff
    → (what it returns, (g_head, g_x)): the compiler's body of a tile, and the
    tile loop's default."""
    return jax.value_and_grad(
        functools.partial(_tile_nll, head_fn), argnums=(0, 1), has_aux=bool(weights)
    )(head, x, labels, scale, *weights)


def _head_over_labelled(head_fn, head, x, labels, axes, with_grads: bool, weights=None, tile_body=tile_grads):
    """One shard's share of the loss: ``x`` [..., h] and ``labels`` [...] are
    the rows this device holds, ``axes`` the mesh axes the batch is split
    over, ``head_fn(head, x)`` the float32 logits of rows ``x``.  → (loss, positions the head ran at), both summed over ``axes``,
    and with ``with_grads`` the loss's gradients (head summed over ``axes``,
    x for this shard's rows).  With ``weights`` [...] (float32, a position's
    own) the loss is the sum of weight x NLL over the labelled positions, not
    their mean, and each position's NLL [...] (float32, 0 without a label)
    comes third: → (loss, positions, nll) and then the gradients.
    ``tile_body`` makes one tile's loss and gradients, called as
    :func:`tile_grads` is and returning what it returns."""
    x2, lab = x.reshape(-1, x.shape[-1]), labels.reshape(-1)
    n = lab.shape[0]
    tile = head_tile(n)
    slots = -(-n // tile) * tile
    order = jnp.argsort(lab < 0, stable=True)  # labelled rows first
    # slots past n read row 0 and carry no label
    rows = jnp.pad(order, (0, slots - n))
    row_labels = jnp.pad(lab[order], (0, slots - n), constant_values=-100)
    count = jnp.sum(lab >= 0)
    total = jax.lax.psum(count, axes) if axes else count
    scale = 1.0 / jnp.maximum(total, 1).astype(jnp.float32)
    weighted = weights is not None
    if weighted:
        row_weights = jnp.pad(weights.reshape(-1).astype(jnp.float32)[order], (0, slots - n))

    def run_tile(carry):
        k, loss, grads, nlls = carry
        at = k * tile
        xt = x2[jax.lax.dynamic_slice(rows, (at,), (tile,))]
        lt = jax.lax.dynamic_slice(row_labels, (at,), (tile,))
        wt = (jax.lax.dynamic_slice(row_weights, (at,), (tile,)),) if weighted else ()
        if with_grads:
            part, (g_head, g_x) = tile_body(head_fn, head, xt, lt, scale, *wt)
            acc_head, acc_x = grads
            grads = (
                jax.tree.map(jnp.add, acc_head, g_head),
                jax.lax.dynamic_update_slice(acc_x, g_x, (at, 0)),
            )
        else:
            part = _tile_nll(head_fn, head, xt, lt, scale, *wt)
        if weighted:
            part, tile_nll = part
            nlls = jax.lax.dynamic_update_slice(nlls, tile_nll, (at,))
        return k + 1, loss + part, grads, nlls

    grads = (
        (jax.tree.map(jnp.zeros_like, head), jnp.zeros((slots, x2.shape[1]), x2.dtype))
        if with_grads else ()
    )
    tiles = (count + tile - 1) // tile
    _, loss, grads, nlls = jax.lax.while_loop(
        lambda carry: carry[0] < tiles, run_tile,
        (jnp.int32(0), jnp.float32(0.0), grads, jnp.zeros((slots,), jnp.float32) if weighted else ()),
    )
    positions = tiles * tile
    if axes:
        loss, positions = jax.lax.psum((loss, positions), axes)
    out = (loss, positions)
    if weighted:  # back from labelled-first order, as the rows' gradients below
        out += (nlls[jnp.argsort(order)].reshape(labels.shape),)
    if not with_grads:
        return out
    g_head, g_rows = grads
    if axes:
        g_head = jax.lax.psum(g_head, axes)
    # back from labelled-first order; rows of tiles that never ran are zero
    g_x = g_rows[jnp.argsort(order)].reshape(x.shape)
    return *out, g_head, g_x


def _sharded_head(head_fn, head, x, labels, batch_sharding, with_grads: bool, weights=None, tile_body=tile_grads):
    if batch_sharding is None:
        return _head_over_labelled(head_fn, head, x, labels, (), with_grads, weights, tile_body)
    spec = batch_sharding.spec
    axes = spec_axes(spec)
    per_position = () if weights is None else (spec,)
    out_specs = (P(), P(), *per_position, P(), spec) if with_grads else (P(), P(), *per_position)
    # every device gathers among its own rows; only sums cross the mesh
    return jax.shard_map(
        lambda head, x, labels, *weights: _head_over_labelled(
            head_fn, head, x, labels, axes, with_grads, *weights, tile_body=tile_body
        ),
        mesh=batch_sharding.mesh, in_specs=(P(), spec, spec, *per_position), out_specs=out_specs,
        check_vma=False,
    )(head, x, labels, *(() if weights is None else (weights,)))


@functools.partial(jax.custom_vjp, nondiff_argnums=(0, 4, 6))
def labelled_nll(head_fn, head, x, labels, batch_sharding=None, weights=None, tile_body=tile_grads):
    """Hidden states ``x`` [..., h] and ``labels`` [...] → (mean NLL over the
    positions with labels >= 0, positions the head ran at), the logits of rows
    being ``head_fn(head, rows)`` in float32.  The one tile loop of every
    head-and-loss here: masked-LM runs it over the 15% it labels, a causal LM
    over every position.

    It has two forms.  Without ``weights`` (the masked-LM loss,
    :func:`mlm_head_loss`; ``models/causal_lm.py: lm_loss`` and ``mtp_loss``):
    the mean, as above.  With ``weights`` [...] float32, a position's own
    (``models/causal_lm.py: exit_loss``, whose weights are a looped model's
    exit distribution over the stacked passes): → (the SUM of weight x NLL
    over the labelled positions, positions, each position's NLL [...] float32:
    0 where it has no label).  A mean is then the caller's to fold into the
    weights; the weights and the per-position NLL carry no gradient (the
    caller who wants one through a weight takes it from the returned NLL by
    the product rule).  ``positions`` counts the rows the tile loop ran the
    head over in either form: whole tiles of :func:`head_tile` rows covering
    the labelled positions, so at least their number, and with weights over
    every stacked pass.

    The gradients are made in the FORWARD pass, tile by tile inside the loop
    (a loop whose length follows the data has no reverse-mode derivative), for
    a scalar cotangent: the backward pass only scales them by the loss's
    cotangent (:func:`_labelled_nll_bwd`), so nothing but the loss itself may
    be differentiated through, and no [positions, vocab] array exists in either
    pass.  What makes a tile's loss and gradients there is ``tile_body``: by
    default :func:`tile_grads`, the compiler's (a float32 log-softmax, a
    gather and autodiff: the masked-LM loss passes nothing); a causal LM's
    losses pass ``models/causal_lm.py: fused_tile``.  Without gradients
    (evaluation) a tile is :func:`_tile_nll` whatever the body."""
    return _sharded_head(head_fn, head, x, labels, batch_sharding, False, weights)


def _labelled_nll_fwd(head_fn, head, x, labels, batch_sharding, weights=None, tile_body=tile_grads):
    *out, g_head, g_x = _sharded_head(head_fn, head, x, labels, batch_sharding, True, weights, tile_body)
    return tuple(out), (g_head, g_x)


def _labelled_nll_bwd(head_fn, batch_sharding, tile_body, grads, cotangents):
    g_head, g_x = grads
    ct = cotangents[0]
    return jax.tree.map(lambda g: ct * g, g_head), ct.astype(g_x.dtype) * g_x, None, None


labelled_nll.defvjp(_labelled_nll_fwd, _labelled_nll_bwd)


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
