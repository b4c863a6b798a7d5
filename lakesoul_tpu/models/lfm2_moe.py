"""Hybrid causal LM of the LFM2-MoE family: gated short convolutions and
grouped-query softmax attention in one stack, leading dense layers and then a
dropless mixture of experts routed by sigmoid scores under a per-expert bias.
This file is the family: its configuration, its weights and its convolution
mixer; the layer stack, attention, the head and the loss are
``models/causal_lm.py``'s, shared with the other families.

Layer ``l`` is what ``layer_types[l]`` says (``conv`` or ``full_attention``);
its feed-forward is a dense SwiGLU where ``l < num_dense_layers`` and the
routed experts after that, with no shared expert.  A layer is ``h = x +
op(norm(x)); x' = h + ffn(norm(h))`` with plain RMS norms in float32 (weights
start at 1), then a final norm and the head, which is the embedding (tied).
Matrix products run in ``cfg.dtype`` (bfloat16) with float32 accumulation;
norms, the router's sigmoid and the loss are float32.

- ``conv``: ``[B | C | X] = y W_in``; ``u = B * X``; a depthwise causal
  convolution of ``conv_L_cache`` taps over ``u``, no bias, no activation;
  ``out = (C * conv(u)) W_out``.  No state scan and no softmax.
- ``full_attention``: RMS norm over each query and key head's channels, rotary
  positions over the whole head, causal softmax, no gate, no window.
- routing: ``parallel/moe.py: route_sigmoid_top_k``.  ``expert_bias`` is a
  buffer (``params["buffers"]``): it moves which experts a token takes, not
  their weights, and no gradient and no optimizer touches it.

Departures from the published model: the bias has no update rule here (the
published config and modelling code give none: it stays as made), no router
auxiliary loss, no document boundaries (a row is one packed sequence: the
convolution and the mask do not restart inside it).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from lakesoul_tpu.models.causal_lm import (
    ATTN_SCOPE,
    causal_conv,
    lm_loss,
    normal_init as normal,
    softmax_attention,
)
from lakesoul_tpu.models.norms import rms_norm
from lakesoul_tpu.parallel.moe import route_sigmoid_top_k

CONV_SCOPE = "lakesoul.lm.conv"
_KINDS = {"conv": "conv", "full_attention": "attn"}  # the published layer type → the stack's mixer kind


@dataclass(frozen=True)
class Lfm2MoeConfig:
    """The published ``config.json`` keys the layers read, under their
    published names, and what this chip holds of the model."""

    vocab_size: int = 65536
    hidden_size: int = 2048
    layer_types: tuple[str, ...] = ("conv", "conv", "full_attention", "conv", "conv", "conv")
    num_dense_layers: int = 2
    intermediate_size: int = 7168
    # gated short convolution
    conv_L_cache: int = 3
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 8
    rope_theta: float = 1e6
    # experts
    num_experts: int = 32
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1792
    routed_scaling_factor: float = 1.0
    norm_eps: float = 1e-5
    # this chip's share: (first expert, how many) of ``num_experts``
    experts_held: tuple[int, int] = (0, 32)
    dtype: str = "bfloat16"

    @staticmethod
    def from_published(model: dict, **share) -> "Lfm2MoeConfig":
        """From a dict with the published keys (others are ignored).  The
        family's other switches are held to what the layers compute."""
        expected = {"conv_bias": False, "norm_topk_prob": True, "use_expert_bias": True}
        wrong = {k: model[k] for k, v in expected.items() if model.get(k, v) != v}
        if wrong:
            raise ValueError(f"the LFM2-MoE layers are written for {expected}; the configuration says {wrong}")
        names = Lfm2MoeConfig.__dataclass_fields__
        kept = {k: tuple(v) if isinstance(v, list) else v for k, v in model.items() if k in names}
        return Lfm2MoeConfig(**kept, **share)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    def layer_kinds(self) -> tuple[str, ...]:
        return tuple(_KINDS[t] for t in self.layer_types)

    def ffn_kinds(self) -> tuple[str, ...]:
        return tuple("dense" if i < self.num_dense_layers else "moe" for i in range(len(self.layer_types)))

    def mixer(self, kind: str):
        if kind == "conv":
            return gated_short_conv, CONV_SCOPE
        return functools.partial(attention, cfg=self), ATTN_SCOPE

    def norm(self, x, w):
        return rms_norm(x, w, self.norm_eps, centred=False)

    def route(self, x, router_w, bias):
        return route_sigmoid_top_k(
            x, router_w, bias, top_k=self.num_experts_per_tok, scale=self.routed_scaling_factor
        )

    def init(self, key: jax.Array) -> dict:
        return init_lm_params(self, key)

    def loss(self, params, ids, labels, *, batch_sharding=None):
        return lm_loss(params, ids, labels, cfg=self, batch_sharding=batch_sharding)


def init_lm_params(cfg: Lfm2MoeConfig, key: jax.Array) -> dict:
    """Weights from a key: matrices and the convolution normal(0, 0.02), norm
    weights 1; ``expert_bias`` normal(0, 0.003), so that selection and weights
    really differ (a sigmoid score moves by 0.19 from token to token, so one
    assignment in seventy follows the bias); no ``head`` (tied to ``embed``)."""
    h, f, ff = cfg.hidden_size, cfg.moe_intermediate_size, cfg.intermediate_size
    count = cfg.experts_held[1]

    def layer(key, kind, ffn):
        ks = jax.random.split(key, 9)
        if kind == "conv":
            mixer = {
                "w_in": normal(ks[0], h, 3 * h),  # columns [B | C | X]
                "conv": normal(ks[1], h, cfg.conv_L_cache),
                "w_out": normal(ks[2], h, h),
            }
        else:
            heads, kv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
            mixer = {
                "w_q": normal(ks[0], h, heads * d),
                "w_k": normal(ks[1], h, kv * d),
                "w_v": normal(ks[2], h, kv * d),
                "w_o": normal(ks[3], heads * d, h),
                "q_norm": jnp.ones((d,)),
                "k_norm": jnp.ones((d,)),
            }
        lp = {"norm1": jnp.ones((h,)), kind: mixer, "norm2": jnp.ones((h,))}
        if ffn == "dense":
            lp["mlp"] = {"w_gate": normal(ks[4], h, ff), "w_up": normal(ks[5], h, ff), "w_down": normal(ks[6], ff, h)}
            return lp, {}
        lp["moe"] = {
            "router": normal(ks[4], h, cfg.num_experts),
            "w_gate": normal(ks[5], count, h, f),
            "w_up": normal(ks[6], count, h, f),
            "w_down": normal(ks[7], count, f, h),
        }
        return lp, {"expert_bias": (jax.random.normal(ks[8], (cfg.num_experts,)) * 0.003).astype(jnp.float32)}

    k_emb, k_layers = jax.random.split(key)
    kinds = cfg.layer_kinds()
    layers, buffers = zip(*(
        layer(k, kind, ffn) for k, kind, ffn in zip(jax.random.split(k_layers, len(kinds)), kinds, cfg.ffn_kinds())
    ))
    return {
        "embed": normal(k_emb, cfg.vocab_size, h),
        "layers": list(layers),
        "final_norm": jnp.ones((h,)),
        "buffers": {"layers": list(buffers)},
    }


def gated_short_conv(x, p):
    """The gated short-convolution mixer: x [B, T, h] (normed) → [B, T, h].
    Two elementwise gates round a depthwise causal convolution."""
    dtype = x.dtype
    f32 = jnp.float32
    b, c, xs = jnp.split(x @ p["w_in"].astype(dtype), 3, axis=-1)
    u = (b.astype(f32) * xs.astype(f32)).astype(dtype)
    gated = (c.astype(f32) * causal_conv(u, p["conv"]).astype(f32)).astype(dtype)
    return gated @ p["w_out"].astype(dtype)


def attention(x, p, *, cfg: Lfm2MoeConfig):
    """The grouped-query attention mixer: x [B, T, h] (normed) → [B, T, h]."""
    return softmax_attention(
        x, p, heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        rotary_dim=cfg.head_dim, theta=cfg.rope_theta, eps=cfg.norm_eps, centred=False, gated=False,
    )
