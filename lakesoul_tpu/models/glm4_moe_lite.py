"""Sparse causal LM of the ``glm4_moe_lite`` family (GLM-4.7-Flash): latent
attention in every layer, leading dense layers and then a dropless mixture of
experts routed by sigmoid scores under a per-expert bias beside a shared
expert, and a multi-token-prediction module whose loss is added to the
next-token loss.  This file is the family: its configuration and its weights;
the layer stack, latent attention, the prediction module, the head and the
loss are ``models/causal_lm.py``'s, shared with the other families.

Layer ``l`` is ``h = x + mla(norm(x)); x' = h + ffn(norm(h))`` with plain RMS
norms in float32 (weights start at 1); its feed-forward is a dense SwiGLU where
``l < first_k_dense_replace`` and after that the routed experts plus a shared
expert with no gate (``n_shared_experts`` x ``moe_intermediate_size`` wide).
Then a final norm and an untied head.  Matrix products run in ``cfg.dtype``
(bfloat16) with float32 accumulation; norms, the router's sigmoid, the
attention softmax and the loss are float32.

- latent attention: ``causal_lm.latent_attention`` with ``q_lora_rank``,
  ``kv_lora_rank``, ``qk_nope_head_dim``, ``qk_rope_head_dim``, ``v_head_dim``
  as published; computed unabsorbed (per-head keys and values materialised).
- routing: ``parallel/moe.py: route_sigmoid_top_k`` at ``routed_scaling_factor``
  with the family's denominator (the picked scores' sum plus 1e-20).
  ``e_score_correction_bias`` is a buffer (``params["buffers"]``, there
  ``expert_bias``): it moves which experts a token takes, not their weights,
  and no gradient and no optimizer touches it.
- the prediction module (``num_nextn_predict_layers`` 1): ``causal_lm.mtp_loss``
  over ``params["mtp"]``: two norms, ``eh_proj``, one whole sparse layer of its
  own, the module's head norm; the loss is ``L_main + mtp_loss_weight x L_mtp``.

Departures from the published model: the bias has no update rule here (the
published config gives none: it stays as made), ``mtp_loss_weight`` is not a
published key (0.3: the DeepSeek-V3 and GLM-4.5 reports' value for the first
phase of pre-training) and has no schedule, no document boundaries (a row is
one packed sequence), the rotary pairing is the stack's half split (under
weights from a seed a fixed permutation of ``w_uq``'s and ``w_dkv``'s
columns), no absorbed form and no latent cache (serving matters).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from lakesoul_tpu.models.causal_lm import ATTN_SCOPE, latent_attention, lm_loss, normal_init as normal
from lakesoul_tpu.models.norms import rms_norm
from lakesoul_tpu.parallel.moe import route_sigmoid_top_k

# the switches the layers are written for: any other published value is refused, not ignored
_EXPECTED = {
    "n_group": 1, "topk_group": 1, "norm_topk_prob": True, "rope_scaling": None, "attention_bias": False,
    "partial_rotary_factor": 1, "tie_word_embeddings": False, "hidden_act": "silu", "topk_method": "noaux_tc",
}


@dataclass(frozen=True)
class Glm4MoeLiteConfig:
    """The published ``config.json`` keys the layers read, under their
    published names, and what this chip holds of the model."""

    vocab_size: int = 154880
    hidden_size: int = 2048
    num_hidden_layers: int = 47
    first_k_dense_replace: int = 1
    intermediate_size: int = 10240
    # latent attention
    num_attention_heads: int = 20
    q_lora_rank: int = 768
    kv_lora_rank: int = 512
    qk_nope_head_dim: int = 192
    qk_rope_head_dim: int = 64
    v_head_dim: int = 256
    rope_theta: float = 1e6
    # experts
    n_routed_experts: int = 64
    n_shared_experts: int = 1
    num_experts_per_tok: int = 4
    moe_intermediate_size: int = 1536
    routed_scaling_factor: float = 1.8
    rms_norm_eps: float = 1e-5
    # the prediction module
    num_nextn_predict_layers: int = 1
    mtp_loss_weight: float = 0.3
    # this chip's share: (first expert, how many) of ``n_routed_experts``
    experts_held: tuple[int, int] = (0, 64)
    dtype: str = "bfloat16"

    @staticmethod
    def from_published(model: dict, **share) -> "Glm4MoeLiteConfig":
        """From a dict with the published keys (others are ignored).  The
        family's other switches are held to what the layers compute."""
        wrong = {k: model[k] for k, v in _EXPECTED.items() if model.get(k, v) != v}
        if wrong:
            raise ValueError(f"the glm4_moe_lite layers are written for {_EXPECTED}; the configuration says {wrong}")
        names = Glm4MoeLiteConfig.__dataclass_fields__
        return Glm4MoeLiteConfig(**{k: v for k, v in model.items() if k in names}, **share)

    def __post_init__(self):
        if self.num_nextn_predict_layers not in (0, 1):
            raise ValueError(f"one prediction module or none; num_nextn_predict_layers={self.num_nextn_predict_layers}")
        if self.qk_nope_head_dim + self.qk_rope_head_dim != self.v_head_dim:
            raise ValueError(
                "causal_attention takes one head size: qk_nope_head_dim + qk_rope_head_dim"
                f" ({self.qk_nope_head_dim} + {self.qk_rope_head_dim}) has to be v_head_dim ({self.v_head_dim})"
            )

    @property
    def num_experts(self) -> int:
        return self.n_routed_experts

    def layer_kinds(self) -> tuple[str, ...]:
        return ("mla",) * self.num_hidden_layers

    def ffn_kinds(self) -> tuple[str, ...]:
        return tuple("dense" if i < self.first_k_dense_replace else "moe" for i in range(self.num_hidden_layers))

    def mixer(self, kind: str):
        return functools.partial(
            latent_attention, heads=self.num_attention_heads, nope_dim=self.qk_nope_head_dim,
            rope_dim=self.qk_rope_head_dim, theta=self.rope_theta, norm=self.norm,
        ), ATTN_SCOPE

    def norm(self, x, w):
        return rms_norm(x, w, self.rms_norm_eps, centred=False)

    def route(self, x, router_w, bias):
        return route_sigmoid_top_k(
            x, router_w, bias, top_k=self.num_experts_per_tok, scale=self.routed_scaling_factor, eps=1e-20
        )

    def init(self, key: jax.Array) -> dict:
        return init_lm_params(self, key)

    def loss(self, params, ids, labels, *, batch_sharding=None):
        return lm_loss(params, ids, labels, cfg=self, batch_sharding=batch_sharding)


def init_lm_params(cfg: Glm4MoeLiteConfig, key: jax.Array) -> dict:
    """Weights from a key: matrices normal(0, 0.02), norm weights 1;
    ``expert_bias`` normal(0, 0.003), so that selection and weights really
    differ; the prediction module (``"mtp"``) where the configuration has
    one, its layer a sparse layer like the stack's last."""
    h, f, ff = cfg.hidden_size, cfg.moe_intermediate_size, cfg.intermediate_size
    heads, nope, rope = cfg.num_attention_heads, cfg.qk_nope_head_dim, cfg.qk_rope_head_dim
    count = cfg.experts_held[1]

    def layer(key, ffn):
        ks = jax.random.split(key, 16)
        mixer = {
            "w_dq": normal(ks[0], h, cfg.q_lora_rank),
            "q_norm": jnp.ones((cfg.q_lora_rank,)),
            "w_uq": normal(ks[1], cfg.q_lora_rank, heads * (nope + rope)),     # per head [nope | rope]
            "w_dkv": normal(ks[2], h, cfg.kv_lora_rank + rope),               # [latent | the shared rotary key]
            "kv_norm": jnp.ones((cfg.kv_lora_rank,)),
            "w_ukv": normal(ks[3], cfg.kv_lora_rank, heads * (nope + cfg.v_head_dim)),  # per head [k_nope | v]
            "w_o": normal(ks[4], heads * cfg.v_head_dim, h),
        }
        lp = {"norm1": jnp.ones((h,)), "mla": mixer, "norm2": jnp.ones((h,))}
        if ffn == "dense":
            lp["mlp"] = {"w_gate": normal(ks[5], h, ff), "w_up": normal(ks[6], h, ff), "w_down": normal(ks[7], ff, h)}
            return lp, {}
        lp["moe"] = {
            "router": normal(ks[5], h, cfg.n_routed_experts),
            "w_gate": normal(ks[6], count, h, f),
            "w_up": normal(ks[7], count, h, f),
            "w_down": normal(ks[8], count, f, h),
        }
        if cfg.n_shared_experts:
            fs = cfg.n_shared_experts * f
            lp["moe"]["shared"] = {
                "w_gate": normal(ks[9], h, fs), "w_up": normal(ks[10], h, fs), "w_down": normal(ks[11], fs, h)
            }
        return lp, {"expert_bias": (jax.random.normal(ks[12], (cfg.n_routed_experts,)) * 0.003).astype(jnp.float32)}

    k_emb, k_head, k_layers, k_mtp = jax.random.split(key, 4)
    ffns = cfg.ffn_kinds()
    layers, buffers = zip(*(layer(k, ffn) for k, ffn in zip(jax.random.split(k_layers, len(ffns)), ffns)))
    params = {
        "embed": normal(k_emb, cfg.vocab_size, h),
        "layers": list(layers),
        "final_norm": jnp.ones((h,)),
        "head": normal(k_head, h, cfg.vocab_size),
        "buffers": {"layers": list(buffers)},
    }
    if cfg.num_nextn_predict_layers:
        k_proj, k_layer = jax.random.split(k_mtp)
        lp, held = layer(k_layer, ffns[-1])
        params["mtp"] = {
            "enorm": jnp.ones((h,)), "hnorm": jnp.ones((h,)),
            "eh_proj": normal(k_proj, 2 * h, h),  # rows [the next token's embedding | the hidden state]
            "layer": lp, "shared_head_norm": jnp.ones((h,)),
        }
        params["buffers"]["mtp"] = held
    return params
