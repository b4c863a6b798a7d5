"""Sparse causal LM of the ``afmoe`` family (Trinity-Mini, 26B-A3B): window
and full attention mixed in one stack, a gated attention output, four norms a
layer, and a dropless mixture of 128 small experts routed by sigmoid scores
under a per-expert bias beside a shared expert.  This file is the family: its
configuration and its weights; the layer stack, the attention mixer with its
two masks, the head and the loss are ``models/causal_lm.py``'s, shared with
the other families.

The equations (sizes from the published ``config.json``; what it does not say
is marked *assumed*: each is the public ``afmoe`` implementation's reading, and
the reference, ``benchmarks/chip/reference/afmoe_f32.py``, follows the same):

- ``x0 = Emb[ids] * sqrt(hidden_size)`` where ``mup_enabled`` (*assumed*: the
  key is published, its equation is not).
- Layer ``l``, ``N`` a plain RMS norm in float32 (weight starts at 1):
  ``h = x + N2(Mix_l(N1(x)))``, ``x' = h + N4(FFN_l(N3(h)))`` (*assumed*: four
  norms a layer; the weights' ``norm1``, ``norm1_out``, ``norm2``,
  ``norm2_out``).
- ``Mix_l``: ``q = y W_q`` (``num_attention_heads`` x ``head_dim``),
  ``k = y W_k``, ``v = y W_v`` (``num_key_value_heads`` x ``head_dim``),
  ``g = y W_g`` (as wide as ``q``; *assumed*: the gate); ``q`` and ``k``
  normed over a head's channels (*assumed*).  Where ``layer_types[l]`` is
  ``"sliding_attention"`` (kind ``"swa"``): rotary positions over all of a
  head's channels at ``rope_theta``, and key ``j`` visible to query ``i`` iff
  ``0 <= i - j < sliding_window`` (*assumed*: the window counts the query's own
  position).  Where ``"full_attention"`` (kind ``"attn"``): NO rotary
  (*assumed*: the full layers see no positions) and ``j <= i``.  Scores scaled
  by ``head_dim ** -0.5``, softmax in float32,
  ``Mix = ((softmax(..) v) * sigmoid(g)) W_o``.
- ``FFN_l``: a dense SwiGLU ``intermediate_size`` wide where
  ``l < num_dense_layers``; else ``s = sigmoid(y32 W_r)`` over ``num_experts``
  in float32, the ``num_experts_per_tok`` largest of ``s + expert_bias``,
  weights ``s_picked / (sum s_picked + 1e-20) * route_scale``
  (``route_norm``), ``sum_e w_e Expert_e(y) + Shared(y)``: experts and the one
  shared expert SwiGLU ``moe_intermediate_size`` wide, the shared expert with
  no gate.
- Final norm, untied head, next-token cross-entropy in float32.

Matrix products run in ``cfg.dtype`` (bfloat16) with float32 accumulation;
norms, the router's product and sigmoid, the attention softmax and the loss
are float32.

Departures from the published model: ``expert_bias`` has no update rule here
(``load_balance_coeff`` 0.001 is a published RATE; the rule it scales is not
in the config: the bias is a buffer, stays as made, and a step returns it bit
for bit), no document boundaries (a row is one packed sequence), no auxiliary
loss, the rotary pairing is the stack's half split (under weights from a seed
a fixed permutation of ``W_q``'s and ``W_k``'s columns).
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from lakesoul_tpu.models.causal_lm import ATTN_SCOPE, lm_loss, normal_init as normal, softmax_attention
from lakesoul_tpu.models.norms import rms_norm
from lakesoul_tpu.parallel.moe import route_sigmoid_top_k

SWA_SCOPE = "lakesoul.lm.swa"  # the window layers' mixers; the full layers' stand under ATTN_SCOPE
KINDS = {"sliding_attention": "swa", "full_attention": "attn"}  # published layer type → the mixer's kind

# the switches the layers are written for: any other published value is refused, not ignored
_EXPECTED = {
    "score_func": "sigmoid", "route_norm": True, "n_group": 1, "topk_group": 1, "num_expert_groups": 1,
    "num_limited_groups": 1, "rope_scaling": None, "tie_word_embeddings": False, "hidden_act": "silu",
    "num_shared_experts": 1,
}


@dataclass(frozen=True)
class AfmoeConfig:
    """The published ``config.json`` keys the layers read, under their
    published names, and what this chip holds of the model."""

    vocab_size: int = 200192
    hidden_size: int = 2048
    num_hidden_layers: int = 32
    num_dense_layers: int = 2
    intermediate_size: int = 6144
    layer_types: tuple[str, ...] = ("sliding_attention", "sliding_attention", "sliding_attention", "full_attention") * 8
    # attention
    num_attention_heads: int = 32
    num_key_value_heads: int = 4
    head_dim: int = 128
    sliding_window: int = 2048
    rope_theta: float = 10000.0
    mup_enabled: bool = True
    # experts
    num_experts: int = 128
    num_shared_experts: int = 1
    num_experts_per_tok: int = 8
    moe_intermediate_size: int = 1024
    route_scale: float = 2.826
    rms_norm_eps: float = 1e-5
    # this chip's share: (first expert, how many) of ``num_experts``
    experts_held: tuple[int, int] = (0, 128)
    dtype: str = "bfloat16"

    @staticmethod
    def from_published(model: dict, **share) -> "AfmoeConfig":
        """From a dict with the published keys (others are ignored).  The
        family's other switches are held to what the layers compute."""
        wrong = {k: model[k] for k, v in _EXPECTED.items() if model.get(k, v) != v}
        if wrong:
            raise ValueError(f"the afmoe layers are written for {_EXPECTED}; the configuration says {wrong}")
        names = AfmoeConfig.__dataclass_fields__
        given = {k: tuple(v) if k == "layer_types" else v for k, v in model.items() if k in names}
        return AfmoeConfig(**given, **share)

    def __post_init__(self):
        if len(self.layer_types) != self.num_hidden_layers or set(self.layer_types) - set(KINDS):
            raise ValueError(
                f"layer_types names {len(self.layer_types)} layers of kinds {sorted(set(self.layer_types))};"
                f" num_hidden_layers={self.num_hidden_layers}, kinds {sorted(KINDS)}"
            )

    @property
    def embed_scale(self) -> float | None:
        return self.hidden_size**0.5 if self.mup_enabled else None

    def layer_kinds(self) -> tuple[str, ...]:
        return tuple(KINDS[kind] for kind in self.layer_types)

    def ffn_kinds(self) -> tuple[str, ...]:
        return tuple("dense" if i < self.num_dense_layers else "moe" for i in range(self.num_hidden_layers))

    def mixer(self, kind: str):
        local = kind == "swa"
        return functools.partial(
            softmax_attention, heads=self.num_attention_heads, kv_heads=self.num_key_value_heads,
            head_dim=self.head_dim, rotary_dim=self.head_dim if local else None, theta=self.rope_theta,
            eps=self.rms_norm_eps, centred=False, gated=False, window=self.sliding_window if local else None,
        ), SWA_SCOPE if local else ATTN_SCOPE

    def norm(self, x, w):
        return rms_norm(x, w, self.rms_norm_eps, centred=False)

    def route(self, x, router_w, bias):
        return route_sigmoid_top_k(
            x, router_w, bias, top_k=self.num_experts_per_tok, scale=self.route_scale, eps=1e-20
        )

    def init(self, key: jax.Array) -> dict:
        return init_lm_params(self, key)

    def loss(self, params, ids, labels, *, batch_sharding=None):
        return lm_loss(params, ids, labels, cfg=self, batch_sharding=batch_sharding)


def init_lm_params(cfg: AfmoeConfig, key: jax.Array) -> dict:
    """Weights from a key: matrices normal(0, 0.02), norm weights 1;
    ``expert_bias`` normal(0, 0.003), so that selection and weights really
    differ."""
    h, f, ff = cfg.hidden_size, cfg.moe_intermediate_size, cfg.intermediate_size
    q_width, kv_width = cfg.num_attention_heads * cfg.head_dim, cfg.num_key_value_heads * cfg.head_dim
    count = cfg.experts_held[1]

    def layer(key, kind, ffn):
        ks = jax.random.split(key, 13)
        mixer = {
            "w_q": normal(ks[0], h, q_width), "w_k": normal(ks[1], h, kv_width), "w_v": normal(ks[2], h, kv_width),
            "w_gate": normal(ks[3], h, q_width), "w_o": normal(ks[4], q_width, h),
            "q_norm": jnp.ones((cfg.head_dim,)), "k_norm": jnp.ones((cfg.head_dim,)),
        }
        lp = {"norm1": jnp.ones((h,)), kind: mixer, "norm1_out": jnp.ones((h,)),
              "norm2": jnp.ones((h,)), "norm2_out": jnp.ones((h,))}
        if ffn == "dense":
            lp["mlp"] = {"w_gate": normal(ks[5], h, ff), "w_up": normal(ks[6], h, ff), "w_down": normal(ks[7], ff, h)}
            return lp, {}
        fs = cfg.num_shared_experts * f
        lp["moe"] = {
            "router": normal(ks[5], h, cfg.num_experts),
            "w_gate": normal(ks[6], count, h, f),
            "w_up": normal(ks[7], count, h, f),
            "w_down": normal(ks[8], count, f, h),
            "shared": {"w_gate": normal(ks[9], h, fs), "w_up": normal(ks[10], h, fs), "w_down": normal(ks[11], fs, h)},
        }
        return lp, {"expert_bias": (jax.random.normal(ks[12], (cfg.num_experts,)) * 0.003).astype(jnp.float32)}

    k_emb, k_head, k_layers = jax.random.split(key, 3)
    kinds, ffns = cfg.layer_kinds(), cfg.ffn_kinds()
    layers, buffers = zip(*(
        layer(k, kind, ffn) for k, kind, ffn in zip(jax.random.split(k_layers, len(kinds)), kinds, ffns)
    ))
    return {
        "embed": normal(k_emb, cfg.vocab_size, h),
        "layers": list(layers),
        "final_norm": jnp.ones((h,)),
        "head": normal(k_head, h, cfg.vocab_size),
        "buffers": {"layers": list(buffers)},
    }
