"""Looped causal LM of the ``ouro`` family (Ouro-2.6B; "Scaling Latent
Reasoning via Looped Language Models", arXiv:2510.25741): a dense stack that
runs ``total_ut_steps`` times over ONE set of weights, a loss after every pass
through one head, an exit gate over the passes and the expected loss under its
distribution.  This file is the family: its configuration and its weights; the
pass loop, the attention mixer, the head and the objective are
``models/causal_lm.py``'s (``loop_hidden``, ``softmax_attention``,
``exit_loss``), shared with the other families.

The equations (sizes from the published ``config.json``; what it does not say
is marked *assumed*, and the reference, ``benchmarks/chip/reference/
ouro_f32.py``, follows the same), ``R = total_ut_steps``, ``N`` a plain RMS
norm in float32 (eps ``rms_norm_eps``, weight starts at 1):

- ``x^(0) = Emb[ids]`` (no scale).
- One pass, layers ``l = 1..L`` with the SAME weights in every pass:
  ``h = x + N2_l(Attn_l(N1_l(x)))``, ``x' = h + N4_l(FFN_l(N3_l(h)))``
  (*assumed*: four norms a layer; the weights' ``norm1``, ``norm1_out``,
  ``norm2``, ``norm2_out``).
- ``Attn``: ``q, k, v = y W_q, y W_k, y W_v`` as ``num_attention_heads`` /
  ``num_key_value_heads`` heads of ``head_dim``; NO norm over a head
  (*assumed*: the config names none); rotary positions over all of a head's
  channels at ``rope_theta``; scores scaled by ``head_dim ** -0.5``, causal
  softmax over the whole row in float32; ``W_o``.  No gate, no bias.
- ``FFN(y) = (silu(y W_gate) * (y W_up)) W_down``, ``intermediate_size`` wide.
- After pass ``t``: ``z^(t) = N_f(x after pass t)``, the one ``final_norm``;
  ``z^(t)`` is what pass ``t + 1`` starts from (*assumed*: the norm is inside
  the loop and its output carried) and what the head reads: ``logits^(t) =
  z^(t) W_head`` (untied), ``nll_i^(t)`` the float32 cross-entropy.
- Exit gate (*assumed*: section 3 of the paper; the config carries only
  ``early_exit_threshold``): ``lambda_i^(t) = sigmoid(z_i^(t) . w_exit +
  b_exit)``, float32; ``S_i^(0) = 1``, ``S_i^(t) = S_i^(t-1) (1 -
  lambda_i^(t))``; ``p_i(t) = lambda_i^(t) S_i^(t-1)`` for ``t < R``,
  ``p_i(R) = S_i^(R-1)``.
- ``Loss = mean_i [ sum_t p_i(t) nll_i^(t) - beta H(p_i) ]``, ``H(p) = -sum_t
  p(t) log p(t)``, mean over the labelled positions, ``beta`` 0.05
  (*assumed*: not in the config).

Matrix products run in ``cfg.dtype`` (bfloat16) with float32 accumulation;
norms, the attention softmax, the gate, the distribution, the entropy and the
loss are float32.

Departures from the published model: the gate's second training stage (the
stack frozen) and inference-time exit at ``early_exit_threshold`` are not
modelled (training runs all ``R`` passes whatever the threshold; at the
published 1 no pass is skipped either), no document boundaries (a row is one
packed sequence), the rotary pairing is the stack's half split.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from lakesoul_tpu.models.causal_lm import ATTN_SCOPE, loop_loss, normal_init as normal, softmax_attention
from lakesoul_tpu.models.norms import rms_norm

# the switches the layers are written for: any other published value is refused, not ignored
_EXPECTED = {
    "sliding_window": None, "use_sliding_window": False, "rope_scaling": None, "hidden_act": "silu",
    "tie_word_embeddings": False,
}


@dataclass(frozen=True)
class OuroConfig:
    """The published ``config.json`` keys the layers read, under their
    published names, and what the config leaves to this family."""

    vocab_size: int = 49152
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    intermediate_size: int = 5632
    num_attention_heads: int = 16
    num_key_value_heads: int = 16
    head_dim: int = 128
    rope_theta: float = 1000000.0
    rms_norm_eps: float = 1e-6
    total_ut_steps: int = 4
    exit_beta: float = 0.05  # the entropy term's weight: not in the published config
    dtype: str = "bfloat16"

    @staticmethod
    def from_published(model: dict, **more) -> "OuroConfig":
        """From a dict with the published keys (others are ignored).  The
        family's other switches are held to what the layers compute."""
        wrong = {k: model[k] for k, v in _EXPECTED.items() if model.get(k, v) != v}
        if wrong:
            raise ValueError(f"the ouro layers are written for {_EXPECTED}; the configuration says {wrong}")
        names = OuroConfig.__dataclass_fields__
        return OuroConfig(**{**{k: v for k, v in model.items() if k in names}, **more})

    def __post_init__(self):
        if self.num_attention_heads % self.num_key_value_heads:
            raise ValueError(
                f"num_key_value_heads={self.num_key_value_heads} does not divide"
                f" num_attention_heads={self.num_attention_heads}"
            )

    @property
    def loop_passes(self) -> int:
        return self.total_ut_steps

    def layer_kinds(self) -> tuple[str, ...]:
        return ("attn",) * self.num_hidden_layers

    def ffn_kinds(self) -> tuple[str, ...]:
        return ("dense",) * self.num_hidden_layers

    def mixer(self, kind: str):
        return functools.partial(  # the weights hold no head norm and no gate: plain attention
            softmax_attention, heads=self.num_attention_heads, kv_heads=self.num_key_value_heads,
            head_dim=self.head_dim, rotary_dim=self.head_dim, theta=self.rope_theta,
            eps=self.rms_norm_eps, centred=False, gated=False,
        ), ATTN_SCOPE

    def norm(self, x, w):
        return rms_norm(x, w, self.rms_norm_eps, centred=False)

    def init(self, key: jax.Array) -> dict:
        return init_lm_params(self, key)

    def loss(self, params, ids, labels, *, batch_sharding=None):
        return loop_loss(params, ids, labels, cfg=self, batch_sharding=batch_sharding)


def init_lm_params(cfg: OuroConfig, key: jax.Array) -> dict:
    """Weights from a key: matrices and the gate's vector normal(0, 0.02), its
    bias 0 (so ``lambda`` starts near a half and the exit distribution near
    (1/2, 1/4, 1/8, 1/8)), norm weights 1."""
    h, ff = cfg.hidden_size, cfg.intermediate_size
    q_width, kv_width = cfg.num_attention_heads * cfg.head_dim, cfg.num_key_value_heads * cfg.head_dim

    def layer(key):
        ks = jax.random.split(key, 7)
        return {
            "norm1": jnp.ones((h,)), "norm1_out": jnp.ones((h,)), "norm2": jnp.ones((h,)), "norm2_out": jnp.ones((h,)),
            "attn": {"w_q": normal(ks[0], h, q_width), "w_k": normal(ks[1], h, kv_width),
                     "w_v": normal(ks[2], h, kv_width), "w_o": normal(ks[3], q_width, h)},
            "mlp": {"w_gate": normal(ks[4], h, ff), "w_up": normal(ks[5], h, ff), "w_down": normal(ks[6], ff, h)},
        }

    k_emb, k_head, k_exit, k_layers = jax.random.split(key, 4)
    return {
        "embed": normal(k_emb, cfg.vocab_size, h),
        "layers": [layer(k) for k in jax.random.split(k_layers, cfg.num_hidden_layers)],
        "final_norm": jnp.ones((h,)),
        "head": normal(k_head, h, cfg.vocab_size),
        "exit": {"w": normal(k_exit, h), "b": jnp.zeros(())},
    }
