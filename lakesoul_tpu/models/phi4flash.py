"""Decoder-hybrid-decoder causal LM of the ``phi4flash`` family
(Phi-4-mini-flash-reasoning; SambaY, "Decoder-Hybrid-Decoder Architecture for
Efficient Reasoning with Long Generation", arXiv:2507.06607, with differential
attention, arXiv:2410.05258): a first decoder of Mamba-1 and window-attention
layers, and a second whose layers compute no state of their own but read the
first's: gated memory units over ONE Mamba layer's scan output and
differential cross-attention over ONE attention layer's keys and values.  This
file is the family: its configuration, its weights and its mixers; the stack
(with its contract for a state one layer publishes and later ones read), the
head and the loss are ``models/causal_lm.py``'s, the attention kernels
``models/attention.py``'s and the scan ``models/selective_scan.py``'s.

By published layer index ``i`` of ``n = num_hidden_layers`` (the public
implementation's rule; ``mb_per_layer`` 2):

- ``i < n/2``: even ``"ssm"`` (Mamba-1), odd ``"swa"`` (differential
  attention under ``sliding_window``);
- ``i = n/2``: ``"ssm"``, whose scan output ``y`` (before the gate) is the
  memory ``m`` the second decoder reads; ``i = n/2 + 1``: ``"attn"``
  (differential attention, full causal), whose ``k``, ``v`` it reads;
- ``i >= n/2 + 2``: even ``"gmu"``, odd ``"xattn"`` (differential
  cross-attention, full causal: a query of its own alone).

A layer is ``h = x + mixer(LN1(x)); x' = h + MLP(LN2(h))``, LayerNorm with
weight and bias at ``layer_norm_eps``; a final LayerNorm; logits through the
embedding (tied).  With ``x`` a normed row, E = ``mamba_expand`` x hidden,
N = ``mamba_d_state``, R = ``mamba_dt_rank``, K = ``mamba_d_conv``, d the
head size:

- Mamba-1 (arXiv:2312.00752): ``[u, z] = x W_in``; ``u = silu(conv_K(u) +
  b_conv)`` (depthwise, causal); ``[delta, B, C] = u W_x``; ``Delta =
  softplus(delta W_dt + b_dt)``; ``A = -exp(A_log)``; the recurrence of
  ``selective_scan.py`` → ``y``; ``out = (y * silu(z)) W_out``.
- GMU: ``out = (m * silu(x W_1)) W_2``, ``m`` the same token's.
- Differential attention: ``q = x W_q + b_q`` (``num_attention_heads`` of d),
  ``k``, ``v`` likewise (``num_key_value_heads`` of d); no rotary, no head
  norm.  Adjacent heads pair: query pair ``p`` is heads ``2p, 2p+1`` (``q1``,
  ``q2``); key-value pair ``g`` is ``k1, k2`` = key heads ``2g, 2g+1`` and
  ``V = [v_2g ; v_2g+1]`` (2d wide); pair ``p`` reads ``g = p // (pairs a
  key-value pair)``.  ``A1 = softmax(q1 k1^T / sqrt(d))``, ``A2`` likewise
  under the layer's mask; ``o_p = (A1 - lambda A2) V``, then
  ``RMSNorm_2d(o_p; g_sub, 1e-5) (1 - lambda_init)``; ``out = concat_p(o_p)
  W_o + b_o``.  ``lambda = exp(lq1 . lk1) - exp(lq2 . lk2) + lambda_init``,
  ``lambda_init = 0.8 - 0.6 exp(-0.3 i)`` by the PUBLISHED index (a buffer of
  the layer: nothing trains it).  A cross layer has ``W_q, b_q, W_o, b_o``,
  its lambdas and ``g_sub``.
- MLP: SwiGLU ``intermediate_size`` wide, no bias (``causal_lm.dense_mlp``).

Matrix products run in ``cfg.dtype`` (bfloat16) with float32 accumulation;
the scan's state, ``Delta``, the exponentials, the softmaxes, ``lambda``, both
norms and the loss are float32.

Every Mamba layer hands its scan output to the stack as a shared state
(``shares``: the stack then keeps it once for the backward pass and the
forward kernel runs once), and an attention source its keys and values; a
gated memory unit reads the nearest Mamba layer's before it, a cross layer
the nearest ``"attn"`` layer's, which by the rule above are layers ``n/2`` and
``n/2 + 1``.

This chip may hold a run of the published layers (``layers_held``: published
indices, in order) and a slice of the vocabulary; a held ``gmu`` or ``xattn``
needs its source held before it.  Departures from the published model: no
document boundaries (a row is one packed sequence; the scan's state and the
masks do not restart inside it); the mechanism the architecture is named for
(prefill that skips the second decoder, decoding against one layer's cache)
is a serving matter and not modelled; ``fc1`` is held as its two halves
``w_gate``, ``w_up`` (a permutation under seeded weights).
"""

from __future__ import annotations

import functools
import math
from dataclasses import dataclass

import jax
import jax.numpy as jnp

from lakesoul_tpu.models.attention import paired_attention
from lakesoul_tpu.models.causal_lm import ATTN_SCOPE, causal_conv, lm_loss, normal_init as normal
from lakesoul_tpu.models.norms import layer_norm, rms_norm
from lakesoul_tpu.models.selective_scan import SCAN_KEPT, scan_takes, selective_scan

SSM_SCOPE = "lakesoul.lm.ssm"      # a Mamba layer's mixer whole; the scan kernels inside it under their own names
SWA_SCOPE = "lakesoul.lm.swa"      # the window layers' mixers; the full source's stands under ATTN_SCOPE
GMU_SCOPE = "lakesoul.lm.gmu"
XATTN_SCOPE = "lakesoul.lm.xattn"  # the cross layers' mixers
SUBLN_EPS = 1e-5                   # the norm over a pair's 2d output channels (arXiv:2410.05258)
MEMORY, KEYS_VALUES = "memory", "kv"  # the two shared states' names

# the switches the layers are written for: any other published value is refused, not ignored
_EXPECTED = {
    "hidden_act": "silu", "tie_word_embeddings": True, "mb_per_layer": 2, "mlp_bias": False, "lm_head_bias": False,
    "embd_pdrop": 0, "resid_pdrop": 0,
}


@dataclass(frozen=True)
class Phi4FlashConfig:
    """The published ``config.json`` keys the layers read, under their
    published names; the ``mamba_*`` sizes the catalog row does not give (the
    ``phi4flash`` configuration class's defaults, the Mamba paper's); and
    what this chip holds of the model."""

    vocab_size: int = 200064
    hidden_size: int = 2560
    num_hidden_layers: int = 32
    intermediate_size: int = 10240
    num_attention_heads: int = 40
    num_key_value_heads: int = 20
    sliding_window: int = 512
    layer_norm_eps: float = 1e-5
    mamba_d_state: int = 16
    mamba_d_conv: int = 4
    mamba_expand: int = 2
    mamba_dt_rank: int = 160  # ceil(hidden_size / 16)
    # this chip's share: the published indices of the layers it holds, in order (None: all of them)
    layers_held: tuple[int, ...] | None = None
    dtype: str = "bfloat16"

    @staticmethod
    def from_published(model: dict, **share) -> "Phi4FlashConfig":
        """From a dict with the published keys (others are ignored).  The
        family's other switches are held to what the layers compute."""
        wrong = {k: model[k] for k, v in _EXPECTED.items() if model.get(k, v) != v}
        if wrong:
            raise ValueError(f"the phi4flash layers are written for {_EXPECTED}; the configuration says {wrong}")
        names = Phi4FlashConfig.__dataclass_fields__
        return Phi4FlashConfig(**{**{k: v for k, v in model.items() if k in names}, **share})

    def __post_init__(self):
        if self.num_hidden_layers % 4:
            raise ValueError(f"num_hidden_layers={self.num_hidden_layers}: each decoder is whole (Mamba, attention) pairs")
        if self.hidden_size % self.num_attention_heads or self.num_attention_heads % self.num_key_value_heads \
                or self.num_key_value_heads % 2:
            raise ValueError(
                f"{self.num_attention_heads} query heads on {self.num_key_value_heads} key-value heads over"
                f" {self.hidden_size} channels do not pair"
            )
        if self.layers_held is not None:
            object.__setattr__(self, "layers_held", tuple(self.layers_held))
        seen = set()
        for i, kind in zip(self.held(), self.layer_kinds(), strict=True):
            source = {"gmu": "ssm", "xattn": "attn"}.get(kind)
            if source is not None and source not in seen:
                raise ValueError(f"layer {i} ({kind}) reads a {source} layer's state and none is held before it")
            seen.add(kind)

    @property
    def head_dim(self) -> int:
        return self.hidden_size // self.num_attention_heads

    @property
    def inner(self) -> int:
        return self.mamba_expand * self.hidden_size

    kept = (SCAN_KEPT,)  # what ``causal_lm._row_by_row`` keeps of a row beside the attention kernels' two

    def held(self) -> tuple[int, ...]:
        return tuple(range(self.num_hidden_layers)) if self.layers_held is None else self.layers_held

    def layer_kinds(self) -> tuple[str, ...]:
        half = self.num_hidden_layers // 2

        def kind(i: int) -> str:
            if i % 2 == 0:
                return "ssm" if i <= half else "gmu"
            return "swa" if i < half else "attn" if i == half + 1 else "xattn"

        return tuple(kind(i) for i in self.held())

    def ffn_kinds(self) -> tuple[str, ...]:
        return ("dense",) * len(self.held())

    def mixer(self, kind: str):
        if kind == "ssm":
            return gated_scan_output, SSM_SCOPE
        if kind == "gmu":
            return gated_memory_unit, GMU_SCOPE
        attend = functools.partial(differential_attention, cfg=self)
        if kind == "swa":  # a function of its own input alone: its keys and values are no one else's
            window = self.sliding_window
            return lambda x, p: attend(x, p, keys_values(x, p), window=window), SWA_SCOPE
        return attend, ATTN_SCOPE if kind == "attn" else XATTN_SCOPE

    def shares(self, kind: str):
        """``causal_lm.lm_layer``'s: (the shared state a kind's mixer reads,
        what makes it from the layer's own input or None where an earlier
        layer's is read); None for a mixer of its own input alone."""
        return {
            "ssm": (MEMORY, functools.partial(mamba_scan, cfg=self)), "gmu": (MEMORY, None),
            "attn": (KEYS_VALUES, keys_values), "xattn": (KEYS_VALUES, None),
        }.get(kind)

    def norm(self, x, w):
        return layer_norm(x, w["w"], w["b"], self.layer_norm_eps)

    def init(self, key: jax.Array) -> dict:
        return init_lm_params(self, key)

    def loss(self, params, ids, labels, *, batch_sharding=None):
        """``lm_loss`` and, among its counts, the rows of the scan by what
        ran them (host integers: the shapes decide)."""
        loss, counts = lm_loss(params, ids, labels, cfg=self, batch_sharding=batch_sharding)
        rows = ids.shape[0] * self.layer_kinds().count("ssm")
        in_kernel = scan_takes(self.inner, self.mamba_d_state) is not None
        return loss, dict(counts, ssm_rows_kernel=rows if in_kernel else 0, ssm_rows_twin=0 if in_kernel else rows)


def lambda_init(index: int) -> float:
    """A differential layer's ``lambda_init`` by its published index."""
    return 0.8 - 0.6 * math.exp(-0.3 * index)


def init_lm_params(cfg: Phi4FlashConfig, key: jax.Array) -> dict:
    """Weights from a key: matrices normal(0, 0.02) and the convolution's taps
    normal(0, K^-1/2); the biases of the projections and of the convolution
    normal(0, 0.02) (zero biases would hide a bias left out); ``A_log =
    log(1..N)`` a channel, ``D`` 1, ``b_dt`` the inverse softplus of values
    log-uniform in [1e-3, 0.1], the lambdas normal(0, 0.1), ``g_sub`` and the
    LayerNorms' weights 1, their biases 0.  ``buffers["layers"][i]["mixer"]``
    of an attention layer holds its ``lambda_init``."""
    h, ff, e, n, r, taps = (cfg.hidden_size, cfg.intermediate_size, cfg.inner, cfg.mamba_d_state,
                            cfg.mamba_dt_rank, cfg.mamba_d_conv)
    d = cfg.head_dim
    q_width, kv_width = cfg.num_attention_heads * d, cfg.num_key_value_heads * d
    f32 = jnp.float32

    def ln():
        return {"w": jnp.ones((h,)), "b": jnp.zeros((h,))}

    def ssm(key):
        ks = jax.random.split(key, 7)
        dt = jnp.exp(jax.random.uniform(ks[6], (e,)) * (math.log(0.1) - math.log(1e-3)) + math.log(1e-3))
        return {
            "w_in": normal(ks[0], h, 2 * e), "conv": (jax.random.normal(ks[1], (e, taps)) * taps**-0.5).astype(f32),
            "b_conv": normal(ks[2], e), "w_x": normal(ks[3], e, r + 2 * n), "w_dt": normal(ks[4], r, e),
            "b_dt": dt + jnp.log(-jnp.expm1(-dt)), "w_out": normal(ks[5], e, h),
            "A_log": jnp.broadcast_to(jnp.log(jnp.arange(1, n + 1, dtype=f32)), (e, n)), "D": jnp.ones((e,)),
        }

    def attention(key, cross: bool):
        ks = jax.random.split(key, 12)
        p = {
            "w_q": normal(ks[0], h, q_width), "b_q": normal(ks[1], q_width),
            "w_o": normal(ks[2], q_width, h), "b_o": normal(ks[3], h), "g_sub": jnp.ones((2 * d,)),
            **{name: (jax.random.normal(k, (d,)) * 0.1).astype(f32)
               for name, k in zip(("lq1", "lk1", "lq2", "lk2"), ks[4:8])},
        }
        if not cross:
            p.update(w_k=normal(ks[8], h, kv_width), b_k=normal(ks[9], kv_width),
                     w_v=normal(ks[10], h, kv_width), b_v=normal(ks[11], kv_width))
        return p

    def gmu(key):
        k1, k2 = jax.random.split(key)
        return {"w_1": normal(k1, h, e), "w_2": normal(k2, e, h)}

    mixers = {"ssm": ssm, "gmu": gmu, "xattn": functools.partial(attention, cross=True),
              "swa": functools.partial(attention, cross=False), "attn": functools.partial(attention, cross=False)}

    def layer(key, kind):
        k_mix, *ks = jax.random.split(key, 4)
        return {
            "norm1": ln(), "norm2": ln(), kind: mixers[kind](k_mix),
            "mlp": {"w_gate": normal(ks[0], h, ff), "w_up": normal(ks[1], h, ff), "w_down": normal(ks[2], ff, h)},
        }

    kinds = cfg.layer_kinds()
    k_emb, k_layers = jax.random.split(key)
    return {
        "embed": normal(k_emb, cfg.vocab_size, h),
        "layers": [layer(k, kind) for k, kind in zip(jax.random.split(k_layers, len(kinds)), kinds)],
        "final_norm": ln(),
        "buffers": {"layers": [
            {"mixer": {"lambda_init": jnp.float32(lambda_init(i))}} if kind in ("swa", "attn", "xattn") else {}
            for i, kind in zip(cfg.held(), kinds)
        ]},
    }


# ------------------------------------------------------------- the mixers


def mamba_scan(x, p, *, cfg: Phi4FlashConfig):
    """A Mamba-1 layer up to its gate: x [B, T, h] (normed) → the scan's
    output ``y`` [B, T, E] in x's type: what the layer's own gate reads
    (:func:`gated_scan_output`) and, of the memory source, every gated memory
    unit after it."""
    dtype = x.dtype
    f32 = jnp.float32
    e, n, r = cfg.inner, cfg.mamba_d_state, cfg.mamba_dt_rank
    u = x @ p["w_in"][:, :e].astype(dtype)
    u = jax.nn.silu(causal_conv(u, p["conv"]).astype(f32) + p["b_conv"]).astype(dtype)
    dbc = jnp.dot(u, p["w_x"].astype(dtype), preferred_element_type=f32)
    delta = jax.nn.softplus(
        jnp.dot(dbc[..., :r].astype(dtype), p["w_dt"].astype(dtype), preferred_element_type=f32) + p["b_dt"]
    )
    a = -jnp.exp(p["A_log"].astype(f32))
    return selective_scan(u, delta, a, dbc[..., r:r + n], dbc[..., r + n:], p["D"])


def gated_scan_output(x, p, y):
    """The rest of a Mamba-1 layer: ``(y * silu(z)) W_out`` with ``z`` the
    second half of ``x W_in``."""
    dtype = x.dtype
    f32 = jnp.float32
    z = x @ p["w_in"][:, y.shape[-1]:].astype(dtype)
    return (y.astype(f32) * jax.nn.silu(z.astype(f32))).astype(dtype) @ p["w_out"].astype(dtype)


def gated_memory_unit(x, p, m):
    """``(m * silu(x W_1)) W_2``: x [B, T, h] (normed), ``m`` [B, T, E] the
    memory source's scan output at the same tokens."""
    dtype = x.dtype
    f32 = jnp.float32
    gate = jax.nn.silu((x @ p["w_1"].astype(dtype)).astype(f32))
    return (m.astype(f32) * gate).astype(dtype) @ p["w_2"].astype(dtype)


def keys_values(x, p):
    """An attention layer's keys and values as the products leave them:
    x [B, T, h] (normed) → (k, v) [B, T, kv heads x d] in x's type; of the
    key-value source, what every cross layer after it reads."""
    dtype = x.dtype

    def projected(w, b):
        return (jnp.dot(x, w.astype(dtype), preferred_element_type=jnp.float32) + b).astype(dtype)

    return projected(p["w_k"], p["b_k"]), projected(p["w_v"], p["b_v"])


def differential_attention(x, p, kv, *, cfg: Phi4FlashConfig, window: int | None = None):
    """The differential-attention mixer over given keys and values: x
    [B, T, h] (normed), ``kv`` as :func:`keys_values` leaves them (this
    layer's own, or the source's at a cross layer) → [B, T, h].  The two score
    maps a pair go through ``attention.paired_attention``; the difference,
    the norm over a pair's 2d channels and the scale here, float32."""
    dtype = x.dtype
    f32 = jnp.float32
    b, t, _ = x.shape
    d = cfg.head_dim
    q = jnp.dot(x, p["w_q"].astype(dtype), preferred_element_type=f32) + p["b_q"]
    q = (q * d**-0.5).astype(dtype).reshape(b, t, cfg.num_attention_heads, d)
    k, v = (a.reshape(b, t, cfg.num_key_value_heads, d) for a in kv)
    o1, o2 = paired_attention(q, k, v, window)  # [B, T, pairs, 2d] each
    lam = jnp.exp(jnp.sum(p["lq1"] * p["lk1"])) - jnp.exp(jnp.sum(p["lq2"] * p["lk2"])) + p["lambda_init"]
    o = rms_norm(o1.astype(f32) - lam * o2.astype(f32), p["g_sub"], SUBLN_EPS, centred=False) * (1.0 - p["lambda_init"])
    out = jnp.dot(o.astype(dtype).reshape(b, t, -1), p["w_o"].astype(dtype), preferred_element_type=f32) + p["b_o"]
    return out.astype(dtype)
