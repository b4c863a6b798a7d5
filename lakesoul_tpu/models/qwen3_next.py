"""Hybrid causal LM of the Qwen3-Next family: Gated DeltaNet layers and gated
softmax-attention layers in one stack, a dropless mixture of experts after
every mixer.  This file is the family: its configuration, its weights and its
two mixers; the layer stack, the head, the loss and the attention under the
gate are ``models/causal_lm.py``'s, shared with the other families.

Layer ``i`` is gated attention where ``(i + 1) % full_attention_interval == 0``
and Gated DeltaNet otherwise; nothing here branches on a model's name.  A layer
is ``x = x + mixer(norm(x)); x = x + moe(norm(x))`` with zero-centred RMS norms
in float32, then a final norm and an untied head.  Matrix products run in
``cfg.dtype`` (bfloat16) with float32 accumulation; norms, the router's
softmax, the DeltaNet decay's running sum and its state are float32.

Departures from the published model: no multi-token-prediction module, no
router auxiliary loss, no document boundaries (a row is one packed sequence).
Within ``in_proj_qkvz`` the columns are ``[q | k | v | z]`` by kind, heads in
order inside each, not interleaved per key head as the published checkpoint
stores them; with weights from a seed the two are the same model.
"""

from __future__ import annotations

import functools
from dataclasses import dataclass

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from lakesoul_tpu.models.causal_lm import (  # noqa: F401  (the stack, under the names this family's callers import)
    ATTN_SCOPE,
    _rms_norm,
    causal_conv,
    lm_head,
    lm_hidden,
    lm_logits,
    lm_loss,
    normal_init as normal,
    softmax_attention,
)
from lakesoul_tpu.parallel.moe import route_top_k
from lakesoul_tpu.vector.kernels import _on_tpu

GDN_SCOPE = "lakesoul.lm.gdn"
GDN_CHUNK = 128    # tokens a DeltaNet chunk holds: a v5e matrix unit is 128 wide (the family's public kernels use 64)


@dataclass(frozen=True)
class Qwen3NextConfig:
    """The published ``config.json`` keys the layers read, under their
    published names, and what this chip holds of the model."""

    vocab_size: int = 151936
    hidden_size: int = 2048
    num_hidden_layers: int = 48
    full_attention_interval: int = 4
    # gated attention
    num_attention_heads: int = 16
    num_key_value_heads: int = 2
    head_dim: int = 256
    partial_rotary_factor: float = 0.25
    rope_theta: float = 1e7
    # Gated DeltaNet
    linear_num_key_heads: int = 16
    linear_num_value_heads: int = 32
    linear_key_head_dim: int = 128
    linear_value_head_dim: int = 128
    linear_conv_kernel_dim: int = 4
    # experts
    num_experts: int = 512
    num_experts_per_tok: int = 10
    moe_intermediate_size: int = 512
    shared_expert_intermediate_size: int = 512
    rms_norm_eps: float = 1e-6
    # this chip's share: (first expert, how many) of ``num_experts``
    experts_held: tuple[int, int] = (0, 512)
    dtype: str = "bfloat16"

    @staticmethod
    def from_published(model: dict, **share) -> "Qwen3NextConfig":
        """From a dict with the published keys (others are ignored)."""
        names = Qwen3NextConfig.__dataclass_fields__
        return Qwen3NextConfig(**{k: v for k, v in model.items() if k in names}, **share)

    def layer_kinds(self) -> tuple[str, ...]:
        return tuple(
            "attn" if (i + 1) % self.full_attention_interval == 0 else "gdn"
            for i in range(self.num_hidden_layers)
        )

    def ffn_kinds(self) -> tuple[str, ...]:
        return ("moe",) * self.num_hidden_layers  # decoder_sparse_step 1, no mlp_only_layers

    def mixer(self, kind: str):
        if kind == "gdn":
            return functools.partial(gated_delta_net, cfg=self), GDN_SCOPE
        return functools.partial(gated_attention, cfg=self), ATTN_SCOPE

    def norm(self, x, w):
        return _rms_norm(x, w, self.rms_norm_eps)

    def route(self, x, router_w, bias):
        del bias  # the family has none: no assignment is moved
        return *route_top_k(x, router_w, top_k=self.num_experts_per_tok), jnp.int32(0)

    def init(self, key: jax.Array) -> dict:
        return init_lm_params(self, key)

    def loss(self, params, ids, labels, *, batch_sharding=None):
        return lm_loss(params, ids, labels, cfg=self, batch_sharding=batch_sharding)

    @property
    def key_dim(self) -> int:
        return self.linear_num_key_heads * self.linear_key_head_dim

    @property
    def value_dim(self) -> int:
        return self.linear_num_value_heads * self.linear_value_head_dim


def init_lm_params(cfg: Qwen3NextConfig, key: jax.Array) -> dict:
    """Weights from a key: matrices normal(0, 0.02); ``A_log = log U(0, 16)``,
    ``dt_bias = 1``; zero-centred norm weights 0, the DeltaNet output norm 1."""
    h, f = cfg.hidden_size, cfg.moe_intermediate_size
    count = cfg.experts_held[1]

    def layer(key, kind):
        ks = jax.random.split(key, 12)
        if kind == "gdn":
            hv = cfg.linear_num_value_heads
            mixer = {
                "w_qkvz": normal(ks[0], h, 2 * cfg.key_dim + 2 * cfg.value_dim),
                "w_ba": normal(ks[1], h, 2 * hv),
                "conv": normal(ks[2], 2 * cfg.key_dim + cfg.value_dim, cfg.linear_conv_kernel_dim),
                "A_log": jnp.log(jax.random.uniform(ks[3], (hv,), minval=0.0, maxval=16.0)),
                "dt_bias": jnp.ones((hv,)),
                "norm": jnp.ones((cfg.linear_value_head_dim,)),
                "w_o": normal(ks[4], cfg.value_dim, h),
            }
        else:
            heads, kv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
            mixer = {
                "w_q": normal(ks[0], h, heads * 2 * d),  # per head: query, then gate
                "w_k": normal(ks[1], h, kv * d),
                "w_v": normal(ks[2], h, kv * d),
                "w_o": normal(ks[3], heads * d, h),
                "q_norm": jnp.zeros((d,)),
                "k_norm": jnp.zeros((d,)),
            }
        fs = cfg.shared_expert_intermediate_size
        moe = {
            "router": normal(ks[5], h, cfg.num_experts),
            "w_gate": normal(ks[6], count, h, f),
            "w_up": normal(ks[7], count, h, f),
            "w_down": normal(ks[8], count, f, h),
            "shared": {
                "w_gate": normal(ks[9], h, fs),
                "w_up": normal(ks[10], h, fs),
                "w_down": normal(ks[11], fs, h),
                "gate": normal(jax.random.fold_in(key, 12), h),
            },
        }
        return {"norm1": jnp.zeros((h,)), kind: mixer, "norm2": jnp.zeros((h,)), "moe": moe}

    k_emb, k_head, k_layers = jax.random.split(key, 3)
    kinds = cfg.layer_kinds()
    return {
        "embed": normal(k_emb, cfg.vocab_size, h),
        "layers": [layer(k, kind) for k, kind in zip(jax.random.split(k_layers, len(kinds)), kinds)],
        "final_norm": jnp.zeros((h,)),
        "head": normal(k_head, h, cfg.vocab_size),
    }


# -------------------------------------------------------- Gated DeltaNet


def _mm_high(a, b):
    # three bfloat16 passes: float32 products to about 2**-16
    return jnp.matmul(a, b, precision=jax.lax.Precision.HIGH)


def _lower_left(row, col, s):
    """Level ``s`` of the block inversion: inside each diagonal block of
    ``2 s`` the lower-left ``s x s`` quarter."""
    return (row // (2 * s) == col // (2 * s)) & ((row // s) % 2 == 1) & ((col // s) % 2 == 0)


def _chunk_decay(g):
    """g [..., C] float32, a chunk's running log decay → [..., C, C]:
    ``exp(g_i - g_j)`` for ``i >= j``, else 0.  Masked before the exponential:
    above the diagonal the difference is positive and may overflow."""
    c = g.shape[-1]
    lower = jnp.tril(jnp.ones((c, c), bool))
    return jnp.exp(jnp.where(lower, g[..., :, None] - g[..., None, :], -jnp.inf))


def _chunk_system(a, log_decay):
    """The ``A`` of :func:`unit_lower_inverse`."""
    return a if log_decay is None else jnp.tril(a * _chunk_decay(log_decay), -1)


def _unit_lower_inverse_jnp(a, dtype, log_decay=None):
    """The doubling as whole-array ``jnp`` operations: what a chunk narrower
    than a lane tile runs, and the kernel's twin and reference."""
    a = _chunk_system(a, log_decay)
    c = a.shape[-1]

    def mm(x, y):
        return jnp.matmul(x.astype(dtype), y.astype(dtype), preferred_element_type=jnp.float32)

    at = jnp.arange(c)
    eye = jnp.eye(c, dtype=jnp.float32)
    x = jnp.broadcast_to(eye, a.shape)
    s = 1
    while s < c:
        x = x - mm(mm(x, jnp.where(_lower_left(at[:, None], at[None, :], s), a, 0.0)), x)
        s *= 2
    return x + _mm_high(x, eye - _mm_high(eye + a, x))


def _unit_lower_inverse_kernel(a_ref, *refs, dtype):
    """A block of systems [G, C, C], in VMEM from ``A``'s making to the Newton
    step; ``refs`` are the log decay [G, 1, C], where the system has one, and
    the result.  ``X`` is block diagonal at every level, so ``X L X`` is
    ``X A X`` inside the level's lower-left quarters with the same terms in
    every sum: the mask moves from the factor to the result, ``A`` is rounded
    once, and level 1 (``X = I``) needs no product."""
    *g_ref, x_ref = refs
    a = a_ref[...]
    c = a.shape[-1]
    row = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0)
    col = jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    eye = (row == col).astype(jnp.float32)
    if g_ref:
        g_j = jnp.broadcast_to(g_ref[0][...], a.shape)  # [G, i, j] = g_j; its transpose g_i
        a = a * jnp.exp(jnp.where(row > col, jnp.swapaxes(g_j, 1, 2) - g_j, -jnp.inf))

    def mm(x, y, **kwargs):
        return jnp.einsum("gij,gjk->gik", x, y, preferred_element_type=jnp.float32, **kwargs)

    a_lo = a.astype(dtype)
    x = eye - jnp.where(_lower_left(row, col, 1), a_lo.astype(jnp.float32), 0.0)
    s = 2
    while s < c:
        x_lo = x.astype(dtype)
        x = jnp.where(_lower_left(row, col, s), x - mm(mm(x_lo, a_lo).astype(dtype), x_lo), x)
        s *= 2
    highest = jax.lax.Precision.HIGHEST  # Mosaic has no three-pass product: six
    x_ref[...] = x + mm(x, eye - x - mm(a, x, precision=highest), precision=highest)


INVERSE_BLOCK = 8  # systems a grid step holds: 64 KB each, in and out, twice for the pipeline


def _unit_lower_inverse_pallas(a, dtype, log_decay=None, *, interpret: bool):
    c = a.shape[-1]
    flat = a.reshape(-1, c, c)

    def block(*shape):
        return pl.BlockSpec((INVERSE_BLOCK, *shape), lambda i: (i, 0, 0))

    decay = [] if log_decay is None else [log_decay.reshape(-1, 1, c)]
    x = pl.pallas_call(
        functools.partial(_unit_lower_inverse_kernel, dtype=dtype),
        out_shape=jax.ShapeDtypeStruct(flat.shape, jnp.float32),
        grid=(pl.cdiv(flat.shape[0], INVERSE_BLOCK),),
        in_specs=[block(c, c)] + [block(1, c) for _ in decay],
        out_specs=block(c, c),
        compiler_params=pltpu.CompilerParams(dimension_semantics=("parallel",)),
        name="unit_lower_inverse",
        interpret=interpret,
    )(flat, *decay)
    return x.reshape(a.shape)


@functools.partial(jax.custom_vjp, nondiff_argnums=(1,))
def unit_lower_inverse(a, dtype=jnp.float32, log_decay=None):
    """``(I + A)^-1`` in float32 for ``A`` [..., C, C] strictly lower
    triangular, ``C`` a power of two; with ``log_decay`` g [..., C], ``A`` is
    the strictly lower part of ``a_ij exp(g_i - g_j)`` (a DeltaNet chunk's
    system: ``a`` the keys' products, ``g`` the running log decay).

    By block inversion from the diagonal outwards: with the inverses ``X1, X2``
    of two neighbouring diagonal blocks known, the block ``[[D1, 0], [L, D2]]``
    has the inverse ``[[X1, 0], [-X2 L X1, X2]]``.  Each doubling is two
    products over the whole matrix (``X - X L X`` with ``L`` masked to the
    lower-left blocks), which is what a matrix unit is for; their factors are
    rounded to ``dtype`` (one bfloat16 pass each on a TPU), and one Newton step
    ``X + X (I - M X)`` at three passes or more squares the error that leaves.

    Where a chunk fills a lane tile (``C % 128 == 0``) one Pallas kernel does
    all of it, a block of systems at a time: a 128 x 128 system is 64 KB and
    lives in VMEM from ``A``'s making to the Newton step, so HBM sees ``a``
    once and ``X`` once (as eight whole-array fusions the levels were bound by
    HBM traffic: PERF.md section 6, PR 29).  It runs compiled on a TPU and in
    the Pallas interpreter elsewhere.  A narrower chunk takes the same doubling
    as ``jnp`` operations, the kernel's twin.  (The compiler's own triangular
    solve took 21 ms a call here on a v5e: PERF.md section 6, PR 28.)"""
    c = a.shape[-1]
    if c & (c - 1):
        raise ValueError(f"chunk {c} is not a power of two")
    if c % 128:
        return _unit_lower_inverse_jnp(a, dtype, log_decay)
    return _unit_lower_inverse_pallas(a, dtype, log_decay, interpret=not _on_tpu())


def _unit_lower_inverse_fwd(a, dtype, log_decay):
    x = unit_lower_inverse(a, dtype, log_decay)
    return x, (x, None if log_decay is None else (a, log_decay))


def _unit_lower_inverse_bwd(dtype, saved, g):
    # d(M^-1) = -M^-1 dM M^-1, and only the strictly lower part of M moves
    x, decayed = saved
    xt = jnp.swapaxes(x, -1, -2)
    d_system = -jnp.tril(_mm_high(_mm_high(xt, g), xt), -1)
    if decayed is None:
        return d_system, None
    return jax.vjp(_chunk_system, *decayed)[1](d_system)


unit_lower_inverse.defvjp(_unit_lower_inverse_fwd, _unit_lower_inverse_bwd)


def chunk_gated_delta_rule(q, k, v, g, beta, *, chunk: int | None = None):
    """The gated delta rule, a chunk of tokens at a time.

    q, k [B, T, H, dk] (normalised and scaled), v [B, T, H, dv], g [B, T, H]
    float32 log decay, beta [B, T, H] float32 → o [B, T, H, dv], equal to the
    recurrence ``S' = exp(g_t) S; u = beta_t (v_t - S'^T k_t); S = S' + k_t u^T;
    o_t = S^T q_t`` from ``S_0 = 0``.  Inside a chunk the tokens' updates are
    solved together: a unit lower-triangular system, made from the keys'
    products and the decay and inverted for every chunk at once by
    :func:`unit_lower_inverse` (at ``GDN_CHUNK`` = 128 one Pallas kernel with
    each system in VMEM; a chunk under 128, as the tests use, takes its ``jnp``
    twin).  Across chunks the float32 state is carried by a scan whose backward
    pass keeps one state a chunk and computes the rest of the chunk again."""
    chunk = chunk or GDN_CHUNK
    lo = q.dtype
    f32 = jnp.float32
    b, t, h, dk = q.shape
    pad = -t % chunk
    if pad:  # a token with k = 0 and beta = 0 leaves the state as it is
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))) for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (g, beta))
    n = (t + pad) // chunk
    # chunks first, for the scan: [n, B, chunk, H, ...]
    q, k, v, g, beta = (
        jnp.moveaxis(a.reshape(b, n, chunk, *a.shape[2:]), 1, 0) for a in (q, k, v, g, beta)
    )
    gc = jnp.cumsum(g.astype(f32), axis=2)  # the decay's running sum inside the chunk
    beta = beta.astype(f32)
    kb = (k.astype(f32) * beta[..., None]).astype(lo)
    kk = jnp.einsum("nbihd,nbjhd->nbhij", kb, k, preferred_element_type=f32)
    # (I + A)^-1, A the strictly lower part: every token's update given the ones before it
    inv = unit_lower_inverse(kk, lo, jnp.swapaxes(gc, -1, -2)).astype(lo)

    @jax.checkpoint  # the backward pass keeps the carried state of each chunk and nothing else
    def step(state, xs):
        q_i, k_i, v_i, gc_i, beta_i, inv_i = xs
        s_lo = state.astype(lo)
        grow = jnp.exp(gc_i)[..., None]
        g_last = gc_i[:, -1]  # [B, H]
        vb = (v_i.astype(f32) * beta_i[..., None]).astype(lo)
        kbg = (k_i.astype(f32) * (beta_i[..., None] * grow)).astype(lo)
        u = jnp.einsum("bhij,bjhd->bihd", inv_i, vb, preferred_element_type=f32)
        w = jnp.einsum("bhij,bjhd->bihd", inv_i, kbg)
        v_new = (u - jnp.einsum("bihk,bhkv->bihv", w, s_lo, preferred_element_type=f32)).astype(lo)
        decay = _chunk_decay(jnp.swapaxes(gc_i, -1, -2))  # [B, H, chunk, chunk]
        local = jnp.einsum("bihd,bjhd->bhij", q_i, k_i, preferred_element_type=f32) * decay
        q_in = (q_i.astype(f32) * grow).astype(lo)
        o_i = (jnp.einsum("bihk,bhkv->bihv", q_in, s_lo, preferred_element_type=f32)
               + jnp.einsum("bhij,bjhv->bihv", local.astype(lo), v_new, preferred_element_type=f32))
        k_out = (k_i.astype(f32) * jnp.exp(g_last[:, None] - gc_i)[..., None]).astype(lo)
        state = state * jnp.exp(g_last)[..., None, None] + jnp.einsum(
            "bihk,bihv->bhkv", k_out, v_new, preferred_element_type=f32
        )
        return state, o_i.astype(lo)

    _, o = jax.lax.scan(step, jnp.zeros((b, h, dk, v.shape[-1]), f32), (q, k, v, gc, beta, inv))
    return jnp.moveaxis(o, 0, 1).reshape(b, t + pad, h, -1)[:, :t]


def gated_delta_net(x, p, *, cfg: Qwen3NextConfig, chunk: int | None = None):
    """The Gated DeltaNet mixer: x [B, T, h] (normed) → [B, T, h]."""
    dtype = x.dtype
    f32 = jnp.float32
    b, t, _ = x.shape
    hk, hv = cfg.linear_num_key_heads, cfg.linear_num_value_heads
    dk, dv = cfg.linear_key_head_dim, cfg.linear_value_head_dim
    qkvz = x @ p["w_qkvz"].astype(dtype)
    ba = jnp.dot(x, p["w_ba"].astype(dtype), preferred_element_type=f32)
    conv_dim = 2 * cfg.key_dim + cfg.value_dim
    qkv = jax.nn.silu(causal_conv(qkvz[..., :conv_dim], p["conv"]).astype(f32)).astype(dtype)
    z = qkvz[..., conv_dim:].reshape(b, t, hv, dv)
    q = qkv[..., : cfg.key_dim].reshape(b, t, hk, dk).astype(f32)
    k = qkv[..., cfg.key_dim: 2 * cfg.key_dim].reshape(b, t, hk, dk).astype(f32)
    v = qkv[..., 2 * cfg.key_dim:].reshape(b, t, hv, dv)
    q = q * jax.lax.rsqrt(jnp.sum(q * q, axis=-1, keepdims=True) + 1e-6) * dk**-0.5
    k = k * jax.lax.rsqrt(jnp.sum(k * k, axis=-1, keepdims=True) + 1e-6)
    # each key head serves hv // hk value heads
    q, k = (jnp.repeat(a.astype(dtype), hv // hk, axis=2) for a in (q, k))
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])
    o = chunk_gated_delta_rule(q, k, v, g, beta, chunk=chunk)  # [B, T, hv, dv]
    o = _rms_norm(o, p["norm"], cfg.rms_norm_eps, centred=False) * jax.nn.silu(z.astype(f32))
    return o.reshape(b, t, hv * dv).astype(dtype) @ p["w_o"].astype(dtype)


# ------------------------------------------------------- gated attention


def gated_attention(x, p, *, cfg: Qwen3NextConfig):
    """The gated softmax-attention mixer: x [B, T, h] (normed) → [B, T, h]."""
    return softmax_attention(
        x, p, heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        rotary_dim=int(cfg.head_dim * cfg.partial_rotary_factor), theta=cfg.rope_theta,
        norm=cfg.norm, gated=True,
    )
