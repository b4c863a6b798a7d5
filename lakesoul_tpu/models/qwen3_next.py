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
    causal_conv,
    lm_head,
    lm_hidden,
    lm_logits,
    lm_loss,
    normal_init as normal,
    softmax_attention,
)
from lakesoul_tpu.models.norms import rms_norm
from lakesoul_tpu.parallel.moe import route_top_k
from lakesoul_tpu.utils import platform

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
        return rms_norm(x, w, self.rms_norm_eps)

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
    return _unit_lower_inverse_pallas(a, dtype, log_decay, interpret=not platform.on_tpu())


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


@functools.partial(jax.custom_vjp, nondiff_argnums=(0,))
def _mm(eq, a, b):
    """``einsum(eq, a, b)`` of two factors of one type, summed in float32.  Its
    own rule so that the backward pass's products are of the forward's kind:
    the cotangent rounded to the factors' type (what XLA's default precision
    does to a float32 operand on a TPU; Mosaic runs a product with a float32
    factor in several passes) and each product written so that its result
    needs no transpose after it."""
    return jnp.einsum(eq, a, b, preferred_element_type=jnp.float32)


def _mm_bwd(eq, kept, d):
    a, b = kept
    factors, z = eq.split("->")
    x, y = factors.split(",")
    d = d.astype(a.dtype)
    da = jnp.einsum(f"{z},{y}->{x}", d, b, preferred_element_type=jnp.float32)
    db = jnp.einsum(f"{x},{z}->{y}", a, d, preferred_element_type=jnp.float32)
    return da.astype(a.dtype), db.astype(b.dtype)


_mm.defvjp(lambda eq, a, b: (_mm(eq, a, b), (a, b)), _mm_bwd)


@jax.custom_vjp
def _decayed(state, g_last):
    """state [H, dk, dv] under a chunk's whole decay, g_last [H, 1, 1].  Its own
    rule only because autodiff sums g_last's cotangent over both axes at once,
    which Mosaic does not lower."""
    return state * jnp.exp(g_last)


def _decayed_bwd(kept, d):
    state, g_last = kept
    d_g = jnp.sum(jnp.sum(d * state, axis=2, keepdims=True), axis=1, keepdims=True)
    return d * jnp.exp(g_last), d_g * jnp.exp(g_last)


_decayed.defvjp(lambda state, g_last: (_decayed(state, g_last), (state, g_last)), _decayed_bwd)


def _unit_heads(x, scale: float = 1.0):
    """x [..., d] in float32, each head's vector over its length, times ``scale``."""
    x = x.astype(jnp.float32)
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6) * scale


def _delta_chunk(q, k, v, inv, g_col, g_row, g_last, beta, state, *, eps: float):
    """One chunk of the gated delta rule for a batch of heads, the scan's body
    and the kernels': q, k [H, C, dk] as the convolution leaves them (each
    head's vector is normalised here, q scaled by ``dk**-0.5`` too), v
    [H, C, dv], inv [H, C, C] in the factors' type; a token's running log
    decay three ways, g_col [H, C, 1], g_row [H, 1, C] and the chunk's last
    g_last [H, 1, 1], and beta [H, C, 1], float32; state [H, dk, dv] float32 →
    (o [H, C, dv] float32: each head's output, rounded to the factors' type,
    over its root mean square under ``eps``; the state after the chunk).
    Factors in ``q.dtype``, norms, sums and the state in float32."""
    lo, f32 = q.dtype, jnp.float32
    q, k = _unit_heads(q, q.shape[-1] ** -0.5).astype(lo), _unit_heads(k).astype(lo)
    s_lo = state.astype(lo)
    grow = jnp.exp(g_col)
    vb = (v.astype(f32) * beta).astype(lo)
    kbg = (k.astype(f32) * (beta * grow)).astype(lo)
    u = _mm("hij,hjd->hid", inv, vb)
    w = _mm("hij,hjd->hid", inv, kbg).astype(lo)
    v_new = (u - _mm("hik,hkv->hiv", w, s_lo)).astype(lo)
    c = g_col.shape[1]
    lower = jax.lax.broadcasted_iota(jnp.int32, (c, c), 0) >= jax.lax.broadcasted_iota(jnp.int32, (c, c), 1)
    # masked before the exponential: above the diagonal the difference is positive and may overflow
    decay = jnp.exp(jnp.where(lower, g_col - g_row, -jnp.inf))
    local = _mm("hid,hjd->hij", q, k) * decay
    q_in = (q.astype(f32) * grow).astype(lo)
    o = _mm("hik,hkv->hiv", q_in, s_lo) + _mm("hij,hjv->hiv", local.astype(lo), v_new)
    k_out = (k.astype(f32) * jnp.exp(g_last - g_col)).astype(lo)
    o = o.astype(lo).astype(f32)
    o = o * jax.lax.rsqrt(jnp.mean(o * o, axis=-1, keepdims=True) + eps)
    return o, _decayed(state, g_last) + _mm("hik,hiv->hkv", k_out, v_new)


def _gated_delta_scan(q, k, v, gc, beta, inv, eps):
    """The recurrence across chunks as a ``lax.scan`` over :func:`_delta_chunk`
    with the state in HBM, every (row, head) a batch entry: what a chunk
    narrower than a lane tile runs, and the kernels' twin and reference.
    Arguments and result as :func:`_gated_delta`'s.  The backward pass keeps
    the carried state of each chunk and computes the rest of the chunk again."""
    b, _, hk, dk = q.shape
    _, n, hv, c = gc.shape

    def chunks_first(a, serves=1):  # [B, T, h, d] → [n, B h serves, C, d]
        a = jnp.repeat(a.reshape(b, n, c, a.shape[2], 1, -1), serves, axis=4)
        return a.transpose(1, 0, 3, 4, 2, 5).reshape(n, b * hv, c, -1)

    def scalars_first(a):  # [B, n, hv, ...] → [n, B hv, ...]
        return jnp.moveaxis(a, 1, 0).reshape(n, b * hv, *a.shape[3:])

    @jax.checkpoint
    def step(state, xs):
        q_i, k_i, v_i, inv_i, gc_i, beta_i = xs
        o_i, state = _delta_chunk(
            q_i, k_i, v_i, inv_i, gc_i[..., None], gc_i[:, None], gc_i[:, -1:, None], beta_i[..., None], state,
            eps=eps,
        )
        return state, o_i

    xs = (chunks_first(q, hv // hk), chunks_first(k, hv // hk), chunks_first(v), *map(scalars_first, (inv, gc, beta)))
    _, o = jax.lax.scan(step, jnp.zeros((b * hv, dk, v.shape[-1]), jnp.float32), xs)
    return o.reshape(n, b, hv, c, -1).transpose(1, 0, 3, 2, 4).reshape(v.shape)


GDN_HEADS = 8  # value heads a grid step of the recurrence's kernels holds, where a row has as many
GDN_VMEM_BYTES = 64 * 2**20  # of a v5e's 128 MiB


def _turned(x):
    """A token's scalar along the lanes, [H, 1, C], down the sublanes, [H, C, 1],
    or the other way."""
    h, c = x.shape[0], max(x.shape[1:])
    square = jnp.swapaxes(jnp.broadcast_to(x, (h, c, c)), 1, 2)
    return square[:, :, :1] if x.shape[1] == 1 else square[:, :1]


def _heads_first(ref, heads: int, d: int):
    """A block with its heads of ``d`` channels along the lanes, as the
    projections write them → [heads, C, d]; where the block has fewer, each
    of them serves as many of the ``heads`` in a row."""
    serves = heads * d // ref.shape[1]
    return jnp.stack([ref[:, j // serves * d:(j // serves + 1) * d] for j in range(heads)])


def _heads_along_lanes(ref, x):
    """x [heads, C, d] into a block with its heads along the lanes:
    :func:`_heads_first` the other way, the heads that one head of the block
    serves summed in float32."""
    heads, _, d = x.shape
    serves = heads * d // ref.shape[1]
    for j in range(heads // serves):
        total = sum(x[j * serves + r].astype(jnp.float32) for r in range(serves))
        ref[:, j * d:(j + 1) * d] = total.astype(ref.dtype)


def _chunk_operands(q_ref, k_ref, v_ref, g_ref, beta_ref, inv_ref, state_shape):
    """A grid step's blocks as :func:`_delta_chunk`'s arguments before the
    state [H, dk, dv]: q, k [C, Hk dk] (each key head serves ``H / Hk`` value
    heads), v [C, H dv], g, beta [H, 1, C], inv [H, C, C]."""
    heads, dk, dv = state_shape
    g_row = g_ref[...]
    return (
        _heads_first(q_ref, heads, dk), _heads_first(k_ref, heads, dk), _heads_first(v_ref, heads, dv),
        inv_ref[...], _turned(g_row), g_row, g_row[:, :, -1:], _turned(beta_ref[...]),
    )


def _gated_delta_fwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, inv_ref, o_ref, kept_ref, state_ref, *, eps):
    """One chunk of a block of heads.  The float32 state [H, dk, dv] stays in
    VMEM from a row's first chunk to its last (the grid's last axis, in
    order); HBM sees it once a chunk, the state the chunk starts from, kept
    for the backward kernel."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        state_ref[...] = jnp.zeros_like(state_ref)

    kept_ref[...] = state_ref[...]
    o, state_ref[...] = _delta_chunk(
        *_chunk_operands(q_ref, k_ref, v_ref, g_ref, beta_ref, inv_ref, state_ref.shape), state_ref[...], eps=eps
    )
    _heads_along_lanes(o_ref, o)


def _gated_delta_bwd_kernel(q_ref, k_ref, v_ref, g_ref, beta_ref, inv_ref, kept_ref, do_ref,
                            dq_ref, dk_ref, dv_ref, dg_ref, dbeta_ref, dinv_ref, dstate_ref, *, eps):
    """One chunk of a block of heads, the chunks from last to first: the
    chunk's intermediates again from the state it started from, the cotangent
    of the state [H, dk, dv] in VMEM between chunks (nothing follows the last
    chunk's state: zero), every cotangent by :func:`_delta_chunk`'s own
    ``jax.vjp``; dq and dk summed over the value heads a key head serves."""
    @pl.when(pl.program_id(2) == 0)
    def _():
        dstate_ref[...] = jnp.zeros_like(dstate_ref)

    heads, _, width = dstate_ref.shape  # dv, which names v's cotangent below
    operands = _chunk_operands(q_ref, k_ref, v_ref, g_ref, beta_ref, inv_ref, dstate_ref.shape)
    _, pull = jax.vjp(functools.partial(_delta_chunk, eps=eps), *operands, kept_ref[...])
    dq, dk, dv, dinv_ref[...], dg_col, dg_row, dg_last, dbeta, dstate_ref[...] = pull(
        (_heads_first(do_ref, heads, width), dstate_ref[...])
    )
    _heads_along_lanes(dq_ref, dq)
    _heads_along_lanes(dk_ref, dk)
    _heads_along_lanes(dv_ref, dv)
    last = jax.lax.broadcasted_iota(jnp.int32, dg_row.shape, 2) == dg_row.shape[2] - 1
    dg_ref[...] = dg_row + _turned(dg_col) + jnp.where(last, dg_last, 0.0)
    dbeta_ref[...] = _turned(dbeta)


def _gated_delta_grid(q, v, gc, *, back: bool, ins, outs):
    """What the two kernels' ``pallas_call``s share, over q [B, T, Hk, dk], v
    [B, T, H, dv] and gc [B, n, H, C]: the grid (row, block of heads, chunk: a
    row's chunks in order, from the last where ``back``), and block specs by
    what a block follows, which ``ins`` and ``outs`` name: of a chunk its keys
    [C, Hk dk] and values [C, H dv] as the projections write them, its tokens'
    scalars [H, 1, C], its system [H, C, C] and its state [H, dk, dv].
    Returns the call's keyword arguments."""
    b, _, hk, dk = q.shape
    _, n, hv, c = gc.shape
    dv = v.shape[-1]
    serves = hv // hk
    heads = next(h for h in range(max(GDN_HEADS, serves), 0, -1) if hv % h == 0 and h % serves == 0)

    def by_lanes(width):
        return pl.BlockSpec((None, c, width), lambda r, h, i: (r, n - 1 - i if back else i, h))

    def by_head(*block):
        return pl.BlockSpec((None, None, heads, *block), lambda r, h, i: (r, n - 1 - i if back else i, h, 0, 0))

    specs = {
        "keys": by_lanes(heads // serves * dk), "values": by_lanes(heads * dv),
        "tokens": by_head(1, c), "system": by_head(c, c), "state": by_head(dk, dv),
    }
    return dict(
        grid=(b, hv // heads, n), in_specs=[specs[s] for s in ins], out_specs=[specs[s] for s in outs],
        scratch_shapes=[pltpu.VMEM((heads, dk, dv), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"), vmem_limit_bytes=GDN_VMEM_BYTES
        ),
    )


def _kernel_layout(q, k, v, gc, beta):
    """The kernels' views of their operands: heads x channels in one axis, as
    the projections write them, and a chunk's scalars as rows [1, C]."""
    return *(a.reshape(*a.shape[:2], -1) for a in (q, k, v)), gc[..., None, :], beta[..., None, :]


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _gated_delta_forward(q, k, v, gc, beta, inv, *, eps: float, interpret: bool):
    """→ (o as v in float32, the state each chunk starts from [B, n, H, dk, dv] float32)."""
    grid = _gated_delta_grid(
        q, v, gc, back=False, ins=("keys", "keys", "values", "tokens", "tokens", "system"), outs=("values", "state")
    )
    flat = jax.ShapeDtypeStruct((*v.shape[:2], v.shape[2] * v.shape[3]), jnp.float32)
    states = jax.ShapeDtypeStruct((*inv.shape[:3], q.shape[-1], v.shape[-1]), jnp.float32)
    o, states = pl.pallas_call(
        functools.partial(_gated_delta_fwd_kernel, eps=eps), out_shape=(flat, states),
        name="gated_delta_fwd", interpret=interpret, **grid,
    )(*_kernel_layout(q, k, v, gc, beta), inv)
    return o.reshape(v.shape), states


@functools.partial(jax.jit, static_argnames=("eps", "interpret"))
def _gated_delta_backward(q, k, v, gc, beta, inv, states, do, *, eps: float, interpret: bool):
    """The six cotangents, in their arguments' shapes and types."""
    grid = _gated_delta_grid(
        q, v, gc, back=True, ins=("keys", "keys", "values", "tokens", "tokens", "system", "state", "values"),
        outs=("keys", "keys", "values", "tokens", "tokens", "system"),
    )
    flat = _kernel_layout(q, k, v, gc, beta)
    dq, dk, dv, dg, dbeta, dinv = pl.pallas_call(
        functools.partial(_gated_delta_bwd_kernel, eps=eps),
        out_shape=[jax.ShapeDtypeStruct(a.shape, a.dtype) for a in (*flat, inv)],
        name="gated_delta_bwd", interpret=interpret, **grid,
    )(*flat, inv, states, do.reshape(flat[2].shape))
    return dq.reshape(q.shape), dk.reshape(k.shape), dv.reshape(v.shape), dg[..., 0, :], dbeta[..., 0, :], dinv


@functools.partial(jax.custom_vjp, nondiff_argnums=(6,))
def _gated_delta(q, k, v, gc, beta, inv, eps):
    """The recurrence across chunks as two Pallas kernels: q, k [B, T, Hk, dk],
    v [B, T, H, dv] (``T = n C``, each key head serving ``H / Hk`` value
    heads), a chunk's running log decay gc and beta [B, n, H, C] float32, the
    chunk systems' inverses inv [B, n, H, C, C] → o [B, T, H, dv] float32, as
    :func:`_delta_chunk` leaves it."""
    return _gated_delta_fwd(q, k, v, gc, beta, inv, eps)[0]


def _gated_delta_fwd(q, k, v, gc, beta, inv, eps):
    o, states = _gated_delta_forward(q, k, v, gc, beta, inv, eps=eps, interpret=not platform.on_tpu())
    return o, (q, k, v, gc, beta, inv, states)


def _gated_delta_bwd(eps, kept, do):
    return _gated_delta_backward(*kept, do, eps=eps, interpret=not platform.on_tpu())


_gated_delta.defvjp(_gated_delta_fwd, _gated_delta_bwd)


def chunk_gated_delta_rule(q, k, v, g, beta, *, eps: float, chunk: int | None = None):
    """The gated delta rule, a chunk of tokens at a time.

    q, k [B, T, Hk, dk] as the convolution leaves them, v [B, T, H, dv] (each
    key head serves ``H / Hk`` value heads in a row), g [B, T, H] float32 log
    decay, beta [B, T, H] float32 → o [B, T, H, dv] float32: the recurrence
    ``S' = exp(g_t) S; u = beta_t (v_t - S'^T k_t); S = S' + k_t u^T; o_t = S^T
    q_t`` from ``S_0 = 0`` over each head's q and k normalised
    (:func:`_unit_heads`; q scaled by ``dk**-0.5``), each ``o_t`` rounded to
    q's type and then over its root mean square under ``eps`` (the mixer's
    output norm before its weight).  The chunk body does both norms on the
    rows it holds, so the normalised q and the unnormalised o are never
    written, and the normalised k only for the chunk systems.  Inside a chunk
    the tokens' updates are solved together: a unit lower-triangular system,
    made from the keys' products and the decay and inverted for every chunk at
    once by :func:`unit_lower_inverse`.  Across chunks the float32 state
    [dk, dv] of a head is carried through :func:`_delta_chunk`.

    What runs where: with whole lane tiles (``chunk``, ``dk`` and ``dv``
    multiples of 128: ``GDN_CHUNK`` at the published head sizes) the inverse is
    one Pallas kernel and the recurrence two under one ``custom_vjp``
    (:func:`_gated_delta`: a block of heads' state in VMEM through a row's
    chunks, q, k, v read and o written where the projections' layout has them,
    no key head repeated; the backward kernel walks the chunks from the last
    with the state's cotangent in VMEM and computes each chunk again from the
    state the forward kernel kept for it), compiled on a TPU and in the Pallas
    interpreter elsewhere.  Any other shape, as the tests' chunks under 128,
    takes the kernels' ``jnp`` twins: the doubling as whole-array operations
    and :func:`_gated_delta_scan`."""
    chunk = chunk or GDN_CHUNK
    lo, f32 = q.dtype, jnp.float32
    b, t, hk, dk = q.shape
    hv, dv = v.shape[2:]
    pad = -t % chunk
    if pad:  # a token with k = 0 and beta = 0 leaves the state as it is
        q, k, v = (jnp.pad(a, ((0, 0), (0, pad), (0, 0), (0, 0))) for a in (q, k, v))
        g, beta = (jnp.pad(a, ((0, 0), (0, pad), (0, 0))) for a in (g, beta))
    n = (t + pad) // chunk
    # a chunk's scalars with its tokens last: [B, n, H, chunk]
    g, beta = (jnp.swapaxes(a.astype(f32).reshape(b, n, chunk, hv), 2, 3) for a in (g, beta))
    gc = jnp.cumsum(g, axis=-1)  # the decay's running sum inside the chunk
    k_n = jnp.swapaxes(_unit_heads(k).astype(lo).reshape(b, n, chunk, hk, dk), 2, 3)  # [B, n, Hk, chunk, dk]
    kb = (k_n[:, :, :, None].astype(f32) * beta.reshape(b, n, hk, hv // hk, chunk, 1)).astype(lo)
    kk = jnp.einsum("bnhsid,bnhjd->bnhsij", kb, k_n, preferred_element_type=f32)
    # (I + A)^-1, A the strictly lower part: every token's update given the ones before it
    inv = unit_lower_inverse(kk.reshape(b, n, hv, chunk, chunk), lo, gc).astype(lo)
    in_kernels = chunk % 128 == 0 and dk % 128 == 0 and dv % 128 == 0
    return (_gated_delta if in_kernels else _gated_delta_scan)(q, k, v, gc, beta, inv, eps)[:, :t]


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
    q = qkv[..., : cfg.key_dim].reshape(b, t, hk, dk)
    k = qkv[..., cfg.key_dim: 2 * cfg.key_dim].reshape(b, t, hk, dk)
    v = qkv[..., 2 * cfg.key_dim:].reshape(b, t, hv, dv)
    beta = jax.nn.sigmoid(ba[..., :hv])
    g = -jnp.exp(p["A_log"].astype(f32)) * jax.nn.softplus(ba[..., hv:] + p["dt_bias"])
    # each key head serves hv // hk value heads; q, k and each head's output are normalised inside
    o = chunk_gated_delta_rule(q, k, v, g, beta, eps=cfg.rms_norm_eps, chunk=chunk)  # [B, T, hv, dv]
    o = o * p["norm"] * jax.nn.silu(z.astype(f32))
    return o.reshape(b, t, hv * dv).astype(dtype) @ p["w_o"].astype(dtype)


# ------------------------------------------------------- gated attention


def gated_attention(x, p, *, cfg: Qwen3NextConfig):
    """The gated softmax-attention mixer: x [B, T, h] (normed) → [B, T, h]."""
    return softmax_attention(
        x, p, heads=cfg.num_attention_heads, kv_heads=cfg.num_key_value_heads, head_dim=cfg.head_dim,
        rotary_dim=int(cfg.head_dim * cfg.partial_rotary_factor), theta=cfg.rope_theta,
        eps=cfg.rms_norm_eps, centred=True, gated=True,
    )
