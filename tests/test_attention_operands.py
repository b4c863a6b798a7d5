"""The operand kernels (``models/attention.py: attn_operands_fwd`` and
``attn_operands_bwd``) against the ``jnp`` lines they stand for, in the Pallas
interpreter: the flash kernels' operands, every gradient, the mixer through
its checkpoint on both paths, and the rule that picks the path.
``tensorplane/smoke.py`` holds the pair to the same twin on the chip at the
deployed shape; ``tests/test_tpu_lowering.py`` lowers it for the ``tpu``
platform."""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lakesoul_tpu.models import attention, causal_lm

EPS, THETA = 1e-5, 10000.0


def _raw(t, heads, kv, d, rows=1, seed=3):
    keys = jax.random.split(jax.random.key(seed), 8)
    q, k, v = (jax.random.normal(key, (rows, t, n, d)).astype(jnp.bfloat16) for key, n in zip(keys, (heads, kv, kv)))
    return q, k, v, keys[3:]


def _f32(x):
    return np.asarray(x.astype(jnp.float32))


@pytest.mark.parametrize("centred", [False, True], ids=["plain-weight", "centred-weight"])
@pytest.mark.parametrize("turned", [True, False], ids=["rotary-whole", "rotary-none"])
@pytest.mark.parametrize("heads,kv,t", [(32, 4, 256), (8, 8, 512)], ids=["groups-of-8", "groups-of-1"])
def test_the_kernel_pair_is_the_xla_lines(heads, kv, t, turned, centred):
    """Operands bit for bit or within one bfloat16 unit (float32 inside and
    one rounding on both sides; a sum in another order may cross a rounding
    boundary), the raw q, k, v cotangents by norm, the two norm weights'
    gradients (float32 sums over every token and head) to 1e-5."""
    d = 128
    q, k, v, keys = _raw(t, heads, kv, d, rows=2)
    centre = 0.0 if centred else 1.0
    wq, wk = (centre + 0.2 * jax.random.normal(key, (d,)) for key in keys[:2])
    recipe = dict(eps=EPS, centred=centred, rotary_dim=d if turned else None, theta=THETA)
    bt = attention._operand_tiles(t, heads, kv, d, recipe["rotary_dim"])
    assert bt == t
    want, pull_want = jax.vjp(functools.partial(attention._xla_operands, **recipe), q, k, v, wq, wk)
    got, pull_got = jax.vjp(lambda *a: attention._kernel_operands(*a, bt, **recipe), q, k, v, wq, wk)
    for a, b in zip(got, want, strict=True):
        assert a.shape == b.shape and a.dtype == b.dtype == jnp.bfloat16
        a, b = _f32(a), _f32(b)
        assert np.all(np.abs(a - b) <= np.abs(b) * 2.0**-7) and np.mean(a != b) < 1e-3
    cots = tuple(jax.random.normal(key, a.shape).astype(a.dtype) for key, a in zip(keys[2:], want))
    grads, grads_want = pull_got(cots), pull_want(cots)
    for a, b, limit in zip(grads, grads_want, (2e-4, 2e-4, 0.0, 1e-5, 1e-5), strict=True):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert np.linalg.norm(_f32(a) - _f32(b)) <= limit * np.linalg.norm(_f32(b))


def test_a_head_of_two_lane_tiles_turns_by_one_tile():
    """The rule takes any head of whole lane tiles: at 256 channels the turn
    is a roll by 128 lanes, and a group wider than a block's budget gets the
    smallest block."""
    t, heads, kv, d = 256, 2, 1, 256
    q, k, v, keys = _raw(t, heads, kv, d)
    wq, wk = (1.0 + 0.2 * jax.random.normal(key, (d,)) for key in keys[:2])
    recipe = dict(eps=EPS, centred=False, rotary_dim=d, theta=THETA)
    bt = attention._operand_tiles(t, heads, kv, d, d)
    want = attention._xla_operands(q, k, v, wq, wk, **recipe)
    got = attention._kernel_operands(q, k, v, wq, wk, bt, **recipe)
    for a, b in zip(got, want, strict=True):
        a, b = _f32(a), _f32(b)
        assert np.all(np.abs(a - b) <= np.abs(b) * 2.0**-7) and np.mean(a != b) < 1e-3
    assert attention._operand_tiles(8192, 64, 2, 256, 256) == 128  # 32 heads of 256 a group: 1 MB at 64 tokens


@pytest.mark.parametrize("shape,want", [
    ((8192, 32, 4, 128, 128), 512),     # a Trinity-Mini window layer
    ((8192, 32, 4, 128, None), 512),    # its full layer: no positions
    ((8192, 16, 2, 256, 64), None),     # Qwen3-Next: 64 of 256 channels turned
    ((8192, 32, 8, 64, 64), None),      # LFM2: two heads share a lane tile
    ((384, 8, 2, 128, 128), 128),       # a row of three 128-token tiles
    ((150, 8, 2, 128, 128), None),      # a row the flash kernels do not take
    ((8192, 12, 5, 128, 128), None),    # heads that do not share evenly
    ((16384, 8, 2, 256, 256), None),    # T x D over the flash kernels' row
], ids=["trinity-window", "trinity-full", "qwen3-next", "lfm2", "three-tiles", "ragged-row", "uneven-groups", "long-row"])
def test_the_rule(shape, want):
    assert attention._operand_tiles(*shape) == want


def _mixer(heads, kv, d, *, rotary_dim, window, centred=False):
    return functools.partial(
        causal_lm.softmax_attention, heads=heads, kv_heads=kv, head_dim=d, rotary_dim=rotary_dim, theta=THETA,
        eps=EPS, centred=centred, gated=False, window=window,
    )


@pytest.mark.parametrize("kind", ["window-turned", "full-unturned"])
def test_the_mixer_through_its_checkpoint_is_equal_on_both_paths(kind, monkeypatch):
    """``softmax_attention`` under ``_row_by_row``'s checkpoint (which keeps
    the flash kernels' output and log-sum-exp and nothing of the operands: the
    backward pass makes the raw q and k again), value and every gradient, with
    the operand kernels and with the rule refusing the shape."""
    h, heads, kv, d, t = 64, 4, 2, 128, 256
    window, rotary_dim = (100, d) if kind == "window-turned" else (None, None)
    keys = jax.random.split(jax.random.key(5), 9)
    x = jax.random.normal(keys[0], (2, t, h)).astype(jnp.bfloat16)
    p = {"w_q": causal_lm.normal_init(keys[1], h, heads * d) * 10, "w_k": causal_lm.normal_init(keys[2], h, kv * d) * 10,
         "w_v": causal_lm.normal_init(keys[3], h, kv * d) * 10, "w_gate": causal_lm.normal_init(keys[4], h, heads * d),
         "w_o": causal_lm.normal_init(keys[5], heads * d, h), "q_norm": 1 + 0.1 * jax.random.normal(keys[6], (d,)),
         "k_norm": 1 + 0.1 * jax.random.normal(keys[7], (d,))}
    cot = jax.random.normal(keys[8], x.shape).astype(jnp.bfloat16)
    mixer = _mixer(heads, kv, d, rotary_dim=rotary_dim, window=window)

    def run():
        out, pull = jax.vjp(lambda x, p: causal_lm._row_by_row(mixer, x, p, None), x, p)
        return out, pull(cot)

    counts = attention.mixer_counts(mixer, x, p)
    assert (counts["attn_operands_kernel"], counts["attn_operands_xla"]) == (2, 0)
    with_kernels = run()
    monkeypatch.setattr(attention, "_operand_tiles", lambda *shape: None)
    counts = attention.mixer_counts(mixer, x, p)
    assert (counts["attn_operands_kernel"], counts["attn_operands_xla"]) == (0, 2)
    without = run()
    for a, b in zip(jax.tree.leaves(with_kernels), jax.tree.leaves(without), strict=True):
        assert a.shape == b.shape and a.dtype == b.dtype
        assert np.linalg.norm(_f32(a) - _f32(b)) <= 2e-3 * np.linalg.norm(_f32(b))


def _pallas_calls(fn, *args) -> set[str]:
    """The names of the Pallas kernels in ``fn``'s jaxpr, value and gradient."""
    text = str(jax.make_jaxpr(lambda *a: jax.vjp(fn, *a)[1](jax.eval_shape(fn, *a)))(*args))
    return {name for name in ("flash_attention_fwd", "flash_attention_bwd", "attn_operands_fwd", "attn_operands_bwd")
            if name in text}


@pytest.mark.parametrize("family", ["qwen3-next", "glm-4.7-flash", "lfm2", "trinity-mini"])
def test_which_published_mixers_hold_the_operand_kernels(family):
    """At the published widths and 8,192 tokens, from an abstract trace: the
    Trinity-Mini mixers (both kinds) hold the pair, the Qwen3-Next, GLM and
    LFM2 mixers hold the flash kernels alone, as before the pair existed."""
    from lakesoul_tpu.models import afmoe, glm4_moe_lite, lfm2_moe, qwen3_next

    cfg, kinds = {
        "qwen3-next": (qwen3_next.Qwen3NextConfig(), ("attn",)),
        "glm-4.7-flash": (glm4_moe_lite.Glm4MoeLiteConfig(), ("mla",)),
        "lfm2": (lfm2_moe.Lfm2MoeConfig(), ("attn",)),
        "trinity-mini": (afmoe.AfmoeConfig(), ("swa", "attn")),
    }[family]
    x = jax.ShapeDtypeStruct((1, 8192, cfg.hidden_size), jnp.bfloat16)
    layer_kinds = cfg.layer_kinds()
    flash = {"flash_attention_fwd", "flash_attention_bwd"}
    for kind in kinds:
        weights = _mixer_weights(cfg, layer_kinds.index(kind), kind)
        held = _pallas_calls(cfg.mixer(kind)[0], x, weights)
        assert held == (flash | {"attn_operands_fwd", "attn_operands_bwd"} if family == "trinity-mini" else flash), (kind, held)


def _mixer_weights(cfg, layer: int, kind: str):
    """Layer ``layer``'s mixer weights as shapes, without making the model:
    the family's ``init`` is traced for one leading slice of the stack."""
    shapes = jax.eval_shape(cfg.init, jax.random.key(0))
    return shapes["layers"][layer][kind]


# ------------------------------------------------- the output side of the flash pair


def _published(family):
    from lakesoul_tpu.models import afmoe, glm4_moe_lite, lfm2_moe, ouro, qwen3_next

    return {
        "qwen3-next": (qwen3_next.Qwen3NextConfig, ("attn",)),
        "glm-4.7-flash": (glm4_moe_lite.Glm4MoeLiteConfig, ("mla",)),
        "lfm2": (lfm2_moe.Lfm2MoeConfig, ("attn",)),
        "trinity-mini": (afmoe.AfmoeConfig, ("swa", "attn")),
        "ouro": (ouro.OuroConfig, ("attn",)),
    }[family]


def _named(eqn, name: str) -> bool:
    return eqn.primitive.name in ("pjit", "jit") and eqn.params.get("name") == name


def _around_the_flash_pair(mixer, x, weights) -> tuple[set[str], set[str]]:
    """The primitives of ``mixer``'s value-and-gradient jaxpr on the data's way
    (from the forward kernels' output on to the first matrix product, the
    output projection's and its transposes'; from the backward kernels'
    cotangent operand back to the first matrix product)."""
    def pulled(x, p, cot):
        return jax.vjp(mixer, x, p)[1](cot)

    eqns = jax.make_jaxpr(pulled)(x, weights, jax.eval_shape(mixer, x, weights)).jaxpr.eqns
    (forward,) = [e for e in eqns if _named(e, "_flash_forward")]
    (backward,) = [e for e in eqns if _named(e, "_flash_backward")]
    after, reached = set(), {forward.outvars[0]}
    for eqn in eqns[eqns.index(forward) + 1:]:
        if not any(v in reached for v in eqn.invars if not hasattr(v, "val")):
            continue
        if eqn.primitive.name == "dot_general" or eqn is backward:
            continue
        after.add(eqn.primitive.name)
        reached.update(eqn.outvars)
    before, wanted = set(), {backward.invars[5]}  # q, k, v, o, the log-sum-exp, do
    for eqn in reversed(eqns[:eqns.index(backward)]):
        if not any(v in wanted for v in eqn.outvars) or eqn.primitive.name == "dot_general":
            continue
        before.add(eqn.primitive.name)
        wanted.update(v for v in eqn.invars if not hasattr(v, "val"))
    return after, before


@pytest.mark.parametrize("family", ["qwen3-next", "glm-4.7-flash", "lfm2", "trinity-mini", "ouro"])
def test_no_transpose_stands_between_the_flash_pair_and_the_output_projection(family):
    """At the published widths and 8,192 tokens, from an abstract trace: where
    a head is whole lane tiles (every family but LFM2's head of 64) the forward
    kernels' output reaches ``w_o``, through the gate where there is one, and
    the cotangent reaches the backward kernels from ``w_o``'s transpose,
    through reshapes, casts and the gate's products alone; the LFM2 mixer keeps
    its transposes on both ways."""
    config, kinds = _published(family)
    cfg = config()
    x = jax.ShapeDtypeStruct((1, 8192, cfg.hidden_size), jnp.bfloat16)
    for kind in kinds:
        mixer = cfg.mixer(kind)[0]
        weights = _mixer_weights(cfg, cfg.layer_kinds().index(kind), kind)
        after, before = _around_the_flash_pair(mixer, x, weights)
        counts = attention.mixer_counts(mixer, x, weights)
        if family == "lfm2":
            assert "transpose" in after and "transpose" in before
            assert (counts["attn_out_tokens"], counts["attn_out_heads"]) == (0, 1)
        else:
            assert "transpose" not in after | before, (kind, after, before)
            assert "reshape" in after and "reshape" in before  # the walk did go from the kernels to the products
            assert (counts["attn_out_tokens"], counts["attn_out_heads"]) == (1, 0)


def test_the_lfm2_mixer_is_the_program_it_was():
    """A head of 64: ``softmax_attention`` over ``causal_attention`` traces to
    the operations it ran when the transpose was the caller's: the jaxpr of
    value and gradient, at the published widths, equal as text to the mixer
    written out the old way from the same pieces."""
    config, _ = _published("lfm2")
    cfg = config()
    x = jax.ShapeDtypeStruct((1, 8192, cfg.hidden_size), jnp.bfloat16)
    weights = _mixer_weights(cfg, cfg.layer_kinds().index("attn"), "attn")
    heads, kv, d = cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim
    assert d == 64 and not attention._token_major(8192, heads // kv, d)

    def as_it_was(x, p):
        dtype = x.dtype
        b, t, _ = x.shape
        q, k, v = ((x @ p[w].astype(dtype)).reshape(b, t, n, d) for w, n in (("w_q", heads), ("w_k", kv), ("w_v", kv)))
        q, k, v = attention._xla_operands(
            q, k, v, p["q_norm"], p["k_norm"], eps=cfg.norm_eps, centred=False, rotary_dim=d, theta=cfg.rope_theta
        )
        o = attention._flash_attention(
            q.reshape(b * kv, heads // kv, t, d), *(a.reshape(b * kv, t, d) for a in (k, v)),
            *attention._flash_tiles(t, heads // kv, d), None, None,
        ).reshape(q.shape)
        o = o.transpose(0, 3, 1, 2, 4).reshape(b, t, heads, d)
        return o.reshape(b, t, heads * d) @ p["w_o"].astype(dtype)

    def text(mixer):
        return str(jax.make_jaxpr(lambda x, p, cot: jax.vjp(mixer, x, p)[1](cot))(x, weights, x))

    assert text(cfg.mixer("attn")[0]) == text(as_it_was)
