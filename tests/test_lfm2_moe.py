"""The LFM2-MoE family (``models/lfm2_moe.py``) on the shared causal-LM stack
(``models/causal_lm.py``), its routing rule (``parallel/moe.py:
route_sigmoid_top_k``) and its train step (``models/train.py``) against the
plain float32 reference in ``benchmarks/chip/reference/lfm2_moe_f32.py`` (the
one copy of it, loaded by path).

Small on purpose (hidden 64) with the published shape kept: one leading dense
layer, then an attention layer and three convolution layers with routed
experts, four query heads a key-value head, a three-tap convolution, 16
experts top-4 of which 4 are held, a tied head, an expert bias.  The program
runs with ``dtype="float32"`` here so that the comparison is of the
algorithms (blocks against the full softmax, tiles against a masked loop),
not of bfloat16.
"""

from __future__ import annotations

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lakesoul_tpu.models import attention, causal_lm
from lakesoul_tpu.models import lfm2_moe as lm
from lakesoul_tpu.models.train import (
    MOE_ASSIGNMENTS_FAMILY,
    MOE_LOAD_FAMILY,
    TOKENS_FAMILY,
    make_lm_train_state,
    make_lm_train_step,
)
from lakesoul_tpu.obs import registry
from lakesoul_tpu.parallel import moe
from lakesoul_tpu.parallel.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_spec = importlib.util.spec_from_file_location(
    "lfm2_moe_f32", os.path.join(REPO, "benchmarks", "chip", "reference", "lfm2_moe_f32.py")
)
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

MODEL = dict(
    vocab_size=96, hidden_size=64, layer_types=["conv", "full_attention", "conv", "conv", "conv"],
    num_dense_layers=1, intermediate_size=112, conv_L_cache=3, conv_bias=False,
    num_attention_heads=8, num_key_value_heads=2, rope_theta=1e6, num_experts=16,
    num_experts_per_tok=4, moe_intermediate_size=32, norm_eps=1e-5, norm_topk_prob=True,
    routed_scaling_factor=1.0, use_expert_bias=True,
)
HELD = (4, 4)
CFG = lm.Lfm2MoeConfig.from_published(MODEL, experts_held=HELD, dtype="float32")
B, T = 2, 150


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _scaled(tree):
    """Five times the family's 0.02 (and 0.01 of the bias), so that no path's
    signal is lost in the residual; norm weights stay 1."""
    return jax.tree.map(lambda a: a * 5 if a.ndim >= 2 or a.shape == (MODEL["num_experts"],) else a, tree)


@pytest.fixture(scope="module")
def params():
    return _scaled(lm.init_lm_params(CFG, jax.random.key(0)))


def tokens(seed=0, rows=B, length=T):
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(0, MODEL["vocab_size"], (rows, length)), jnp.int32)
    labels = jnp.concatenate([ids[:, 1:], jnp.full((rows, 1), -100, jnp.int32)], axis=1)
    return ids, labels


def hidden(seed, length=T, width=MODEL["hidden_size"]):
    return jax.random.normal(jax.random.key(seed), (B, length, width))


def assert_close(got, want, tol=2e-4):
    """Every leaf within ``tol`` of the reference by relative norm."""
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want), strict=True):
        err = float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))
        assert err < tol, f"{jax.tree_util.keystr(path)}: {err}"


def test_the_stack_is_built_from_layer_types_with_one_leading_dense_layer(params):
    assert CFG.layer_kinds() == ("conv", "attn", "conv", "conv", "conv")
    assert CFG.ffn_kinds() == ("dense", "moe", "moe", "moe", "moe")
    assert [sorted(lp) for lp in params["layers"]] == [
        ["conv", "mlp", "norm1", "norm2"], ["attn", "moe", "norm1", "norm2"], *[["conv", "moe", "norm1", "norm2"]] * 3
    ]
    assert [sorted(b) for b in params["buffers"]["layers"]] == [[], *[["expert_bias"]] * 4]
    assert "head" not in params  # tied
    whole = lm.Lfm2MoeConfig()  # the published stack: 18 conv and 6 attention layers would follow this prefix
    assert whole.layer_kinds()[:3] == ("conv", "conv", "attn") and whole.ffn_kinds()[:3] == ("dense", "dense", "moe")
    assert whole.head_dim == 64
    with pytest.raises(ValueError, match="conv_bias"):
        lm.Lfm2MoeConfig.from_published(MODEL | {"conv_bias": True})


# ------------------------------------------------------------------ mixers


@pytest.mark.parametrize("length", [150, 128, 7, 2], ids=["over-a-block", "a-row-block", "short", "under-the-taps"])
def test_conv_mixer_equals_the_token_by_token_window(params, length):
    """``c[t] = sum_j k[:, j] u[t-2+j]`` a token at a time over the three
    tokens it sees, zeros left of the row; 128 is the attention's row block,
    which the convolution must not care about."""
    p = params["layers"][0]["conv"]
    x = hidden(1, length)
    got = lm.gated_short_conv(x, p)
    bcx = np.asarray(x @ p["w_in"], np.float64)
    b, c, xs = np.split(bcx, 3, axis=-1)
    u, k = b * xs, np.asarray(p["conv"], np.float64)
    conv = np.zeros_like(u)
    for t in range(length):
        for j in range(3):
            if t - 2 + j >= 0:
                conv[:, t] += k[:, j] * u[:, t - 2 + j]
    want = (c * conv) @ np.asarray(p["w_out"], np.float64)
    assert_close(got, jnp.asarray(want, jnp.float32))
    assert_close(ref.short_conv(x, p), jnp.asarray(want, jnp.float32))
    weigh = jax.random.normal(jax.random.key(2), x.shape)
    assert_close(
        jax.grad(lambda p, x: jnp.sum(weigh * lm.gated_short_conv(x, p)), argnums=(0, 1))(p, x),
        jax.grad(lambda p, x: jnp.sum(weigh * ref.short_conv(x, p)), argnums=(0, 1))(p, x),
    )


def test_the_convolution_is_causal(params):
    p = params["layers"][0]["conv"]
    x = hidden(3)
    later = x.at[:, 100:].set(0.0)
    np.testing.assert_allclose(lm.gated_short_conv(x, p)[:, :100], lm.gated_short_conv(later, p)[:, :100], atol=1e-6)


WIDE_HEADS = MODEL | {"hidden_size": 256, "num_attention_heads": 4, "num_key_value_heads": 1}  # head size 64


@pytest.mark.parametrize("model", [MODEL, WIDE_HEADS], ids=["head-8", "head-64"])
@pytest.mark.parametrize("band, rows", [(1024, 128), (64, 16)], ids=["one-block", "bands-of-row-blocks"])
def test_attention_blocks_equal_the_masked_softmax(monkeypatch, model, band, rows):
    """Four query heads a key-value head, at the tiny head size and at the
    published 64."""
    monkeypatch.setattr(attention, "ATTN_BAND", band)
    monkeypatch.setattr(attention, "ATTN_ROWS", rows)
    cfg = lm.Lfm2MoeConfig.from_published(model, experts_held=HELD, dtype="float32")
    assert cfg.num_attention_heads // cfg.num_key_value_heads == 4
    p = _scaled(lm.init_lm_params(cfg, jax.random.key(0)))["layers"][1]["attn"]
    x = hidden(3, width=model["hidden_size"])
    weigh = jax.random.normal(jax.random.key(4), x.shape)
    assert_close(lm.attention(x, p, cfg=cfg), ref.attention(x, p, model))
    assert_close(
        jax.grad(lambda p, x: jnp.sum(weigh * lm.attention(x, p, cfg=cfg)), argnums=(0, 1))(p, x),
        jax.grad(lambda p, x: jnp.sum(weigh * ref.attention(x, p, model)), argnums=(0, 1))(p, x),
    )


def test_attention_is_causal_and_rotates_every_channel(params):
    p = params["layers"][1]["attn"]
    x = hidden(5)
    later = x.at[:, 100:].set(0.0)
    np.testing.assert_allclose(
        lm.attention(x, p, cfg=CFG)[:, :100], lm.attention(later, p, cfg=CFG)[:, :100], atol=1e-5
    )
    q = jax.random.normal(jax.random.key(6), (1, 8, 2, 64))
    turned = attention._rotary(q, jnp.arange(8), 64, 1e6)
    assert not np.any(np.isclose(turned[:, 1:], q[:, 1:]).all(axis=(0, 1, 2)))  # no channel is left as it was
    assert_close(turned, ref.rotary(q, 1e6), tol=1e-6)
    # the program asks for the whole head
    seen = []
    real = attention._rotary
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(attention, "_rotary", lambda x, pos, dim, theta: seen.append((dim, theta)) or real(x, pos, dim, theta))
        lm.attention(x, p, cfg=CFG)
    assert seen == [(CFG.head_dim, 1e6)] * 2


# ----------------------------------------------------------------- routing


def test_sigmoid_routing_picks_on_the_biased_score_and_weighs_with_the_unbiased():
    """Two tokens over six experts, top 2.  Token 0: the bias lifts expert 4
    (score 0.5) over expert 1 (0.7); its weight is still made of 0.5.  Token
    1: the bias changes nothing."""
    logit = lambda s: float(np.log(s / (1 - s)))  # noqa: E731
    want_scores = np.array([[0.9, 0.7, 0.1, 0.2, 0.5, 0.3], [0.2, 0.8, 0.6, 0.1, 0.1, 0.3]], np.float32)
    x = jnp.eye(2, dtype=jnp.float32)
    router = jnp.asarray(np.vectorize(logit)(want_scores), jnp.float32)  # x @ router = the logits
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.0, 0.3, 0.0])
    top_e, w, moved = moe.route_sigmoid_top_k(x, router, bias, top_k=2)
    assert np.asarray(top_e).tolist() == [[0, 4], [1, 2]]
    np.testing.assert_allclose(w[0], np.array([0.9, 0.5]) / (1.4 + 1e-6), rtol=1e-5)
    np.testing.assert_allclose(w[1], np.array([0.8, 0.6]) / (1.4 + 1e-6), rtol=1e-5)
    assert int(moved) == 1
    unbiased_e, unbiased_w, none = moe.route_sigmoid_top_k(x, router, jnp.zeros(6), top_k=2)
    assert np.asarray(unbiased_e).tolist() == [[0, 1], [1, 2]] and int(none) == 0
    np.testing.assert_allclose(unbiased_w[0], np.array([0.9, 0.7]) / (1.6 + 1e-6), rtol=1e-5)
    # the scaling factor scales the weights and nothing else; the reference agrees on all of it
    _, doubled, _ = moe.route_sigmoid_top_k(x, router, bias, top_k=2, scale=2.0)
    np.testing.assert_allclose(doubled, 2 * w, rtol=1e-6)
    ref_e, ref_w = ref.route(x, router, bias, {"num_experts_per_tok": 2})
    assert np.asarray(ref_e).tolist() == np.asarray(top_e).tolist()
    np.testing.assert_allclose(ref_w, w, rtol=1e-6)
    # the bias carries no gradient; the router's comes through the unbiased scores
    g_router, g_bias = jax.grad(
        lambda r, b: jnp.sum(moe.route_sigmoid_top_k(x, r, b, top_k=2)[1] * jnp.array([1.0, -1.0])), argnums=(0, 1)
    )(router, bias)
    assert float(jnp.max(jnp.abs(g_bias))) == 0.0 and float(jnp.max(jnp.abs(g_router))) > 0.01


# ------------------------------------------------------------ expert layer


def _route_to(router, experts):
    """A router that sends every token whose first channel is 10 to ``experts``
    (its top-k): their logits stand 50 above the rest, their scores at 1."""
    return (router * 1e-3).at[0, jnp.asarray(experts)].add(5.0)


def _expert_layer(x, p, bias, *, held, tile=None, top_k=4, n_experts=16):
    """Routing and the held experts as ``causal_lm.lm_layer`` puts them together."""
    top_e, w, moved = moe.route_sigmoid_top_k(x, p["router"], bias, top_k=top_k)
    y, counts = moe.held_experts(x, top_e, w, p, n_experts=n_experts, held=held, tile=tile)
    return y, dict(counts, moe_bias_moved=moved)


ROUTINGS = ["even", "all-on-one-held", "none-held", "top-1", "one-of-one"]  # as tests/test_qwen3_next.py has them


def _routed(p, bias, model, routing):
    p, held, top_k = dict(p), HELD, 4
    if routing == "all-on-one-held":  # expert 5 takes every token, its three companions are not held
        p["router"] = _route_to(p["router"], [5, 0, 1, 2])
    elif routing == "none-held":
        p["router"] = _route_to(p["router"], [0, 1, 2, 3])
    elif routing == "top-1":
        model, top_k = model | {"num_experts_per_tok": 1}, 1
    elif routing == "one-of-one":  # one expert, held: the layer is the dense SwiGLU
        model, held, top_k = model | {"num_experts": 1, "num_experts_per_tok": 1}, (0, 1), 1
        p["router"], bias = p["router"][:, :1], bias[:1]
        p.update({k: p[k][:1] for k in ("w_gate", "w_up", "w_down")})
    return p, bias, model, held, top_k


@pytest.mark.parametrize("routing", ROUTINGS)
def test_expert_layer_equals_the_loop_over_experts(params, routing):
    p, bias, model, held, top_k = _routed(
        params["layers"][1]["moe"], params["buffers"]["layers"][1]["expert_bias"], MODEL, routing
    )
    x = hidden(7).at[..., 0].set(10.0)
    weigh = jax.random.normal(jax.random.key(8), x.shape)
    layer = functools.partial(_expert_layer, held=held, tile=16, top_k=top_k, n_experts=model["num_experts"])
    y, counts = layer(x, p, bias)
    assert_close(y, ref.moe(x, p, bias, model, held))
    got = jax.grad(lambda p, x: jnp.sum(weigh * layer(x, p, bias)[0]), argnums=(0, 1))(p, x)
    want = jax.grad(lambda p, x: jnp.sum(weigh * ref.moe(x, p, bias, model, held)), argnums=(0, 1))(p, x)
    if top_k == 1:  # a lone weight is s / (s + 1e-6): the router's gradient is a millionth, not a number to compare
        for grads in (got, want):
            assert float(jnp.max(jnp.abs(grads[0].pop("router")))) < 1e-3
    assert_close(got, want)
    n = B * T
    assert int(counts["moe_all"]) == top_k * n
    if routing in ("all-on-one-held", "one-of-one"):
        assert (int(counts["moe_held"]), int(counts["moe_load_max"])) == (n, n)
    elif routing == "none-held":
        assert (int(counts["moe_held"]), int(counts["moe_load_max"])) == (0, 0)
        assert float(jnp.max(jnp.abs(y))) == 0.0  # no shared expert: nothing is added
    else:
        # (with one expert a token the sigmoid's largest score may sit on one held expert alone)
        assert 0 < int(counts["moe_load_max"]) <= int(counts["moe_held"]) < top_k * n
        assert routing == "top-1" or int(counts["moe_load_max"]) < int(counts["moe_held"])
    if routing == "one-of-one":
        dense = ref.swiglu(x, p["w_gate"][0], p["w_up"][0], p["w_down"][0])
        assert_close(y, dense / (1 + 1e-6 / ref.scores(x, p["router"])))  # the lone weight, s / (s + 1e-6)


def test_the_four_shares_add_up_to_the_uncut_layer(params):
    """16 experts over four chips of 4 (experts 0-3, 4-7, 8-11, 12-15): the
    four shares' parts are the whole layer of the reference; the family has
    no shared expert to count once."""
    whole = dict(params["layers"][2]["moe"])
    bias = params["buffers"]["layers"][2]["expert_bias"]
    keys = jax.random.split(jax.random.key(9), 3)
    for name, key in zip(("w_gate", "w_up", "w_down"), keys):  # all 16 experts' weights
        whole[name] = jax.random.normal(key, (16,) + whole[name].shape[1:]) * 0.1
    x = hidden(10)
    top_e, w, _ = moe.route_sigmoid_top_k(x, whole["router"], bias, top_k=4)
    routed = 0.0
    for first in range(0, 16, 4):
        share = {k: whole[k][first:first + 4] for k in ("w_gate", "w_up", "w_down")}
        y, _ = moe.held_experts(x, top_e, w, share, n_experts=16, held=(first, 4), tile=32)
        routed = routed + y
    assert_close(routed, ref.moe(x, whole, bias, MODEL, (0, 16)))


# ------------------------------------------------------------- whole model


def test_loss_and_every_gradient_leaf_equal_the_reference(params):
    ids, labels = tokens()
    (loss, counts), grads = jax.jit(jax.value_and_grad(
        lambda p: CFG.loss(p, ids, labels), has_aux=True
    ))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.lm_loss(p, ids, labels, cfg=MODEL, held=HELD)
    ))(params)
    np.testing.assert_allclose(float(loss), float(want), rtol=2e-6)
    for tree in (grads, want_grads):  # the bias steers the selection: no gradient reaches it, in either
        assert all(float(jnp.max(jnp.abs(g))) == 0.0 for g in jax.tree.leaves(tree.pop("buffers")))
    assert_close(grads, want_grads)
    assert all(float(jnp.linalg.norm(g)) > 0 for g in jax.tree.leaves(grads))
    assert int(counts["tokens"]) == B * T and int(counts["moe_all"]) == 4 * 4 * B * T
    assert 0 < int(counts["moe_bias_moved"]) < int(counts["moe_all"])
    assert_close(causal_lm.lm_logits(params, ids, cfg=CFG), ref.lm_logits(params, ids, cfg=MODEL, held=HELD))


def test_bfloat16_program_stays_near_the_reference(params):
    """The dtype the chip runs: products in bfloat16, float32 accumulation."""
    ids, labels = tokens(1)
    cfg = lm.Lfm2MoeConfig.from_published(MODEL, experts_held=HELD)
    loss, _ = cfg.loss(params, ids, labels)
    want = ref.lm_loss(params, ids, labels, cfg=MODEL, held=HELD)
    assert abs(float(loss) - float(want)) < 0.02


def _series(family, **labels) -> float:
    inner = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
    return registry().snapshot().get(family + ("{" + inner + "}" if inner else ""), 0)


STEPS = 3


@pytest.fixture(scope="module")
def stepped():
    """Three optimizer steps on one device and the same on a dp=2 mesh, from
    one seed, with the counters read before the first."""
    ids, labels = tokens(2)
    out = {}
    for dp in (1, 2):
        plan = make_mesh(jax.devices()[:dp], dp=dp, tp=1, sp=1)
        with jax.default_matmul_precision("highest"):
            state, opt_state, tx, shardings = make_lm_train_state(CFG, plan, lr=1e-2, seed=3)
            step = make_lm_train_step(CFG, plan, tx, shardings)
            counted = {kind: _series(MOE_ASSIGNMENTS_FAMILY, kind=kind)
                       for kind in ("held", "all", "tile_rows", "bias_moved")}
            counted.update(tokens=_series(TOKENS_FAMILY), max=_series(MOE_LOAD_FAMILY, stat="max"),
                           mean=_series(MOE_LOAD_FAMILY, stat="mean"))
            states, losses = [jax.device_get(state)], []
            for _ in range(STEPS):
                state, opt_state, loss = step(state, opt_state, ids, labels)
                states.append(jax.device_get(state))
                losses.append(float(loss))
            out[dp] = dict(states=states, losses=losses, counted=counted, step=step, opt_state=opt_state)
    return ids, labels, out


def assert_moves_agree(before, after, want, lr=1e-2):
    """A first AdamW step moves a weight by ``lr * g / (|g| + 1e-8)``: by
    ``lr`` whatever the gradient's size.  Where the reference moved by nearly
    ``lr`` the program moved the same way, which is where a wrong sign or a
    missed leaf shows; elsewhere it moved by no more than ``lr``."""
    for (path, a), b, target in zip(
        jax.tree_util.tree_leaves_with_path(after), jax.tree.leaves(before), jax.tree.leaves(want), strict=True
    ):
        name = jax.tree_util.keystr(path)
        moved, wanted = (a - b) / lr, (target - b) / lr
        decisive = np.abs(wanted) > 0.9
        assert decisive.any(), name
        np.testing.assert_allclose(moved[decisive], wanted[decisive], atol=2e-2, err_msg=name)
        assert float(np.max(np.abs(moved))) < 1.02, name


def _trained(state):
    return {k: v for k, v in state.items() if k != "buffers"}


def test_one_step_is_the_references_adamw_step_and_the_bias_is_returned_bit_for_bit(stepped):
    ids, labels, out = stepped
    states = out[1]["states"]
    loss, grads = jax.value_and_grad(
        lambda p: ref.lm_loss(p, ids, labels, cfg=MODEL, held=HELD)
    )(states[0])
    trained = _trained(states[0])
    zeros = jax.tree.map(jnp.zeros_like, trained)
    want, _, _ = ref.adamw_step(trained, _trained(grads), zeros, zeros, 0, lr=1e-2)
    np.testing.assert_allclose(out[1]["losses"][0], float(loss), rtol=2e-6)
    assert_moves_agree(trained, _trained(states[1]), want)
    # three steps on, every weight has moved three times and the bias not at all: no gradient, no
    # moment and no weight decay (which would shrink it by lr * 1e-4 a step) has reached it
    assert out[1]["losses"][-1] < out[1]["losses"][0]
    for run in out.values():
        first, last = run["states"][0], run["states"][-1]
        for a, b in zip(jax.tree.leaves(first["buffers"]), jax.tree.leaves(last["buffers"]), strict=True):
            assert a.dtype == b.dtype == np.float32 and a.tobytes() == b.tobytes() and np.abs(a).max() > 0
        assert all(np.abs(a - b).max() > 1e-3 for a, b in
                   zip(jax.tree.leaves(_trained(first)), jax.tree.leaves(_trained(last))))
        # and the optimizer holds nothing for it: moments for the trained leaves only
        moments = [leaf for leaf in jax.tree.leaves(run["opt_state"]) if leaf.ndim]
        assert len(moments) == 2 * len(jax.tree.leaves(_trained(first)))


def test_step_on_a_dp2_mesh_equals_one_device(stepped):
    _, _, out = stepped
    np.testing.assert_allclose(out[2]["losses"][0], out[1]["losses"][0], rtol=1e-5)
    assert_moves_agree(_trained(out[2]["states"][0]), _trained(out[2]["states"][1]), _trained(out[1]["states"][1]))


def test_counters_for_a_known_routing(stepped):
    ids, labels, out = stepped
    run = out[1]
    before = run["states"][0]
    # what the first step must have counted, from the reference's routing of the same weights
    held = load_max = tile_rows = dw_writes = bias_moved = 0
    x = jnp.asarray(before["embed"])[ids]
    for i, (lp, buffers, kind) in enumerate(zip(before["layers"], before["buffers"]["layers"], MODEL["layer_types"])):
        if i >= MODEL["num_dense_layers"]:
            mixed = x + (ref.short_conv if kind == "conv" else functools.partial(ref.attention, cfg=MODEL))(
                ref.rms_norm(x, lp["norm1"], 1e-5), lp[ref.KINDS[kind]])
            y = ref.rms_norm(mixed, lp["norm2"], 1e-5).reshape(-1, MODEL["hidden_size"])
            s = np.asarray(ref.scores(y, lp["moe"]["router"]))
            top_e = np.asarray(ref.route(y, lp["moe"]["router"], buffers["expert_bias"], MODEL)[0])
            unbiased = np.argsort(-s, axis=-1)[:, :4]
            bias_moved += sum(len(set(a) - set(b)) for a, b in zip(top_e.tolist(), unbiased.tolist()))
            loads = np.bincount(top_e.ravel(), minlength=16)[HELD[0]:HELD[0] + HELD[1]]
            held += int(loads.sum())
            load_max += int(loads.max())
            tile_rows += sum(-(-int(load) // moe.EXPERT_TILE) * moe.EXPERT_TILE for load in loads)
            # experts this narrow keep the tile loop (no slot in the grouped kernels), its weight-gradient sums written once a tile
            dw_writes += sum(-(-int(load) // moe.EXPERT_TILE) for load in loads)
        x = ref.layer(x, lp, buffers, ref.KINDS[kind], i < MODEL["num_dense_layers"], MODEL, HELD)
    assert bias_moved > 0
    # the step's own counts are over its three steps; the weights move, so only the first step's
    # routing is known: run it again from the first state
    plan = make_mesh(jax.devices()[:1], dp=1, tp=1, sp=1)
    with jax.default_matmul_precision("highest"):
        state, opt_state, tx, shardings = make_lm_train_state(CFG, plan, lr=1e-2, seed=3)
        once = make_lm_train_step(CFG, plan, tx, shardings)
        once(state, opt_state, ids, labels)
    assert once.counts() == {"tokens": B * T, "moe_all": 4 * 4 * B * T, "moe_held": held, "moe_load_max": load_max,
                             "moe_tile_rows": tile_rows, "moe_dw_writes": dw_writes, "moe_grouped": 0, "moe_bias_moved": bias_moved,
                             "head_all": B * (T - 1), "head_mtp": 0,  # one loss, no prediction module
                             "attn_tiles_run": 0, "attn_tiles_causal": 0,  # 150 tokens: the kernels list no tile
                             "attn_pair_tiles_run": 0, "attn_pair_tiles": 0,  # no head pairs
                             "attn_operands_kernel": 0, "attn_operands_xla": B,
                             "attn_out_tokens": 0, "attn_out_heads": B,  # the twin writes heads first
                             "loss_rows_fused": 0, "loss_rows_compiler": B * T,  # every row to the tile loop; tiles this small stay the compiler's
                             "head_loop": 0, "loop_layers_run": 0, "loop_layers": 0,  # one attention layer, its operands the jnp lines'; no pass loop
                             "ssm_rows_kernel": 0, "ssm_rows_twin": 0, "shared_reads": 0}  # no selective scan, no state one layer reads of another
    # the registry's series: three steps on one device, three on the mesh, and the one above
    counted = run["counted"]
    steps = 2 * STEPS + 1
    assert _series(TOKENS_FAMILY) - counted["tokens"] == steps * B * T
    assert _series(MOE_ASSIGNMENTS_FAMILY, kind="all") - counted["all"] == steps * 16 * B * T
    got = run["step"].counts()
    assert got["moe_all"] == STEPS * 16 * B * T and got["moe_held"] >= held and got["moe_bias_moved"] >= bias_moved
    assert _series(MOE_ASSIGNMENTS_FAMILY, kind="bias_moved") - counted["bias_moved"] == (
        got["moe_bias_moved"] + out[2]["step"].counts()["moe_bias_moved"] + bias_moved
    )
    assert _series(MOE_ASSIGNMENTS_FAMILY, kind="held") - counted["held"] == (
        got["moe_held"] + out[2]["step"].counts()["moe_held"] + held
    )
    assert _series(MOE_LOAD_FAMILY, stat="mean") - counted["mean"] == pytest.approx(
        (got["moe_held"] + out[2]["step"].counts()["moe_held"] + held) / HELD[1]
    )


def test_lm_step_runs_on_dp_only():
    plan = make_mesh(jax.devices()[:2], dp=1, tp=2, sp=1)
    with pytest.raises(NotImplementedError, match="dp only"):
        make_lm_train_state(CFG, plan)
