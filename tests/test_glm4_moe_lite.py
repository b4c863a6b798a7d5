"""The ``glm4_moe_lite`` family (``models/glm4_moe_lite.py``: GLM-4.7-Flash) on
the shared causal-LM stack (``models/causal_lm.py``): latent attention, sigmoid
routing under a bias beside an ungated shared expert (``parallel/moe.py``), the
multi-token-prediction module and its second loss, and the train step
(``models/train.py``), against the plain float32 reference in
``benchmarks/chip/reference/glm4_moe_lite_f32.py`` (the one copy of it, loaded
by path).

Small on purpose (hidden 64) with the published shape kept: one leading dense
layer, then sparse layers, four heads with a decoupled rotary key they share
(12 + 4 channels a key, 16 a value), 16 experts top-4 of which 4 are held, a
shared expert, an untied head, an expert bias, one prediction module.  The
program runs with ``dtype="float32"`` here so that the comparison is of the
algorithms (blocks against the full softmax, tiles against a masked loop),
not of bfloat16.
"""

from __future__ import annotations

import functools
import importlib.util
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lakesoul_tpu.models import attention, causal_lm
from lakesoul_tpu.models import glm4_moe_lite as lm
from lakesoul_tpu.models.train import (
    HEAD_POSITIONS_FAMILY,
    MOE_ASSIGNMENTS_FAMILY,
    TOKENS_FAMILY,
    make_lm_train_state,
    make_lm_train_step,
)
from lakesoul_tpu.obs import registry
from lakesoul_tpu.parallel import moe
from lakesoul_tpu.parallel.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks", "chip")
_spec = importlib.util.spec_from_file_location(
    "glm4_moe_lite_f32", os.path.join(BENCH, "reference", "glm4_moe_lite_f32.py")
)
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

MODEL = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=3, first_k_dense_replace=1, intermediate_size=112,
    num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4,
    v_head_dim=16, rope_theta=1e6, rope_scaling=None, partial_rotary_factor=1, attention_bias=False,
    n_routed_experts=16, n_shared_experts=1, num_experts_per_tok=4, moe_intermediate_size=32,
    routed_scaling_factor=1.8, n_group=1, topk_group=1, topk_method="noaux_tc", norm_topk_prob=True,
    rms_norm_eps=1e-5, num_nextn_predict_layers=1, mtp_loss_weight=0.3, tie_word_embeddings=False,
)
HELD = (4, 4)
CFG = lm.Glm4MoeLiteConfig.from_published(MODEL, experts_held=HELD, dtype="float32")
B, T = 2, 150


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _scaled(tree):
    """Five times the family's 0.02 (and 0.003 of the bias), so that no path's
    signal is lost in the residual; norm weights stay 1."""
    return jax.tree.map(lambda a: a * 5 if a.ndim >= 2 or a.shape == (MODEL["n_routed_experts"],) else a, tree)


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


def mixer(cfg=CFG):
    return cfg.mixer("mla")[0]


def test_the_stack_is_latent_attention_throughout_with_one_leading_dense_layer(params):
    assert CFG.layer_kinds() == ("mla",) * 3 and CFG.ffn_kinds() == ("dense", "moe", "moe")
    assert CFG.mixer("mla")[1] == causal_lm.ATTN_SCOPE  # the kernels' scope; ``lakesoul.lm.mla`` lies inside it
    assert [sorted(lp) for lp in params["layers"]] == [
        ["mla", "mlp", "norm1", "norm2"], *[["mla", "moe", "norm1", "norm2"]] * 2
    ]
    assert sorted(params["layers"][1]["mla"]) == ["kv_norm", "q_norm", "w_dkv", "w_dq", "w_o", "w_ukv", "w_uq"]
    assert sorted(params["layers"][1]["moe"]["shared"]) == ["w_down", "w_gate", "w_up"]  # no gate
    assert [sorted(b) for b in params["buffers"]["layers"]] == [[], ["expert_bias"], ["expert_bias"]]
    assert "head" in params and params["head"].shape == (64, 96)  # untied
    assert sorted(params["mtp"]) == ["eh_proj", "enorm", "hnorm", "layer", "shared_head_norm"]
    assert sorted(params["mtp"]["layer"]) == ["mla", "moe", "norm1", "norm2"]  # a whole sparse layer of its own
    assert sorted(params["buffers"]["mtp"]) == ["expert_bias"]
    whole = lm.Glm4MoeLiteConfig()  # the published stack
    assert len(whole.layer_kinds()) == 47 and whole.ffn_kinds()[:2] == ("dense", "moe") and whole.num_experts == 64
    assert (whole.qk_nope_head_dim + whole.qk_rope_head_dim, whole.v_head_dim) == (256, 256)


@pytest.mark.parametrize("key, value", [
    ("n_group", 2), ("topk_group", 2), ("norm_topk_prob", False), ("attention_bias", True),
    ("rope_scaling", {"type": "yarn", "factor": 4}), ("num_nextn_predict_layers", 2), ("v_head_dim", 32),
    ("partial_rotary_factor", 0.5), ("tie_word_embeddings", True),
])
def test_the_configuration_refuses_what_the_layers_do_not_compute(key, value):
    with pytest.raises(ValueError, match=key):
        lm.Glm4MoeLiteConfig.from_published(MODEL | {key: value})


# -------------------------------------------------------- latent attention


def token_by_token(x, p, model):
    """Latent attention a query at a time over the keys it sees, per head, in
    float64: the equations, with no block, no kernel and no batched softmax."""
    f64 = np.float64
    heads, nope, rope = model["num_attention_heads"], model["qk_nope_head_dim"], model["qk_rope_head_dim"]
    latent, eps, theta = model["kv_lora_rank"], model["rms_norm_eps"], model["rope_theta"]
    p = jax.tree.map(lambda a: np.asarray(a, f64), p)
    x = np.asarray(x, f64)
    b, t, _ = x.shape

    def norm(a, w):
        return a / np.sqrt(np.mean(a * a, axis=-1, keepdims=True) + eps) * w

    def turn(a):  # [..., t, rope] rotate-half over every channel
        half = rope // 2
        angle = np.arange(t)[:, None] * theta ** (-np.arange(half) * 2.0 / rope)
        a1, a2 = a[..., :half], a[..., half:]
        return np.concatenate([a1 * np.cos(angle) - a2 * np.sin(angle), a2 * np.cos(angle) + a1 * np.sin(angle)], -1)

    q = (norm(x @ p["w_dq"], p["q_norm"]) @ p["w_uq"]).reshape(b, t, heads, nope + rope)
    down = x @ p["w_dkv"]
    kv = (norm(down[..., :latent], p["kv_norm"]) @ p["w_ukv"]).reshape(b, t, heads, -1)
    k_r = turn(down[..., latent:])  # [b, t, rope]: one head
    out = np.zeros((b, t, heads, kv.shape[-1] - nope))
    for h in range(heads):
        q_h = np.concatenate([q[:, :, h, :nope], turn(q[:, :, h, nope:])], axis=-1)
        k_h = np.concatenate([kv[:, :, h, :nope], k_r], axis=-1)
        for i in range(t):
            s = np.einsum("bd,bkd->bk", q_h[:, i], k_h[:, : i + 1]) / np.sqrt(nope + rope)
            w = np.exp(s - s.max(axis=-1, keepdims=True))
            out[:, i, h] = np.einsum("bk,bkd->bd", w / w.sum(axis=-1, keepdims=True), kv[:, : i + 1, h, nope:])
    return jnp.asarray(out.reshape(b, t, -1) @ p["w_o"], jnp.float32)


@pytest.mark.parametrize("length", [150, 128, 7], ids=["over-a-block", "a-row-block", "short"])
def test_latent_mixer_equals_the_masked_softmax_token_by_token(params, length):
    p = params["layers"][1]["mla"]
    x = hidden(1, length)
    want = token_by_token(x, p, MODEL)
    assert_close(mixer()(x, p), want)
    assert_close(ref.attention(x, p, MODEL), want)
    weigh = jax.random.normal(jax.random.key(2), x.shape)
    assert_close(
        jax.jit(jax.grad(lambda p, x: jnp.sum(weigh * mixer()(x, p)), argnums=(0, 1)))(p, x),
        jax.jit(jax.grad(lambda p, x: jnp.sum(weigh * ref.attention(x, p, MODEL)), argnums=(0, 1)))(p, x),
    )


PUBLISHED_HEADS = MODEL | {"num_attention_heads": 2, "qk_nope_head_dim": 192, "qk_rope_head_dim": 64, "v_head_dim": 256}


def test_latent_mixer_at_the_published_head_takes_the_flash_kernels(monkeypatch):
    """192 + 64 channels a key and 256 a value, one query head a key-value
    head, a row of one 128-key tile: the kernels (in the interpreter here),
    against the reference's full softmax, forward and every gradient."""
    cfg = lm.Glm4MoeLiteConfig.from_published(PUBLISHED_HEADS, experts_held=HELD, dtype="float32")
    p = _scaled(lm.init_lm_params(cfg, jax.random.key(0)))["layers"][1]["mla"]
    calls = []
    kernel = attention._flash_forward
    monkeypatch.setattr(attention, "_flash_forward", lambda *a, **k: calls.append((a[0].shape, k)) or kernel(*a, **k))
    x = hidden(3, 128)[:1]
    weigh = jax.random.normal(jax.random.key(4), x.shape)
    assert_close(mixer(cfg)(x, p), ref.attention(x, p, PUBLISHED_HEADS))
    # [heads, group of 1, T, 256]; ``batch``: a head of two lane tiles, the output written token-major, [1, T, 2 x 256]
    assert calls == [((2, 1, 128, 256), {"bq": 128, "bk": 128, "window": None, "batch": 1, "interpret": True})]
    assert_close(
        jax.jit(jax.grad(lambda p, x: jnp.sum(weigh * mixer(cfg)(x, p)), argnums=(0, 1)))(p, x),
        jax.jit(jax.grad(lambda p, x: jnp.sum(weigh * ref.attention(x, p, PUBLISHED_HEADS)), argnums=(0, 1)))(p, x),
    )


def test_latent_attention_is_causal_and_every_head_shares_one_rotary_key(params, monkeypatch):
    p = params["layers"][1]["mla"]
    x = hidden(5)
    later = x.at[:, 100:].set(0.0)
    np.testing.assert_allclose(mixer()(x, p)[:, :100], mixer()(later, p)[:, :100], atol=1e-5)
    # what reaches the attention: per-head keys whose last 4 channels are one head's, the same for all four
    seen = {}
    real = attention.causal_attention
    monkeypatch.setattr(causal_lm, "causal_attention", lambda q, k, v: seen.update(q=q, k=k, v=v) or real(q, k, v))
    turned = []
    rotary = attention._rotary
    monkeypatch.setattr(causal_lm, "_rotary", lambda a, pos, dim, theta: turned.append((a.shape[2], dim, theta)) or rotary(a, pos, dim, theta))
    mixer()(x, p)
    assert seen["q"].shape == (B, 4, 1, T, 16) and seen["k"].shape == seen["v"].shape == (B, 4, T, 16)
    k_rope = seen["k"][..., 12:]
    assert float(jnp.max(jnp.abs(k_rope - k_rope[:, :1]))) == 0.0 and float(jnp.std(k_rope)) > 0
    assert float(jnp.min(jnp.std(seen["k"][..., :12], axis=1))) > 0  # the other 12 channels are each head's own
    assert turned == [(4, 4, 1e6), (1, 4, 1e6)]  # four query heads and ONE key head, all 4 rotary channels
    q_ref, k_ref, v_ref = ref.latent_qkv(x, p, MODEL)
    assert_close(seen["k"], k_ref.transpose(0, 2, 1, 3))
    assert_close(seen["q"][:, :, 0] * 4.0, q_ref.transpose(0, 2, 1, 3))  # the program scales by 1 / sqrt(16)
    assert_close(seen["v"], v_ref.transpose(0, 2, 1, 3))


# ----------------------------------------------------------------- routing


def test_routing_picks_on_the_biased_score_and_weighs_with_the_unbiased_times_the_scale():
    """Two tokens over six experts, top 2, the family's epsilon and scale.
    Token 0: the bias lifts expert 4 (score 0.5) over expert 1 (0.7); its
    weight is still made of 0.5."""
    logit = lambda s: float(np.log(s / (1 - s)))  # noqa: E731
    want_scores = np.array([[0.9, 0.7, 0.1, 0.2, 0.5, 0.3], [0.2, 0.8, 0.6, 0.1, 0.1, 0.3]], np.float32)
    x = jnp.eye(2, dtype=jnp.float32)
    router = jnp.asarray(np.vectorize(logit)(want_scores), jnp.float32)  # x @ router = the logits
    bias = jnp.asarray([0.0, 0.0, 0.0, 0.0, 0.3, 0.0])
    cfg = lm.Glm4MoeLiteConfig.from_published(MODEL | {"num_experts_per_tok": 2, "n_routed_experts": 6})
    top_e, w, moved = cfg.route(x, router, bias)
    assert np.asarray(top_e).tolist() == [[0, 4], [1, 2]] and int(moved) == 1
    np.testing.assert_allclose(w[0], np.array([0.9, 0.5]) / 1.4 * 1.8, rtol=1e-6)
    np.testing.assert_allclose(w[1], np.array([0.8, 0.6]) / 1.4 * 1.8, rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(w, axis=-1), 1.8, rtol=1e-6)  # no 1e-6 in the denominator
    ref_e, ref_w = ref.route(x, router, bias, {"num_experts_per_tok": 2, "routed_scaling_factor": 1.8})
    assert np.asarray(ref_e).tolist() == np.asarray(top_e).tolist()
    np.testing.assert_allclose(ref_w, w, rtol=1e-6)
    # the epsilon is an argument; the default is the other sigmoid family's 1e-6
    _, lfm2, _ = moe.route_sigmoid_top_k(x, router, bias, top_k=2)
    np.testing.assert_array_equal(lfm2, moe.route_sigmoid_top_k(x, router, bias, top_k=2, eps=1e-6)[1])
    np.testing.assert_allclose(lfm2[0], np.array([0.9, 0.5]) / (1.4 + 1e-6), rtol=1e-6)
    _, tiny, _ = moe.route_sigmoid_top_k(x * 0 - 1e4, jnp.abs(router), bias, top_k=2, eps=1e-20)
    assert bool(jnp.all(jnp.isfinite(tiny)))  # scores of 0: 0 / 1e-20, not 0 / 0
    # the bias carries no gradient; the router's comes through the unbiased scores
    g_router, g_bias = jax.grad(
        lambda r, b: jnp.sum(cfg.route(x, r, b)[1] * jnp.array([1.0, -1.0])), argnums=(0, 1)
    )(router, bias)
    assert float(jnp.max(jnp.abs(g_bias))) == 0.0 and float(jnp.max(jnp.abs(g_router))) > 0.01


@pytest.mark.parametrize("gated", [False, True], ids=["ungated", "gated"])
def test_one_shared_expert_function_with_and_without_a_gate(params, gated):
    """The family's shared expert has no gate; the other family's has one:
    ``shared_expert`` applies the sigmoid gate where the weights hold it."""
    p = dict(params["layers"][1]["moe"]["shared"])
    x = hidden(6)
    want = ref.shared(x, p)
    if gated:
        p["gate"] = jax.random.normal(jax.random.key(7), (MODEL["hidden_size"],)) * 0.1
        want = want * jax.nn.sigmoid(x @ p["gate"])[..., None]
    assert_close(moe.shared_expert(x, p), want)
    if gated:
        assert float(jnp.linalg.norm(moe.shared_expert(x, p) - ref.shared(x, p))) > 0


# ------------------------------------------------------------ expert layer


def _route_to(router, experts):
    """A router that sends every token whose first channel is 10 to ``experts``
    (its top-k): their logits stand 50 above the rest, their scores at 1."""
    return (router * 1e-3).at[0, jnp.asarray(experts)].add(5.0)


def _expert_layer(x, p, bias, *, held, cfg=CFG, tile=None):
    """Routing, the held experts and the shared expert as ``causal_lm.lm_layer``
    puts them together."""
    top_e, w, moved = cfg.route(x, p["router"], bias)
    y, counts = moe.held_experts(x, top_e, w, p, n_experts=cfg.num_experts, held=held, tile=tile)
    return y + moe.shared_expert(x, p["shared"]), dict(counts, moe_bias_moved=moved)


ROUTINGS = ["even", "all-on-one-held", "none-held", "top-1"]


def _routed(p, model, routing):
    p = dict(p)
    if routing == "all-on-one-held":  # expert 5 takes every token, its three companions are not held
        p["router"] = _route_to(p["router"], [5, 0, 1, 2])
    elif routing == "none-held":
        p["router"] = _route_to(p["router"], [0, 1, 2, 3])
    elif routing == "top-1":
        model = model | {"num_experts_per_tok": 1}
    return p, model


@pytest.mark.parametrize("routing", ROUTINGS)
def test_expert_layer_equals_the_loop_over_experts_plus_the_shared_expert(params, routing):
    p, model = _routed(params["layers"][1]["moe"], MODEL, routing)
    cfg = lm.Glm4MoeLiteConfig.from_published(model, experts_held=HELD, dtype="float32")
    bias = params["buffers"]["layers"][1]["expert_bias"]
    x = hidden(7).at[..., 0].set(10.0)
    weigh = jax.random.normal(jax.random.key(8), x.shape)
    layer = functools.partial(_expert_layer, held=HELD, cfg=cfg, tile=16)
    y, counts = layer(x, p, bias)
    assert_close(y, ref.moe(x, p, bias, model, HELD))
    got = jax.jit(jax.grad(lambda p, x: jnp.sum(weigh * layer(x, p, bias)[0]), argnums=(0, 1)))(p, x)
    want = jax.jit(jax.grad(lambda p, x: jnp.sum(weigh * ref.moe(x, p, bias, model, HELD)), argnums=(0, 1)))(p, x)
    if routing == "top-1":  # a lone weight is s / (s + 1e-20) x 1.8 = 1.8: the router has no gradient to compare
        for grads in (got, want):
            assert float(jnp.max(jnp.abs(grads[0].pop("router")))) < 1e-3
    assert_close(got, want)
    n, top_k = B * T, model["num_experts_per_tok"]
    assert int(counts["moe_all"]) == top_k * n
    if routing == "all-on-one-held":
        assert (int(counts["moe_held"]), int(counts["moe_load_max"])) == (n, n)
    elif routing == "none-held":
        assert (int(counts["moe_held"]), int(counts["moe_load_max"])) == (0, 0)
        assert_close(y, ref.shared(x, p["shared"]))  # the shared expert alone
    else:
        assert 0 < int(counts["moe_load_max"]) <= int(counts["moe_held"]) < top_k * n


def test_the_eight_shares_add_up_to_the_uncut_layer_with_the_shared_expert_counted_once(params):
    """16 experts over eight chips of 2: the eight shares' routed parts and
    ONE shared expert (every chip computes it alike, on its own rows) are the
    whole layer of the reference."""
    whole = dict(params["layers"][2]["moe"])
    bias = params["buffers"]["layers"][2]["expert_bias"]
    keys = jax.random.split(jax.random.key(9), 3)
    for name, key in zip(("w_gate", "w_up", "w_down"), keys):  # all 16 experts' weights
        whole[name] = jax.random.normal(key, (16,) + whole[name].shape[1:]) * 0.1
    x = hidden(10)
    top_e, w, _ = CFG.route(x, whole["router"], bias)
    total = moe.shared_expert(x, whole["shared"])
    for first in range(0, 16, 2):
        share = {k: whole[k][first:first + 2] for k in ("w_gate", "w_up", "w_down")}
        y, _ = moe.held_experts(x, top_e, w, share, n_experts=16, held=(first, 2), tile=32)
        total = total + y
    assert_close(total, ref.moe(x, whole, bias, MODEL, (0, 16)))
    assert float(jnp.linalg.norm(ref.shared(x, whole["shared"]))) > 0.1 * float(jnp.linalg.norm(total))


# ------------------------------------------------------------- whole model


def test_both_losses_and_every_gradient_leaf_equal_the_reference(params):
    ids, labels = tokens()
    (loss, counts), grads = jax.jit(jax.value_and_grad(
        lambda p: CFG.loss(p, ids, labels), has_aux=True
    ))(params)
    positions = jnp.arange(0, T, 7)
    (want, terms), want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.lm_loss(p, ids, labels, cfg=MODEL, held=HELD, logits_at=positions), has_aux=True
    ))(params)
    np.testing.assert_allclose(float(loss), float(want), rtol=2e-6)
    np.testing.assert_allclose(float(counts["loss_main"]), float(terms["loss_main"]), rtol=2e-6)
    np.testing.assert_allclose(float(counts["loss_mtp"]), float(terms["loss_mtp"]), rtol=2e-6)
    np.testing.assert_allclose(float(loss), float(counts["loss_main"]) + 0.3 * float(counts["loss_mtp"]), rtol=1e-6)
    assert abs(float(counts["loss_main"]) - float(counts["loss_mtp"])) > 1e-4  # two losses, not one twice
    for tree in (grads, want_grads):  # the biases steer the selection: no gradient reaches them, in either
        assert all(float(jnp.max(jnp.abs(g))) == 0.0 for g in jax.tree.leaves(tree.pop("buffers")))
    assert_close(grads, want_grads)
    assert all(float(jnp.linalg.norm(g)) > 0 for g in jax.tree.leaves(grads))
    # counts: three routed layers (two of the stack, the module's), the labelled positions of each loss
    assert int(counts["tokens"]) == B * T and int(counts["moe_all"]) == 3 * 4 * B * T
    assert 0 < int(counts["moe_bias_moved"]) < int(counts["moe_all"])
    assert (int(counts["head_mtp"]), int(counts["head_all"])) == (B * (T - 2), B * (T - 1) + B * (T - 2))
    # both heads' logits
    x, _ = causal_lm.lm_hidden(params, ids, cfg=CFG)
    h, _ = causal_lm.mtp_hidden(params, x, labels, cfg=CFG)
    assert_close(causal_lm.lm_head(causal_lm.head_params(params), x[:, positions], cfg=CFG), terms["logits"])
    assert_close(causal_lm.lm_head(causal_lm.mtp_head_params(params), h[:, positions], cfg=CFG), terms["logits_mtp"])
    assert_close(causal_lm.lm_logits(params, ids, cfg=CFG), ref.lm_logits(params, ids, cfg=MODEL, held=HELD))


def test_the_module_predicts_the_token_after_next_from_the_next_tokens_embedding(params):
    """Position i of the module sees tokens up to i + 1 and is scored on token
    i + 2: a row's last two positions carry no second loss, and a change of the
    last token moves the second loss through position T - 3's label alone."""
    ids, labels = tokens(3, rows=1)
    x, _ = causal_lm.lm_hidden(params, ids, cfg=CFG)
    loss, _, positions = causal_lm.mtp_loss(params, x, labels, cfg=CFG)
    assert int(positions) == T - 2
    h, _ = causal_lm.mtp_hidden(params, x, labels, cfg=CFG)
    swapped = labels.at[0, T - 2].set((labels[0, T - 2] + 1) % MODEL["vocab_size"])  # the row's last token
    h_swapped, _ = causal_lm.mtp_hidden(params, x, swapped, cfg=CFG)
    np.testing.assert_allclose(h[:, : T - 2], h_swapped[:, : T - 2], atol=1e-6)  # causal: earlier positions unmoved
    assert float(jnp.max(jnp.abs(h[:, T - 2] - h_swapped[:, T - 2]))) > 1e-4    # its embedding feeds position T - 2
    logits = causal_lm.lm_head(causal_lm.mtp_head_params(params), h, cfg=CFG)
    logp = jax.nn.log_softmax(logits[0, : T - 2], axis=-1)
    want = -jnp.mean(jnp.take_along_axis(logp, ids[0, 2:, None], axis=-1))
    np.testing.assert_allclose(float(loss), float(want), rtol=1e-5)


def test_without_a_module_the_loss_is_the_next_token_loss_alone():
    model = MODEL | {"num_nextn_predict_layers": 0}
    cfg = lm.Glm4MoeLiteConfig.from_published(model, experts_held=HELD, dtype="float32")
    bare = _scaled(cfg.init(jax.random.key(0)))
    assert "mtp" not in bare and "mtp" not in bare["buffers"]
    ids, labels = tokens(4)
    loss, counts = cfg.loss(bare, ids, labels)
    np.testing.assert_allclose(float(loss), float(ref.lm_loss(bare, ids, labels, cfg=model, held=HELD)), rtol=2e-6)
    assert float(counts["loss_mtp"]) == 0.0 and float(counts["loss_main"]) == float(loss)
    assert (int(counts["head_mtp"]), int(counts["head_all"])) == (0, B * (T - 1))
    assert int(counts["moe_all"]) == 2 * 4 * B * T


def test_bfloat16_program_stays_near_the_reference(params):
    """The dtype the chip runs: products in bfloat16, float32 accumulation."""
    ids, labels = tokens(1)
    cfg = lm.Glm4MoeLiteConfig.from_published(MODEL, experts_held=HELD)
    loss, counts = cfg.loss(params, ids, labels)
    want, terms = ref.lm_loss(params, ids, labels, cfg=MODEL, held=HELD, logits_at=jnp.arange(1))
    assert abs(float(loss) - float(want)) < 0.02
    assert abs(float(counts["loss_mtp"]) - float(terms["loss_mtp"])) < 0.02


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
            counted = {kind: _series(MOE_ASSIGNMENTS_FAMILY, kind=kind) for kind in ("held", "all", "bias_moved")}
            counted.update(tokens=_series(TOKENS_FAMILY), head_mtp=_series(HEAD_POSITIONS_FAMILY, kind="mtp"),
                           head_all=_series(HEAD_POSITIONS_FAMILY, kind="all"))
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


def test_one_step_is_the_references_adamw_step_and_every_bias_is_returned_bit_for_bit(stepped):
    ids, labels, out = stepped
    states = out[1]["states"]
    loss, grads = jax.jit(jax.value_and_grad(
        lambda p: ref.lm_loss(p, ids, labels, cfg=MODEL, held=HELD)
    ))(states[0])
    trained = _trained(states[0])
    zeros = jax.tree.map(jnp.zeros_like, trained)
    want, _, _ = ref.adamw_step(trained, _trained(grads), zeros, zeros, 0, lr=1e-2)
    np.testing.assert_allclose(out[1]["losses"][0], float(loss), rtol=2e-6)
    assert_moves_agree(trained, _trained(states[1]), want)
    # three steps on, every weight has moved three times and no bias at all (the module's among them): no
    # gradient, no moment and no weight decay (which would shrink it by lr * 1e-4 a step) has reached one
    assert out[1]["losses"][-1] < out[1]["losses"][0]
    for run in out.values():
        first, last = run["states"][0], run["states"][-1]
        biases = jax.tree.leaves(first["buffers"])
        assert len(biases) == 3  # two routed layers and the module's
        for a, b in zip(biases, jax.tree.leaves(last["buffers"]), strict=True):
            assert a.dtype == b.dtype == np.float32 and a.tobytes() == b.tobytes() and np.abs(a).max() > 0
        assert all(np.abs(a - b).max() > 1e-3 for a, b in
                   zip(jax.tree.leaves(_trained(first)), jax.tree.leaves(_trained(last))))
        # and the optimizer holds nothing for them: moments for the trained leaves only
        moments = [leaf for leaf in jax.tree.leaves(run["opt_state"]) if leaf.ndim]
        assert len(moments) == 2 * len(jax.tree.leaves(_trained(first)))


def test_step_on_a_dp2_mesh_equals_one_device(stepped):
    _, _, out = stepped
    np.testing.assert_allclose(out[2]["losses"][0], out[1]["losses"][0], rtol=1e-5)
    assert_moves_agree(_trained(out[2]["states"][0]), _trained(out[2]["states"][1]), _trained(out[1]["states"][1]))


def test_counters_for_a_known_routing_and_the_head_positions(stepped):
    ids, labels, out = stepped
    run = out[1]
    before = run["states"][0]
    # what the first step must have counted, from the reference's routing of the same weights: the
    # stack's two routed layers, then the module's on the module's input
    held = load_max = tile_rows = dw_writes = bias_moved = 0

    def count(x, lp, buffers):
        nonlocal held, load_max, tile_rows, dw_writes, bias_moved
        mixed = x + ref.attention(ref.rms_norm(x, lp["norm1"], 1e-5), lp["mla"], MODEL)
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

    x = jnp.asarray(before["embed"])[ids]
    for lp, buffers in zip(before["layers"], before["buffers"]["layers"]):
        if "moe" in lp:
            count(x, lp, buffers)
        x = ref.layer(x, lp, buffers, MODEL, HELD)
    p = before["mtp"]
    e = ref.rms_norm(jnp.asarray(before["embed"])[jnp.maximum(labels, 0)], p["enorm"], 1e-5)
    h = ref.rms_norm(ref.rms_norm(x, before["final_norm"], 1e-5), p["hnorm"], 1e-5)
    count(jnp.concatenate([e, h], axis=-1) @ p["eh_proj"], p["layer"], before["buffers"]["mtp"])
    assert bias_moved > 0
    # the step's own counts are over its three steps; the weights move, so only the first step's
    # routing is known: run it again from the first state
    plan = make_mesh(jax.devices()[:1], dp=1, tp=1, sp=1)
    with jax.default_matmul_precision("highest"):
        state, opt_state, tx, shardings = make_lm_train_state(CFG, plan, lr=1e-2, seed=3)
        once = make_lm_train_step(CFG, plan, tx, shardings)
        once(state, opt_state, ids, labels)
    main, second = B * (T - 1), B * (T - 2)
    assert once.counts() == {"tokens": B * T, "moe_all": 3 * 4 * B * T, "moe_held": held, "moe_load_max": load_max,
                             "moe_tile_rows": tile_rows, "moe_dw_writes": dw_writes, "moe_grouped": 0, "moe_bias_moved": bias_moved,
                             "head_mtp": second, "head_all": main + second,
                             "attn_tiles_run": 0, "attn_tiles_causal": 0,  # 150 tokens: the kernels list no tile
                             "attn_pair_tiles_run": 0, "attn_pair_tiles": 0,  # no head pairs
                             "attn_operands_kernel": 0, "attn_operands_xla": 0,
                             "attn_out_tokens": 0, "attn_out_heads": 4 * B,  # three layers and the module's, in the twin: heads first
                             "loss_rows_fused": 0, "loss_rows_compiler": 2 * B * T,  # both losses' rows to the tile loop; tiles this small stay the compiler's
                             "head_loop": 0, "loop_layers_run": 0, "loop_layers": 0,  # latent attention makes its own operands; no pass loop
                             "ssm_rows_kernel": 0, "ssm_rows_twin": 0, "shared_reads": 0}  # no selective scan, no state one layer reads of another
    # the registry's series: three steps on one device, three on the mesh, and the one above
    counted = run["counted"]
    steps = 2 * STEPS + 1
    assert _series(TOKENS_FAMILY) - counted["tokens"] == steps * B * T
    assert _series(MOE_ASSIGNMENTS_FAMILY, kind="all") - counted["all"] == steps * 12 * B * T
    assert _series(HEAD_POSITIONS_FAMILY, kind="mtp") - counted["head_mtp"] == steps * second
    assert _series(HEAD_POSITIONS_FAMILY, kind="all") - counted["head_all"] == steps * (main + second)
    got = run["step"].counts()
    assert got["moe_all"] == STEPS * 12 * B * T and got["moe_held"] >= held and got["head_mtp"] == STEPS * second
    assert _series(MOE_ASSIGNMENTS_FAMILY, kind="held") - counted["held"] == (
        got["moe_held"] + out[2]["step"].counts()["moe_held"] + held
    )


# --------------------------------------------- the benchmark's configuration


@pytest.fixture(scope="module")
def deployed():
    with open(os.path.join(BENCH, "configs", "glm47_flash_clm_pk.json")) as f:
        config = json.load(f)
    m = config["model"]
    cfg = lm.Glm4MoeLiteConfig.from_published(
        m, experts_held=(m["first_expert_held"], m["num_experts_held"]), dtype=m["compute_dtype"]
    )
    shapes = jax.eval_shape(cfg.init, jax.random.key(0))
    return config, cfg, shapes


def _count(tree) -> int:
    return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))


PARTS = {
    "latent attention, a layer": (lambda s: s["layers"][1]["mla"], 21_759_232),
    "layer 0": (lambda s: s["layers"][0], 84_677_888),
    "a sparse layer at 8 held": (lambda s: s["layers"][1], 106_829_056),
    "layers 1 to 4": (lambda s: s["layers"][1:], 427_316_224),
    "embedding, head, final norm": (lambda s: [s["embed"], s["head"], s["final_norm"]], 79_300_608),
    "prediction module": (lambda s: s["mtp"], 115_223_808),
    "total": (lambda s: {k: v for k, v in s.items() if k != "buffers"}, 706_518_528),
}


@pytest.mark.parametrize("part", sorted(PARTS))
def test_the_held_models_parameter_table(deployed, part):
    """The cut as ``configs/glm47_flash_clm_pk.json`` states it, counted on
    ``jax.eval_shape(cfg.init, ...)``: nothing is allocated."""
    _, _, shapes = deployed
    pick, want = PARTS[part]
    assert _count(pick(shapes)) == want
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(shapes))


def test_the_cut_keeps_every_published_width_and_states_its_share(deployed):
    config, cfg, shapes = deployed
    published, model = config["published"], config["model"]
    cut = {"num_hidden_layers", "vocab_size"}
    assert {k for k in published if published[k] != model[k]} == cut
    assert (model["num_hidden_layers"], model["vocab_size"], model["num_experts_held"]) == (5, 154880 // 8, 8)
    assert cfg.ffn_kinds() == ("dense", "moe", "moe", "moe", "moe") and cfg.experts_held == (0, 8)
    assert shapes["layers"][1]["moe"]["router"].shape == (2048, 64)  # the router keeps its 64 outputs
    assert [b["expert_bias"].shape for b in shapes["buffers"]["layers"][1:]] == [(64,)] * 4
    assert shapes["buffers"]["mtp"]["expert_bias"].shape == (64,)
    assert cfg.mtp_loss_weight == 0.3 and cfg.routed_scaling_factor == 1.8
    for name in ("reduced_why", "assumed", "guarantees", "program_departures", "deployment", "optimizer"):
        assert config[name], name
    assert sorted(config["reduced_why"]) == sorted(
        ["num_layers_held", "num_experts_held", "vocab_size", "table_rows", "storage", "token_source"]
    )


def test_the_adaptors_operation_count_is_the_hand_count(deployed):
    """``flops_per_row``: every product once forward and twice backward, no
    recomputation, both heads and the module among them."""
    import sys

    config, _, _ = deployed
    sys.path[:0] = [p for p in (BENCH,) if p not in sys.path]  # the adaptor imports ``chipbench``
    spec = importlib.util.spec_from_file_location(
        "glm4_moe_lite_clm", os.path.join(BENCH, "consumers", "glm4_moe_lite_clm.py")
    )
    adaptor = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(adaptor)
    mixer_params, expert = 21_759_232 - 768 - 512, 3 * 2048 * 1536
    per_token = (
        6 * mixer_params + 3 * 2048 * 10240 + 5 * (2048 * 64 + expert + 4 * 8 / 64 * expert)
        + 2 * 2048 * 2048 + 2 * 2048 * 19360
    )
    scores = 6 * 4 * 8192 * 20 * 256 / 2
    assert adaptor.flops_per_row(config) == pytest.approx(3 * 8192 * (2 * per_token + scores), rel=1e-12)
    assert 1.20e9 < 2 * per_token + scores < 1.22e9  # 1.21 GFLOP a token forward
