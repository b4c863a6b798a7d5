"""The ``afmoe`` family (``models/afmoe.py``: Trinity-Mini) on the shared
causal-LM stack (``models/causal_lm.py``): window and full attention mixed in
one stack, the gate on the attention output, four norms a layer, the scaled
embedding, sigmoid routing under a bias beside an ungated shared expert
(``parallel/moe.py``) and the train step (``models/train.py``), against the
plain float32 reference in ``benchmarks/chip/reference/afmoe_f32.py`` (the one
copy of it, loaded by path).

Small on purpose (hidden 64) with the published shape kept: one leading dense
layer, then sparse layers, window layers three to one full layer, four query
heads on two key-value heads, 16 experts top-4 of which 4 are held, a shared
expert, an untied head, an expert bias.  The window (40 of 150 tokens, 200 of
512 at the kernels' shapes) is smaller than the row and no multiple of a
tile.  The program runs with ``dtype="float32"`` here so that the comparison
is of the algorithms (tile lists against the whole mask, tiles against a
masked loop), not of bfloat16.
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

from lakesoul_tpu.models import afmoe as lm
from lakesoul_tpu.models import attention, causal_lm
from lakesoul_tpu.models.train import (
    ATTN_KEY_TILES_FAMILY,
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
_spec = importlib.util.spec_from_file_location("afmoe_f32", os.path.join(BENCH, "reference", "afmoe_f32.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

SWA, FULL = "sliding_attention", "full_attention"
MODEL = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=4, num_dense_layers=1, intermediate_size=112,
    layer_types=[SWA, SWA, FULL, SWA], num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    sliding_window=40, rope_theta=10000, rope_scaling=None, mup_enabled=True,
    num_experts=16, num_shared_experts=1, num_experts_per_tok=4, moe_intermediate_size=32, route_scale=2.826,
    route_norm=True, score_func="sigmoid", n_group=1, topk_group=1, num_expert_groups=1, num_limited_groups=1,
    rms_norm_eps=1e-5, tie_word_embeddings=False, hidden_act="silu", load_balance_coeff=0.001,
)
# the kernels' shapes: a head of 64, rows of four 128-key tiles, a window that is no multiple of a tile
KERNELS = MODEL | {"head_dim": 64, "sliding_window": 200}
HELD = (4, 4)
CFG = lm.AfmoeConfig.from_published(MODEL, experts_held=HELD, dtype="float32")
B, T = 2, 150


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _scaled(tree):
    """Five times the family's 0.02 (and 0.003 of the bias), so that no path's
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


def hidden(seed, length=T, width=MODEL["hidden_size"], rows=B):
    return jax.random.normal(jax.random.key(seed), (rows, length, width))


def assert_close(got, want, tol=2e-4):
    """Every leaf within ``tol`` of the reference by relative norm."""
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want), strict=True):
        err = float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))
        assert err < tol, f"{jax.tree_util.keystr(path)}: {err}"


def test_the_stack_mixes_window_and_full_layers_with_four_norms_each(params):
    assert CFG.layer_kinds() == ("swa", "swa", "attn", "swa") and CFG.ffn_kinds() == ("dense", "moe", "moe", "moe")
    assert CFG.mixer("swa")[1] == lm.SWA_SCOPE == "lakesoul.lm.swa" and CFG.mixer("attn")[1] == causal_lm.ATTN_SCOPE
    norms = ["norm1", "norm1_out", "norm2", "norm2_out"]
    assert [sorted(lp) for lp in params["layers"]] == [
        sorted(["swa", "mlp", *norms]), sorted(["swa", "moe", *norms]), sorted(["attn", "moe", *norms]),
        sorted(["swa", "moe", *norms]),
    ]
    for lp, kind in zip(params["layers"], CFG.layer_kinds()):  # one set of weights whatever the mask
        assert sorted(lp[kind]) == ["k_norm", "q_norm", "w_gate", "w_k", "w_o", "w_q", "w_v"]
        assert lp[kind]["w_gate"].shape == lp[kind]["w_q"].shape == (64, 4 * 16) and lp[kind]["w_k"].shape == (64, 2 * 16)
    assert sorted(params["layers"][1]["moe"]["shared"]) == ["w_down", "w_gate", "w_up"]  # no gate
    assert [sorted(b) for b in params["buffers"]["layers"]] == [[], *[["expert_bias"]] * 3]
    assert "head" in params and params["head"].shape == (64, 96)  # untied
    whole = lm.AfmoeConfig()  # the published stack
    assert len(whole.layer_kinds()) == 32 and whole.layer_kinds()[:4] == ("swa", "swa", "swa", "attn")
    assert whole.layer_kinds().count("attn") == 8 and whole.ffn_kinds()[:3] == ("dense", "dense", "moe")
    assert (whole.num_experts, whole.num_experts_per_tok, whole.sliding_window, whole.head_dim) == (128, 8, 2048, 128)
    assert whole.embed_scale == 2048**0.5 and lm.AfmoeConfig(mup_enabled=False).embed_scale is None


@pytest.mark.parametrize("key, value", [
    ("score_func", "softmax"), ("route_norm", False), ("n_group", 2), ("topk_group", 2), ("num_expert_groups", 4),
    ("num_limited_groups", 2), ("rope_scaling", {"type": "yarn", "factor": 4}), ("tie_word_embeddings", True),
    ("hidden_act", "gelu"), ("num_shared_experts", 2),
])
def test_the_configuration_refuses_what_the_layers_do_not_compute(key, value):
    with pytest.raises(ValueError, match=key):
        lm.AfmoeConfig.from_published(MODEL | {key: value})


def test_layer_types_has_to_name_every_layer():
    with pytest.raises(ValueError, match="layer_types"):
        lm.AfmoeConfig.from_published(MODEL | {"layer_types": [SWA, FULL]})
    with pytest.raises(ValueError, match="layer_types"):
        lm.AfmoeConfig.from_published(MODEL | {"layer_types": [SWA, "linear_attention", FULL, SWA]})


# ---------------------------------------------------------------- attention


def token_by_token(x, p, model, kind):
    """The mixer a query at a time over the keys it sees, per head, in
    float64: the equations, with no block, no kernel, no mask and no batched
    softmax."""
    f64 = np.float64
    heads, kv, d = model["num_attention_heads"], model["num_key_value_heads"], model["head_dim"]
    eps, theta, window = model["rms_norm_eps"], model["rope_theta"], model["sliding_window"]
    p = jax.tree.map(lambda a: np.asarray(a, f64), p)
    x = np.asarray(x, f64)
    b, t, _ = x.shape

    def norm(a, w):
        return a / np.sqrt(np.mean(a * a, axis=-1, keepdims=True) + eps) * w

    def turn(a):  # [b, t, heads, d] rotate-half over every channel
        half = d // 2
        angle = (np.arange(t)[:, None] * theta ** (-np.arange(half) * 2.0 / d))[:, None, :]
        a1, a2 = a[..., :half], a[..., half:]
        return np.concatenate([a1 * np.cos(angle) - a2 * np.sin(angle), a2 * np.cos(angle) + a1 * np.sin(angle)], -1)

    q = norm((x @ p["w_q"]).reshape(b, t, heads, d), p["q_norm"])
    k = norm((x @ p["w_k"]).reshape(b, t, kv, d), p["k_norm"])
    v = (x @ p["w_v"]).reshape(b, t, kv, d)
    gate = 1.0 / (1.0 + np.exp(-(x @ p["w_gate"]).reshape(b, t, heads, d)))
    if kind == SWA:
        q, k = turn(q), turn(k)
    out = np.zeros((b, t, heads, d))
    for h in range(heads):
        g = h // (heads // kv)
        for i in range(t):
            first = max(0, i - window + 1) if kind == SWA else 0
            s = np.einsum("bd,bkd->bk", q[:, i, h], k[:, first: i + 1, g]) / np.sqrt(d)
            w = np.exp(s - s.max(axis=-1, keepdims=True))
            out[:, i, h] = np.einsum("bk,bkd->bd", w / w.sum(axis=-1, keepdims=True), v[:, first: i + 1, g])
    return jnp.asarray((out * gate).reshape(b, t, -1) @ p["w_o"], jnp.float32)


@pytest.mark.parametrize("length", [150, 128, 7], ids=["over-a-block", "a-row-block", "short"])
@pytest.mark.parametrize("kind, layer", [(SWA, 1), (FULL, 2)], ids=["window", "full"])
def test_mixer_equals_the_softmax_over_the_visible_keys_token_by_token(params, kind, layer, length):
    mixer = CFG.mixer(lm.KINDS[kind])[0]
    p = params["layers"][layer][lm.KINDS[kind]]
    x = hidden(1, length)
    want = token_by_token(x, p, MODEL, kind)
    assert_close(mixer(x, p), want)
    assert_close(ref.attention(x, p, MODEL, kind), want)
    weigh = jax.random.normal(jax.random.key(2), x.shape)
    assert_close(
        jax.jit(jax.grad(lambda p, x: jnp.sum(weigh * mixer(x, p)), argnums=(0, 1)))(p, x),
        jax.jit(jax.grad(lambda p, x: jnp.sum(weigh * ref.attention(x, p, MODEL, kind)), argnums=(0, 1)))(p, x),
    )


def test_a_window_layer_sees_its_own_position_and_the_window_less_one_before(params):
    """Query ``i`` sees keys ``i - 39 .. i``: a change at ``i - 40`` moves
    nothing at ``i``, a change at ``i - 39`` does; the full layer sees all."""
    x = hidden(3)
    moved = x.at[:, 60].add(1.0)
    for kind, layer in (("swa", 1), ("attn", 2)):
        mixer, p = CFG.mixer(kind)[0], params["layers"][layer][kind]
        apart = jnp.max(jnp.abs(mixer(x, p) - mixer(moved, p)), axis=(0, 2))
        assert float(jnp.max(apart[:60])) == 0.0  # causal
        assert float(apart[99]) > 1e-6            # 99 - 60 = 39: the last position still inside the window
        assert (float(jnp.max(apart[100:])) == 0.0) == (kind == "swa")  # 100 - 60 = 40: outside it


def test_only_the_window_layers_see_positions_and_the_gate_is_a_matrix_of_its_own(params, monkeypatch):
    turned, seen = [], []
    rotary, attend = attention._rotary, attention.causal_attention
    monkeypatch.setattr(attention, "_rotary", lambda a, pos, dim, theta: turned.append((a.shape[2], dim, theta)) or rotary(a, pos, dim, theta))
    monkeypatch.setattr(causal_lm, "causal_attention", lambda q, k, v, window=None: seen.append((q.shape, k.shape, window)) or attend(q, k, v, window))
    x = hidden(4)
    CFG.mixer("swa")[0](x, params["layers"][1]["swa"])
    assert turned == [(4, 16, 10000), (2, 16, 10000)]  # four query and two key heads, all 16 channels
    full, p = CFG.mixer("attn")[0], params["layers"][2]["attn"]
    out = full(x, p)
    assert len(turned) == 2  # no rotary at all
    assert seen == [((B, 2, 2, T, 16), (B, 2, T, 16), 40), ((B, 2, 2, T, 16), (B, 2, T, 16), None)]
    # a layer with no positions and no window does not care where in the row a prefix stands ... (causal: a prefix)
    np.testing.assert_allclose(full(x[:, :50], p), out[:, :50], atol=1e-5)
    # the gate: sigmoid(0) halves every head's output; without the matrix there is no gate
    halved = full(x, p | {"w_gate": jnp.zeros_like(p["w_gate"])})
    bare = full(x, {k: v for k, v in p.items() if k != "w_gate"})
    np.testing.assert_allclose(2 * halved, bare, atol=1e-5)
    assert float(jnp.linalg.norm(out - halved)) > 0.01 * float(jnp.linalg.norm(out))


@pytest.fixture
def tiles_of_128(monkeypatch):
    """512 tokens as 4 x 4 tiles of 128 queries (a group of 2: 256 score rows)
    by 128 keys, where the kernels' own sizes would make them one tile."""
    monkeypatch.setattr(attention, "FLASH_KEYS", 128)
    monkeypatch.setattr(attention, "FLASH_ROWS", 256)


def test_the_window_mixer_at_a_head_of_64_takes_the_flash_kernels_on_a_banded_tile_list(monkeypatch, tiles_of_128):
    """512 tokens, tiles of 128 x 128, a window of 200: a query tile's first
    key tile holds keys its last query no longer sees (masked from below), its
    last the diagonal (masked from above), the first query tile's one tile is
    both, and tile (3, 0) is no step at all; against the reference's whole
    mask, forward and every gradient."""
    cfg = lm.AfmoeConfig.from_published(KERNELS, experts_held=HELD, dtype="float32")
    assert attention._flash_tiles(512, 2, 64) == (128, 128)
    pairs = attention._flash_pairs(512, 128, 128, 200)
    assert pairs == [(0, 0), (1, 0), (1, 1), (2, 0), (2, 1), (2, 2), (3, 1), (3, 2), (3, 3)]
    assert attention.key_tile_steps(512, 2, 64, 200) == (9, 10)
    p = _scaled(lm.init_lm_params(cfg, jax.random.key(0)))["layers"][1]["swa"]
    calls = []
    kernel = attention._flash_forward
    monkeypatch.setattr(attention, "_flash_forward", lambda *a, **k: calls.append((a[0].shape, k)) or kernel(*a, **k))
    x = hidden(5, 512, rows=1)
    mixer = cfg.mixer("swa")[0]
    assert_close(mixer(x, p), ref.attention(x, p, KERNELS, SWA))
    assert calls == [((2, 2, 512, 64), {"bq": 128, "bk": 128, "window": 200, "batch": None, "interpret": True})]  # a head of 64: heads first
    weigh = jax.random.normal(jax.random.key(6), x.shape)
    assert_close(
        jax.jit(jax.grad(lambda p, x: jnp.sum(weigh * mixer(x, p)), argnums=(0, 1)))(p, x),
        jax.jit(jax.grad(lambda p, x: jnp.sum(weigh * ref.attention(x, p, KERNELS, SWA)), argnums=(0, 1)))(p, x),
    )


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
    cfg = lm.AfmoeConfig.from_published(MODEL | {"num_experts_per_tok": 2, "num_experts": 6})
    top_e, w, moved = cfg.route(x, router, bias)
    assert np.asarray(top_e).tolist() == [[0, 4], [1, 2]] and int(moved) == 1
    np.testing.assert_allclose(w[0], np.array([0.9, 0.5]) / 1.4 * 2.826, rtol=1e-6)
    np.testing.assert_allclose(jnp.sum(w, axis=-1), 2.826, rtol=1e-6)  # route_norm, no 1e-6 in the denominator
    ref_e, ref_w = ref.route(x, router, bias, {"num_experts_per_tok": 2, "route_scale": 2.826})
    assert np.asarray(ref_e).tolist() == np.asarray(top_e).tolist()
    np.testing.assert_allclose(ref_w, w, rtol=1e-6)


# ------------------------------------------------------------ expert layer


def _route_to(router, experts):
    """A router that sends every token whose first channel is 10 to ``experts``
    (its top-k): their logits stand 50 above the rest, their scores at 1."""
    return (router * 1e-3).at[0, jnp.asarray(experts)].add(5.0)


def _expert_layer(x, p, bias, *, held, cfg=CFG, tile=None):
    """Routing, the held experts and the shared expert as ``causal_lm.lm_layer``
    sums them before the layer's last norm."""
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
    cfg = lm.AfmoeConfig.from_published(model, experts_held=HELD, dtype="float32")
    bias = params["buffers"]["layers"][1]["expert_bias"]
    x = hidden(7).at[..., 0].set(10.0)
    weigh = jax.random.normal(jax.random.key(8), x.shape)
    layer = functools.partial(_expert_layer, held=HELD, cfg=cfg, tile=16)
    y, counts = layer(x, p, bias)
    assert_close(y, ref.moe(x, p, bias, model, HELD))
    got = jax.jit(jax.grad(lambda p, x: jnp.sum(weigh * layer(x, p, bias)[0]), argnums=(0, 1)))(p, x)
    want = jax.jit(jax.grad(lambda p, x: jnp.sum(weigh * ref.moe(x, p, bias, model, HELD)), argnums=(0, 1)))(p, x)
    if routing == "top-1":  # a lone weight is s / (s + 1e-20) x 2.826 = 2.826: the router has no gradient to compare
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


def test_the_eight_shares_add_up_to_the_uncut_layer_before_its_output_norm(params):
    """16 experts over eight chips of 2: the eight shares' routed parts and
    ONE shared expert (every chip computes it alike, on its own rows) are the
    whole feed-forward of the reference BEFORE the layer's fourth norm, which
    in a deployment stands after the shares have met; normed, the sum is the
    reference's sparse layer."""
    lp = params["layers"][2]
    whole = dict(lp["moe"])
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
    # and one chip that held all 16 computes that layer whole, its fourth norm after the sum
    every = lm.AfmoeConfig.from_published(MODEL, experts_held=(0, 16), dtype="float32")
    h = hidden(11)
    got, _ = causal_lm.lm_layer(h, lp | {"moe": whole}, {"expert_bias": bias}, kind="attn", ffn="moe", cfg=every)
    assert_close(got, ref.layer(h, lp | {"moe": whole}, {"expert_bias": bias}, FULL, MODEL, (0, 16)))


# ------------------------------------------------------------- whole model


@pytest.mark.parametrize("layer", [0, 1, 2], ids=["dense-window", "sparse-window", "sparse-full"])
def test_a_layer_norms_each_sublayers_output_before_the_residual_add(params, layer):
    """``h = x + N2(Mix(N1(x)))``, ``x' = h + N4(FFN(N3(h)))``: against the
    reference's layer, and the two output norms really stand there (their
    weights scale what a sublayer adds, not what is carried)."""
    lp, buffers = params["layers"][layer], params["buffers"]["layers"][layer]
    kind, ffn = CFG.layer_kinds()[layer], CFG.ffn_kinds()[layer]
    x = hidden(12)
    run = functools.partial(causal_lm.lm_layer, kind=kind, ffn=ffn, cfg=CFG)
    got, counts = run(x, lp, buffers)
    assert_close(got, ref.layer(x, lp, buffers, MODEL["layer_types"][layer], MODEL, HELD))
    assert (counts is not None) == (ffn == "moe")
    assert causal_lm.layer_attention_counts(CFG, kind, x, lp[kind]) == {  # 150 tokens: neither kernel pair takes the row
        "attn_tiles_run": 0, "attn_tiles_causal": 0, "attn_out_tokens": 0, "attn_out_heads": x.shape[0],
        "attn_pair_tiles_run": 0, "attn_pair_tiles": 0, "attn_operands_kernel": 0, "attn_operands_xla": x.shape[0]}
    for name in ("norm1_out", "norm2_out"):
        scaled = lp | {name: 2 * lp[name]}
        doubled, _ = run(x, scaled, buffers)
        assert_close(doubled, ref.layer(x, scaled, buffers, MODEL["layer_types"][layer], MODEL, HELD))
        assert float(jnp.linalg.norm(doubled - got)) > 0.05 * float(jnp.linalg.norm(got - x))
    # a norm's weight scales what its sublayer adds, and only that: at 0 the sublayer adds nothing
    no_ffn, _ = run(x, lp | {"norm2_out": 0 * lp["norm2_out"]}, buffers)
    kind_type = MODEL["layer_types"][layer]
    mixed = ref.attention(ref.rms_norm(x, lp["norm1"], 1e-5), lp[kind], MODEL, kind_type)
    assert_close(no_ffn, x + ref.rms_norm(mixed, lp["norm1_out"], 1e-5))


def test_the_embedding_is_scaled_by_the_root_of_the_width(params):
    ids, _ = tokens(5)
    none = params | {"layers": [], "buffers": {"layers": []}}
    bare = lm.AfmoeConfig.from_published(MODEL | {"num_hidden_layers": 0, "layer_types": []}, dtype="float32")
    x, _ = causal_lm.lm_hidden(none, ids, cfg=bare)
    assert_close(x, params["embed"][ids] * 8.0)
    flat = lm.AfmoeConfig.from_published(MODEL | {"num_hidden_layers": 0, "layer_types": [], "mup_enabled": False}, dtype="float32")
    assert_close(causal_lm.lm_hidden(none, ids, cfg=flat)[0], params["embed"][ids])


def test_loss_logits_and_every_gradient_leaf_equal_the_reference(params):
    ids, labels = tokens()
    (loss, counts), grads = jax.jit(jax.value_and_grad(
        lambda p: CFG.loss(p, ids, labels), has_aux=True
    ))(params)
    positions = jnp.arange(0, T, 7)
    (want, logits), want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.lm_loss(p, ids, labels, cfg=MODEL, held=HELD, logits_at=positions), has_aux=True
    ))(params)
    np.testing.assert_allclose(float(loss), float(want), rtol=2e-6)
    assert float(counts["loss_mtp"]) == 0.0 and float(counts["loss_main"]) == float(loss)
    for tree in (grads, want_grads):  # the biases steer the selection: no gradient reaches them, in either
        assert all(float(jnp.max(jnp.abs(g))) == 0.0 for g in jax.tree.leaves(tree.pop("buffers")))
    assert_close(grads, want_grads)
    assert all(float(jnp.linalg.norm(g)) > 0 for g in jax.tree.leaves(grads))  # every kind of leaf, the four norms too
    assert int(counts["tokens"]) == B * T and int(counts["moe_all"]) == 3 * 4 * B * T
    assert 0 < int(counts["moe_bias_moved"]) < int(counts["moe_all"])
    assert (int(counts["head_mtp"]), int(counts["head_all"])) == (0, B * (T - 1))
    x, _ = causal_lm.lm_hidden(params, ids, cfg=CFG)
    assert_close(causal_lm.lm_head(causal_lm.head_params(params), x[:, positions], cfg=CFG), logits)
    assert_close(causal_lm.lm_logits(params, ids, cfg=CFG), ref.lm_logits(params, ids, cfg=MODEL, held=HELD))


def test_the_whole_model_at_the_kernels_shapes_equals_the_reference(tiles_of_128):
    """One row of 512 tokens at a head of 64: both masks through the flash
    kernels (in the interpreter here), first-and-last, first-only and
    last-only tiles among them, loss and every gradient leaf; the step's tile
    counts are the tables' lengths."""
    cfg = lm.AfmoeConfig.from_published(KERNELS, experts_held=HELD, dtype="float32")
    weights = _scaled(lm.init_lm_params(cfg, jax.random.key(1)))
    ids, labels = tokens(6, rows=1, length=512)
    (loss, counts), grads = jax.jit(jax.value_and_grad(lambda p: cfg.loss(p, ids, labels), has_aux=True))(weights)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.lm_loss(p, ids, labels, cfg=KERNELS, held=HELD)
    ))(weights)
    np.testing.assert_allclose(float(loss), float(want), rtol=2e-6)
    for tree in (grads, want_grads):
        tree.pop("buffers")
    assert_close(grads, want_grads)
    # two key-value heads a layer: three window layers of 9 steps a head, one full layer of 10
    assert (int(counts["attn_tiles_run"]), int(counts["attn_tiles_causal"])) == (2 * (3 * 9 + 10), 2 * 4 * 10)


def test_bfloat16_program_stays_near_the_reference(params):
    """The dtype the chip runs: products in bfloat16, float32 accumulation."""
    ids, labels = tokens(1)
    cfg = lm.AfmoeConfig.from_published(MODEL, experts_held=HELD)
    loss, _ = cfg.loss(params, ids, labels)
    want = ref.lm_loss(params, ids, labels, cfg=MODEL, held=HELD)
    assert abs(float(loss) - float(want)) < 0.05  # the scaled embedding's activations are eight times the others'


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
            counted.update(tokens=_series(TOKENS_FAMILY))
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
    # three steps on, every weight has moved three times and no bias at all: no gradient, no moment and no
    # weight decay (which would shrink it by lr * 1e-4 a step) has reached one, and load_balance_coeff moves none
    assert out[1]["losses"][-1] < out[1]["losses"][0]
    for run in out.values():
        first, last = run["states"][0], run["states"][-1]
        biases = jax.tree.leaves(first["buffers"])
        assert len(biases) == 3  # the three routed layers'
        for a, b in zip(biases, jax.tree.leaves(last["buffers"]), strict=True):
            assert a.dtype == b.dtype == np.float32 and a.tobytes() == b.tobytes() and np.abs(a).max() > 0
        assert all(np.abs(a - b).max() > 1e-3 for a, b in
                   zip(jax.tree.leaves(_trained(first)), jax.tree.leaves(_trained(last))))
        moments = [leaf for leaf in jax.tree.leaves(run["opt_state"]) if leaf.ndim]
        assert len(moments) == 2 * len(jax.tree.leaves(_trained(first)))


def test_step_on_a_dp2_mesh_equals_one_device(stepped):
    _, _, out = stepped
    np.testing.assert_allclose(out[2]["losses"][0], out[1]["losses"][0], rtol=1e-5)
    assert_moves_agree(_trained(out[2]["states"][0]), _trained(out[2]["states"][1]), _trained(out[1]["states"][1]))


def test_the_steps_counters(stepped):
    """Tokens and assignments as the other families count them; the key-tile
    family stays at 0 here (150 tokens: the blockwise path lists no tile) and
    is held to the tile tables in ``tests/test_stage_spans.py``."""
    _, _, out = stepped
    run = out[1]
    got = run["step"].counts()
    assert got["tokens"] == STEPS * B * T and got["moe_all"] == STEPS * 3 * 4 * B * T
    assert 0 < got["moe_held"] < got["moe_all"] and 0 < got["moe_bias_moved"] < got["moe_all"]
    assert (got["attn_tiles_run"], got["attn_tiles_causal"], got["head_mtp"]) == (0, 0, 0)
    steps = 2 * STEPS
    assert _series(TOKENS_FAMILY) - run["counted"]["tokens"] == steps * B * T
    assert _series(MOE_ASSIGNMENTS_FAMILY, kind="all") - run["counted"]["all"] == steps * 12 * B * T
    assert f'{ATTN_KEY_TILES_FAMILY}{{kind="run"}}' in registry().snapshot()


def test_lm_step_runs_on_dp_only():
    plan = make_mesh(jax.devices()[:2], dp=1, tp=2, sp=1)
    with pytest.raises(NotImplementedError, match="dp only"):
        make_lm_train_state(CFG, plan)


# --------------------------------------------- the benchmark's configuration


@pytest.fixture(scope="module")
def deployed():
    with open(os.path.join(BENCH, "configs", "trinity_mini_clm_pk.json")) as f:
        config = json.load(f)
    m = config["model"]
    cfg = lm.AfmoeConfig.from_published(
        m, experts_held=(m["first_expert_held"], m["num_experts_held"]), dtype=m["compute_dtype"]
    )
    shapes = jax.eval_shape(cfg.init, jax.random.key(0))
    return config, cfg, shapes


def _count(tree) -> int:
    return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))


PARTS = {
    "a mixer with its gate": (lambda s: s["layers"][1]["swa"], 27_263_232),
    "the dense layer": (lambda s: s["layers"][0], 65_020_160),
    "a sparse layer at 16 held": (lambda s: s["layers"][1], 134_488_320),
    "layers 2 to 5": (lambda s: s["layers"][1:], 537_953_280),
    "embedding, head, final norm": (lambda s: [s["embed"], s["head"], s["final_norm"]], 102_500_352),
    "total": (lambda s: {k: v for k, v in s.items() if k != "buffers"}, 705_473_792),
}


@pytest.mark.parametrize("part", sorted(PARTS))
def test_the_held_models_parameter_table(deployed, part):
    """The cut as ``configs/trinity_mini_clm_pk.json`` states it, counted on
    ``jax.eval_shape(cfg.init, ...)``: nothing is allocated."""
    _, _, shapes = deployed
    pick, want = PARTS[part]
    assert _count(pick(shapes)) == want
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(shapes))


def test_the_cut_keeps_every_published_width_and_states_its_share(deployed):
    config, cfg, shapes = deployed
    published, model = config["published"], config["model"]
    cut = {"num_hidden_layers", "num_dense_layers", "layer_types", "vocab_size"}
    assert {k for k in published if published[k] != model[k]} == cut
    assert {k for k in published if published[k] != config[k]} == {"vocab_size"}  # the top level: the published keys
    assert model["layer_types"] == published["layer_types"][1:6]  # published layers 1 to 5
    assert (model["num_hidden_layers"], model["vocab_size"], model["num_experts_held"]) == (5, 200192 // 8, 16)
    assert cfg.layer_kinds() == ("swa", "swa", "attn", "swa", "swa") and cfg.experts_held == (0, 16)
    assert cfg.ffn_kinds() == ("dense", "moe", "moe", "moe", "moe")
    assert shapes["layers"][1]["moe"]["router"].shape == (2048, 128)  # the router keeps its 128 outputs
    assert [b["expert_bias"].shape for b in shapes["buffers"]["layers"][1:]] == [(128,)] * 4
    assert (cfg.route_scale, cfg.sliding_window, cfg.num_experts_per_tok, cfg.embed_scale) == (2.826, 2048, 8, 2048**0.5)
    for name in ("reduced_why", "assumed", "guarantees", "program_departures", "deployment", "optimizer"):
        assert config[name], name
    assert sorted(config["reduced_why"]) == sorted(
        ["num_layers_held", "num_experts_held", "vocab_size", "table_rows", "storage", "token_source"]
    )
    for starred in ("embedding_scale", "four_norms_a_layer", "attention_gate", "qk_norm", "window_counts_the_query",
                    "no_positions_on_full_layers"):
        assert config["assumed"][starred], starred


def test_the_adaptors_operation_count_is_the_hand_count(deployed):
    """``flops_per_row``: every product once forward and twice backward, no
    recomputation; the scores and values over the pairs each mask lets
    through, not over the tiles the kernels run."""
    import sys

    config, _, _ = deployed
    sys.path[:0] = [p for p in (BENCH,) if p not in sys.path]  # the adaptor imports ``chipbench``
    spec = importlib.util.spec_from_file_location("afmoe_clm", os.path.join(BENCH, "consumers", "afmoe_clm.py"))
    adaptor = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(adaptor)
    mixer_params, expert = 27_263_232 - 2 * 128, 3 * 2048 * 1024
    per_token = (
        5 * mixer_params + 3 * 2048 * 6144 + 4 * (2048 * 128 + expert + 8 * 16 / 128 * expert) + 2048 * 25024
    )
    seq, width = 8192, 32 * 128
    causal_pairs = seq * (seq + 1) // 2
    window_pairs = sum(min(i + 1, 2048) for i in range(seq))
    assert (causal_pairs, window_pairs) == (33_558_528, 14_681_088)
    scores = 4 * width * (4 * window_pairs + causal_pairs)  # a row's, forward: QK^T and PV at 2 operations each
    assert adaptor.flops_per_row(config) == pytest.approx(3 * (seq * 2 * per_token + scores), rel=1e-12)
