"""The hybrid causal LM (``models/qwen3_next.py``), its dropless expert layer
(``parallel/moe.py``: ``route_top_k``, ``held_experts``, ``shared_expert``)
and its train step (``models/train.py``) against the plain float32 reference in
``benchmarks/chip/reference/qwen3_next_f32.py`` (the one copy of it, loaded by path).

Small on purpose (hidden 64) with the published ratios kept: three DeltaNet
layers to one attention layer, 2 value heads a key head, 8 query heads a
key-value head, a quarter of the channels rotated, 16 experts top-4 of which 4
are held, one shared expert.  The program runs with ``dtype="float32"`` here so
that the comparison is of the algorithms (chunks against tokens, tiles against
a masked loop), not of bfloat16.
"""

from __future__ import annotations

import functools
import importlib.util
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lakesoul_tpu.models import attention, qwen3_next as lm
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
    "qwen3_next_f32", os.path.join(REPO, "benchmarks", "chip", "reference", "qwen3_next_f32.py")
)
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

MODEL = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=4, full_attention_interval=4,
    num_attention_heads=8, num_key_value_heads=1, head_dim=16, partial_rotary_factor=0.25,
    rope_theta=1e7, linear_num_key_heads=2, linear_num_value_heads=4, linear_key_head_dim=16,
    linear_value_head_dim=16, linear_conv_kernel_dim=4, num_experts=16, num_experts_per_tok=4,
    moe_intermediate_size=32, shared_expert_intermediate_size=32, rms_norm_eps=1e-6,
)
HELD = (4, 4)
CFG = lm.Qwen3NextConfig.from_published(MODEL, experts_held=HELD, dtype="float32")
B, T = 2, 150  # 150 is two chunks of 64 and a part of a third


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture(scope="module")
def params():
    # five times the family's 0.02, so that no path's signal is lost in the residual
    tree = lm.init_lm_params(CFG, jax.random.key(0))
    return jax.tree.map(lambda a: a * 5 if a.ndim >= 2 else a, tree)


def tokens(seed=0, rows=B, length=T):
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(0, MODEL["vocab_size"], (rows, length)), jnp.int32)
    labels = jnp.concatenate([ids[:, 1:], jnp.full((rows, 1), -100, jnp.int32)], axis=1)
    return ids, labels


def hidden(seed, length=T):
    return jax.random.normal(jax.random.key(seed), (B, length, MODEL["hidden_size"]))


def assert_close(got, want, tol=2e-4):
    """Every leaf within ``tol`` of the reference by relative norm."""
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want)):
        err = float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))
        assert err < tol, f"{jax.tree_util.keystr(path)}: {err}"


def test_layer_kinds_come_from_the_interval():
    assert CFG.layer_kinds() == ("gdn", "gdn", "gdn", "attn")
    six = lm.Qwen3NextConfig.from_published(dict(MODEL, num_hidden_layers=6, full_attention_interval=2))
    assert six.layer_kinds() == ("gdn", "attn") * 3
    assert ref.layer_kinds(MODEL) == list(CFG.layer_kinds())


# ------------------------------------------------------------------ mixers


@pytest.mark.parametrize("length, chunk", [(150, 64), (48, 64), (64, 16), (300, 128)],
                         ids=["part-of-a-chunk-over", "a-single-chunk", "whole-chunks", "chunks-in-the-kernel"])
def test_gated_delta_net_chunks_equal_the_token_recurrence(params, length, chunk):
    p = params["layers"][0]["gdn"]
    x = hidden(1, length)
    weigh = jax.random.normal(jax.random.key(2), x.shape)

    def program(p, x):
        return jnp.sum(weigh * lm.gated_delta_net(x, p, cfg=CFG, chunk=chunk))

    def plain(p, x):
        return jnp.sum(weigh * ref.gated_delta_net(x, p, MODEL))

    assert_close(lm.gated_delta_net(x, p, cfg=CFG, chunk=chunk), ref.gated_delta_net(x, p, MODEL))
    assert_close(jax.grad(program, argnums=(0, 1))(p, x), jax.grad(plain, argnums=(0, 1))(p, x))


LEADS = [(3,), (2, 5), (2, lm.INVERSE_BLOCK)]  # the last alone fills the kernel's blocks


def lower_systems(lead, size, seed=11):
    return jnp.tril(jax.random.normal(jax.random.key(seed), (*lead, size, size)) * 0.3, -1)


@pytest.mark.parametrize("lead, size", [((3,), 16), *((lead, 128) for lead in LEADS)],
                         ids=lambda v: "x".join(map(str, v)) if isinstance(v, tuple) else str(v))
def test_unit_lower_inverse_is_the_inverse_with_its_gradient(lead, size):
    a = lower_systems(lead, size)
    eye = jnp.eye(size)
    np.testing.assert_allclose(lm.unit_lower_inverse(a) @ (eye + a), jnp.broadcast_to(eye, a.shape), atol=2e-4)
    # the doublings in bfloat16, as on the chip: the Newton step brings them back
    # (at 0.3 the inverse's entries reach 80: far worse conditioned than normalised keys make it)
    assert_close(lm.unit_lower_inverse(a, jnp.bfloat16), jnp.linalg.inv(eye + a), tol=1e-3)
    weigh = jax.random.normal(jax.random.key(12), a.shape)
    got = jax.grad(lambda a: jnp.sum(weigh * lm.unit_lower_inverse(a)))(a)
    want = jax.grad(lambda a: jnp.sum(weigh * jnp.linalg.inv(eye + jnp.tril(a, -1))))(a)
    assert_close(got, want, tol=1e-3)
    with pytest.raises(ValueError, match="power of two"):
        lm.unit_lower_inverse(jnp.zeros((24, 24)))


@pytest.mark.parametrize("decayed", [False, True], ids=["plain", "decayed"])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16], ids=["float32", "bfloat16"])
@pytest.mark.parametrize("lead", LEADS, ids=lambda lead: "x".join(map(str, lead)))
def test_inverse_kernel_equals_its_jnp_twin(lead, dtype, decayed):
    """A chunk of 128 runs the Pallas kernel (in the interpreter here); the
    ``jnp`` doubling is the same arithmetic as whole-array operations.  With a
    log decay the kernel makes ``A`` itself, from the whole of ``a``."""
    a, decay = lower_systems(lead, 128, seed=13), None
    if decayed:  # a running sum of negative steps, as a chunk's is; a's upper part must not matter
        a = a + jnp.triu(jax.random.normal(jax.random.key(15), a.shape))
        decay = jnp.cumsum(-jnp.exp(jax.random.normal(jax.random.key(16), a.shape[:-1])), axis=-1)
    got = lm._unit_lower_inverse_pallas(a, dtype, decay, interpret=True)
    assert_close(got, lm._unit_lower_inverse_jnp(a, dtype, decay), tol=1e-6)
    system = jnp.eye(128) + lm._chunk_system(a, decay)
    assert_close(got, jnp.linalg.inv(system), tol=2e-4 if dtype == jnp.float32 else 1e-3)
    # the inverse's own backward rule on the kernel's result against autodiff through the twin
    # (of a, the strictly lower part is all that moves either system)
    weigh = jax.random.normal(jax.random.key(14), a.shape)
    wrt = (0, 1) if decayed else 0
    got = jax.grad(lambda a, g: jnp.sum(weigh * lm.unit_lower_inverse(a, dtype, g)), argnums=wrt)(a, decay)
    want = jax.grad(
        lambda a, g: jnp.sum(weigh * lm._unit_lower_inverse_jnp(jnp.tril(a, -1), dtype, g)), argnums=wrt
    )(a, decay)
    assert_close(got, want, tol=1e-3)


@pytest.mark.parametrize("size, kernel_calls", [(16, 0), (64, 0), (128, 1)])
def test_only_a_chunk_that_fills_a_lane_tile_takes_the_kernel(monkeypatch, size, kernel_calls):
    calls = []
    kernel = lm._unit_lower_inverse_pallas
    monkeypatch.setattr(lm, "_unit_lower_inverse_pallas", lambda *a, **k: calls.append(k) or kernel(*a, **k))
    lm.unit_lower_inverse(lower_systems((2,), size))
    assert calls == [{"interpret": True}] * kernel_calls  # no TPU here: the interpreter


def delta_rule_operands(hk, hv, length, d=128, seed=21):
    """q, k, v, log decay and beta of one row, as the mixer hands them to the rule."""
    ks = jax.random.split(jax.random.key(seed), 5)
    q, k = (jax.random.normal(key, (1, length, hk, d)) for key in ks[:2])
    v = jax.random.normal(ks[2], (1, length, hv, d))
    g = -jnp.exp(jax.random.normal(ks[3], (1, length, hv)) - 2)
    return q, k, v, g, jax.nn.sigmoid(jax.random.normal(ks[4], (1, length, hv)))


@pytest.mark.parametrize("length", [256, 200], ids=["whole-chunks", "part-of-a-chunk-over"])
@pytest.mark.parametrize("serves", [1, 2], ids=["a-value-head-a-key-head", "two-value-heads-a-key-head"])
def test_recurrence_kernels_equal_their_scan_twin(monkeypatch, serves, length):
    """At a chunk of 128 and heads of 128 the recurrence across chunks runs as
    two Pallas kernels (in the interpreter here); the ``lax.scan`` over the
    same chunk body is their twin.  Values and the gradients of all five
    inputs through the whole rule (a length that is not whole chunks is
    padded), and the six cotangents of the pair alone, the inverse's among
    them."""
    operands = delta_rule_operands(2, 2 * serves, length)
    weigh = jax.random.normal(jax.random.key(22), operands[2].shape)

    def rule(*operands):
        return jnp.sum(weigh * lm.chunk_gated_delta_rule(*operands, eps=1e-6))

    got = jax.value_and_grad(rule, argnums=(0, 1, 2, 3, 4))(*operands)
    calls = []
    kernels = lm._gated_delta
    with monkeypatch.context() as patch:
        patch.setattr(lm, "_gated_delta", lambda *a: calls.append(a) or lm._gated_delta_scan(*a))
        want = jax.value_and_grad(rule, argnums=(0, 1, 2, 3, 4))(*operands)
        rule(*operands)  # outside a gradient: the pair's operands as arrays
    assert len(calls) == 2
    assert_close(got, want, tol=1e-5)
    *made, eps = calls[1]
    pair, twin = (jax.vjp(lambda *made: path(*made, eps), *made) for path in (kernels, lm._gated_delta_scan))
    assert_close(pair[0], twin[0], tol=1e-6)
    weigh = jax.random.normal(jax.random.key(23), pair[0].shape)
    assert_close(pair[1](weigh), twin[1](weigh), tol=1e-5)


@pytest.mark.parametrize("chunk, head, kernel_calls", [(64, 128, 0), (128, 64, 0), (128, 128, 1)],
                         ids=["chunk-under-a-lane-tile", "head-under-a-lane-tile", "whole-lane-tiles"])
def test_only_whole_lane_tiles_take_the_recurrence_kernels(monkeypatch, chunk, head, kernel_calls):
    calls = []
    kernel = lm._gated_delta_forward
    monkeypatch.setattr(lm, "_gated_delta_forward", lambda *a, **k: calls.append(k) or kernel(*a, **k))
    operands = delta_rule_operands(1, 2, 128, d=head)
    got = lm.chunk_gated_delta_rule(*operands, eps=1e-6, chunk=chunk)
    assert calls == [{"eps": 1e-6, "interpret": True}] * kernel_calls  # no TPU here: the interpreter
    assert got.shape == operands[2].shape


@pytest.mark.parametrize("band, rows", [(1024, 128), (64, 16), (64, 64)],
                         ids=["one-block", "bands-of-row-blocks", "bands"])
def test_gated_attention_blocks_equal_the_masked_softmax(params, monkeypatch, band, rows):
    monkeypatch.setattr(attention, "ATTN_BAND", band)
    monkeypatch.setattr(attention, "ATTN_ROWS", rows)
    p = params["layers"][3]["attn"]
    x = hidden(3)
    weigh = jax.random.normal(jax.random.key(4), x.shape)

    def program(p, x):
        return jnp.sum(weigh * lm.gated_attention(x, p, cfg=CFG))

    def plain(p, x):
        return jnp.sum(weigh * ref.gated_attention(x, p, MODEL))

    assert_close(lm.gated_attention(x, p, cfg=CFG), ref.gated_attention(x, p, MODEL))
    assert_close(jax.grad(program, argnums=(0, 1))(p, x), jax.grad(plain, argnums=(0, 1))(p, x))


def test_attention_is_causal_and_rotates_a_quarter_of_the_channels(params):
    p = params["layers"][3]["attn"]
    x = hidden(5)
    later = x.at[:, 100:].set(0.0)
    np.testing.assert_allclose(
        lm.gated_attention(x, p, cfg=CFG)[:, :100], lm.gated_attention(later, p, cfg=CFG)[:, :100],
        atol=1e-5,
    )
    q = jax.random.normal(jax.random.key(6), (1, 8, 2, 16))
    turned = attention._rotary(q, jnp.arange(8), 4, 1e7)
    np.testing.assert_array_equal(turned[..., 4:], q[..., 4:])
    assert not np.allclose(turned[:, 1:, :, :4], q[:, 1:, :, :4])


# ------------------------------------------------------------ expert layer


def _route_to(router, experts):
    """A router that sends every token whose first channel is 10 to ``experts``
    (its top-k): their scores stand 50 above the rest."""
    return (router * 1e-3).at[0, jnp.asarray(experts)].add(5.0)


def _expert_layer(x, p, *, held, tile=None, top_k=4, n_experts=16):
    """The three pieces of ``parallel/moe.py`` as ``lm_layer`` puts them
    together (there with its norm and its rematerialisation around them)."""
    top_e, w = moe.route_top_k(x, p["router"], top_k=top_k)
    y, counts = moe.held_experts(x, top_e, w, p, n_experts=n_experts, held=held, tile=tile)
    return y + moe.shared_expert(x, p["shared"]), counts


ROUTINGS = ["even", "all-on-one-held", "none-held", "top-1", "one-of-one"]


def _routed(p, model, routing):
    """A layer's weights and its model under one of :data:`ROUTINGS` →
    (weights, model, held, top_k)."""
    p, held, top_k = dict(p), HELD, 4
    if routing == "all-on-one-held":  # expert 5 takes every token, its three companions are not held
        p["router"] = _route_to(p["router"], [5, 0, 1, 2])
    elif routing == "none-held":
        p["router"] = _route_to(p["router"], [0, 1, 2, 3])
    elif routing == "top-1":  # a token's one expert, its weight 1 after the division
        model, top_k = model | {"num_experts_per_tok": 1}, 1
    elif routing == "one-of-one":  # one expert, held: the layer is the dense SwiGLU
        model, held, top_k = model | {"num_experts": 1, "num_experts_per_tok": 1}, (0, 1), 1
        p["router"] = p["router"][:, :1]
        p.update({k: p[k][:1] for k in ("w_gate", "w_up", "w_down")})
    return p, model, held, top_k


@pytest.mark.parametrize("routing", ROUTINGS)
def test_expert_layer_equals_the_loop_over_experts(params, routing):
    p, model, held, top_k = _routed(params["layers"][0]["moe"], MODEL, routing)
    x = hidden(7).at[..., 0].set(10.0)
    weigh = jax.random.normal(jax.random.key(8), x.shape)
    layer = functools.partial(_expert_layer, held=held, tile=16, top_k=top_k, n_experts=model["num_experts"])

    def program(p, x):
        return jnp.sum(weigh * layer(x, p)[0])

    def plain(p, x):
        return jnp.sum(weigh * ref.moe(x, p, model, held))

    y, counts = layer(x, p)
    assert_close(y, ref.moe(x, p, model, held))
    got, want = jax.grad(program, argnums=(0, 1))(p, x), jax.grad(plain, argnums=(0, 1))(p, x)
    if top_k == 1:  # a lone weight is p / p: the router's gradient is zero, not a number to compare
        for grads in (got, want):
            assert float(jnp.max(jnp.abs(grads[0].pop("router")))) < 1e-5
    assert_close(got, want)
    n = B * T
    assert int(counts["moe_all"]) == top_k * n
    if routing in ("all-on-one-held", "one-of-one"):
        assert (int(counts["moe_held"]), int(counts["moe_load_max"])) == (n, n)
    elif routing == "none-held":
        assert (int(counts["moe_held"]), int(counts["moe_load_max"])) == (0, 0)
        assert_close(y, ref.shared_expert(x, p["shared"]))
    else:
        assert 0 < int(counts["moe_load_max"]) < int(counts["moe_held"]) < top_k * n
    if routing == "one-of-one":
        dense = ref.swiglu(x, p["w_gate"][0], p["w_up"][0], p["w_down"][0])
        assert_close(y, dense + ref.shared_expert(x, p["shared"]))


def test_the_four_shares_add_up_to_the_uncut_layer(params):
    """16 experts over four chips of 4: the routed parts of the four shares and
    the shared expert, counted once, are the whole layer of the reference."""
    whole = dict(params["layers"][1]["moe"])
    keys = jax.random.split(jax.random.key(9), 3)
    for name, key in zip(("w_gate", "w_up", "w_down"), keys):  # all 16 experts' weights
        whole[name] = jax.random.normal(key, (16,) + whole[name].shape[1:]) * 0.1
    x = hidden(10)
    want = ref.moe(x, whole, MODEL, (0, 16))
    top_e, w = moe.route_top_k(x, whole["router"], top_k=4)
    routed = 0.0
    for first in range(0, 16, 4):
        share = {k: whole[k][first:first + 4] for k in ("w_gate", "w_up", "w_down")}
        y, _ = moe.held_experts(x, top_e, w, share, n_experts=16, held=(first, 4), tile=32)
        routed = routed + y
    assert_close(routed + ref.shared_expert(x, whole["shared"]), want)
    # and the program's own shared expert is the reference's
    assert_close(moe.shared_expert(x, whole["shared"]), ref.shared_expert(x, whole["shared"]))


def test_expert_layer_refuses_a_share_that_does_not_fit(params):
    p = params["layers"][0]["moe"]
    with pytest.raises(ValueError, match="held"):
        _expert_layer(hidden(0), p, held=(14, 4))
    with pytest.raises(ValueError, match="held"):
        _expert_layer(hidden(0), p, held=(0, 8))


# ------------------------------------------------- a tile's rows moved by DMA

WIDE = MODEL | {"hidden_size": 256}  # a float32 row of the sums is two lane tiles: rows move by DMA
WIDE_CFG = lm.Qwen3NextConfig.from_published(WIDE, experts_held=HELD, dtype="float32")


@pytest.fixture(scope="module")
def wide_params():
    tree = lm.init_lm_params(WIDE_CFG, jax.random.key(0))
    return jax.tree.map(lambda a: a * 5 if a.ndim >= 2 else a, tree)


def _rows_and_slots(width, n, n_rows=40, tile=16):
    """An accumulator as the tile loops carry it, a tile's slots and fresh rows
    for them.  Slots 11 to 15 hold the indices of slots 0 to 4 and, for a
    write, stale rows: written, they would land over the fresh rows of the
    prefix.  A whole tile repeats no index, as a tile of one expert's
    assignments does not."""
    table = jax.random.normal(jax.random.key(11), (n_rows, 1, width))
    fresh = jax.random.normal(jax.random.key(12), (tile, 1, width))
    slots = np.random.default_rng(13).permutation(n_rows)[:tile].astype(np.int32)
    if n < tile:
        slots[-5:] = slots[:5]
    return table, fresh, jnp.asarray(slots)


ROW_COUNTS = {"none": 0, "a-prefix": 7, "up-to-the-repeats": 11, "a-whole-tile": 16}


@pytest.mark.parametrize("n", ROW_COUNTS.values(), ids=ROW_COUNTS.keys())
@pytest.mark.parametrize("width", [128, 384])
def test_take_rows_equals_indexing(width, n):
    table, _, slots = _rows_and_slots(width, n)
    got = moe.take_rows(table, slots, jnp.int32(n), interpret=True)
    assert got.shape == (16, 1, width)
    np.testing.assert_array_equal(got[:n], table[slots[:n]])


@pytest.mark.parametrize("n", ROW_COUNTS.values(), ids=ROW_COUNTS.keys())
@pytest.mark.parametrize("width", [128, 384])
def test_put_rows_writes_the_prefix_and_nothing_else(width, n):
    table, fresh, slots = _rows_and_slots(width, n)
    got = moe.put_rows(table, slots, jnp.int32(n), fresh, interpret=True)
    np.testing.assert_array_equal(got, table.at[slots[:n]].set(fresh[:n]))


@pytest.mark.parametrize("n", ROW_COUNTS.values(), ids=ROW_COUNTS.keys())
def test_add_rows_by_dma_equals_the_scatter_add(n):
    """The read, the sum and the write of a tile against ``.at[].add`` of its
    valid rows; what the slots past them hold in ``rows`` must not matter."""
    table, fresh, slots = _rows_and_slots(256, n)
    valid = jnp.arange(16) < n
    got = jax.jit(moe._add_rows)(table, slots, valid, fresh[:, 0])
    np.testing.assert_array_equal(got, table.at[slots[:n]].add(fresh[:n]))
    twin = moe._add_rows(table[:, 0], slots, valid, fresh[:, 0].at[n:].set(0.0))
    np.testing.assert_array_equal(got[:, 0], twin)


@pytest.mark.parametrize("hidden, dtype, by_dma", [
    (64, jnp.float32, False), (96, jnp.bfloat16, False), (128, jnp.float32, True), (256, jnp.bfloat16, True),
], ids=["64-float32", "96-bfloat16", "128-float32", "256-bfloat16"])
def test_only_sums_over_rows_of_whole_lane_tiles_move_by_dma(monkeypatch, hidden, dtype, by_dma):
    calls = {"take_rows": [], "put_rows": []}
    for name in calls:
        kernel = getattr(moe, name)
        monkeypatch.setattr(
            moe, name, lambda *a, _seen=calls[name], _kernel=kernel, **k: _seen.append(k) or _kernel(*a, **k)
        )
    x = jax.random.normal(jax.random.key(14), (48, hidden)).astype(dtype)
    top_e = jnp.tile(jnp.arange(4, dtype=jnp.int32), (48, 1))
    w = jnp.full((48, 4), 0.25)
    p = {name: jax.random.normal(jax.random.key(15), shape) * 0.1 for name, shape in
         (("w_gate", (2, hidden, 8)), ("w_up", (2, hidden, 8)), ("w_down", (2, 8, hidden)))}
    jax.grad(lambda x: jnp.sum(moe.held_experts(x, top_e, w, p, n_experts=4, held=(1, 2), tile=16)[0]
                               .astype(jnp.float32)))(x)
    # traced once in the forward loop and once after it (the last tile's rows), once in the
    # backward loop.  No TPU here: the interpreter
    assert calls["take_rows"] == calls["put_rows"] == [{"interpret": True}] * (3 if by_dma else 0)


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("routing", ROUTINGS)
def test_expert_layer_by_dma_equals_the_indexing_bit_for_bit(wide_params, monkeypatch, routing, dtype):
    p, model, held, top_k = _routed(wide_params["layers"][0]["moe"], WIDE, routing)
    x = jax.random.normal(jax.random.key(7), (B, T, 256)).at[..., 0].set(10.0).astype(dtype)
    weigh = jax.random.normal(jax.random.key(8), x.shape)
    layer = functools.partial(_expert_layer, held=held, tile=16, top_k=top_k, n_experts=model["num_experts"])

    def program(p, x):
        y, counts = layer(x, p)
        return jnp.sum(weigh * y), (y, counts)

    run = jax.jit(jax.value_and_grad(program, argnums=(0, 1), has_aux=True))
    (_, (y, counts)), grads = run(p, x)
    assert moe._rows_by_dma(x.reshape(-1, 256))
    monkeypatch.setattr(moe, "_rows_by_dma", lambda x: False)
    (_, (y_twin, counts_twin)), grads_twin = jax.jit(jax.value_and_grad(program, argnums=(0, 1), has_aux=True))(p, x)
    for (path, got), want in zip(jax.tree_util.tree_leaves_with_path((y, grads, counts)),
                                 jax.tree.leaves((y_twin, grads_twin, counts_twin))):
        np.testing.assert_array_equal(got, want, err_msg=jax.tree_util.keystr(path))
    if dtype == "float32":
        assert_close(y, ref.moe(x, p, model, held))
        want = jax.grad(lambda p, x: jnp.sum(weigh * ref.moe(x, p, model, held)), argnums=(0, 1))(p, x)
        if top_k == 1:
            for tree in (grads, want):  # zero but for rounding, which grows with the width
                assert float(jnp.max(jnp.abs(tree[0].pop("router")))) < 1e-4
        assert_close(grads, want)


# ------------------------------------------------------------- whole model


def test_loss_and_every_gradient_leaf_equal_the_reference(params):
    ids, labels = tokens()
    (loss, counts), grads = jax.jit(jax.value_and_grad(
        lambda p: lm.lm_loss(p, ids, labels, cfg=CFG), has_aux=True
    ))(params)
    want, want_grads = jax.jit(jax.value_and_grad(
        lambda p: ref.lm_loss(p, ids, labels, cfg=MODEL, held=HELD)
    ))(params)
    np.testing.assert_allclose(float(loss), float(want), rtol=2e-6)
    assert_close(grads, want_grads)
    assert all(float(jnp.linalg.norm(g)) > 0 for g in jax.tree.leaves(grads))
    assert int(counts["tokens"]) == B * T and int(counts["moe_all"]) == 4 * 4 * B * T
    logits = lm.lm_logits(params, ids, cfg=CFG)
    assert_close(logits, ref.lm_logits(params, ids, cfg=MODEL, held=HELD))


def test_bfloat16_program_stays_near_the_reference(params):
    """The dtype the chip runs: products in bfloat16, float32 accumulation."""
    ids, labels = tokens(1)
    cfg = lm.Qwen3NextConfig.from_published(MODEL, experts_held=HELD)
    loss, _ = lm.lm_loss(params, ids, labels, cfg=cfg)
    want = ref.lm_loss(params, ids, labels, cfg=MODEL, held=HELD)
    assert abs(float(loss) - float(want)) < 0.02


def _series(family, **labels) -> float:
    inner = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
    return registry().snapshot().get(family + ("{" + inner + "}" if inner else ""), 0)


@pytest.fixture(scope="module")
def stepped():
    """One optimizer step on one device and the same on a dp=2 mesh, from one
    seed, with the counters read before and after the first."""
    ids, labels = tokens(2)
    out = {}
    for dp in (1, 2):
        plan = make_mesh(jax.devices()[:dp], dp=dp, tp=1, sp=1)
        with jax.default_matmul_precision("highest"):
            state, opt_state, tx, shardings = make_lm_train_state(CFG, plan, lr=1e-2, seed=3)
            before = jax.device_get(state)
            step = make_lm_train_step(CFG, plan, tx, shardings)
            counted = {
                "tokens": _series(TOKENS_FAMILY),
                "held": _series(MOE_ASSIGNMENTS_FAMILY, kind="held"),
                "all": _series(MOE_ASSIGNMENTS_FAMILY, kind="all"),
                "tile_rows": _series(MOE_ASSIGNMENTS_FAMILY, kind="tile_rows"),
                "max": _series(MOE_LOAD_FAMILY, stat="max"),
                "mean": _series(MOE_LOAD_FAMILY, stat="mean"),
            }
            after, _, loss = step(state, opt_state, ids, labels)
            out[dp] = dict(before=before, after=jax.device_get(after), loss=float(loss),
                           counted=counted, step=step)
    return ids, labels, out


def assert_moves_agree(run, want, lr=1e-2):
    """A first AdamW step moves a weight by ``lr * g / (|g| + 1e-8)``: by
    ``lr`` whatever the gradient's size, unless the gradient is of the size of
    AdamW's epsilon, where its rounding shows in the move (a DeltaNet head that
    forgets fast has such an ``A_log``).  So: where the reference moved by
    nearly ``lr`` the program moved the same way, which is where a wrong sign
    or a missed leaf shows; elsewhere it moved by no more than ``lr``."""
    for (path, after), before, target in zip(
        jax.tree_util.tree_leaves_with_path(run["after"]), jax.tree.leaves(run["before"]),
        jax.tree.leaves(want),
    ):
        name = jax.tree_util.keystr(path)
        moved, wanted = (after - before) / lr, (target - before) / lr
        decisive = np.abs(wanted) > 0.9
        assert decisive.any(), name
        np.testing.assert_allclose(moved[decisive], wanted[decisive], atol=2e-2, err_msg=name)
        assert float(np.max(np.abs(moved))) < 1.02, name


def test_one_step_is_the_references_adamw_step(stepped):
    ids, labels, out = stepped
    run = out[1]
    loss, grads = jax.value_and_grad(
        lambda p: ref.lm_loss(p, ids, labels, cfg=MODEL, held=HELD)
    )(run["before"])
    zeros = jax.tree.map(jnp.zeros_like, run["before"])
    want, _, _ = ref.adamw_step(run["before"], grads, zeros, zeros, 0, lr=1e-2)
    np.testing.assert_allclose(run["loss"], float(loss), rtol=2e-6)
    assert_moves_agree(run, want)


def test_step_on_a_dp2_mesh_equals_one_device(stepped):
    _, _, out = stepped
    np.testing.assert_allclose(out[2]["loss"], out[1]["loss"], rtol=1e-5)
    assert_moves_agree(out[2], out[1]["after"])


def test_counters_for_a_known_routing(stepped):
    ids, labels, out = stepped
    run = out[1]
    # what the step must have counted, from the reference's routing of the same weights
    held = load_max = tile_rows = dw_writes = 0
    x = jnp.asarray(run["before"]["embed"])[ids]
    for lp, kind in zip(run["before"]["layers"], CFG.layer_kinds()):
        y = ref.rms_norm(x, lp["norm1"], 1e-6)
        x = x + (ref.gated_delta_net(y, lp["gdn"], MODEL) if kind == "gdn"
                 else ref.gated_attention(y, lp["attn"], MODEL))
        y = ref.rms_norm(x, lp["norm2"], 1e-6)
        top_e, _ = ref.route(y.reshape(-1, y.shape[-1]), lp["moe"]["router"], 4)
        loads = np.bincount(np.asarray(top_e).ravel(), minlength=16)[HELD[0]:HELD[0] + HELD[1]]
        held += int(loads.sum())
        load_max += int(loads.max())
        # an expert's rows run in whole tiles: the slots moved and multiplied
        tile_rows += sum(-(-int(load) // moe.EXPERT_TILE) * moe.EXPERT_TILE for load in loads)
        # experts this narrow keep the tile loop (no slot in the grouped kernels), its weight-gradient sums written once a tile
        dw_writes += sum(-(-int(load) // moe.EXPERT_TILE) for load in loads)
        x = x + ref.moe(y, lp["moe"], MODEL, HELD)
    got = run["step"].counts()
    assert got == {"tokens": B * T, "moe_all": 4 * 4 * B * T, "moe_held": held, "moe_load_max": load_max,
                   "moe_tile_rows": tile_rows, "moe_dw_writes": dw_writes, "moe_grouped": 0, "moe_bias_moved": 0,
                   "head_all": B * (T - 1), "head_mtp": 0,  # one loss, no prediction module
                   "attn_tiles_run": 0, "attn_tiles_causal": 0,  # 150 tokens: the kernels list no tile
                   "attn_pair_tiles_run": 0, "attn_pair_tiles": 0,  # no head pairs
                   "attn_operands_kernel": 0, "attn_operands_xla": B,
                   "attn_out_tokens": 0, "attn_out_heads": B,  # the twin writes heads first
                   "loss_rows_fused": 0, "loss_rows_compiler": B * T,  # every row to the tile loop; tiles this small stay the compiler's
                   "head_loop": 0, "loop_layers_run": 0, "loop_layers": 0,  # one attention layer, its operands the jnp lines'; no pass loop
                   "ssm_rows_kernel": 0, "ssm_rows_twin": 0, "shared_reads": 0}  # no selective scan, no state one layer reads of another
    assert held < tile_rows
    before = run["counted"]
    # the dp=2 step of the fixture ran after this read and counted the same batch again
    again = 2
    assert _series(TOKENS_FAMILY) - before["tokens"] == again * B * T
    assert _series(MOE_ASSIGNMENTS_FAMILY, kind="all") - before["all"] == again * 16 * B * T
    assert _series(MOE_ASSIGNMENTS_FAMILY, kind="held") - before["held"] == again * held
    # each of the two shards rounds its own rows of an expert up to whole tiles
    assert _series(MOE_ASSIGNMENTS_FAMILY, kind="tile_rows") - before["tile_rows"] >= again * tile_rows
    assert _series(MOE_LOAD_FAMILY, stat="mean") - before["mean"] == pytest.approx(again * held / HELD[1])
    assert _series(MOE_LOAD_FAMILY, stat="max") - before["max"] >= load_max
    assert out[2]["step"].counts()["moe_held"] == held


def test_lm_step_runs_on_dp_only():
    plan = make_mesh(jax.devices()[:2], dp=1, tp=2, sp=1)
    with pytest.raises(NotImplementedError, match="dp only"):
        make_lm_train_state(CFG, plan)
