"""The ``phi4flash`` family (``models/phi4flash.py``: Phi-4-mini-flash-
reasoning, a decoder-hybrid-decoder model) on the shared causal-LM stack
(``models/causal_lm.py``): a stack whose layers publish a state for later
layers and read an earlier one's (``lm_layer``, ``cfg.shares``), the Mamba-1
mixer over ``models/selective_scan.py`` (kernel pair and twin), gated memory
units, differential attention (window, full, cross) over
``models/attention.py: paired_attention``, LayerNorm with bias and the train
step (``models/train.py``), against the plain float32 reference in
``benchmarks/chip/reference/phi4flash_f32.py`` (the one copy of it, loaded by
path).

Small on purpose (hidden 32) with the published shape kept: ``n`` = 8 layers,
so that the public rule gives every kind of layer and two more (Mamba, window,
Mamba, window, the two sources, a gated memory unit, a cross layer), twice as
many query heads as key-value heads, a tied head, a vocabulary slice.  At 8
states of 64 channels the scan runs its ``lax.scan`` twin and at a head of 4
the attention its blockwise twin; ``test_the_scan_*`` reach the scan kernels
in the interpreter at 128 channels.  The program runs with ``dtype="float32"``
here so that the comparison is of the algorithms, not of bfloat16.
"""

from __future__ import annotations

import contextlib
import importlib.util
import math
import os
import re
from unittest import mock

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from lakesoul_tpu.models import attention, causal_lm, selective_scan
from lakesoul_tpu.models import phi4flash as lm
from lakesoul_tpu.models.train import (
    SHARED_READS_FAMILY,
    SSM_SCAN_ROWS_FAMILY,
    TOKENS_FAMILY,
    make_lm_train_state,
    make_lm_train_step,
)
from lakesoul_tpu.obs import registry
from lakesoul_tpu.parallel.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks", "chip")
_spec = importlib.util.spec_from_file_location("phi4flash_f32", os.path.join(BENCH, "reference", "phi4flash_f32.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

MODEL = dict(
    vocab_size=64, hidden_size=32, num_hidden_layers=8, intermediate_size=48, num_attention_heads=8,
    num_key_value_heads=4, sliding_window=5, layer_norm_eps=1e-5, mb_per_layer=2, hidden_act="silu",
    tie_word_embeddings=True, mlp_bias=False, lm_head_bias=False, embd_pdrop=0, resid_pdrop=0,
    mamba_d_state=8, mamba_d_conv=4, mamba_expand=2, mamba_dt_rank=2, layers_held=list(range(8)),
)
CFG = lm.Phi4FlashConfig.from_published(MODEL, dtype="float32")
KINDS = ("ssm", "swa", "ssm", "swa", "ssm", "attn", "gmu", "xattn")
SOURCE, KV_SOURCE, GMU, CROSS = 4, 5, 6, 7  # the two sources and the two layers that read them
B, T = 2, 19


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _scaled(tree, key=7):
    """Matrices five times the family's 0.02, so that no path's signal is lost
    in the residual; vectors moved off their starting values, so that each
    one's gradient is its own (``lambda_init`` stays: nothing trains it)."""
    keys = iter(jax.random.split(jax.random.key(key), len(jax.tree.leaves(tree))))
    moved = jax.tree.map(
        lambda a: a * 5 if a.ndim >= 2 and a.shape[-1] != MODEL["mamba_d_state"] else
        a + 0.1 * jax.random.normal(next(keys), a.shape), {k: v for k, v in tree.items() if k != "buffers"},
    )
    return {**moved, "buffers": tree["buffers"]}


def tokens(seed=0, rows=B, length=T):
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(0, MODEL["vocab_size"], (rows, length)), jnp.int32)
    labels = jnp.concatenate([ids[:, 1:], jnp.full((rows, 1), -100, jnp.int32)], axis=1)
    return ids, labels


def _trained(params):
    return {k: v for k, v in params.items() if k != "buffers"}


def _loss_and_grads(loss_fn, params):
    buffers = params["buffers"]
    return jax.jit(jax.value_and_grad(lambda p: loss_fn({**p, "buffers": buffers})))(_trained(params))


@pytest.fixture(scope="module")
def both():
    """(params, the program's (loss, gradients, logits), the reference's) on
    one batch, computed once for the cases below."""
    with jax.default_matmul_precision("highest"):
        params = _scaled(CFG.init(jax.random.key(0)))
        ids, labels = tokens()
        got = _loss_and_grads(lambda p: CFG.loss(p, ids, labels)[0], params)
        want = _loss_and_grads(lambda p: ref.lm_loss(p, ids, labels, cfg=MODEL), params)
        logits = jax.jit(lambda p: (causal_lm.lm_logits(p, ids, cfg=CFG), ref.lm_logits(p, ids, cfg=MODEL)))(params)
    return params, got, want, logits


# ---------------------------------------------------- the program and the reference


def test_the_layer_kinds_follow_the_public_rule():
    assert CFG.layer_kinds() == KINDS
    whole = lm.Phi4FlashConfig()
    kinds = whole.layer_kinds()
    assert [kinds.count(k) for k in ("ssm", "swa", "attn", "gmu", "xattn")] == [9, 8, 1, 7, 7]
    assert kinds[16] == "ssm" and kinds[17] == "attn" and kinds[18:20] == ("gmu", "xattn") and kinds[14:16] == ("ssm", "swa")
    held = lm.Phi4FlashConfig(layers_held=(14, 15, 16, 17, 18, 19))
    assert held.layer_kinds() == ("ssm", "swa", "ssm", "attn", "gmu", "xattn")
    four = lm.Phi4FlashConfig(num_hidden_layers=4)  # the floor's four layers hold no layer of the second decoder
    assert four.layer_kinds() == ("ssm", "swa", "ssm", "attn")


def test_the_loss_and_the_logits_are_the_references(both):
    _, (loss, _), (ref_loss, _), (logits, ref_logits) = both
    assert abs(float(loss) - float(ref_loss)) < 2e-6
    assert float(jnp.max(jnp.abs(logits - ref_logits))) < 2e-5
    assert logits.shape == (B, T, MODEL["vocab_size"])


def _leaf_paths():
    shapes = jax.eval_shape(CFG.init, jax.random.key(0))
    return [jax.tree_util.keystr(path) for path, _ in jax.tree_util.tree_leaves_with_path(_trained(shapes))]


@pytest.mark.parametrize("path", _leaf_paths())
def test_every_leafs_gradient_is_the_references(both, path):
    """Each trained leaf on its own: relative norm of the difference under
    2e-4.  A key bias's gradient is zero in the mathematics (the same vector
    added to every key moves no softmax): held to the layer's ``W_k``'s scale."""
    _, (_, grads), (_, ref_grads), _ = both
    got = dict((jax.tree_util.keystr(p), g) for p, g in jax.tree_util.tree_leaves_with_path(grads))[path]
    want = dict((jax.tree_util.keystr(p), g) for p, g in jax.tree_util.tree_leaves_with_path(ref_grads))[path]
    if path.endswith("['b_k']"):
        scale = dict((jax.tree_util.keystr(p), g) for p, g in jax.tree_util.tree_leaves_with_path(ref_grads))
        assert float(jnp.linalg.norm(got)) < 1e-5 * float(jnp.linalg.norm(scale[path.replace("b_k", "w_k")]))
        return
    assert float(jnp.linalg.norm(want)) > 0
    assert float(jnp.linalg.norm(got - want)) <= 2e-4 * float(jnp.linalg.norm(want)), path


def test_the_vocabulary_slice():
    """The embedding, the logits and the loss are over ``vocab_size`` rows and
    no more: the slice is a smaller vocabulary."""
    params = CFG.init(jax.random.key(1))
    assert params["embed"].shape == (MODEL["vocab_size"], MODEL["hidden_size"]) and "head" not in params
    ids, labels = tokens(3)
    loss, counts = CFG.loss(params, ids, labels)
    assert abs(float(loss) - math.log(MODEL["vocab_size"])) < 0.5  # near uniform over the slice
    assert int(counts["head_all"]) == B * (T - 1) and int(counts["tokens"]) == B * T


def _count(shapes) -> int:
    return sum(math.prod(leaf.shape) for leaf in jax.tree.leaves(_trained(shapes)))


def test_the_parameter_counts():
    """The cell's cut holds 697,094,272 trained parameters and the whole model
    by the same rule 3,852,562,944, the published "3.8B"."""
    cut = lm.Phi4FlashConfig(vocab_size=25008, layers_held=(14, 15, 16, 17, 18, 19))
    assert _count(jax.eval_shape(cut.init, jax.random.key(0))) == 697_094_272
    assert _count(jax.eval_shape(lm.Phi4FlashConfig().init, jax.random.key(0))) == 3_852_562_944
    by_kind = {kind: _count({"x": layer[kind]}) for kind, layer in zip(
        cut.layer_kinds(), jax.eval_shape(cut.init, jax.random.key(0))["layers"])}
    assert by_kind == {"ssm": 41_241_600, "swa": 19_668_864, "attn": 19_668_864, "gmu": 26_214_400, "xattn": 13_112_704}


def test_values_the_layers_do_not_compute_are_refused():
    with pytest.raises(ValueError, match="mb_per_layer"):
        lm.Phi4FlashConfig.from_published(MODEL | {"mb_per_layer": 4})
    with pytest.raises(ValueError, match="tie_word_embeddings"):
        lm.Phi4FlashConfig.from_published(MODEL | {"tie_word_embeddings": False})
    with pytest.raises(ValueError, match="reads a ssm layer's state"):
        lm.Phi4FlashConfig(layers_held=(17, 18, 19))  # a gated memory unit without its source
    with pytest.raises(ValueError, match="reads a attn layer's state"):
        lm.Phi4FlashConfig(layers_held=(16, 18, 19))
    with pytest.raises(ValueError, match="pairs"):
        lm.Phi4FlashConfig(num_hidden_layers=6)


# ------------------------------------------- an assumption a wrong reading fails


def _reference_loss(params, **patched):
    ids, labels = tokens()
    with mock.patch.multiple(ref, **patched) if patched else contextlib.nullcontext():
        return float(ref.lm_loss(params, ids, labels, cfg=MODEL))


def test_a_wrong_source_layer_fails(both):
    """The memory is layer ``n/2``'s and the keys and values layer
    ``n/2 + 1``'s: a reference that takes an earlier layer's is another
    model."""
    params, (loss, _), _, _ = both
    right = ref.layer_kind
    memory_early = lambda i, n: {2: "memory_source", 4: "mamba"}.get(i) or right(i, n)  # noqa: E731
    assert abs(_reference_loss(params, layer_kind=memory_early) - float(loss)) > 1e-4
    assert abs(_reference_loss(params) - float(loss)) < 2e-6


def test_the_memory_is_taken_before_the_gate(both):
    params, (loss, _), _, _ = both
    right = ref.mamba

    def gated(x, p, cfg):
        out, y = right(x, p, cfg)
        _, z = jnp.split(x @ p["w_in"], 2, axis=-1)
        return out, y * ref.silu(z)

    assert abs(_reference_loss(params, mamba=gated) - float(loss)) > 1e-4


def test_lambda_init_is_by_the_published_index():
    """Held layers 14 to 19: the attention layers' buffers hold
    ``0.8 - 0.6 exp(-0.3 i)`` for i = 15, 17, 19, not for their place in the
    held list, and nothing trains them."""
    cut = lm.Phi4FlashConfig(
        vocab_size=64, hidden_size=32, intermediate_size=48, num_attention_heads=8, num_key_value_heads=4,
        mamba_dt_rank=2, layers_held=(14, 15, 16, 17, 18, 19), dtype="float32",
    )
    buffers = cut.init(jax.random.key(0))["buffers"]["layers"]
    for place, index in ((1, 15), (3, 17), (5, 19)):
        value = float(buffers[place]["mixer"]["lambda_init"])
        assert abs(value - (0.8 - 0.6 * math.exp(-0.3 * index))) < 1e-6
        assert abs(value - (0.8 - 0.6 * math.exp(-0.3 * place))) > 1e-3
    assert all(b == {} for b in (buffers[0], buffers[2], buffers[4]))
    assert abs(lm.lambda_init(0) - 0.2) < 1e-12


def test_a_wrong_lambda_init_fails(both):
    """The reference computes ``lambda_init`` from the index itself: one that
    counts layers from 1 gives another loss."""
    params, (loss, _), _, _ = both
    right = ref.differential_attention
    shifted = lambda x, p, kv, index, cfg, window=None: right(x, p, kv, index + 1, cfg, window)  # noqa: E731
    assert abs(_reference_loss(params, differential_attention=shifted) - float(loss)) > 1e-5


def _pair_by_hand(q, k, v, window, halves: bool):
    """``paired_attention`` as whole softmaxes in numpy; ``halves`` pairs head
    ``p`` with head ``p + heads / 2`` instead of its neighbour."""
    b, t, heads, d = q.shape
    kv = k.shape[2]
    back = np.arange(t)[:, None] - np.arange(t)[None, :]
    seen = (back >= 0) if window is None else (back >= 0) & (back < window)
    outs = []
    for which in (0, 1):
        per_pair = []
        for p in range(heads // 2):
            g = p // ((heads // 2) // (kv // 2))
            qh = (p + which * heads // 2) if halves else 2 * p + which
            kh = (g + which * kv // 2) if halves else 2 * g + which
            vs = (g, g + kv // 2) if halves else (2 * g, 2 * g + 1)
            s = np.einsum("bqd,bkd->bqk", q[:, :, qh], k[:, :, kh])
            s = np.where(seen, s, -1e30)
            a = np.exp(s - s.max(-1, keepdims=True))
            a = a / a.sum(-1, keepdims=True)
            per_pair.append(np.einsum("bqk,bke->bqe", a, np.concatenate([v[:, :, vs[0]], v[:, :, vs[1]]], axis=-1)))
        outs.append(np.stack(per_pair, axis=2))
    return outs


@pytest.mark.parametrize("window", [None, 5])
def test_adjacent_heads_pair(window):
    """Query pair ``p`` is heads ``2p, 2p+1`` on keys ``2g, 2g+1`` and the
    value ``[v_2g ; v_2g+1]``: the two maps against whole softmaxes by hand,
    and not the pairing by halves."""
    keys = jax.random.split(jax.random.key(2), 3)
    q = np.asarray(jax.random.normal(keys[0], (2, 12, 8, 4)))
    k = np.asarray(jax.random.normal(keys[1], (2, 12, 4, 4)))
    v = np.asarray(jax.random.normal(keys[2], (2, 12, 4, 4)))
    o1, o2 = attention.paired_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), window)
    assert o1.shape == o2.shape == (2, 12, 4, 8)
    for got, want, wrong in zip((o1, o2), _pair_by_hand(q, k, v, window, False), _pair_by_hand(q, k, v, window, True)):
        assert np.abs(np.asarray(got) - want).max() < 1e-5
        assert np.abs(np.asarray(got) - wrong).max() > 1e-2


@pytest.mark.parametrize("window", [None, 200], ids=["causal", "window-no-multiple-of-a-tile"])
def test_adjacent_heads_pair_through_the_flash_kernels(monkeypatch, window):
    """The same pairing at a shape the attention kernels take (256 tokens as
    2 x 2 tiles of 128, heads of 64; the interpreter here): ONE call of two
    key-value heads a key-value pair, two query heads each, beside a value of
    128, held to the whole softmaxes by hand and not the pairing by halves;
    and the gradients of a weighted sum of both outputs to the blockwise
    path's, which the cases above hold to the same hand."""
    monkeypatch.setattr(attention, "FLASH_KEYS", 128)
    monkeypatch.setattr(attention, "FLASH_ROWS", 256)
    b, t, d = 2, 256, 64
    keys = jax.random.split(jax.random.key(5), 5)
    q = np.asarray(jax.random.normal(keys[0], (b, t, 8, d))) * d**-0.5
    k, v = (np.asarray(jax.random.normal(key, (b, t, 4, d))) for key in keys[1:3])
    calls = []
    kernel = attention._flash_forward
    monkeypatch.setattr(attention, "_flash_forward", lambda *a, **kw: calls.append([x.shape for x in a]) or kernel(*a, **kw))
    o1, o2 = attention.paired_attention(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), window)
    assert calls == [[(b * 4, 2, t, d), (b * 4, t, d), (b * 4, t, 2 * d)]]  # two pairs x two maps a row
    assert o1.shape == o2.shape == (b, t, 4, 2 * d)
    for got, want, wrong in zip((o1, o2), _pair_by_hand(q, k, v, window, False), _pair_by_hand(q, k, v, window, True)):
        assert np.abs(np.asarray(got) - want).max() < 1e-5
        assert np.abs(np.asarray(got) - wrong).max() > 1e-2

    weigh = [jax.random.normal(key, o1.shape) for key in keys[3:5]]

    def weighted(q, k, v):
        return sum(jnp.sum(w * o) for w, o in zip(weigh, attention.paired_attention(q, k, v, window)))

    got = jax.grad(weighted, argnums=(0, 1, 2))(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    assert len(calls) == 2
    monkeypatch.setattr(attention, "_flash_tiles", lambda *shape: None)  # the blockwise path
    want = jax.grad(weighted, argnums=(0, 1, 2))(jnp.asarray(q), jnp.asarray(k), jnp.asarray(v))
    assert len(calls) == 2
    for a, b_ in zip(got, want, strict=True):
        assert float(jnp.linalg.norm(a - b_)) < 2e-4 * float(jnp.linalg.norm(b_))


def test_the_window_counts_the_querys_own_position():
    """Under a window of 3 the query at position 7 sees keys 5, 6 and 7: with
    one-hot values the output is the weights, and three of them are not 0."""
    t, d = 10, 4
    q = jnp.zeros((1, t, 4, d))
    k = jnp.zeros((1, t, 2, d))
    v = jnp.zeros((1, t, 2, d)).at[0, :, 0, 0].set(jnp.arange(t, dtype=jnp.float32))  # the position in channel 0
    o1, _ = attention.paired_attention(q, k, v, 3)
    # uniform weights over the visible keys: the mean position seen
    assert abs(float(o1[0, 7, 0, 0]) - 6.0) < 1e-5  # (5 + 6 + 7) / 3, not (4 + 5 + 6 + 7) / 4 = 5.5
    assert abs(float(o1[0, 1, 0, 0]) - 0.5) < 1e-5  # the row's start: keys 0 and 1


def test_no_position_reaches_the_attention():
    """No rotary, no other positional encoding: the last query's output does
    not change when the tokens before it are permuted (their keys and values
    with them).  A layer that turned q and k by position would."""
    params = _scaled(CFG.init(jax.random.key(3)))
    p = causal_lm._mixer_weights(params["layers"][KV_SOURCE], "attn", params["buffers"]["layers"][KV_SOURCE])
    x = jax.random.normal(jax.random.key(4), (1, T, MODEL["hidden_size"]))
    order = jnp.concatenate([jax.random.permutation(jax.random.key(5), T - 1), jnp.array([T - 1])])
    mixer = CFG.mixer("attn")[0]
    out = mixer(x, p, lm.keys_values(x, p))
    moved = mixer(x[:, order], p, lm.keys_values(x[:, order], p))
    assert float(jnp.max(jnp.abs(out[:, -1] - moved[:, -1]))) < 1e-5
    assert float(jnp.max(jnp.abs(out[:, 3] - moved[:, 3]))) > 1e-3  # an earlier query sees other tokens now


# --------------------------------------------------------- what the stack shares


def _without(params, layer: int, kind: str, leaves):
    """``params`` with the named leaves of one layer's mixer set to 0."""
    layers = list(params["layers"])
    layers[layer] = {**layers[layer], kind: {**layers[layer][kind], **{n: jnp.zeros_like(layers[layer][kind][n]) for n in leaves}}}
    return {**params, "layers": layers}


def _source_grads(params):
    ids, labels = tokens()
    _, grads = _loss_and_grads(lambda p: CFG.loss(p, ids, labels)[0], params)
    return grads["layers"][SOURCE]["ssm"], grads["layers"][KV_SOURCE]["attn"]


def test_the_sources_gradients_hold_the_second_decoders_part(both):
    """Silencing the gated memory unit changes the memory source's scan
    weights' gradients (and not the key-value source's ``W_k``'s path through
    the cross layer alone); silencing the cross layer changes the key-value
    source's ``W_k`` and ``W_v``."""
    params, (_, grads), _, _ = both
    ssm, attn = grads["layers"][SOURCE]["ssm"], grads["layers"][KV_SOURCE]["attn"]
    no_gmu = _source_grads(_without(params, GMU, "gmu", ["w_2"]))[0]
    no_cross = _source_grads(_without(params, CROSS, "xattn", ["w_o", "b_o"]))[1]
    for name in ("w_x", "w_dt", "A_log", "D"):
        assert float(jnp.linalg.norm(ssm[name] - no_gmu[name])) > 1e-3 * float(jnp.linalg.norm(ssm[name])), name
    for name in ("w_k", "w_v"):
        assert float(jnp.linalg.norm(attn[name] - no_cross[name])) > 1e-3 * float(jnp.linalg.norm(attn[name])), name


def test_the_sources_are_computed_once():
    """The forward pass runs the scan of each Mamba layer once and the
    key-value source's two products once: a state is a row loop's result and
    the next one's argument.  In the jaxpr of the loss, three ``scan``s over
    tokens (the three Mamba layers' twins) and no more."""
    params = CFG.init(jax.random.key(0))
    ids, labels = tokens()
    text = str(jax.make_jaxpr(lambda p: CFG.loss(p, ids, labels)[0])(params))
    assert len(re.findall(rf"length={T}\b", text)) == KINDS.count("ssm")


def test_the_counts_of_a_loss():
    params = CFG.init(jax.random.key(0))
    ids, labels = tokens()
    _, counts = CFG.loss(params, ids, labels)
    assert counts["shared_reads"] == 2 * B                 # the gated memory unit and the cross layer
    assert (counts["ssm_rows_kernel"], counts["ssm_rows_twin"]) == (0, 3 * B)  # 64 channels of 8 states: the twin
    assert counts["attn_out_heads"] == 4 * B and counts["attn_tiles_run"] == 0  # four attention layers, blockwise
    at_width = lm.Phi4FlashConfig(
        vocab_size=64, hidden_size=64, intermediate_size=48, num_attention_heads=8, num_key_value_heads=4,
        mamba_dt_rank=2, num_hidden_layers=4, dtype="float32",
    )
    assert selective_scan.scan_takes(at_width.inner, at_width.mamba_d_state) == 128


def _parents_row_by_row(mixer, x, p, batch_sharding):
    """``causal_lm._row_by_row`` as it stood before a row could be a tree."""
    keep = jax.checkpoint_policies.save_only_these_names(*attention.ATTN_KEPT)

    def local(x, p):
        return jax.lax.map(jax.checkpoint(lambda row: mixer(row[None], p)[0], policy=keep), x)

    assert batch_sharding is None
    return local(x, p)


def test_a_family_that_shares_nothing_walks_the_parents_program():
    """A family without ``shares`` and without ``kept``: the jaxpr of its loss
    and gradients is, equation for equation, what it is with the row loop as
    it stood before this family (a row an array, the attention kernels' two
    names kept), so every accepted step's program is the parent's."""
    from lakesoul_tpu.models import afmoe

    model = dict(
        vocab_size=96, hidden_size=64, num_hidden_layers=4, num_dense_layers=1, intermediate_size=112,
        layer_types=["sliding_attention", "sliding_attention", "full_attention", "sliding_attention"],
        num_attention_heads=4, num_key_value_heads=2, head_dim=16, sliding_window=40, rope_theta=10000,
        rope_scaling=None, mup_enabled=True, num_experts=16, num_shared_experts=1, num_experts_per_tok=4,
        moe_intermediate_size=32, route_scale=2.826, route_norm=True, score_func="sigmoid", n_group=1, topk_group=1,
        num_expert_groups=1, num_limited_groups=1, rms_norm_eps=1e-5, tie_word_embeddings=False, hidden_act="silu",
        load_balance_coeff=0.001,
    )
    cfg = afmoe.AfmoeConfig.from_published(model, experts_held=(4, 4), dtype="float32")
    assert not hasattr(cfg, "shares") and not hasattr(cfg, "kept")
    params = cfg.init(jax.random.key(0))
    rng = np.random.default_rng(0)
    ids = jnp.asarray(rng.integers(0, 96, (2, 48)), jnp.int32)

    def text():
        jaxpr = jax.make_jaxpr(jax.grad(lambda p: cfg.loss(p, ids, ids)[0]))(_trained(params) | {"buffers": params.get("buffers", {})})
        return re.sub(r" at 0x[0-9a-f]+", "", str(jaxpr))

    now = text()
    with mock.patch.object(causal_lm, "_row_by_row", lambda mixer, x, p, sharding, kept=(): _parents_row_by_row(mixer, x, p, sharding)):
        before = text()
    assert now == before
    assert "shared_reads" not in cfg.loss(params, ids, ids)[1]


# ------------------------------------------------------------------ the scan


def _scan_case(seed, rows, t, e, n):
    ks = jax.random.split(jax.random.key(seed), 7)
    u = jax.random.normal(ks[0], (rows, t, e))
    delta = jax.nn.softplus(jax.random.normal(ks[1], (rows, t, e)) - 1.0)
    a = -jnp.exp(0.5 * jax.random.normal(ks[2], (e, n)))
    b, c = jax.random.normal(ks[3], (rows, t, n)), jax.random.normal(ks[4], (rows, t, n))
    d, w = jax.random.normal(ks[5], (e,)), jax.random.normal(ks[6], (rows, t, e))
    return (u, delta, a, b, c, d), w


def _token_by_token(u, delta, a, b, c, d):
    """The recurrence in numpy, float64, one token after the other."""
    u, delta, a, b, c, d = (np.asarray(x, np.float64) for x in (u, delta, a, b, c, d))
    rows, t, e = u.shape
    s = np.zeros((rows, e, a.shape[1]))
    y = np.zeros((rows, t, e))
    for i in range(t):
        s = np.exp(delta[:, i, :, None] * a) * s + (delta[:, i] * u[:, i])[..., None] * b[:, i, None, :]
        y[:, i] = (s * c[:, i, None, :]).sum(-1) + d * u[:, i]
    return y


SCAN_CASES = {
    "twin": (1, 2, 37, 24, 8),           # no lane tile of channels: the ``lax.scan``
    "kernel": (2, 2, 150, 128, 8),       # one block of 128 channels; 150 tokens are two blocks with 106 of padding
    "kernel_wide": (3, 1, 130, 256, 16),  # a block of 256 channels, the published 16 states
}


@pytest.mark.parametrize("case", SCAN_CASES)
def test_the_scan_is_the_recurrence(case):
    seed, rows, t, e, n = SCAN_CASES[case]
    args, _ = _scan_case(seed, rows, t, e, n)
    assert (selective_scan.scan_takes(e, n) is None) == (case == "twin")
    y = selective_scan.selective_scan(*args)
    assert y.shape == (rows, t, e) and y.dtype == args[0].dtype
    assert np.abs(np.asarray(y, np.float64) - _token_by_token(*args)).max() < 2e-4


@pytest.mark.parametrize("case", SCAN_CASES)
def test_the_scans_gradients_are_the_recurrences(case):
    """All six cotangents against autodiff through the token-by-token
    ``lax.scan`` (which the first case IS, so it is held to finite
    differences of the numpy recurrence instead)."""
    seed, rows, t, e, n = SCAN_CASES[case]
    args, w = _scan_case(seed, rows, t, e, n)

    def loss(fn):
        return lambda *xs: jnp.sum(fn(*xs) * w)

    got = jax.grad(loss(selective_scan.selective_scan), argnums=range(6))(*args)
    if case == "twin":
        base = float((_token_by_token(*args) * np.asarray(w, np.float64)).sum())
        for i, name in enumerate(("u", "delta", "a", "b", "c", "d")):
            x = np.asarray(args[i], np.float64)
            at = tuple(0 for _ in x.shape)
            moved = x.copy()
            moved[at] += 1e-5
            bumped = float((_token_by_token(*args[:i], moved, *args[i + 1:]) * np.asarray(w, np.float64)).sum())
            assert abs((bumped - base) / 1e-5 - float(got[i][at])) < 2e-3 * max(1.0, abs(float(got[i][at]))), name
        return
    want = jax.grad(loss(selective_scan._scan_twin), argnums=range(6))(*args)
    for name, g, r in zip(("u", "delta", "a", "b", "c", "d"), got, want):
        assert g.shape == r.shape
        assert float(jnp.linalg.norm(g - r)) <= 2e-5 * float(jnp.linalg.norm(r)), name


def test_the_scans_backward_pass_keeps_the_block_boundaries():
    """Under a checkpoint that keeps ``SCAN_KEPT`` (the row loop's, for this
    family) the backward pass runs no second forward kernel; without the name
    it does."""
    args, w = _scan_case(5, 1, 128, 128, 8)

    def kernels(policy):
        fn = jax.checkpoint(lambda *xs: jnp.sum(selective_scan.selective_scan(*xs) * w), policy=policy)
        text = str(jax.make_jaxpr(jax.grad(fn, argnums=(0, 1)))(*args))
        return len(re.findall(r"name=selective_scan_fwd", text)), len(re.findall(r"name=selective_scan_bwd", text))

    assert kernels(jax.checkpoint_policies.save_only_these_names(*CFG.kept)) == (1, 1)
    assert kernels(jax.checkpoint_policies.save_only_these_names()) == (2, 1)
    assert CFG.kept == (selective_scan.SCAN_KEPT,)


# ------------------------------------------------------------- the train step


def _series(family, **labels) -> float:
    inner = ",".join(f'{k}="{v}"' for k, v in sorted(labels.items()))
    return registry().snapshot().get(family + ("{" + inner + "}" if inner else ""), 0)


def test_the_train_step_learns_and_counts():
    """Three optimizer steps through ``make_lm_train_state`` and
    ``make_lm_train_step``: the loss falls, ``lambda_init`` comes back bit for
    bit, and the step's series count the scan's rows and the shared reads."""
    plan = make_mesh(jax.devices()[:1], dp=1, tp=1, sp=1)
    params, opt_state, tx, shardings = make_lm_train_state(CFG, plan, lr=1e-2, seed=3)
    step = make_lm_train_step(CFG, plan, tx, shardings)
    before = {
        "tokens": _series(TOKENS_FAMILY), "twin": _series(SSM_SCAN_ROWS_FAMILY, path="twin"),
        "kernel": _series(SSM_SCAN_ROWS_FAMILY, path="kernel"), "reads": _series(SHARED_READS_FAMILY),
    }
    buffers = jax.tree.map(np.asarray, params["buffers"])
    ids, labels = tokens(2)
    losses = []
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, ids, labels)
        losses.append(float(loss))
    assert losses[2] < losses[0] and all(math.isfinite(x) for x in losses)
    assert jax.tree.all(jax.tree.map(lambda a, b: np.array_equal(np.asarray(a), b), params["buffers"], buffers))
    assert _series(TOKENS_FAMILY) - before["tokens"] == 3 * B * T
    assert _series(SSM_SCAN_ROWS_FAMILY, path="twin") - before["twin"] == 3 * B * 3
    assert _series(SSM_SCAN_ROWS_FAMILY, path="kernel") - before["kernel"] == 0
    assert _series(SHARED_READS_FAMILY) - before["reads"] == 3 * B * 2


def test_rows_on_a_mesh_are_the_rows_on_one_device():
    """dp = 2: every device takes its own rows through both row loops of a
    sharing layer, and the loss is the one device's."""
    ids, labels = tokens(4)
    params = _scaled(CFG.init(jax.random.key(1)))
    plan = make_mesh(jax.devices()[:2], dp=2, tp=1, sp=1)
    from jax.sharding import NamedSharding, PartitionSpec as P

    sharding = NamedSharding(plan.mesh, P("dp"))
    buffers = params["buffers"]

    def graded(**mesh):
        return jax.jit(jax.value_and_grad(lambda p, i, l: CFG.loss({**p, "buffers": buffers}, i, l, **mesh)[0]))

    alone, grads = graded()(_trained(params), ids, labels)
    meshed, mesh_grads = graded(batch_sharding=sharding)(
        _trained(params), jax.device_put(ids, sharding), jax.device_put(labels, sharding)
    )
    assert abs(float(alone) - float(meshed)) < 2e-6
    for kind, layer in ((SOURCE, "ssm"), (KV_SOURCE, "attn"), (GMU, "gmu"), (CROSS, "xattn")):
        for name, g in grads["layers"][kind][layer].items():
            if name != "b_k":  # zero in the mathematics
                apart = float(jnp.linalg.norm(g - mesh_grads["layers"][kind][layer][name]))
                assert apart <= 2e-4 * float(jnp.linalg.norm(g)), (layer, name)
