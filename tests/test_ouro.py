"""The ``ouro`` family (``models/ouro.py``: Ouro-2.6B, a looped language
model) on the shared causal-LM stack (``models/causal_lm.py``): the stack run
``R`` times over one set of weights (``loop_hidden``), a loss after every pass
through one head as ONE call of the weighted tile loop (``models/head_loss.py:
labelled_nll``), the exit gate and the expected loss (``exit_loss``), attention
without head norms (``softmax_attention``) and the train step
(``models/train.py``), against the plain float32 reference in
``benchmarks/chip/reference/ouro_f32.py`` (the one copy of it, loaded by path).

Small on purpose (hidden 64) with the published shape kept: every layer full
attention and a dense SwiGLU with four norms, as many key-value heads as
heads, an untied head, three passes.  At a head of 16 the attention runs the
blockwise twin and the ``jnp`` operand lines; ``KERNELS`` is the same model at
a head of 128 and rows of whole key tiles, where the flash kernels and the
operand kernels (without head norms) run in the interpreter.  The program
runs with ``dtype="float32"`` here so that the comparison is of the
algorithms (a scan over passes against a Python loop, one weighted tile loop
against ``R`` whole softmaxes), not of bfloat16.
"""

from __future__ import annotations

import importlib.util
import json
import os
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from lakesoul_tpu.models import attention, causal_lm, head_loss
from lakesoul_tpu.models import ouro as lm
from lakesoul_tpu.models.train import (
    HEAD_POSITIONS_FAMILY,
    LOOP_EXIT_MASS_FAMILY,
    LOOP_LAYER_PASSES_FAMILY,
    TOKENS_FAMILY,
    make_lm_train_state,
    make_lm_train_step,
)
from lakesoul_tpu.obs import registry
from lakesoul_tpu.parallel.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks", "chip")
_spec = importlib.util.spec_from_file_location("ouro_f32", os.path.join(BENCH, "reference", "ouro_f32.py"))
ref = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(ref)

R = 3
MODEL = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=2, intermediate_size=112, layer_types=["full_attention"] * 2,
    num_attention_heads=4, num_key_value_heads=4, head_dim=16, rope_theta=1000000, rope_scaling=None,
    sliding_window=None, use_sliding_window=False, rms_norm_eps=1e-6, tie_word_embeddings=False, hidden_act="silu",
    total_ut_steps=R, early_exit_threshold=1,
)
# the kernels' shapes: a head of whole lane tiles, rows of whole 128-key tiles
KERNELS = MODEL | {"head_dim": 128, "num_attention_heads": 2, "num_key_value_heads": 2}
CFG = lm.OuroConfig.from_published(MODEL, dtype="float32")
B, T = 2, 150


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision("highest"):
        yield


def _scaled(tree, key=7):
    """Five times the family's 0.02, so that no path's signal is lost in the
    residual; the norm weights and the gate's bias moved off 1 and 0, so that
    each one's gradient is its own."""
    keys = iter(jax.random.split(jax.random.key(key), len(jax.tree.leaves(tree))))
    return jax.tree.map(
        lambda a: a * 5 if a.ndim >= 2 else a + 0.2 * jax.random.normal(next(keys), a.shape), tree
    )


@pytest.fixture(scope="module")
def params():
    return _scaled(lm.init_lm_params(CFG, jax.random.key(0)))


def tokens(seed=0, rows=B, length=T):
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(0, MODEL["vocab_size"], (rows, length)), jnp.int32)
    labels = jnp.concatenate([ids[:, 1:], jnp.full((rows, 1), -100, jnp.int32)], axis=1)
    return ids, labels.at[0, 3:6].set(-100)  # three positions more without a label


def rel(a, b) -> float:
    return float(jnp.linalg.norm(a - b) / (jnp.linalg.norm(b) + 1e-30))


def assert_close(got, want, tol=2e-4):
    """Every leaf within ``tol`` of the reference by relative norm."""
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want), strict=True):
        assert rel(a, b) < tol, f"{jax.tree_util.keystr(path)}: {rel(a, b)}"


def test_the_family_is_one_kind_of_layer_run_several_times(params):
    assert CFG.layer_kinds() == ("attn", "attn") and CFG.ffn_kinds() == ("dense", "dense")
    assert CFG.mixer("attn")[1] == causal_lm.ATTN_SCOPE and CFG.loop_passes == R and CFG.exit_beta == 0.05
    for lp in params["layers"]:
        assert sorted(lp) == sorted(["attn", "mlp", "norm1", "norm1_out", "norm2", "norm2_out"])
        assert sorted(lp["attn"]) == ["w_k", "w_o", "w_q", "w_v"]  # no head norm, no gate
    assert sorted(params) == ["embed", "exit", "final_norm", "head", "layers"]  # one set of weights, no buffers
    assert params["exit"]["w"].shape == (64,) and params["exit"]["b"].shape == ()
    whole = lm.OuroConfig()  # the published model
    assert (whole.num_hidden_layers, whole.loop_passes, whole.num_attention_heads, whole.num_key_value_heads) == (48, 4, 16, 16)
    assert (whole.head_dim, whole.intermediate_size, whole.vocab_size, whole.rope_theta) == (128, 5632, 49152, 1e6)
    fresh = lm.init_lm_params(CFG, jax.random.key(3))
    assert float(fresh["exit"]["b"]) == 0.0 and float(jnp.min(fresh["final_norm"])) == 1.0
    ids, labels = tokens()
    mass = CFG.loss(fresh, ids, labels)[1]["exit_mass"]  # lambda starts near a half
    np.testing.assert_allclose(mass, [0.5, 0.25, 0.25], atol=0.05)


@pytest.mark.parametrize("key, value", [
    ("sliding_window", 4096), ("use_sliding_window", True), ("rope_scaling", {"type": "yarn", "factor": 4}),
    ("hidden_act", "gelu"), ("tie_word_embeddings", True), ("num_key_value_heads", 3),
])
def test_the_configuration_refuses_what_the_layers_do_not_compute(key, value):
    with pytest.raises(ValueError, match=key):
        lm.OuroConfig.from_published(MODEL | {key: value})


# ------------------------------------------------------- against the reference


@pytest.fixture(scope="module")
def compared(params):
    """Loss, counts and every gradient leaf of the program and of the
    reference on one batch, and every pass's logits at a few positions."""
    ids, labels = tokens()
    positions = jnp.arange(0, T, 7)
    with jax.default_matmul_precision("highest"):
        (loss, counts), grads = jax.jit(jax.value_and_grad(lambda p: CFG.loss(p, ids, labels), has_aux=True))(params)
        (want, aux), want_grads = jax.jit(jax.value_and_grad(
            lambda p: ref.lm_loss(p, ids, labels, cfg=MODEL, logits_at=positions), has_aux=True
        ))(params)
        states, _ = causal_lm.loop_hidden(params, ids, cfg=CFG)
        logits = causal_lm.lm_head({"head": params["head"]}, states[:, :, positions], cfg=CFG)
    return dict(loss=loss, counts=counts, grads=grads, want=want, aux=aux, want_grads=want_grads, logits=logits,
                ids=ids, labels=labels)


def test_the_loss_is_the_references(compared):
    np.testing.assert_allclose(float(compared["loss"]), float(compared["want"]), rtol=2e-6)


def test_each_passes_loss_is_the_references(compared):
    np.testing.assert_allclose(compared["counts"]["loss_pass"], compared["aux"]["loss_pass"], rtol=2e-6)
    assert compared["counts"]["loss_pass"].shape == (R,)


def test_the_exit_distribution_is_the_references_and_sums_to_one(compared):
    np.testing.assert_allclose(compared["counts"]["exit_mass"], compared["aux"]["exit_mass"], rtol=2e-6)
    np.testing.assert_allclose(float(jnp.sum(compared["counts"]["exit_mass"])), 1.0, rtol=1e-6)
    assert float(jnp.min(compared["counts"]["exit_mass"])) > 0.05  # no pass is a formality on these weights


@pytest.mark.parametrize("which", [0, R - 1], ids=["first-pass", "last-pass"])
def test_a_passes_logits_are_the_references(compared, which):
    assert rel(compared["logits"][which], compared["aux"]["logits"][which]) < 2e-4


LEAVES = {
    "first layer w_q": lambda g: g["layers"][0]["attn"]["w_q"],
    "last layer w_down": lambda g: g["layers"][-1]["mlp"]["w_down"],
    "norm1_out": lambda g: g["layers"][0]["norm1_out"],
    "final_norm": lambda g: g["final_norm"],
    "head": lambda g: g["head"],
    "embed": lambda g: g["embed"],
    "w_exit": lambda g: g["exit"]["w"],
    "b_exit": lambda g: g["exit"]["b"],
}


@pytest.mark.parametrize("leaf", sorted(LEAVES))
def test_the_gradient_of_each_kind_of_leaf_is_the_references(compared, leaf):
    got, want = LEAVES[leaf](compared["grads"]), LEAVES[leaf](compared["want_grads"])
    assert float(jnp.linalg.norm(want)) > 0 and rel(got, want) < 2e-4, rel(got, want)


def test_every_gradient_leaf_is_the_references_and_the_counts_say_what_ran(compared):
    assert_close(compared["grads"], compared["want_grads"])
    counts = compared["counts"]
    labelled = B * (T - 1) - 3
    assert (int(counts["head_all"]), int(counts["head_loop"]), int(counts["tokens"])) == (R * labelled, (R - 1) * labelled, B * T)
    assert (counts["loop_layers_run"], counts["loop_layers"]) == (R * B * 2, B * 2)  # Python integers
    for t in range(R):  # the gauge's fixed point: a pass's mass in 1,024ths of a position
        assert abs(int(counts[f"exit_mass_{t}"]) / 1024 - float(counts["exit_mass"][t]) * labelled) < 1e-3 * labelled
    # 150 tokens at a head of 16: the blockwise twin and the ``jnp`` operand lines, R times two layer-rows a row
    assert (counts["attn_tiles_run"], counts["attn_operands_kernel"], counts["attn_operands_xla"]) == (0, 0, R * B * 2)


def test_a_shared_leafs_gradient_is_the_sum_over_the_passes_of_an_untied_references(params, compared):
    """The tie: hand the reference ``R`` separate copies of the layers and of
    the final norm; the program's gradient of the one shared leaf is the sum
    of the copies' gradients (the scan's transpose adds them up)."""
    ids, labels = compared["ids"], compared["labels"]
    shared = {"layers": params["layers"], "final_norm": params["final_norm"]}
    copies = [jax.tree.map(jnp.copy, shared) for _ in range(R)]
    grads = jax.jit(jax.grad(lambda untied: ref.lm_loss(params, ids, labels, cfg=MODEL, untied=untied)))(copies)
    per_pass = [float(jnp.linalg.norm(g["layers"][0]["attn"]["w_q"])) for g in grads]
    assert min(per_pass) > 1e-3 * max(per_pass)  # every pass's copy receives a gradient of its own
    summed = jax.tree.map(lambda *g: sum(g), *grads)
    assert_close({k: compared["grads"][k] for k in shared}, summed)
    assert rel(compared["grads"]["final_norm"], grads[-1]["final_norm"]) > 0.1  # and no single pass's is the sum


def test_one_pass_without_the_entropy_term_is_the_plain_stacks_loss(params):
    """``R = 1``, ``beta = 0``: no gate is read, ``p = 1``, and what is left
    is ``lm_loss`` of the same weights on the plain stack (the Python walk, the
    final norm inside ``lm_head``, the unweighted tile loop)."""
    ids, labels = tokens(1)
    once = lm.OuroConfig.from_published(MODEL | {"total_ut_steps": 1}, exit_beta=0.0, dtype="float32")

    class Plain:  # the same layers with no loop: a family without ``loop_passes``
        layer_kinds, ffn_kinds, mixer, norm, dtype = once.layer_kinds, once.ffn_kinds, once.mixer, once.norm, once.dtype

    (loss, counts), grads = jax.value_and_grad(lambda p: once.loss(p, ids, labels), has_aux=True)(params)
    (want, plain), want_grads = jax.value_and_grad(
        lambda p: causal_lm.lm_loss(p, ids, labels, cfg=Plain), has_aux=True
    )(params)
    np.testing.assert_allclose(float(loss), float(want), rtol=2e-6)
    np.testing.assert_allclose(np.asarray(counts["exit_mass"]), [1.0])
    assert (int(counts["head_all"]), int(counts["head_loop"])) == (int(plain["head_all"]), 0)
    gate = grads.pop("exit"), want_grads.pop("exit")
    assert all(float(jnp.max(jnp.abs(g))) == 0.0 for g in jax.tree.leaves(gate))  # no gate before a last pass
    assert_close(grads, want_grads)


# ------------------------------------------- each assumed reading, held by a case


WRONG = {
    "a pass fed the un-normed state": ("carried", lambda x, z: x),
    "a norm over each head": ("head_operand", lambda a, cfg: ref.rms_norm(a, 1.0, cfg["rms_norm_eps"])),
    "the last pass's share as lambda S": (
        "exit_distribution",
        lambda lam: [*(p := ref_exit(lam))[:-1], p[-1] * lam[-1]],
    ),
    "the entropy's sign": ("objective", lambda p, nll, valid, beta: ref_objective(p, nll, valid, -beta)),
}
ref_exit, ref_objective = ref.exit_distribution, ref.objective


@pytest.mark.parametrize("reading", sorted(WRONG))
def test_a_wrong_reading_of_an_assumed_item_is_not_what_the_program_computes(params, compared, monkeypatch, reading):
    """The reference with ONE reading changed stands far from the program,
    which stands on the reference as written (the cases above): the program's
    reading is held, not merely shared."""
    name, wrong = WRONG[reading]
    monkeypatch.setattr(ref, name, wrong)
    ids, labels = compared["ids"], compared["labels"]
    off, off_grads = jax.value_and_grad(lambda p: ref.lm_loss(p, ids, labels, cfg=MODEL))(params)
    assert abs(float(off) - float(compared["loss"])) > 1e-3, (float(off), float(compared["loss"]))
    assert rel(compared["grads"]["exit"]["w"], off_grads["exit"]["w"]) > 0.02


# ------------------------------------------------- the kernels' shapes, bfloat16


def test_the_whole_model_at_the_kernels_shapes_equals_the_reference():
    """One row of 256 tokens at a head of 128: the flash kernels and the
    operand kernels WITHOUT head norms (both in the interpreter here) inside
    the scan over passes, loss and every gradient leaf; the step's counts are
    the tile tables' lengths and the rows, times the passes."""
    cfg = lm.OuroConfig.from_published(KERNELS, dtype="float32")
    weights = _scaled(lm.init_lm_params(cfg, jax.random.key(1)))
    ids, labels = tokens(6, rows=1, length=256)
    (loss, counts), grads = jax.jit(jax.value_and_grad(lambda p: cfg.loss(p, ids, labels), has_aux=True))(weights)
    want, want_grads = jax.jit(jax.value_and_grad(lambda p: ref.lm_loss(p, ids, labels, cfg=KERNELS)))(weights)
    np.testing.assert_allclose(float(loss), float(want), rtol=2e-6)
    assert_close(grads, want_grads)
    steps = attention.key_tile_steps(256, 1, 128)[0]  # one key-value head's list
    assert steps > 0 and (counts["attn_tiles_run"], counts["attn_tiles_causal"]) == (R * 2 * 2 * steps,) * 2
    assert (counts["attn_operands_kernel"], counts["attn_operands_xla"]) == (R * 2, 0)


def test_bfloat16_program_stays_near_the_reference(params):
    """The dtype the chip runs: products in bfloat16, float32 accumulation."""
    ids, labels = tokens(1)
    cfg = lm.OuroConfig.from_published(MODEL)
    loss, counts = cfg.loss(params, ids, labels)
    want, aux = ref.lm_loss(params, ids, labels, cfg=MODEL, logits_at=jnp.arange(2))
    assert abs(float(loss) - float(want)) < 0.03
    np.testing.assert_allclose(counts["exit_mass"], aux["exit_mass"], atol=0.01)


# ------------------------------------------------ the weighted head and loss


def _head_fn(head, x):
    return jnp.dot(x, head["w"], preferred_element_type=jnp.float32)


def _weighted_case(labelled: int, seed=0):
    rng = np.random.default_rng(seed)
    rows, length, h, vocab = 4, 24, 16, 37
    x = jnp.asarray(rng.normal(size=(rows, length, h)), jnp.float32)
    head = {"w": jnp.asarray(rng.normal(size=(h, vocab)), jnp.float32)}
    labels = np.full(rows * length, -100, np.int32)
    labels[rng.permutation(rows * length)[:labelled]] = rng.integers(0, vocab, labelled)
    weights = jnp.asarray(rng.uniform(0.1, 2.0, (rows, length)), jnp.float32)
    return head, x, jnp.asarray(labels.reshape(rows, length)), weights


def _plain_weighted(head, x, labels, weights):
    logp = jax.nn.log_softmax(_head_fn(head, x), axis=-1)
    nll = -jnp.take_along_axis(logp, jnp.maximum(labels, 0)[..., None], axis=-1)[..., 0]
    nll = jnp.where(labels >= 0, nll, 0.0)
    return jnp.sum(weights * nll), nll


@pytest.mark.parametrize("sharded", [False, True], ids=["one-device", "dp4"])
@pytest.mark.parametrize("labelled", [0, 1, 9, 96], ids=["none", "one", "some", "every"])
def test_the_weighted_head_and_loss_is_a_plain_weighted_log_softmax(labelled, sharded):
    """Value, each position's NLL and both gradients, whatever the number of
    labels, on one device and with the rows over a mesh."""
    head, x, labels, weights = _weighted_case(labelled, seed=labelled)
    sharding = None
    if sharded:
        sharding = NamedSharding(make_mesh(jax.devices()[:4], dp=4, tp=1, sp=1).mesh, P("dp"))
        x, labels, weights = (jax.device_put(a, sharding) for a in (x, labels, weights))

    def program(head, x):
        loss, positions, nll = head_loss.labelled_nll(_head_fn, head, x, labels, sharding, weights)
        return loss, (positions, nll)

    (loss, (positions, nll)), grads = jax.jit(jax.value_and_grad(program, argnums=(0, 1), has_aux=True))(head, x)
    (want, want_nll), want_grads = jax.value_and_grad(
        lambda head, x: _plain_weighted(head, x, labels, weights), argnums=(0, 1), has_aux=True
    )(head, x)
    np.testing.assert_allclose(float(loss), float(want), rtol=2e-6, atol=1e-7)
    np.testing.assert_allclose(nll, want_nll, rtol=2e-6, atol=1e-6)
    np.testing.assert_allclose(grads[0]["w"], want_grads[0]["w"], rtol=1e-5, atol=1e-6)
    np.testing.assert_allclose(grads[1], want_grads[1], rtol=1e-5, atol=1e-6)
    assert labelled <= int(positions) <= 96 + (4 if sharded else 1) * head_loss.head_tile(96 // (4 if sharded else 1))


def test_the_weighted_form_takes_no_gradient_through_its_weights_or_its_per_position_loss():
    head, x, labels, weights = _weighted_case(9)
    g = jax.grad(lambda w: head_loss.labelled_nll(_head_fn, head, x, labels, None, w)[0])(weights)
    assert float(jnp.max(jnp.abs(g))) == 0.0
    g = jax.grad(lambda x: jnp.sum(head_loss.labelled_nll(_head_fn, head, x, labels, None, weights)[2]))(x)
    assert float(jnp.max(jnp.abs(g))) == 0.0


def _parents_head_over_labelled(head_fn, head, x, labels):
    """``models/head_loss.py: _head_over_labelled`` as it stood before it took
    weights (one device, with gradients), line for line."""
    x2, lab = x.reshape(-1, x.shape[-1]), labels.reshape(-1)
    n = lab.shape[0]
    tile = head_loss.head_tile(n)
    slots = -(-n // tile) * tile
    order = jnp.argsort(lab < 0, stable=True)
    rows = jnp.pad(order, (0, slots - n))
    row_labels = jnp.pad(lab[order], (0, slots - n), constant_values=-100)
    count = jnp.sum(lab >= 0)
    total = count
    scale = 1.0 / jnp.maximum(total, 1).astype(jnp.float32)

    def tile_nll(head, x, labels, scale):
        logp = jax.nn.log_softmax(head_fn(head, x), axis=-1)
        picked = jnp.take_along_axis(logp, jnp.maximum(labels, 0)[:, None], axis=-1)[:, 0]
        return -scale * jnp.sum(jnp.where(labels >= 0, picked, 0.0))

    def run_tile(carry):
        k, loss, grads = carry
        at = k * tile
        xt = x2[jax.lax.dynamic_slice(rows, (at,), (tile,))]
        lt = jax.lax.dynamic_slice(row_labels, (at,), (tile,))
        part, (g_head, g_x) = jax.value_and_grad(tile_nll, argnums=(0, 1))(head, xt, lt, scale)
        acc_head, acc_x = grads
        grads = (jax.tree.map(jnp.add, acc_head, g_head), jax.lax.dynamic_update_slice(acc_x, g_x, (at, 0)))
        return k + 1, loss + part, grads

    grads = (jax.tree.map(jnp.zeros_like, head), jnp.zeros((slots, x2.shape[1]), x2.dtype))
    tiles = (count + tile - 1) // tile
    _, loss, grads = jax.lax.while_loop(lambda carry: carry[0] < tiles, run_tile, (jnp.int32(0), jnp.float32(0.0), grads))
    positions = tiles * tile
    g_head, g_rows = grads
    g_x = g_rows[jnp.argsort(order)].reshape(x.shape)
    return loss, positions, g_head, g_x


def test_without_weights_the_head_and_loss_is_the_parents_program():
    """No weights passed: the jaxpr of the tile loop with its gradients is,
    equation for equation, that of the function as it stood before it took
    weights, so every accepted step's program is the parent's."""
    head, x, labels, _ = _weighted_case(9)
    now = jax.make_jaxpr(lambda head, x: head_loss._sharded_head(_head_fn, head, x, labels, None, True))(head, x)
    before = jax.make_jaxpr(lambda head, x: _parents_head_over_labelled(_head_fn, head, x, labels))(head, x)
    assert str(now) == str(before)
    out = head_loss.labelled_nll(_head_fn, head, x, labels)
    assert len(out) == 2  # (loss, positions): no per-position loss without weights


# ------------------------------------------------------------- the train step


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
            counted = {
                "tokens": _series(TOKENS_FAMILY), "run": _series(LOOP_LAYER_PASSES_FAMILY, kind="run"),
                "layers": _series(LOOP_LAYER_PASSES_FAMILY, kind="layers"),
                "loop": _series(HEAD_POSITIONS_FAMILY, kind="loop"), "all": _series(HEAD_POSITIONS_FAMILY, kind="all"),
            }
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
        moved, wanted = (np.atleast_1d(np.asarray(x)) / lr for x in (a - b, target - b))  # the gate's bias is a scalar
        decisive = np.abs(wanted) > 0.9
        assert decisive.any(), name
        np.testing.assert_allclose(moved[decisive], wanted[decisive], atol=2e-2, err_msg=name)
        assert float(np.max(np.abs(moved))) < 1.02, name


def test_one_step_is_the_references_adamw_step_over_the_one_set_of_weights(stepped):
    ids, labels, out = stepped
    states = out[1]["states"]
    loss, grads = jax.jit(jax.value_and_grad(lambda p: ref.lm_loss(p, ids, labels, cfg=MODEL)))(states[0])
    zeros = jax.tree.map(jnp.zeros_like, states[0])
    want, _, _ = ref.adamw_step(states[0], grads, zeros, zeros, 0, lr=1e-2)
    np.testing.assert_allclose(out[1]["losses"][0], float(loss), rtol=2e-6)
    assert_moves_agree(states[0], states[1], want)
    assert out[1]["losses"][-1] < out[1]["losses"][0]
    moments = [leaf for leaf in jax.tree.leaves(out[1]["opt_state"]) if leaf.ndim or leaf.dtype == np.float32]
    assert len(moments) == 2 * len(jax.tree.leaves(states[0]))  # the gate's two leaves among them: ordinary trained leaves


def test_step_on_a_dp2_mesh_equals_one_device(stepped):
    _, _, out = stepped
    np.testing.assert_allclose(out[2]["losses"][0], out[1]["losses"][0], rtol=1e-5)
    assert_moves_agree(out[2]["states"][0], out[2]["states"][1], out[1]["states"][1])


def test_the_steps_counters_say_every_pass_and_every_loss_ran(stepped):
    _, labels, out = stepped
    run = out[1]
    got = run["step"].counts()
    labelled = int(jnp.sum(labels >= 0))
    assert got["tokens"] == STEPS * B * T
    assert (got["loop_layers_run"], got["loop_layers"]) == (STEPS * R * B * 2, STEPS * B * 2)
    assert (got["head_all"], got["head_loop"], got["head_mtp"]) == (STEPS * R * labelled, STEPS * (R - 1) * labelled, 0)
    steps = 2 * STEPS  # both meshes' steps feed the registry
    assert _series(TOKENS_FAMILY) - run["counted"]["tokens"] == steps * B * T
    assert _series(LOOP_LAYER_PASSES_FAMILY, kind="run") - run["counted"]["run"] == steps * R * B * 2
    assert _series(LOOP_LAYER_PASSES_FAMILY, kind="layers") - run["counted"]["layers"] == steps * B * 2
    loop = _series(HEAD_POSITIONS_FAMILY, kind="loop") - run["counted"]["loop"]
    every = _series(HEAD_POSITIONS_FAMILY, kind="all") - run["counted"]["all"]
    assert (loop, every) == (steps * (R - 1) * labelled, steps * R * labelled) and loop / every == (R - 1) / R


def test_the_exit_mass_gauge_is_a_distribution_over_the_passes(stepped):
    del stepped  # the gauge reads every step counted so far
    mass = [_series(LOOP_EXIT_MASS_FAMILY, **{"pass": str(t + 1)}) for t in range(R)]
    assert all(0.02 < m < 0.98 for m in mass), mass
    np.testing.assert_allclose(sum(mass), 1.0, atol=1e-4)
    assert registry().kinds()[LOOP_EXIT_MASS_FAMILY] == "gauge"


def test_a_family_that_does_not_loop_counts_no_loop_and_keeps_its_program():
    """The four other families' steps: ``head_loop`` and the layer passes are
    host integers there (0 a step, no operation of the program) and the count
    limbs on the device are the family's own ten (the tokens, two of the head,
    seven of the routed layers: the seventh, ``moe_grouped``, since the
    grouped kernels)."""
    from lakesoul_tpu.models import afmoe

    model = dict(
        vocab_size=96, hidden_size=64, num_hidden_layers=2, num_dense_layers=1, intermediate_size=112,
        layer_types=["sliding_attention", "full_attention"], num_attention_heads=4, num_key_value_heads=2, head_dim=16,
        sliding_window=40, num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
    )
    cfg = afmoe.AfmoeConfig.from_published(model, experts_held=(0, 4), dtype="float32")
    plan = make_mesh(jax.devices()[:1], dp=1, tp=1, sp=1)
    state, opt_state, tx, shardings = make_lm_train_state(cfg, plan, lr=1e-3, seed=0)
    step = make_lm_train_step(cfg, plan, tx, shardings)
    assert step._state["counted"].shape == (10, 2) and "head_loop" not in step._state["keys"]
    ids, labels = tokens(3, rows=1, length=64)
    step(state, opt_state, ids, labels)
    got = step.counts()
    assert (got["head_loop"], got["loop_layers_run"], got["loop_layers"]) == (0, 0, 0) and got["head_all"] == 60


def test_lm_step_runs_on_dp_only():
    plan = make_mesh(jax.devices()[:2], dp=1, tp=2, sp=1)
    with pytest.raises(NotImplementedError, match="dp only"):
        make_lm_train_state(CFG, plan)


# --------------------------------------------- the benchmark's configuration


@pytest.fixture(scope="module")
def deployed():
    with open(os.path.join(BENCH, "configs", "ouro_2_6b_clm_pk.json")) as f:
        config = json.load(f)
    m = config["model"]
    cfg = lm.OuroConfig.from_published(m, dtype=m["compute_dtype"])
    shapes = jax.eval_shape(cfg.init, jax.random.key(0))
    return config, cfg, shapes


def _count(tree) -> int:
    return sum(int(np.prod(a.shape)) for a in jax.tree.leaves(tree))


PARTS = {
    "a layer": (lambda s: s["layers"][0], 51_388_416),
    "six layers": (lambda s: s["layers"], 308_330_496),
    "embedding and head": (lambda s: [s["embed"], s["head"]], 201_326_592),
    "final norm": (lambda s: s["final_norm"], 2_048),
    "the exit gate": (lambda s: s["exit"], 2_049),
    "total": (lambda s: s, 509_661_185),
}


@pytest.mark.parametrize("part", sorted(PARTS))
def test_the_held_models_parameter_table(deployed, part):
    """The cut as ``configs/ouro_2_6b_clm_pk.json`` states it, counted on
    ``jax.eval_shape(cfg.init, ...)``: nothing is allocated."""
    _, _, shapes = deployed
    pick, want = PARTS[part]
    assert _count(pick(shapes)) == want
    assert all(a.dtype == jnp.float32 for a in jax.tree.leaves(shapes))


def test_the_cut_is_depth_alone_and_the_file_states_what_it_assumes(deployed):
    config, cfg, shapes = deployed
    published, model = config["published"], config["model"]
    assert {k for k in published if published[k] != model[k]} == {"num_hidden_layers", "layer_types"}
    assert {k for k in published if published[k] != config[k]} == set()  # the top level: the published keys, whole
    assert (config["num_layers_held"], model["num_hidden_layers"], published["num_hidden_layers"]) == (6, 6, 48)
    assert model["layer_types"] == published["layer_types"][:6] == ["full_attention"] * 6
    assert (cfg.loop_passes, cfg.exit_beta, model["vocab_size"]) == (4, 0.05, 49152)  # every pass, the whole vocabulary
    assert (cfg.num_attention_heads, cfg.num_key_value_heads, cfg.head_dim, cfg.intermediate_size) == (16, 16, 128, 5632)
    assert shapes["head"].shape == (2048, 49152) and shapes["exit"]["w"].shape == (2048,)
    for name in ("reduced_why", "assumed", "guarantees", "program_departures", "deployment", "optimizer"):
        assert config[name], name
    assert sorted(config["reduced_why"]) == sorted(["num_layers_held", "table_rows", "storage", "token_source"])
    for starred in ("four_norms_a_layer", "no_qk_norm", "no_gate_no_bias", "next_pass_takes_the_normed_state",
                    "exit_gate", "expected_loss", "beta", "weights", "per_chip_batch", "learning_rate"):
        assert config["assumed"][starred], starred
    for held in ("all_passes_run", "shared_weights", "reference_loss_tolerance", "reference_tolerances_why"):
        assert config["guarantees"][held], held


def test_the_adaptors_operation_count_is_the_hand_count(deployed):
    """``flops_per_row``: every product once forward and twice backward, no
    recomputation: ``R x L`` layer passes with their scores and values over
    the causal mask's visible pairs, and ``R`` head passes."""
    config, _, _ = deployed
    sys.path[:0] = [p for p in (BENCH,) if p not in sys.path]  # the adaptor imports ``chipbench``
    spec = importlib.util.spec_from_file_location("ouro_clm", os.path.join(BENCH, "consumers", "ouro_clm.py"))
    adaptor = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(adaptor)
    seq, width = 8192, 16 * 128
    layer = 4 * 2048 * width + 3 * 2048 * 5632  # multiply-adds a token
    pairs = seq * (seq + 1) // 2
    a_pass = 6 * (seq * 2 * layer + 4 * width * pairs) + seq * 2 * 2048 * 49152
    assert adaptor.flops_per_row(config) == pytest.approx(3 * 4 * a_pass, rel=1e-12)
    # 100.2 TFLOP a row; the issue's 103.6 counts the backward kernel's scores again (3.5 x a layer's 0.275, not 3 x)
    assert 3 * 4 * a_pass == pytest.approx(100.2e12, rel=0.001)
