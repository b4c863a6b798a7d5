"""The causal-LM losses' fused tile body (``models/loss_tile.py: fused_tile``
over ``models/loss_tile.py``'s kernel, the Pallas interpreter here) against the
compiler's (``models/head_loss.py: tile_grads``: a float32 log-softmax, a gather and
autodiff), and the two sides of the split: the LM losses pass the body, the
masked-LM loss passes none and its process imports no Pallas.

Tiles this small stay the compiler's by the body's own rule (``tile_takes``: a
tile smaller than any the kernel is measured at); a test that wants the kernel
sets the smallest tile it takes to no bytes.
"""

from __future__ import annotations

import functools
import importlib.util
import os
import subprocess
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from lakesoul_tpu.models import afmoe, bert, causal_lm, glm4_moe_lite, loss_tile, norms, ouro
from lakesoul_tpu.models.head_loss import head_tile, labelled_nll, tile_grads
from lakesoul_tpu.models.loss_tile import block_rows, tile_takes
from lakesoul_tpu.models.train import LOSS_ROWS_FAMILY, make_lm_train_state, make_lm_train_step
from lakesoul_tpu.obs import registry
from lakesoul_tpu.parallel.mesh import make_mesh

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture(autouse=True)
def _full_precision():
    with jax.default_matmul_precision("highest"):
        yield


@pytest.fixture
def every_tile_fused(monkeypatch):
    """The smallest tile the kernel takes is none: every tile takes it."""
    monkeypatch.setattr(loss_tile, "MIN_TILE_BYTES", 0)


class _Cfg:
    def __init__(self, dtype):
        self.dtype = dtype

    @staticmethod
    def norm(x, w):
        return norms.rms_norm(x, w, 1e-6)


def _head(kind: str, hidden: int, vocab: int, dtype="float32"):
    """An LM head as ``causal_lm.lm_head`` reads it: tied to the embedding or
    its own matrix, with its final norm, or a looped model's (no norm)."""
    keys = jax.random.split(jax.random.key(11), 2)
    matrix = jax.random.normal(keys[0], (hidden, vocab)) / np.sqrt(hidden)
    norm = {"final_norm": 0.1 * jax.random.normal(keys[1], (hidden,))}
    head = {"tied": {**norm, "embed": matrix.T}, "untied": {**norm, "head": matrix}, "no-norm": {"head": matrix}}[kind]
    return functools.partial(causal_lm.lm_head, cfg=_Cfg(dtype)), head


def _close(got, want, rtol, what=""):
    for (path, g), w in zip(jax.tree_util.tree_leaves_with_path(got), jax.tree.leaves(want), strict=True):
        g, w = (np.asarray(a, np.float32) for a in (g, w))
        assert g.shape == w.shape
        np.testing.assert_allclose(g, w, rtol=rtol, atol=rtol * float(np.max(np.abs(w)) + 1e-30),
                                   err_msg=what + jax.tree_util.keystr(path))


# ---------------------------------------------------------------- one tile

TILE_CASES = {
    # rows, share of them with a label; 300 rows are two blocks of 256, the last ragged (and no whole sublane tile)
    "no-label": (40, 0.0), "all-labelled": (40, 1.0), "some-labelled": (48, 0.6), "ragged-last-block": (300, 0.7),
}


@pytest.mark.parametrize("case", TILE_CASES)
@pytest.mark.parametrize("form", ["mean", "weighted"])
@pytest.mark.parametrize("vocab", [384, 250], ids=["whole-lane-tiles", "ragged-vocabulary"])
@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
def test_fused_tile_is_the_compilers_body(dtype, vocab, form, case, every_tile_fused):
    """Loss, each row's NLL and both gradients of one tile, through a head with
    its norm: a whole-lane-tile vocabulary and one with its last lanes masked,
    the mean and the weighted form, rows without a label, a tile that is not
    whole row blocks, and a row whose largest logit is at its label."""
    rows, share = TILE_CASES[case]
    assert block_rows(rows, vocab, dtype) == (256 if rows == 300 else -(-rows // 16) * 16 if dtype == "bfloat16" else rows)
    rng = np.random.default_rng(rows + vocab)
    head_fn, head = _head("untied", 32, vocab, dtype)
    x = jnp.asarray(2.0 * rng.normal(size=(rows, 32)), dtype)
    labels = np.where(rng.uniform(size=rows) < share, rng.integers(0, vocab, rows), -100)
    if share:
        labels[1] = int(jnp.argmax(head_fn(head, x)[1]))  # the largest logit at the label: NLL near 0, g near 0 there
    labels = jnp.asarray(labels, jnp.int32)
    scale = jnp.float32(1.0 / max(int((labels >= 0).sum()), 1))
    weights = (jnp.asarray(rng.uniform(0.1, 2.0, rows), jnp.float32),) if form == "weighted" else ()
    want, want_g = tile_grads(head_fn, head, x, labels, scale, *weights)
    got, got_g = jax.jit(functools.partial(loss_tile.fused_tile, head_fn))(head, x, labels, scale, *weights)
    assert "loss_tile" in str(jax.make_jaxpr(functools.partial(loss_tile.fused_tile, head_fn))(head, x, labels, scale, *weights))
    if weights:
        (got, got_nll), (want, want_nll) = got, want
        np.testing.assert_allclose(got_nll, want_nll, rtol=2e-6, atol=2e-6)
        assert not np.asarray(got_nll)[np.asarray(labels) < 0].any()
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6, atol=1e-7)
    assert got_g[1].dtype == x.dtype and jax.tree.map(jnp.dtype, got_g[0]) == jax.tree.map(jnp.dtype, head)
    # float32 rows: a float32 cotangent, the sums in another order; bfloat16 rows: the cotangent rounded as it is
    # written, where a CPU's products (unlike a TPU's matrix unit) would have taken it unrounded
    _close(got_g, want_g, 1e-5 if dtype == "float32" else 2e-2)
    assert not np.asarray(got_g[1], np.float32)[np.asarray(labels) < 0].any()  # no gradient into a row without a label


def test_the_rule_takes_every_deployed_tile_and_no_tiny_one():
    """A rule on the tile's size alone: float32 logits of 48 MiB or more go
    through the kernel.  All five LM cells' tiles do, from the GLM step's 53 MB
    to the Ouro step's 538 (each measured to gain on a v5e); a tiny model's
    does not (and the masked-LM loss never asks)."""
    cells = {  # positions a step hands the loop (rows x tokens x passes), vocabulary held
        "ouro": (4 * 8192, 49152), "lfm2": (4 * 8192, 16384), "trinity-mini": (2 * 8192, 25024),
        "qwen3-next": (2 * 8192, 18992), "glm-4.7-flash": (8192, 19360),
    }
    assert all(tile_takes(head_tile(n), vocab) for n, vocab in cells.values())
    assert tile_takes(688, 18304) and not tile_takes(688, 18176)  # 50.37 MB and 50.02 MB about 50.33
    assert not tile_takes(head_tile(4 * 64), 96)
    # rows a block: whole sublane tiles of the cotangent's dtype, the pipeline's four buffers inside their budget
    assert (block_rows(2736, 49152, "bfloat16"), block_rows(2736, 49152, "float32")) == (64, 48)
    assert (block_rows(2736, 16384, "bfloat16"), block_rows(1368, 25024, "bfloat16")) == (208, 128)


# ------------------------------------------------------------ the tile loop


def _loop_case(kind: str, weighted: bool, seed=5):
    vocab, hidden, rows, length = 250, 32, 4, 60
    head_fn, head = _head(kind, hidden, vocab)
    rng = np.random.default_rng(seed)
    x = jnp.asarray(rng.normal(size=(rows, length, hidden)), jnp.float32)
    labels = jnp.asarray(np.where(rng.uniform(size=(rows, length)) < 0.6, rng.integers(0, vocab, (rows, length)), -100), jnp.int32)
    weights = jnp.asarray(rng.uniform(0.1, 2.0, (rows, length)), jnp.float32) if weighted else None
    return head_fn, head, x, labels, weights


@pytest.mark.parametrize("sharded", [False, True], ids=["one-device", "dp4"])
@pytest.mark.parametrize("weighted", [False, True], ids=["mean", "weighted"])
@pytest.mark.parametrize("kind", ["tied", "untied", "no-norm"])
def test_labelled_nll_with_the_fused_body_is_the_default(kind, weighted, sharded, every_tile_fused):
    """Value, positions, each position's NLL and both gradients through the
    whole tile loop (a label count that leaves the last tile part full), on
    one device and with the rows over a mesh (a shard's kernel sees its own
    rows; only sums cross)."""
    head_fn, head, x, labels, weights = _loop_case(kind, weighted)
    sharding = None
    if sharded:
        sharding = NamedSharding(make_mesh(jax.devices()[:4], dp=4, tp=1, sp=1).mesh, P("dp"))
        x, labels = (jax.device_put(a, sharding) for a in (x, labels))
        weights = None if weights is None else jax.device_put(weights, sharding)

    def through(*body):
        return jax.jit(jax.value_and_grad(
            lambda head, x: (lambda out: (out[0], out[1:]))(labelled_nll(head_fn, head, x, labels, sharding, weights, *body)),
            argnums=(0, 1), has_aux=True,
        ))(head, x)

    (got, got_aux), got_g = through(loss_tile.fused_tile)
    (want, want_aux), want_g = through()
    np.testing.assert_allclose(float(got), float(want), rtol=2e-6)
    assert int(got_aux[0]) == int(want_aux[0]) < x.shape[0] * x.shape[1] + 4 * head_tile(60)
    if weighted:
        np.testing.assert_allclose(got_aux[1], want_aux[1], rtol=2e-6, atol=2e-6)
    _close(got_g, want_g, 1e-5)


# ------------------------------------------------------- the three LM losses

OURO = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=2, intermediate_size=112, layer_types=["full_attention"] * 2,
    num_attention_heads=4, num_key_value_heads=4, head_dim=16, rope_theta=1000000, rope_scaling=None,
    sliding_window=None, use_sliding_window=False, rms_norm_eps=1e-6, tie_word_embeddings=False, hidden_act="silu",
    total_ut_steps=3, early_exit_threshold=1,
)
AFMOE = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=2, num_dense_layers=1, intermediate_size=112,
    layer_types=["sliding_attention", "full_attention"], num_attention_heads=4, num_key_value_heads=2, head_dim=16,
    sliding_window=40, num_experts=8, num_experts_per_tok=2, moe_intermediate_size=32,
)
GLM = dict(
    vocab_size=96, hidden_size=64, num_hidden_layers=2, first_k_dense_replace=1, intermediate_size=112,
    num_attention_heads=4, q_lora_rank=24, kv_lora_rank=16, qk_nope_head_dim=12, qk_rope_head_dim=4,
    v_head_dim=16, rope_theta=1e6, rope_scaling=None, partial_rotary_factor=1, attention_bias=False,
    n_routed_experts=16, n_shared_experts=1, num_experts_per_tok=4, moe_intermediate_size=32,
    routed_scaling_factor=1.8, n_group=1, topk_group=1, topk_method="noaux_tc", norm_topk_prob=True,
    rms_norm_eps=1e-5, num_nextn_predict_layers=1, mtp_loss_weight=0.3, tie_word_embeddings=False,
)
FAMILIES = {
    # the loss each exercises: ``lm_loss``; ``lm_loss`` with ``mtp_loss`` on top (two heads); ``exit_loss`` (weighted)
    "lm_loss": lambda: afmoe.AfmoeConfig.from_published(AFMOE, experts_held=(0, 4), dtype="float32"),
    "mtp_loss": lambda: glm4_moe_lite.Glm4MoeLiteConfig.from_published(GLM, experts_held=(4, 4), dtype="float32"),
    "exit_loss": lambda: ouro.OuroConfig.from_published(OURO, dtype="float32"),
}
B, T = 4, 64


def _tokens(seed=0):
    rng = np.random.default_rng(seed)
    ids = jnp.asarray(rng.integers(0, 96, (B, T)), jnp.int32)
    labels = jnp.concatenate([ids[:, 1:], jnp.full((B, 1), -100, jnp.int32)], axis=1)
    return ids, labels.at[0, 3:6].set(-100)


def _loss_and_grads(cfg, params, sharding):
    ids, labels = _tokens()
    if sharding is not None:
        ids, labels = (jax.device_put(a, sharding) for a in (ids, labels))
    (loss, counts), grads = jax.jit(jax.value_and_grad(
        lambda p: cfg.loss(p, ids, labels, batch_sharding=sharding), has_aux=True
    ))(params)
    grads.pop("buffers", None)  # no gradient reaches a buffer
    return float(loss), counts, grads


@pytest.mark.parametrize("sharded", [False, True], ids=["one-device", "dp2"])
@pytest.mark.parametrize("family", sorted(FAMILIES))
def test_each_lm_loss_with_the_fused_body_is_the_compilers(family, sharded, monkeypatch):
    """``lm_loss``, ``lm_loss`` with ``mtp_loss`` on top and ``exit_loss`` on a
    tiny model of a family that runs them: the value, every gradient leaf and
    the rows each says its tiles' body ran, by the compiler's body (tiles this
    small: the rule's own answer) and through the kernel (the smallest tile it
    takes set to no bytes), on one device and with the rows over a mesh."""
    cfg = FAMILIES[family]()
    params = jax.tree.map(lambda a: a * 5 if a.ndim >= 2 else a, cfg.init(jax.random.key(0)))
    sharding = NamedSharding(make_mesh(jax.devices()[:2], dp=2, tp=1, sp=1).mesh, P("dp")) if sharded else None
    want, want_counts, want_g = _loss_and_grads(cfg, params, sharding)
    monkeypatch.setattr(loss_tile, "MIN_TILE_BYTES", 0)
    got, got_counts, got_g = _loss_and_grads(cfg, params, sharding)
    losses = {"lm_loss": 1, "mtp_loss": 2, "exit_loss": 3}[family]  # the prediction module's second head; three passes
    assert (want_counts["loss_rows_fused"], want_counts["loss_rows_compiler"]) == (0, losses * B * T)
    assert (got_counts["loss_rows_fused"], got_counts["loss_rows_compiler"]) == (losses * B * T, 0)  # Python integers
    np.testing.assert_allclose(got, want, rtol=2e-6)
    _close(got_g, want_g, 2e-5, family + " ")


def test_the_lm_step_counts_its_rows_by_the_body_that_ran_them(monkeypatch):
    """``lakesoul_train_loss_rows_total{body="fused"|"compiler"}``: host
    integers a step, summed into the registry; ``head_loss_fused_pct`` reads
    their ratio, and nothing from a program without the series."""
    spec = importlib.util.spec_from_file_location(
        "head_loss_fused_pct", os.path.join(REPO, "benchmarks", "chip", "layer_metrics", "head_loss_fused_pct.py")
    )
    reader = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(reader)
    assert reader.COUNTER == LOSS_ROWS_FAMILY

    def series():
        snapshot = registry().snapshot()
        return {body: snapshot.get(f'{LOSS_ROWS_FAMILY}{{body="{body}"}}', 0) for body in ("fused", "compiler")}

    cfg = FAMILIES["exit_loss"]()
    plan = make_mesh(jax.devices()[:1], dp=1, tp=1, sp=1)
    ids, labels = _tokens(1)
    before = series()
    for fused in (False, True):
        if fused:
            monkeypatch.setattr(loss_tile, "MIN_TILE_BYTES", 0)
        state, opt_state, tx, shardings = make_lm_train_state(cfg, plan, lr=1e-3, seed=0)
        step = make_lm_train_step(cfg, plan, tx, shardings)
        for _ in range(2):
            state, opt_state, _ = step(state, opt_state, ids, labels)
        counts = step.counts()
        assert (counts["loss_rows_fused"], counts["loss_rows_compiler"]) == ((2 * 3 * B * T, 0) if fused else (0, 2 * 3 * B * T))
        assert "loss_rows_fused" not in step._state["keys"]  # no limb on the device: no operation of the step
    moved = {body: n - before[body] for body, n in series().items()}
    assert moved == {"fused": 2 * 3 * B * T, "compiler": 2 * 3 * B * T}
    counters = {f'{LOSS_ROWS_FAMILY}{{body="{body}"}}': float(n) for body, n in moved.items()}
    assert reader.read({"counters": counters}) == 50.0
    assert reader.read({"counters": {f'{LOSS_ROWS_FAMILY}{{body="fused"}}': 25.0 * 4 * 8192,
                                     f'{LOSS_ROWS_FAMILY}{{body="compiler"}}': 0.0}}) == 100.0
    assert reader.read({"counters": {'lakesoul_train_head_positions_total{kind="all"}': 380.0}}) is None  # the parent, a BERT step
    assert reader.read({"counters": {f'{LOSS_ROWS_FAMILY}{{body="fused"}}': 0.0, f'{LOSS_ROWS_FAMILY}{{body="compiler"}}': 0.0}}) is None
    assert reader.read({"counters": {f'{LOSS_ROWS_FAMILY}{{body="fused"}}': 380.0}}) is None  # half a family is no reading


# --------------------------------------------------- the masked-LM side, still

_BERT_STEP = """
import sys
import jax, jax.numpy as jnp
from lakesoul_tpu.models.bert import BertConfig
from lakesoul_tpu.models.train import make_bert_train_state, make_bert_train_step
from lakesoul_tpu.parallel.mesh import make_mesh
cfg = BertConfig.tiny()
plan = make_mesh(jax.devices()[:1], dp=1, tp=1, sp=1)
params, opt_state, tx, shardings = make_bert_train_state(cfg, plan)
step = make_bert_train_step(cfg, plan, tx, shardings)
ids = jnp.zeros((4, 32), jnp.int32)
labels = jnp.where(jnp.arange(32) % 7 == 0, ids, -100)
step.lower(params, opt_state, ids, labels, ids)        # traced and lowered
params, opt_state, loss = step(params, opt_state, ids, labels, ids)  # and run
assert bool(jnp.isfinite(loss))
print("PALLAS", sorted(m for m in sys.modules if "pallas" in m))
print("FAMILIES", sorted(m for m in sys.modules if m.endswith((".phi4flash", ".selective_scan", ".causal_lm", ".attention"))))
"""


def test_a_bert_steps_process_imports_no_pallas():
    """A fresh process that builds, traces, lowers and runs the BERT train
    step has no module with ``pallas`` in its name: its set-up pays no Pallas
    import and no kernel's lowering.  Nor has it the causal LMs' stack, their
    attention, the selective scan or the family that brought it."""
    env = dict(os.environ, JAX_PLATFORMS="cpu", PYTHONPATH=REPO + os.pathsep + os.environ.get("PYTHONPATH", ""))
    done = subprocess.run([sys.executable, "-c", _BERT_STEP], env=env, capture_output=True, text=True, timeout=600)
    assert done.returncode == 0, done.stderr[-2000:]
    assert "PALLAS []" in done.stdout, done.stdout[-500:]
    assert "FAMILIES []" in done.stdout, done.stdout[-500:]


def test_the_masked_lm_loss_lowers_no_kernel_and_an_lm_loss_does(every_tile_fused):
    """``bert_mlm_loss`` under ``jax.grad`` has no ``pallas_call`` whatever
    the rule says (it passes no body); an LM loss under the same rule, every
    tile taken, has the loss kernel's."""
    cfg = bert.BertConfig.tiny()
    params = bert.init_bert_params(cfg, jax.random.key(0))
    ids = jnp.zeros((4, 32), jnp.int32)
    labels = jnp.where(jnp.arange(32) % 7 == 0, ids, -100)
    text = str(jax.make_jaxpr(jax.grad(lambda p: bert.bert_mlm_loss(p, ids, labels, cfg=cfg)))(params))
    assert "pallas_call" not in text and "loss_tile" not in text and "log_softmax" in text  # the compiler's body
    lm_cfg = FAMILIES["exit_loss"]()
    lm_params = lm_cfg.init(jax.random.key(0))
    tokens = _tokens()
    text = str(jax.make_jaxpr(jax.grad(lambda p: lm_cfg.loss(p, *tokens)[0]))(lm_params))
    assert "pallas_call" in text and "loss_tile" in text and "log_softmax" not in text
