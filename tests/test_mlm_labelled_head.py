"""The MLM head and loss at the labelled positions only (``models/bert.py:
mlm_head_loss``) against the all-positions formulation, which stays here as
the plain reference: equal loss and gradients at every labelled count, under a
sharded step, with no ``[B*T, vocab]`` array left in the program, and the
counter that says how many positions the head ran at."""

from __future__ import annotations

import gc
import importlib.util
import os
import re
import sys

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from lakesoul_tpu.models.bert import (
    BertConfig,
    bert_forward,
    bert_mlm_loss,
    init_bert_params,
    mlm_head_loss,
)
from lakesoul_tpu.models.head_loss import head_tile
from lakesoul_tpu.models.train import (
    HEAD_POSITIONS_FAMILY,
    make_bert_train_state,
    make_bert_train_step,
)
from lakesoul_tpu.obs import registry
from lakesoul_tpu.parallel.mesh import make_mesh

# a prime vocabulary: an array whose size it divides is a vocabulary-wide one
CFG = BertConfig(vocab_size=97, hidden=32, layers=2, heads=4, ff=64, max_len=32, dtype="float32")
B, T = 8, 32
N = B * T
TILE = head_tile(N)
COUNTS = {
    "none": 0, "one": 1, "15pct": round(0.15 * N), "half": N // 2,
    "one_tile": TILE, "tile_plus_one": TILE + 1, "every": N,
}


def plain_loss(params, ids, labels, mask=None, *, cfg=CFG):
    """The reference: logits at every position, float32 log-softmax, mean over
    the labelled ones."""
    logits = bert_forward(params, ids, mask, cfg=cfg)
    valid = labels >= 0
    logp = jax.nn.log_softmax(logits.astype(jnp.float32), axis=-1)
    nll = -jnp.take_along_axis(logp, jnp.where(valid, labels, 0)[..., None], axis=-1)[..., 0]
    return jnp.sum(jnp.where(valid, nll, 0.0)) / jnp.maximum(jnp.sum(valid), 1)


def batch(count: int, seed: int = 0, b: int = B, t: int = T, vocab: int = CFG.vocab_size):
    rng = np.random.default_rng(seed)
    ids = rng.integers(0, vocab, (b, t)).astype(np.int32)
    labels = np.full(b * t, -100, np.int32)
    at = rng.choice(b * t, count, replace=False)
    labels[at] = rng.integers(0, vocab, count)
    return ids, labels.reshape(b, t)


@pytest.fixture(scope="module")
def params():
    p = init_bert_params(CFG, jax.random.key(0))
    # a zero bias and unit scales would hide a wrong gradient of either
    p["mlm_bias"] = 0.1 * jax.random.normal(jax.random.key(1), p["mlm_bias"].shape)
    p["mlm_ln"]["scale"] = 1.0 + 0.1 * jax.random.normal(jax.random.key(2), p["mlm_ln"]["scale"].shape)
    return p


def test_tile_is_fixed_by_the_positions():
    assert head_tile(64 * 128) == 688  # a twelfth of a BERT-base batch's 8,192, up to 8
    for n in (1, 7, 8, 100, 256, 8192, 65536):
        tile = head_tile(n)
        assert 0 < tile <= n and (tile % 8 == 0 or tile == n)
        assert -(-n // tile) * tile - n < 8 * 12  # twelve tiles cover n with next to none over


@pytest.mark.parametrize("labelled", COUNTS)
def test_loss_and_every_gradient_equal_the_all_positions_head(params, labelled):
    count = COUNTS[labelled]
    ids, labels = batch(count)
    want, want_g = jax.jit(jax.value_and_grad(plain_loss))(params, ids, labels)
    (got, positions), got_g = jax.jit(jax.value_and_grad(
        lambda p, i, l: bert_mlm_loss(p, i, l, cfg=CFG, with_head_positions=True), has_aux=True,
    ))(params, ids, labels)
    np.testing.assert_allclose(got, want, rtol=1e-6, atol=1e-7)
    flat_want = jax.tree_util.tree_leaves_with_path(want_g)
    flat_got = jax.tree_util.tree_leaves_with_path(got_g)
    assert [p for p, _ in flat_got] == [p for p, _ in flat_want]
    for (path, g), (_, w) in zip(flat_got, flat_want):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-7, err_msg=jax.tree_util.keystr(path))
    # as many tiles as the labels fill, none for none
    assert int(positions) == -(-count // TILE) * TILE
    # the undifferentiated loss (the benchmark's `correct` check) is the same number
    np.testing.assert_allclose(jax.jit(lambda p: bert_mlm_loss(p, ids, labels, cfg=CFG))(params), want,
                               rtol=1e-6, atol=1e-7)


def test_sharded_head_equals_the_single_device_loss_and_gradients(params):
    plan = make_mesh(jax.devices(), dp=2, tp=2, sp=2)
    sharding = NamedSharding(plan.mesh, P("dp", "sp"))
    _, labels = batch(60, seed=3)
    x = jax.random.normal(jax.random.key(4), (B, T, CFG.hidden))
    want, want_g = jax.value_and_grad(lambda p, x: mlm_head_loss(p, x, labels)[0], argnums=(0, 1))(params, x)
    (got, positions), got_g = jax.jit(jax.value_and_grad(
        lambda p, x, l: mlm_head_loss(p, x, l, batch_sharding=sharding), argnums=(0, 1), has_aux=True,
    ))(params, jax.device_put(x, NamedSharding(plan.mesh, P("dp", "sp", None))), jax.device_put(labels, sharding))
    np.testing.assert_allclose(got, want, rtol=1e-6)
    for g, w in zip(jax.tree.leaves(got_g), jax.tree.leaves(want_g)):
        np.testing.assert_allclose(g, w, rtol=1e-5, atol=1e-7)
    # every shard gathers among its own 64 positions, in whole tiles
    local = np.asarray(labels).reshape(2, B // 2, 2, T // 2).transpose(0, 2, 1, 3).reshape(4, -1)
    tile = head_tile(local.shape[1])
    assert int(positions) == sum(-(-int((rows >= 0).sum()) // tile) * tile for rows in local)


@pytest.fixture(scope="module")
def sharded_step():
    plan = make_mesh(jax.devices(), dp=2, tp=2, sp=2)
    params, opt_state, tx, shardings = make_bert_train_state(CFG, plan, lr=1e-3, seed=0)
    return plan, params, opt_state, make_bert_train_step(CFG, plan, tx, shardings)


def _series(kind: str) -> float:
    return registry().snapshot().get(f'{HEAD_POSITIONS_FAMILY}{{kind="{kind}"}}', 0)


def test_sharded_step_equals_the_single_device_loss_and_counts_positions(sharded_step):
    plan, params, opt_state, step = sharded_step
    ids, labels = batch(round(0.15 * N), seed=5)
    mask = np.ones((B, T), bool)
    want = float(plain_loss(jax.device_get(params), ids, labels, mask))
    before = {kind: _series(kind) for kind in ("computed", "all")}
    # the step donates its carries: hand it copies, the fixture's stay whole
    params, opt_state = jax.tree.map(jnp.copy, (params, opt_state))
    losses = []
    for _ in range(3):
        params, opt_state, loss = step(params, opt_state, ids, labels, mask)
        losses.append(float(loss))
    np.testing.assert_allclose(losses[0], want, rtol=1e-5)
    assert losses[-1] < losses[0]
    local = labels.reshape(2, B // 2, 2, T // 2).transpose(0, 2, 1, 3).reshape(4, -1)
    tile = head_tile(local.shape[1])
    a_step = sum(-(-int((rows >= 0).sum()) // tile) * tile for rows in local)
    assert 0 < a_step < N
    assert _series("all") - before["all"] == 3 * N
    assert _series("computed") - before["computed"] == 3 * a_step
    assert step.counts()["computed"] >= 3 * a_step


def test_counter_outlives_its_step():
    plan = make_mesh(jax.devices()[:1], dp=1, tp=1, sp=1)
    params, opt_state, tx, shardings = make_bert_train_state(CFG, plan, seed=1)
    step = make_bert_train_step(CFG, plan, tx, shardings)
    ids, labels = batch(N, seed=6)
    before = {kind: _series(kind) for kind in ("computed", "all")}
    step(params, opt_state, ids, labels, np.ones((B, T), bool))
    del step
    gc.collect()
    # every position labelled: the head ran at all of them, in whole tiles
    assert _series("all") - before["all"] == N
    assert _series("computed") - before["computed"] == -(-N // TILE) * TILE


def test_lowered_step_holds_no_array_of_all_logits(sharded_step):
    plan, params, opt_state, step = sharded_step
    ids, labels = batch(40, seed=7)
    sharding = NamedSharding(plan.mesh, P("dp", "sp"))
    args = [jax.device_put(a, sharding) for a in (ids, labels, np.ones((B, T), bool))]
    text = step.lower(params, opt_state, *args).as_text()
    sizes = {
        int(np.prod([int(d) for d in dims.split("x")]))
        for dims in re.findall(r"tensor<(\d+(?:x\d+)*)x[a-z]\w*>", text)
    }
    assert N * CFG.hidden in sizes  # the pattern does read the program's arrays
    rows = {n // CFG.vocab_size for n in sizes if n % CFG.vocab_size == 0}
    # the embedding (hidden rows) and one tile of a shard's 64 positions; never
    # the logits of a shard's positions, let alone of the batch's
    assert rows and max(rows) <= max(CFG.hidden, head_tile(N // 4)) < N // 4, sorted(rows)
    # the all-positions step, lowered the same way, does hold them
    full = jax.jit(jax.grad(plain_loss)).lower(jax.device_get(params), *args).as_text()
    assert f"tensor<{B}x{T}x{CFG.vocab_size}xf32>" in full


def test_reader_gives_the_share_and_nothing_without_the_counter():
    path = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                        "benchmarks", "chip", "layer_metrics", "head_computed_share_pct.py")
    bench = os.path.dirname(os.path.dirname(path))
    sys.path.insert(0, bench)
    try:
        spec = importlib.util.spec_from_file_location("head_computed_share_pct", path)
        reader = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(reader)
    finally:
        sys.path.remove(bench)
    counters = {
        f'{HEAD_POSITIONS_FAMILY}{{kind="computed"}}': 340 * 1368.0,
        f'{HEAD_POSITIONS_FAMILY}{{kind="all"}}': 340 * 8192.0,
        'lakesoul_loader_rows_total{consumer="local"}': 21760.0,
    }
    assert reader.read({"counters": counters}) == pytest.approx(100 * 1368 / 8192)
    assert reader.read({"counters": {'lakesoul_loader_rows_total{consumer="local"}': 21760.0}}) is None
