"""Model + parallelism tests on the virtual 8-device CPU mesh: ring attention
correctness vs full attention, sharded BERT train step, ResNet/MLP steps,
and the driver entry points."""

import numpy as np
import jax
import jax.numpy as jnp
import optax
import pytest
from jax.sharding import NamedSharding, PartitionSpec as P

from lakesoul_tpu.models.bert import BertConfig, bert_forward, bert_mlm_loss, init_bert_params
from lakesoul_tpu.models.train import (
    make_bert_train_state,
    make_bert_train_step,
    make_mlp_train_step,
    make_resnet_train_step,
)
from lakesoul_tpu.parallel.mesh import make_mesh
from lakesoul_tpu.parallel.ring_attention import make_ring_attention, ring_attention


class TestMesh:
    def test_factorization(self):
        plan = make_mesh(jax.devices())
        assert plan.dp * plan.tp * plan.sp == 8
        assert plan.mesh.axis_names == ("dp", "tp", "sp", "pp", "ep")

    def test_explicit_axes(self):
        plan = make_mesh(jax.devices(), dp=2, tp=2, sp=2)
        assert (plan.dp, plan.tp, plan.sp) == (2, 2, 2)
        with pytest.raises(ValueError):
            make_mesh(jax.devices(), dp=3, tp=1, sp=1)


class TestRingAttention:
    def test_matches_full_attention(self):
        plan = make_mesh(jax.devices(), dp=1, tp=1, sp=8)
        B, H, T, D = 2, 4, 64, 16
        rng = np.random.default_rng(0)
        q = jnp.asarray(rng.normal(size=(B, H, T, D)), dtype=jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, H, T, D)), dtype=jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, H, T, D)), dtype=jnp.float32)
        mask = np.ones((B, T), dtype=bool)
        mask[:, -7:] = False  # padding on the tail
        mask = jnp.asarray(mask)

        # reference: plain softmax attention with masking
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
        s = jnp.where(mask[:, None, None, :], s, -1e30)
        expected = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)

        ring = make_ring_attention(plan.mesh)
        got = jax.jit(ring)(q, k, v, mask)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)

    def test_ring_respects_mask_fully_padded_shard(self):
        # one whole sequence shard masked out must not poison the softmax
        plan = make_mesh(jax.devices(), dp=1, tp=1, sp=8)
        B, H, T, D = 1, 2, 32, 8
        rng = np.random.default_rng(1)
        q = jnp.asarray(rng.normal(size=(B, H, T, D)), dtype=jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, H, T, D)), dtype=jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, H, T, D)), dtype=jnp.float32)
        mask = np.ones((B, T), dtype=bool)
        mask[:, T // 2 :] = False  # entire later shards padded
        ring = make_ring_attention(plan.mesh)
        got = np.asarray(jax.jit(ring)(q, k, v, jnp.asarray(mask)))
        assert np.isfinite(got).all()


class TestUlyssesAttention:
    def _qkvm(self, B, H, T, D, seed=0, pad=7):
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.normal(size=(B, H, T, D)), dtype=jnp.float32)
        k = jnp.asarray(rng.normal(size=(B, H, T, D)), dtype=jnp.float32)
        v = jnp.asarray(rng.normal(size=(B, H, T, D)), dtype=jnp.float32)
        mask = np.ones((B, T), dtype=bool)
        if pad:
            mask[:, -pad:] = False
        return q, k, v, jnp.asarray(mask)

    def test_matches_full_attention(self):
        from lakesoul_tpu.parallel.ulysses import make_ulysses_attention

        plan = make_mesh(jax.devices(), dp=1, tp=1, sp=8)
        B, H, T, D = 2, 8, 64, 16  # heads divisible by sp=8
        q, k, v, mask = self._qkvm(B, H, T, D)
        s = jnp.einsum("bhqd,bhkd->bhqk", q, k) / np.sqrt(D)
        s = jnp.where(mask[:, None, None, :], s, -1e30)
        expected = jnp.einsum("bhqk,bhkd->bhqd", jax.nn.softmax(s, -1), v)
        uly = make_ulysses_attention(plan.mesh)
        got = jax.jit(uly)(q, k, v, mask)
        np.testing.assert_allclose(np.asarray(got), np.asarray(expected), atol=2e-5)

    def test_matches_ring(self):
        from lakesoul_tpu.parallel.ulysses import make_ulysses_attention

        plan = make_mesh(jax.devices(), dp=2, tp=1, sp=4)
        B, H, T, D = 2, 4, 32, 8
        q, k, v, mask = self._qkvm(B, H, T, D, seed=2, pad=3)
        ring = jax.jit(make_ring_attention(plan.mesh))(q, k, v, mask)
        uly = jax.jit(make_ulysses_attention(plan.mesh))(q, k, v, mask)
        np.testing.assert_allclose(np.asarray(uly), np.asarray(ring), atol=2e-5)

    def test_bert_trains_with_ulysses(self):
        plan = make_mesh(jax.devices(), dp=2, tp=1, sp=4)
        cfg = BertConfig(vocab_size=128, hidden=64, layers=1, heads=4, ff=128, max_len=32)
        params, opt_state, tx, shardings = make_bert_train_state(cfg, plan, lr=5e-3)
        step = make_bert_train_step(cfg, plan, tx, shardings, sequence_parallel="ulysses")
        rng = np.random.default_rng(0)
        ids = jnp.asarray(rng.integers(0, 128, (4, 32)), dtype=jnp.int32)
        labels = jnp.where(ids % 5 == 0, ids, -100).astype(jnp.int32)
        mask = jnp.ones((4, 32), dtype=jnp.int32)
        losses = []
        for _ in range(6):
            params, opt_state, loss = step(params, opt_state, ids, labels, mask)
            losses.append(float(loss))
        assert losses[-1] < losses[0]


class TestBert:
    def test_forward_shapes_and_loss(self):
        cfg = BertConfig.tiny()
        params = init_bert_params(cfg, jax.random.key(0))
        ids = jnp.zeros((2, 16), dtype=jnp.int32)
        logits = jax.jit(lambda p, i: bert_forward(p, i, cfg=cfg))(params, ids)
        assert logits.shape == (2, 16, cfg.vocab_size)
        labels = jnp.full((2, 16), -100, dtype=jnp.int32)
        labels = labels.at[0, 3].set(7)
        loss = bert_mlm_loss(params, ids, labels, cfg=cfg)
        assert np.isfinite(float(loss))

    def test_sharded_train_step_runs_and_improves(self):
        plan = make_mesh(jax.devices(), dp=2, tp=2, sp=2)
        cfg = BertConfig(vocab_size=128, hidden=64, layers=2, heads=4, ff=128, max_len=32)
        params, opt_state, tx, shardings = make_bert_train_state(cfg, plan, lr=5e-3)
        step = make_bert_train_step(cfg, plan, tx, shardings)
        rng = np.random.default_rng(0)
        B, T = 4, 32
        sharding = NamedSharding(plan.mesh, P("dp", "sp"))
        ids = jax.device_put(rng.integers(0, 128, (B, T)).astype(np.int32), sharding)
        labels_np = np.full((B, T), -100, np.int32)
        labels_np[:, ::4] = rng.integers(0, 128, labels_np[:, ::4].shape)
        labels = jax.device_put(labels_np, sharding)
        mask = jax.device_put(np.ones((B, T), bool), sharding)
        losses = []
        for _ in range(5):
            params, opt_state, loss = step(params, opt_state, ids, labels, mask)
            losses.append(float(loss))
        assert all(np.isfinite(losses))
        assert losses[-1] < losses[0]  # optimizing

    def test_tp_params_actually_sharded(self):
        plan = make_mesh(jax.devices(), dp=2, tp=2, sp=2)
        cfg = BertConfig(vocab_size=64, hidden=64, layers=2, heads=4, ff=128, max_len=16)
        params, *_ = make_bert_train_state(cfg, plan)
        w1_sharding = params["layers"]["w1"].sharding
        assert w1_sharding.spec == P(None, None, "tp")


class TestDenseEncoderAndEpAxis:
    def test_encoder_returns_hidden_states_and_dense_gradient_leaves(self):
        """The encoder hands back one array, and the loss's gradient has the
        dense layer's leaves and no other on a dp2 x tp2 x sp2 plan."""
        from lakesoul_tpu.models.bert import bert_encode

        plan = make_mesh(jax.devices(), dp=2, tp=2, sp=2)
        cfg = BertConfig(vocab_size=64, hidden=64, layers=2, heads=4, ff=128,
                         max_len=16, dtype="float32")
        params, *_ = make_bert_train_state(cfg, plan)
        rng = np.random.default_rng(0)
        sharding = NamedSharding(plan.mesh, P("dp", "sp"))
        ids = jax.device_put(rng.integers(0, 64, (4, 16)).astype(np.int32), sharding)
        labels = jax.device_put(
            np.where(rng.random((4, 16)) < 0.5, np.asarray(ids), -100).astype(np.int32), sharding
        )
        x = jax.jit(lambda p, i: bert_encode(p, i, cfg=cfg))(params, ids)
        assert isinstance(x, jax.Array) and x.shape == (4, 16, 64)
        grads = jax.jit(jax.grad(
            lambda p: bert_mlm_loss(p, ids, labels, cfg=cfg, batch_sharding=sharding)
        ))(params)
        assert set(grads["layers"]) == {
            "wq", "wk", "wv", "wo", "ln1", "ln2", "w1", "w2", "b1", "b2",
        }
        assert jax.tree.structure(grads) == jax.tree.structure(params)
        assert all(np.isfinite(np.asarray(g)).all() for g in jax.tree.leaves(grads))

    def test_ep_axis_builds_and_the_lm_step_refuses_it(self):
        """Every mesh carries ``ep`` for the expert exchange to come (ROADMAP
        R3); until it is written the one step with experts runs on dp only."""
        from lakesoul_tpu.models.qwen3_next import Qwen3NextConfig
        from lakesoul_tpu.models.train import make_lm_train_state

        plan = make_mesh(jax.devices(), dp=2, tp=1, sp=1, ep=4)
        assert (plan.dp, plan.ep) == (2, 4)
        assert plan.mesh.shape["ep"] == 4
        # refused on the plan alone, before a weight of the published widths is made
        with pytest.raises(NotImplementedError, match="ep=4"):
            make_lm_train_state(Qwen3NextConfig(), plan)


class TestPipeline:
    def test_pipeline_primitive_stages_compose(self):
        # stage i adds 10^i: pipelined result must see every stage once, in
        # stage order, for every microbatch
        from lakesoul_tpu.parallel.pipeline import make_pipeline

        plan = make_mesh(jax.devices(), dp=1, tp=1, sp=1, pp=8)
        adds = jnp.asarray([[10.0**i] for i in range(8)])  # [pp, 1]

        def stage_fn(stage_params, inp):
            return {"x": inp["x"] + stage_params[0]}

        pipe = make_pipeline(plan.mesh, stage_fn)
        micro = {"x": jnp.zeros((5, 4))}  # 5 microbatches of 4
        out = jax.jit(lambda p, m: pipe(p, m))({"a": adds}["a"], micro)
        expected = np.full((5, 4), float(sum(10.0**i for i in range(8))))
        np.testing.assert_allclose(np.asarray(out["x"]), expected)

    def test_pipelined_bert_matches_dense_loss_and_trains(self):
        from lakesoul_tpu.models.train import (
            make_bert_pipeline_train_state,
            make_bert_pipeline_train_step,
        )

        plan = make_mesh(jax.devices(), dp=2, tp=1, sp=1, pp=4)
        cfg = BertConfig(vocab_size=128, hidden=32, layers=4, heads=4, ff=64,
                         max_len=16, dtype="float32")
        params, opt_state, tx, shardings = make_bert_pipeline_train_state(cfg, plan, lr=5e-3)
        # each stage's layer slice is sharded over pp
        assert params["layers"]["wq"].sharding.spec[0] == "pp"
        step = make_bert_pipeline_train_step(cfg, plan, tx, shardings, n_micro=4)
        rng = np.random.default_rng(0)
        B, T = 8, 16
        sharding = NamedSharding(plan.mesh, P("dp"))
        ids = jax.device_put(rng.integers(0, 128, (B, T)).astype(np.int32), sharding)
        labels_np = np.full((B, T), -100, np.int32)
        labels_np[:, ::2] = rng.integers(0, 128, labels_np[:, ::2].shape)
        labels = jax.device_put(labels_np, sharding)
        mask = jax.device_put(np.ones((B, T), np.int32), sharding)

        # the pipelined loss must equal the plain scan-encoder loss on the
        # SAME parameters (pipelining is an execution schedule, not a model)
        host_params = jax.device_get(params)
        ref = float(bert_mlm_loss(
            host_params, jax.device_get(ids), jax.device_get(labels),
            jax.device_get(mask).astype(bool), cfg=cfg,
        ))
        losses = []
        for _ in range(5):
            params, opt_state, loss = step(params, opt_state, ids, labels, mask)
            losses.append(float(loss))
        np.testing.assert_allclose(losses[0], ref, rtol=1e-4)
        assert losses[-1] < losses[0]


class TestOtherModels:
    def test_mlp_step(self):
        from lakesoul_tpu.models.mlp import init_mlp_params

        params = init_mlp_params(jax.random.key(0), 4)
        tx = optax.adam(1e-2)
        opt_state = tx.init(params)
        step, _ = make_mlp_train_step(tx)
        x = jnp.asarray(np.random.default_rng(0).normal(size=(32, 4)), dtype=jnp.float32)
        y = jnp.asarray(np.random.default_rng(1).integers(0, 2, 32), dtype=jnp.int32)
        params, opt_state, loss = step(params, opt_state, x, y)
        assert np.isfinite(float(loss))

    def test_resnet_tiny_step(self):
        from lakesoul_tpu.models.resnet import ResNetConfig, init_resnet_params

        cfg = ResNetConfig(num_classes=10, width=8, dtype="float32")
        params = init_resnet_params(cfg, jax.random.key(0))
        tx = optax.sgd(0.1)
        opt_state = tx.init(params)
        plan = make_mesh(jax.devices())
        step = make_resnet_train_step(cfg, tx, plan)
        rng = np.random.default_rng(0)
        images = jax.device_put(
            rng.normal(size=(8, 32, 32, 3)).astype(np.float32),
            NamedSharding(plan.mesh, P("dp")),
        )
        labels = jax.device_put(
            rng.integers(0, 10, 8).astype(np.int32), NamedSharding(plan.mesh, P("dp"))
        )
        params, opt_state, loss = step(params, opt_state, images, labels)
        assert np.isfinite(float(loss))


class TestGraftEntry:
    def test_dryrun_multichip_8(self):
        import __graft_entry__ as ge

        ge.dryrun_multichip(8)

    def test_entry_compiles_tiny(self):
        # full BERT-base compile on CPU is slow; check the traced shapes only
        import __graft_entry__ as ge

        fn, args = ge.entry()
        shape = jax.eval_shape(fn, *args)
        assert shape.shape == (8, 128, 30522)


class TestTrainCheckpoint:
    def test_save_restore_round_trip(self, tmp_path):
        import optax

        from lakesoul_tpu.models.checkpoint import TrainCheckpointer
        from lakesoul_tpu.models.mlp import init_mlp_params

        params = init_mlp_params(jax.random.key(0), 4)
        tx = optax.adam(1e-3)
        opt_state = tx.init(params)
        ckpt = TrainCheckpointer(str(tmp_path / "ck"), max_to_keep=2)
        try:
            ckpt.save(1, params, opt_state)
            bumped = jax.tree.map(lambda x: x + 1.0, params)
            ckpt.save(2, bumped, opt_state)
            assert ckpt.latest_step() == 2
            p2, o2, step = ckpt.restore_latest(like=(params, opt_state))
            assert step == 2
            np.testing.assert_allclose(
                np.asarray(p2[0]["w"]), np.asarray(bumped[0]["w"])
            )
        finally:
            ckpt.close()

    def test_restore_empty_raises(self, tmp_path):
        from lakesoul_tpu.models.checkpoint import TrainCheckpointer

        ckpt = TrainCheckpointer(str(tmp_path / "empty"))
        try:
            with pytest.raises(FileNotFoundError):
                ckpt.restore_latest()
        finally:
            ckpt.close()


class TestMultiHostDataPlane:
    """Multi-host read rehearsal (the reference fakes multi-node with many
    clients on one PG — SURVEY §4 takeaway): N simulated processes with
    independent catalogs over ONE shared metadata db + warehouse must
    partition the scan exactly and train to identical parameters."""

    def _mk_table(self, wh, rows=4000):
        import numpy as np
        import pyarrow as pa

        from lakesoul_tpu import LakeSoulCatalog

        catalog = LakeSoulCatalog(str(wh))
        schema = pa.schema([("id", pa.int64()), ("v", pa.float32())])
        t = catalog.create_table("mh", schema, primary_keys=["id"], hash_bucket_num=8)
        rng = np.random.default_rng(0)
        t.write_arrow(pa.table({
            "id": np.arange(rows, dtype=np.int64),
            "v": rng.normal(size=rows).astype(np.float32),
        }))
        t.upsert(pa.table({
            "id": rng.choice(rows, rows // 10, replace=False).astype(np.int64),
            "v": rng.normal(size=rows // 10).astype(np.float32),
        }))
        return t

    def test_auto_shard_partitions_exactly(self, tmp_warehouse, monkeypatch):
        import jax

        from lakesoul_tpu import LakeSoulCatalog

        t = self._mk_table(tmp_warehouse)
        world = 4
        all_ids = []
        per_rank_units = []
        for rank in range(world):
            # each "process" opens its own catalog against the shared store,
            # like separate TPU hosts would
            cat = LakeSoulCatalog(str(tmp_warehouse))
            monkeypatch.setattr(jax, "process_index", lambda r=rank: r)
            monkeypatch.setattr(jax, "process_count", lambda w=world: w)
            scan = cat.table("mh").scan().auto_shard()
            units = scan.scan_plan()
            per_rank_units.append({(u.partition_desc, u.bucket_id) for u in units})
            got = scan.to_arrow()
            all_ids.extend(got.column("id").to_pylist())
        # exact partition: no unit on two ranks, every row delivered once
        for a in range(world):
            for b in range(a + 1, world):
                assert not (per_rank_units[a] & per_rank_units[b])
        assert sorted(all_ids) == list(range(4000))

    def test_dp_training_consistent_across_hosts(self, tmp_warehouse, monkeypatch):
        """Each simulated host trains on its shard; psum-style averaging of
        grads (here: summing per-host losses) must see every row exactly
        once — the input-pipeline half of data parallelism."""
        import jax

        from lakesoul_tpu import LakeSoulCatalog

        t = self._mk_table(tmp_warehouse, rows=1000)
        world = 2
        total = 0.0
        rows_seen = 0
        for rank in range(world):
            cat = LakeSoulCatalog(str(tmp_warehouse))
            monkeypatch.setattr(jax, "process_index", lambda r=rank: r)
            monkeypatch.setattr(jax, "process_count", lambda w=world: w)
            for b in cat.table("mh").scan().auto_shard().batch_size(128).to_jax_iter(
                transform=lambda x: x, device_put=False, drop_remainder=False
            ):
                total += float(b["v"].sum())
                rows_seen += len(b["v"])
        assert rows_seen == 1000
        # equals the single-host sum over the same (merged) table
        expected = float(
            LakeSoulCatalog(str(tmp_warehouse)).table("mh").to_arrow().column("v").to_numpy().sum()
        )
        assert abs(total - expected) < 1e-2
