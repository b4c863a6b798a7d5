"""Sharded training steps.

Builds jitted train steps over a MeshPlan: parameters sharded per model rules
(tp), batches sharded over dp, sequence over sp (ring attention).  XLA/GSPMD
inserts all gradient psums and tensor-parallel collectives from the sharding
constraints — no hand-written collectives outside the ring-attention kernel.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from lakesoul_tpu.models.bert import (
    BertConfig,
    bert_mlm_loss,
    init_bert_params,
    param_sharding_rules,
)
from lakesoul_tpu.parallel.mesh import MeshPlan
from lakesoul_tpu.parallel.ring_attention import make_ring_attention


def _specs_to_shardings(mesh, rules):
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        rules,
        is_leaf=lambda x: isinstance(x, P),
    )


def _place_opt_state(opt_state, mesh):
    """Put every optimizer leaf on the mesh: zeros_like moments inherit their
    param's NamedSharding from ``tx.init``, but fresh scalars (adam's
    ``count``) land committed to a single device — mixing the two in one
    jitted step is rejected outright."""
    return jax.tree.map(
        lambda x: x
        if isinstance(x, jax.Array) and isinstance(x.sharding, NamedSharding)
        else jax.device_put(x, NamedSharding(mesh, P())),
        opt_state,
    )


def _jit_step_pinning_opt_shardings(step_fn, param_shardings, batch_shardings,
                                    loss_sharding):
    """jit a (params, opt_state, *batch) step with both donated carries pinned.

    opt_state is donated, and donation requires the output buffer to alias the
    input one exactly — but its leaves' shardings only exist on the concrete
    arrays ``tx.init`` built, not in any spec the factory could precompute.
    Leaving the output unspecified lets GSPMD re-shard a replicated leaf (the
    observed "aliased input/output size" failure), so the shardings are
    captured from the first call's arrays and pinned identically on input and
    output.

    The batch is placed on its pinned shardings before the call.  Under jax
    0.9 a batch the loader delivered uncommitted (``sharding=None``) and one
    delivered on the pinned sharding are different abstract values, and the
    step would trace and compile once for each; placing is free when the
    batch is already there."""
    box: dict = {}

    def call(params, opt_state, *batch):
        batch = jax.device_put(batch, batch_shardings)
        fn = box.get("fn")
        if fn is None:
            opt_shardings = jax.tree.map(
                lambda x: x.sharding if isinstance(x, jax.Array) else None,
                opt_state,
            )
            fn = box["fn"] = jax.jit(
                step_fn,
                in_shardings=(param_shardings, opt_shardings) + batch_shardings,
                out_shardings=(param_shardings, opt_shardings, loss_sharding),
                donate_argnums=(0, 1),
            )
        return fn(params, opt_state, *batch)

    return call


def make_bert_train_state(cfg: BertConfig, plan: MeshPlan, *, lr: float = 1e-4, seed: int = 0):
    """Initialize (params, opt_state) laid out on the mesh."""
    rules = param_sharding_rules(plan, n_experts=cfg.n_experts)
    shardings = _specs_to_shardings(plan.mesh, rules)
    init_fn = jax.jit(functools.partial(init_bert_params, cfg), out_shardings=shardings)
    params = init_fn(jax.random.key(seed))
    tx = optax.adamw(lr)
    # moments mirror param sharding via zeros_like; scalars get replicated
    opt_state = _place_opt_state(tx.init(params), plan.mesh)
    return params, opt_state, tx, shardings


def make_bert_train_step(
    cfg: BertConfig, plan: MeshPlan, tx, param_shardings, *,
    sequence_parallel: str = "ring",
):
    """Jitted MLM train step: (params, opt_state, input_ids, labels, mask) →
    (params, opt_state, loss).  Batch arrives sharded P('dp', 'sp').

    ``sequence_parallel`` picks the long-context strategy when sp > 1:
    "ring" (K/V rotation, O(T/sp) memory, extreme sequence lengths) or
    "ulysses" (two all-to-alls + one fused full attention, better MXU
    utilization when heads % sp == 0) — see parallel/ulysses.py for the
    trade-off."""
    if sequence_parallel not in ("ring", "ulysses"):
        # validate regardless of sp: a typo must fail on the dev box, not
        # first surface when the script scales onto an sp>1 mesh
        raise ValueError(
            f"unknown sequence_parallel {sequence_parallel!r} (ring|ulysses)"
        )
    attention_fn = None
    if plan.sp > 1:
        if sequence_parallel == "ring":
            attention_fn = make_ring_attention(plan.mesh)
        else:
            from lakesoul_tpu.parallel.ulysses import make_ulysses_attention

            attention_fn = make_ulysses_attention(plan.mesh)
    batch_sharding = NamedSharding(plan.mesh, P("dp", "sp"))
    loss_fn = functools.partial(
        bert_mlm_loss, cfg=cfg, attention_fn=attention_fn,
        # the ep constraint routes MoE dispatch over the expert axis; on an
        # ep=1 mesh it is skipped (nothing to route)
        moe_ep_sharding=(
            NamedSharding(plan.mesh, P("ep", None, None)) if plan.ep > 1 else None
        ),
    )

    def train_step(params, opt_state, input_ids, labels, mask):
        loss, grads = jax.value_and_grad(loss_fn)(params, input_ids, labels, mask)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return _jit_step_pinning_opt_shardings(
        train_step, param_shardings,
        (batch_sharding, batch_sharding, batch_sharding),
        NamedSharding(plan.mesh, P()),
    )


def make_bert_pipeline_train_state(cfg: BertConfig, plan: MeshPlan, *, lr: float = 1e-4, seed: int = 0):
    """(params, opt_state) for the PIPELINE layout: the stacked layer axis is
    sharded over 'pp' (each device materializes only its own stage's layers —
    the memory win pipelining exists for), everything else as usual."""
    if cfg.layers % max(plan.pp, 1):
        raise ValueError(f"{cfg.layers} layers do not split over pp={plan.pp}")
    if cfg.n_experts:
        # MoE composes with dp/tp/sp/ep meshes (make_bert_train_state); a
        # pipelined MoE stage would silently all-gather every expert into
        # every stage, so reject rather than run the degraded layout
        raise ValueError("pipeline layout does not support MoE configs")
    rules = param_sharding_rules(plan, n_experts=cfg.n_experts)
    for leaf in ("wq", "wk", "wv", "wo", "w1", "w2", "b1", "b2"):
        if leaf in rules["layers"]:
            spec = rules["layers"][leaf]
            rules["layers"][leaf] = P("pp", *spec[1:])
    for ln in ("ln1", "ln2"):
        rules["layers"][ln] = {"scale": P("pp", None), "bias": P("pp", None)}
    shardings = _specs_to_shardings(plan.mesh, rules)
    init_fn = jax.jit(functools.partial(init_bert_params, cfg), out_shardings=shardings)
    params = init_fn(jax.random.key(seed))
    tx = optax.adamw(lr)
    return params, _place_opt_state(tx.init(params), plan.mesh), tx, shardings


def make_bert_pipeline_train_step(
    cfg: BertConfig, plan: MeshPlan, tx, param_shardings, *, n_micro: int = 4,
):
    """Jitted MLM train step with the encoder pipelined over 'pp': embeddings
    and head run replicated; microbatches stream through the stage ring
    (parallel/pipeline.py) and autodiff through scan+ppermute is the reverse
    pipeline.  Batch arrives sharded P('dp') and is split into n_micro
    microbatches inside the step."""
    from lakesoul_tpu.models.bert import bert_embed, bert_head, bert_layer, masked_nll
    from lakesoul_tpu.parallel.pipeline import (
        make_pipeline,
        merge_microbatches,
        split_microbatches,
        split_stages,
    )

    pp = max(plan.pp, 1)

    def stage_fn(stage_layers, inp):
        def one(x, lp):
            x, _ = bert_layer(x, lp, inp["mask"] != 0, cfg=cfg, moe_ep_sharding=None)
            return x, None

        x, _ = jax.lax.scan(one, inp["x"], stage_layers)
        return {"x": x, "mask": inp["mask"]}

    # microbatch batch-dim stays data-parallel through the stage ring
    pipeline = make_pipeline(plan.mesh, stage_fn, micro_spec=P(None, "dp"))
    batch_sharding = NamedSharding(plan.mesh, P("dp"))

    def loss_fn(params, input_ids, labels, mask):
        B = input_ids.shape[0]
        x = bert_embed(params, input_ids, cfg=cfg)
        # mask rides the ring as int32: the collection psum over pp cannot
        # take booleans
        micro = split_microbatches({"x": x, "mask": mask.astype(jnp.int32)}, n_micro)
        stages = split_stages(params["layers"], pp)
        out = pipeline(stages, micro)
        x = merge_microbatches(out, B)["x"]
        return masked_nll(bert_head(params, x), labels)

    def train_step(params, opt_state, input_ids, labels, mask):
        loss, grads = jax.value_and_grad(loss_fn)(params, input_ids, labels, mask)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return _jit_step_pinning_opt_shardings(
        train_step, param_shardings,
        (batch_sharding, batch_sharding, batch_sharding),
        NamedSharding(plan.mesh, P()),
    )


def make_mlp_train_step(tx, mesh=None):
    """Data-parallel MLP step for tabular pipelines (Titanic config)."""
    from lakesoul_tpu.models.mlp import mlp_loss

    batch_sharding = (
        NamedSharding(mesh, P("dp")) if mesh is not None and "dp" in mesh.axis_names else None
    )

    @functools.partial(jax.jit, donate_argnums=(0, 1))
    def step(params, opt_state, x, y):
        loss, grads = jax.value_and_grad(mlp_loss)(params, x, y)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return step, batch_sharding


def make_resnet_train_step(cfg, tx, plan: MeshPlan | None = None):
    """Data-parallel ResNet step (ImageNet config): batch over dp, params
    replicated."""
    from lakesoul_tpu.models.resnet import resnet_loss

    kwargs = {}
    if plan is not None:
        kwargs = dict(
            in_shardings=(
                NamedSharding(plan.mesh, P()),
                None,
                NamedSharding(plan.mesh, P("dp")),
                NamedSharding(plan.mesh, P("dp")),
            ),
        )

    @functools.partial(jax.jit, donate_argnums=(0, 1), **kwargs)
    def step(params, opt_state, images, labels):
        loss, grads = jax.value_and_grad(
            lambda p, x, y: resnet_loss(p, x, y, cfg=cfg)
        )(params, images, labels)
        updates, opt_state = tx.update(grads, opt_state, params)
        params = optax.apply_updates(params, updates)
        return params, opt_state, loss

    return step
