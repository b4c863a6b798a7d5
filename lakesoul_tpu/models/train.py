"""Sharded training steps.

Builds jitted train steps over a MeshPlan: parameters sharded per model rules
(tp), batches sharded over dp, sequence over sp (ring attention).  XLA/GSPMD
inserts all gradient psums and tensor-parallel collectives from the sharding
constraints — no hand-written collectives outside the ring-attention kernel.
"""

from __future__ import annotations

import functools
import threading
import weakref

import jax
import jax.numpy as jnp
import numpy as np
import optax
from jax.sharding import NamedSharding, PartitionSpec as P

from lakesoul_tpu.models.bert import (
    BertConfig,
    bert_mlm_loss,
    init_bert_params,
    param_sharding_rules,
)
from lakesoul_tpu.obs import registry, stage
from lakesoul_tpu.parallel.mesh import MeshPlan
from lakesoul_tpu.parallel.ring_attention import make_ring_attention


def _specs_to_shardings(mesh, rules):
    return jax.tree.map(
        lambda spec: NamedSharding(mesh, spec),
        rules,
        is_leaf=lambda x: isinstance(x, P),
    )


BUFFERS = "buffers"  # the subtree of a model's parameters that is carried, not trained
# the optimizer's update of every trained leaf and the step's own counts: what a step runs after its gradients
OPTIM_SCOPE = "lakesoul.lm.optim"


def _split_buffers(params: dict) -> tuple[dict, dict]:
    """(what the optimizer trains, what it must not touch): ``params[BUFFERS]``,
    where a model has it, is state its forward pass reads and no gradient, no
    moment and no weight decay ever reaches (a routing bias)."""
    return ({k: v for k, v in params.items() if k != BUFFERS},
            {k: v for k, v in params.items() if k == BUFFERS})


def _init_train_state(init_params, mesh, shardings, lr: float, seed: int):
    """(params, opt_state, tx, shardings) for AdamW at ``lr``, the weights
    being ``init_params(key)``: params and optimizer state come from the seed
    as ONE jitted program that leaves every leaf on the mesh: an optimizer
    leaf that mirrors a parameter (adam's moments; its tree path ends in the
    parameter's) takes that parameter's sharding, any other (adam's
    ``count``) is replicated.  Built eagerly, ``jax.random.key``,
    ``tx.init``'s zeros and the placement of its scalars were a dozen small
    programs, each lowered and looked up in the compile cache on every
    start."""
    tx = optax.adamw(lr)

    def init(seed):
        params = init_params(jax.random.key(seed))
        return params, tx.init(_split_buffers(params)[0])

    by_path = dict(jax.tree_util.tree_leaves_with_path(shardings))
    replicated = NamedSharding(mesh, P())
    opt_shardings = jax.tree_util.tree_map_with_path(
        lambda path, _: next(
            (by_path[path[i:]] for i in range(len(path)) if path[i:] in by_path), replicated
        ),
        jax.eval_shape(init, np.uint32(0))[1],
    )
    # the low 32 bits, as ``jax.random.key`` takes of a Python int
    params, opt_state = jax.jit(init, out_shardings=(shardings, opt_shardings))(
        np.uint32(seed & 0xFFFFFFFF)
    )
    return params, opt_state, tx, shardings


# ``{kind="all"}`` is the denominator of whichever step feeds the family, and the two kinds of step
# count it differently: the MLM step counts every position of the batch, labelled or not, beside
# ``{kind="computed"}``, the positions its head ran over; an LM step counts the positions that carry a
# label, summed over every loss term it adds (next token, and the prediction module's where there is
# one; in a looped model every pass's loss), beside ``{kind="mtp"}``, the module's term alone, and
# ``{kind="loop"}``, the losses of a looped model's passes before the last.  A process that runs both kinds
# sums both.
HEAD_POSITIONS_FAMILY = "lakesoul_train_head_positions_total"
TOKENS_FAMILY = "lakesoul_train_tokens_total"
MOE_ASSIGNMENTS_FAMILY = "lakesoul_train_moe_assignments_total"
MOE_LOAD_FAMILY = "lakesoul_train_moe_expert_load"
ATTN_KEY_TILES_FAMILY = "lakesoul_train_attn_key_tiles_total"
# of ``{kind="run"}`` above, the steps of differential attention's calls (``models/attention.py: paired_attention``),
# ``{kind="run"}``, over ``{kind="required"}``, the steps two score maps a head pair require: equal where every map is
# computed once; host integers, 0 for a family without pairs
ATTN_PAIR_TILES_FAMILY = "lakesoul_train_attn_pair_key_tiles_total"
ATTN_OPERAND_ROWS_FAMILY = "lakesoul_train_attn_operand_rows_total"
ATTN_OUTPUT_ROWS_FAMILY = "lakesoul_train_attn_output_rows_total"
# the rows an LM step hands the head's tile loop for loss and gradients (``models/head_loss.py: labelled_nll``; every loss
# the step sums), by the body that runs their tiles: ``{body="fused"}`` ``models/loss_tile.py: fused_tile``'s one
# kernel between the logits and their cotangent, ``{body="compiler"}`` a float32 log-softmax and autodiff (a tile smaller
# than any the kernel is measured at); host integers, no operation of the step.  The MLM steps pass no body and feed no series
LOSS_ROWS_FAMILY = "lakesoul_train_loss_rows_total"
# a looped model's step (``cfg.loop_passes``): ``{kind="run"}`` the layer passes it ran, rows x layers x
# passes, over ``{kind="layers"}``, rows x layers: a change that skips a pass or exits early moves the ratio
LOOP_LAYER_PASSES_FAMILY = "lakesoul_train_loop_layer_passes_total"
# a gauge, ``{pass="1".."R"}``: the mean share of the exit distribution on each pass over the labelled
# positions of every step so far (``models/causal_lm.py: exit_loss``); the ``R`` series sum to 1
LOOP_EXIT_MASS_FAMILY = "lakesoul_train_loop_exit_mass"

# an LM step's selective-scan rows (``models/selective_scan.py``; rows x the layers that scan), by what ran them:
# ``{path="kernel"}`` the Pallas pair, ``{path="twin"}`` the ``lax.scan``; host integers, the shapes decide
SSM_SCAN_ROWS_FAMILY = "lakesoul_train_ssm_scan_rows_total"
# the rows of the layers whose mixer read a state an EARLIER layer published (``models/causal_lm.py: lm_layer``,
# ``cfg.shares``); host integers
SHARED_READS_FAMILY = "lakesoul_train_shared_state_reads_total"

# live steps, and what the collected ones had counted: the families are
# counters and must not fall when a step is dropped
_live_steps: "weakref.WeakSet[_CountedStep]" = weakref.WeakSet()
_retired: dict[tuple, float] = {}  # (family, labels as sorted items) -> value
# re-entrant: a finalizer can run wherever this thread allocates, the collector included
_retired_lock = threading.RLock()


def _counts(state: dict) -> dict:
    # one copy to the host, which waits for the last step dispatched
    limbs = np.asarray(state["counted"])
    counted = {key: (int(high) << 30) + int(low) for key, (high, low) in zip(state["keys"], limbs)}
    return {**counted, **state["host"]}


def _series_values(state: dict) -> dict[tuple, np.ndarray]:
    """{(family, labels, whether a gauge): (the count x its scale, the count
    it is over)}; a counter is over 1."""
    counts = _counts(state)
    return {
        (family, tuple(sorted(labels.items())), bool(over)):
            np.array([counts[key] * scale, counts[over[0]] if over else 1.0])
        for key, family, labels, scale, *over in state["series"]
    }


def _retire(state: dict) -> None:
    values = _series_values(state)
    with _retired_lock:
        for series, n in values.items():
            _retired[series] = _retired.get(series, 0) + n


def _collect_step_counts() -> list:
    with _retired_lock:
        totals = dict(_retired)
    for step in list(_live_steps):
        for series, n in _series_values(step._state).items():
            totals[series] = totals.get(series, 0) + n
    # a gauge is a ratio of two sums over every step, live or collected
    return [
        (family, "gauge", n / max(over, 1.0), dict(labels)) if gauge else (family, "counter", n, dict(labels))
        for (family, labels, gauge), (n, over) in totals.items()
    ]


class _CountedStep:
    """A jitted ``(params, opt_state, *batch)`` step with both donated carries
    pinned, that counts what its loss says it did.

    opt_state is donated, and donation requires the output buffer to alias the
    input one exactly — but its leaves' shardings only exist on the concrete
    arrays the caller holds (built here, or restored), not in any spec the
    factory is given.
    Leaving the output unspecified lets GSPMD re-shard a replicated leaf (the
    observed "aliased input/output size" failure), so the shardings are
    captured from the first call's arrays and pinned identically on input and
    output.

    The batch is placed on its pinned shardings before the call.  Under jax
    0.9 a batch the loader delivered uncommitted (``sharding=None``) and one
    delivered on the pinned sharding are different abstract values, and the
    step would trace and compile once for each; placing is free when the
    batch is already there.

    ``step_fn`` returns ``(params, opt_state, loss, counts)``, ``counts`` a
    small dict of int32 scalars below 2**30 (positions the head ran at,
    assignments that landed on held experts, ...).  They depend on the data,
    so the step adds each to a count that stays on the device beside the
    carries (two int32 limbs of 30 bits) and is read only when the registry is
    scraped: the step loop reads nothing from the device for them.  ``series``
    says which registry series a count feeds: ``(count key, family, labels,
    scale)``, the series' value being the count times ``scale``; a fifth
    entry, another count's key, makes the series a gauge: the scaled count
    over that count.  The
    ``host_keys`` among the counts are Python integers that do not depend on
    the data (what the shapes make the kernels' grids): known when the step is
    traced, no operation of the program, and added on the host a call; one a
    loss does not return counts 0."""

    def __init__(self, step_fn, param_shardings, batch_shardings, loss_sharding, series, host_keys=()):
        self._step_fn = step_fn
        self._param_shardings = param_shardings
        self._batch_shardings = batch_shardings
        self._replicated = loss_sharding
        self._fn = None
        keys = tuple(sorted({key for entry in series for key in (entry[0], *entry[4:])} - set(host_keys)))
        self._state = {
            "counted": jax.device_put(np.zeros((len(keys), 2), np.int32), loss_sharding),
            "keys": keys, "series": tuple(series),
            "host": dict.fromkeys(host_keys, 0), "a_step": dict.fromkeys(host_keys, 0),
        }
        _live_steps.add(self)
        # the finalizer holds the state, not the step; not at exit, when the
        # device may be gone
        weakref.finalize(self, _retire, self._state).atexit = False
        registry().register_collector(_collect_step_counts)  # idempotent

    def _jitted(self, opt_state):
        if self._fn is None:
            opt_shardings = jax.tree.map(
                lambda x: x.sharding if isinstance(x, jax.Array) else None,
                opt_state,
            )
            step_fn = self._step_fn

            keys = self._state["keys"]

            a_step = self._state["a_step"]

            def train_step(params, opt_state, counted, *batch):
                params, opt_state, loss, counts = step_fn(params, opt_state, *batch)
                a_step.update({key: int(counts.get(key, 0)) for key in a_step})  # while tracing: one batch shape a step
                with jax.named_scope(OPTIM_SCOPE):
                    low = counted[:, 1] + jnp.stack([counts[key] for key in keys]).astype(jnp.int32)
                    counted = jnp.stack([counted[:, 0] + (low >> 30), low & ((1 << 30) - 1)], axis=1)
                return params, opt_state, loss, counted

            carries = (self._param_shardings, opt_shardings)
            self._fn = jax.jit(
                train_step,
                in_shardings=carries + (self._replicated,) + self._batch_shardings,
                out_shardings=carries + (self._replicated, self._replicated),
                donate_argnums=(0, 1),
            )
        return self._fn

    def __call__(self, params, opt_state, *batch):
        with stage("train.place"):
            batch = jax.device_put(batch, self._batch_shardings)
        state = self._state
        with stage("train.dispatch"):
            params, opt_state, loss, state["counted"] = self._jitted(opt_state)(
                params, opt_state, state["counted"], *batch
            )
        for key, n in state["a_step"].items():
            state["host"][key] += n
        return params, opt_state, loss

    def lower(self, params, opt_state, *batch, lowering_platforms=None):
        """The step lowered for these arguments, as ``jax.jit(...).lower``
        (for ``lowering_platforms`` where given: ``("tpu",)`` on a host)."""
        traced = self._jitted(opt_state).trace(params, opt_state, self._state["counted"], *batch)
        return traced.lower(lowering_platforms=lowering_platforms)

    def counts(self) -> dict:
        """{count key: total} over every step dispatched so far."""
        return _counts(self._state)


def make_bert_train_state(cfg: BertConfig, plan: MeshPlan, *, lr: float = 1e-4, seed: int = 0):
    """Initialize (params, opt_state) laid out on the mesh."""
    rules = param_sharding_rules(plan)
    return _init_train_state(
        functools.partial(init_bert_params, cfg), plan.mesh,
        _specs_to_shardings(plan.mesh, rules), lr, seed,
    )


# every position of the batches, and those the MLM head ran at
_HEAD_SERIES = (
    ("computed", HEAD_POSITIONS_FAMILY, {"kind": "computed"}, 1),
    ("all", HEAD_POSITIONS_FAMILY, {"kind": "all"}, 1),
)


def _with_head_counts(loss_fn):
    """``loss_fn → (loss, positions the head ran at)`` as ``→ (loss, counts)``."""

    def counted(params, input_ids, labels, *rest):
        loss, positions = loss_fn(params, input_ids, labels, *rest)
        return loss, {"computed": positions, "all": jnp.int32(labels.size)}

    return counted


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
        batch_sharding=batch_sharding, with_head_positions=True,
    )
    return _CountedStep(
        _adamw_step(_with_head_counts(loss_fn), tx), param_shardings,
        (batch_sharding, batch_sharding, batch_sharding),
        NamedSharding(plan.mesh, P()), _HEAD_SERIES,
    )


def _adamw_step(loss_fn, tx):
    """``loss_fn(params, *batch) → (loss, counts)`` as one optimizer step →
    (params, opt_state, loss, counts).  ``params[BUFFERS]`` is outside the
    gradient and the optimizer and comes back as it went in."""

    def step(params, opt_state, *batch):
        trained, carried = _split_buffers(params)
        (loss, counts), grads = jax.value_and_grad(
            lambda trained: loss_fn({**trained, **carried}, *batch), has_aux=True
        )(trained)
        with jax.named_scope(OPTIM_SCOPE):
            updates, opt_state = tx.update(grads, opt_state, trained)
            trained = optax.apply_updates(trained, updates)
        return {**trained, **carried}, opt_state, loss, counts

    return step


def _lm_plan(plan: MeshPlan) -> None:
    if plan.tp * plan.sp * plan.pp * plan.ep != 1:
        # the expert exchange over ep, and the mixers over tp or sp, are not written
        raise NotImplementedError(
            f"the causal-LM step runs on dp only; got tp={plan.tp} sp={plan.sp} pp={plan.pp} ep={plan.ep}"
        )


def make_lm_train_state(cfg, plan: MeshPlan, *, lr: float = 1e-4, seed: int = 0):
    """(params, opt_state, tx, shardings) of the causal LM ``cfg`` describes,
    every leaf replicated over the mesh.  ``cfg`` is a family's configuration
    object (``models/causal_lm.py`` says what one offers): its ``init(key)``
    makes the weights, and the buffers where the family has any."""
    _lm_plan(plan)
    shardings = jax.tree.map(lambda _: NamedSharding(plan.mesh, P()), jax.eval_shape(cfg.init, jax.random.key(0)))
    return _init_train_state(cfg.init, plan.mesh, shardings, lr, seed)


def make_lm_train_step(cfg, plan: MeshPlan, tx, param_shardings):
    """Jitted next-token train step of whichever family ``cfg`` is (its
    ``loss``): (params, opt_state, input_ids, labels) → (params, opt_state,
    loss); rows arrive sharded P('dp').  What it feeds, by where the count is
    made (the families' comments above say what ``{kind=}`` means where two
    kinds of step share one):

    - ``lakesoul_train_tokens_total`` and ``lakesoul_train_head_positions_total
      {kind="all"|"mtp"|"loop"}``: ``models/causal_lm.py: lm_loss``,
      ``exit_loss`` (0 for a family without a prediction module, without a
      loop);
    - ``lakesoul_train_moe_assignments_total{kind="held"|"all"|"tile_rows"|
      "grouped"|"bias_moved"|"dw_writes"}`` and
      ``lakesoul_train_moe_expert_load{stat="max"|"mean"}`` (``tile_rows``:
      the slots moved and multiplied forward, whole tiles in the tile loop and
      the row blocks up to an expert's last row in the grouped kernels, of
      which ``held`` carried an assignment; ``grouped``: the held assignments
      whose products ran in the grouped kernels and not in the tile loop;
      ``bias_moved``: the assignments
      whose expert a routing bias brought into the top k; ``dw_writes``: the
      times the backward pass writes an expert's weight-gradient sum, for one
      of the three matrices; the load of the fullest and of the mean held
      expert, summed over steps and layers): ``parallel/moe.py: held_experts``
      and the family's ``route``, counted on the device; host zeros for a
      family without experts;
    - ``lakesoul_train_attn_key_tiles_total{kind="run"|"causal"}``,
      ``lakesoul_train_attn_pair_key_tiles_total{kind="run"|"required"}``,
      ``lakesoul_train_attn_operand_rows_total{path="kernel"|"xla"}`` and
      ``lakesoul_train_attn_output_rows_total{layout="tokens"|"heads"}``:
      ``models/attention.py: mixer_counts``, Python integers known when the
      step is traced, added on the host a call and no operation of the step;
    - ``lakesoul_train_loss_rows_total{body="fused"|"compiler"}``:
      ``models/causal_lm.py: _head_nll``, by the same route;
    - of a looped family (``cfg.loop_passes``) also
      ``lakesoul_train_loop_layer_passes_total{kind="run"|"layers"}``
      (``causal_lm.py: loop_hidden``, host integers) and the gauge
      ``lakesoul_train_loop_exit_mass{pass="1".."R"}`` (``exit_loss``);
    - ``lakesoul_train_ssm_scan_rows_total{path="kernel"|"twin"}`` (a family
      with a selective scan: its ``loss``) and
      ``lakesoul_train_shared_state_reads_total`` (a family whose layers read
      what earlier ones published: ``causal_lm.py: _attention_counts``): host
      integers, 0 for every other family."""
    _lm_plan(plan)
    batch_sharding = NamedSharding(plan.mesh, P("dp"))
    loss_fn = functools.partial(cfg.loss, batch_sharding=batch_sharding if plan.dp > 1 else None)
    host_keys = ("attn_tiles_run", "attn_tiles_causal", "attn_pair_tiles_run", "attn_pair_tiles",
                 "attn_out_tokens", "attn_out_heads",
                 "attn_operands_kernel", "attn_operands_xla", "loop_layers_run", "loop_layers",
                 "loss_rows_fused", "loss_rows_compiler", "ssm_rows_kernel", "ssm_rows_twin", "shared_reads")
    held = getattr(cfg, "experts_held", None)
    if held is None:  # a family without experts: its loss returns none of their counts, and they count 0
        host_keys += ("moe_held", "moe_all", "moe_tile_rows", "moe_grouped", "moe_bias_moved", "moe_dw_writes",
                      "moe_load_max")
    passes = getattr(cfg, "loop_passes", None)
    if passes is None:
        host_keys += ("head_loop",)  # no operation of a step that does not loop: its programs stay as they are
        loop_series = ()
    else:
        from lakesoul_tpu.models.causal_lm import EXIT_MASS_UNIT

        loop_series = tuple(
            (f"exit_mass_{t}", LOOP_EXIT_MASS_FAMILY, {"pass": str(t + 1)}, passes / EXIT_MASS_UNIT, "head_all")
            for t in range(passes)
        )
    series = (
        ("tokens", TOKENS_FAMILY, {}, 1),
        ("head_mtp", HEAD_POSITIONS_FAMILY, {"kind": "mtp"}, 1),
        ("head_all", HEAD_POSITIONS_FAMILY, {"kind": "all"}, 1),
        ("moe_held", MOE_ASSIGNMENTS_FAMILY, {"kind": "held"}, 1),
        ("moe_all", MOE_ASSIGNMENTS_FAMILY, {"kind": "all"}, 1),
        ("moe_tile_rows", MOE_ASSIGNMENTS_FAMILY, {"kind": "tile_rows"}, 1),
        ("moe_grouped", MOE_ASSIGNMENTS_FAMILY, {"kind": "grouped"}, 1),
        ("moe_bias_moved", MOE_ASSIGNMENTS_FAMILY, {"kind": "bias_moved"}, 1),
        ("moe_dw_writes", MOE_ASSIGNMENTS_FAMILY, {"kind": "dw_writes"}, 1),
        ("moe_load_max", MOE_LOAD_FAMILY, {"stat": "max"}, 1),
        ("moe_held", MOE_LOAD_FAMILY, {"stat": "mean"}, 1.0 / held[1] if held else 0),
        ("attn_tiles_run", ATTN_KEY_TILES_FAMILY, {"kind": "run"}, 1),
        ("attn_tiles_causal", ATTN_KEY_TILES_FAMILY, {"kind": "causal"}, 1),
        ("attn_pair_tiles_run", ATTN_PAIR_TILES_FAMILY, {"kind": "run"}, 1),
        ("attn_pair_tiles", ATTN_PAIR_TILES_FAMILY, {"kind": "required"}, 1),
        ("attn_out_tokens", ATTN_OUTPUT_ROWS_FAMILY, {"layout": "tokens"}, 1),
        ("attn_out_heads", ATTN_OUTPUT_ROWS_FAMILY, {"layout": "heads"}, 1),
        ("attn_operands_kernel", ATTN_OPERAND_ROWS_FAMILY, {"path": "kernel"}, 1),
        ("attn_operands_xla", ATTN_OPERAND_ROWS_FAMILY, {"path": "xla"}, 1),
        ("loss_rows_fused", LOSS_ROWS_FAMILY, {"body": "fused"}, 1),
        ("loss_rows_compiler", LOSS_ROWS_FAMILY, {"body": "compiler"}, 1),
        ("head_loop", HEAD_POSITIONS_FAMILY, {"kind": "loop"}, 1),
        ("loop_layers_run", LOOP_LAYER_PASSES_FAMILY, {"kind": "run"}, 1),
        ("loop_layers", LOOP_LAYER_PASSES_FAMILY, {"kind": "layers"}, 1),
        ("ssm_rows_kernel", SSM_SCAN_ROWS_FAMILY, {"path": "kernel"}, 1),
        ("ssm_rows_twin", SSM_SCAN_ROWS_FAMILY, {"path": "twin"}, 1),
        ("shared_reads", SHARED_READS_FAMILY, {}, 1),
        *loop_series,
    )
    return _CountedStep(
        _adamw_step(loss_fn, tx), param_shardings, (batch_sharding, batch_sharding),
        NamedSharding(plan.mesh, P()), series, host_keys=host_keys,
    )


def make_bert_pipeline_train_state(cfg: BertConfig, plan: MeshPlan, *, lr: float = 1e-4, seed: int = 0):
    """(params, opt_state) for the PIPELINE layout: the stacked layer axis is
    sharded over 'pp' (each device materializes only its own stage's layers —
    the memory win pipelining exists for), everything else as usual."""
    if cfg.layers % max(plan.pp, 1):
        raise ValueError(f"{cfg.layers} layers do not split over pp={plan.pp}")
    rules = param_sharding_rules(plan)
    for leaf in ("wq", "wk", "wv", "wo", "w1", "w2", "b1", "b2"):
        spec = rules["layers"][leaf]
        rules["layers"][leaf] = P("pp", *spec[1:])
    for ln in ("ln1", "ln2"):
        rules["layers"][ln] = {"scale": P("pp", None), "bias": P("pp", None)}
    return _init_train_state(
        functools.partial(init_bert_params, cfg), plan.mesh,
        _specs_to_shardings(plan.mesh, rules), lr, seed,
    )


def make_bert_pipeline_train_step(
    cfg: BertConfig, plan: MeshPlan, tx, param_shardings, *, n_micro: int = 4,
):
    """Jitted MLM train step with the encoder pipelined over 'pp': embeddings
    and head run replicated; microbatches stream through the stage ring
    (parallel/pipeline.py) and autodiff through scan+ppermute is the reverse
    pipeline.  Batch arrives sharded P('dp') and is split into n_micro
    microbatches inside the step."""
    from lakesoul_tpu.models.bert import bert_embed, bert_layer, mlm_head_loss
    from lakesoul_tpu.parallel.pipeline import (
        make_pipeline,
        merge_microbatches,
        split_microbatches,
        split_stages,
    )

    pp = max(plan.pp, 1)

    def stage_fn(stage_layers, inp):
        def one(x, lp):
            return bert_layer(x, lp, inp["mask"] != 0, cfg=cfg), None

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
        return mlm_head_loss(params, x, labels, batch_sharding=batch_sharding)

    return _CountedStep(
        _adamw_step(_with_head_counts(loss_fn), tx), param_shardings,
        (batch_sharding, batch_sharding, batch_sharding),
        NamedSharding(plan.mesh, P()), _HEAD_SERIES,
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
