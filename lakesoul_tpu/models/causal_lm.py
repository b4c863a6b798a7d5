"""What the causal-LM families share: the layer stack, the head and the loss,
and the pieces more than one family's mixers are made of.

A family is a module with a configuration object (``models/qwen3_next.py``,
``models/lfm2_moe.py``, ``models/glm4_moe_lite.py``, ``models/afmoe.py``,
``models/ouro.py``, ``models/phi4flash.py``); nothing here or in
``models/train.py`` names one.  The stack reads a layer's kinds from the
configuration and asks it for the rest:

- ``layer_kinds()``: each layer's mixer (``"gdn"``, ``"attn"``, ``"conv"``,
  ``"mla"``: latent attention, :func:`latent_attention`; ``"swa"``:
  :func:`softmax_attention` under a window), which is also the
  key of the mixer's weights in the layer;
- ``ffn_kinds()``: each layer's feed-forward, ``"dense"`` (SwiGLU, weights
  under ``"mlp"``) or ``"moe"`` (routed experts, under ``"moe"``);
- ``mixer(kind)`` → (``fn(y, p)`` on the normed input, the ``named_scope`` its
  device time is charged to);
- ``shares(kind)``, where the family has it: None for a mixer of its own
  input alone, else ``(name, make)``: the mixer reads a SHARED STATE and is
  ``fn(y, p, state)``.  ``make(y, p)`` computes that state from the layer's
  own normed input and publishes it under ``name`` for the layers after it
  (a source); ``make`` None: the layer reads what the nearest layer before it
  published under ``name`` (:func:`lm_layer`).  ``kept``, where the family
  has it: the ``checkpoint_name``s of what its own kernels keep of a row for
  the backward pass, beside the attention kernels' two;
- ``norm(x, w)``: the family's RMS norm, float32 out;
- ``route(y32, router, bias)`` → (experts, weights, assignments the bias
  moved): its routing rule over ``parallel/moe.py``;
- ``num_experts``, ``experts_held``, ``dtype``; and for ``models/train.py``
  ``init(key)`` and ``loss(params, ids, labels, batch_sharding=)``;
- ``mtp_loss_weight``, read only where the weights hold a prediction module;
  ``embed_scale``, where the family has one: what the embedding's output is
  multiplied by;
- ``loop_passes``, where the family has it: how many times the stack runs
  over its ONE set of weights (a looped model; :func:`loop_hidden`), with
  ``exit_beta``, the weight of the entropy term in its objective
  (:func:`exit_loss`); ``models/train.py`` reads ``loop_passes`` too, for the
  series a looped step feeds.  A family without it is walked once, by the
  plain Python loop (:func:`lm_hidden`), and its step's program does not
  change by what a looped family needs.

A looped family's step (:func:`loop_loss`): the walk through the layers
``loop_passes`` times as one ``lax.scan`` whose body ends in the final norm
(the normed state is what the next pass starts from and what the head reads),
the passes' states stacked; then ONE call of the head and the loss's tile loop
over the stacked states with a weight a position (``models/head_loss.py:
labelled_nll``'s weighted form: the exit distribution), the exit gate and the
expected loss under :data:`EXIT_SCOPE`.  ``params["exit"]`` holds the gate
(``w`` [h], ``b``): ordinary trained leaves.

The loss (:func:`lm_loss`) is the next-token cross-entropy, and where the
weights hold a multi-token-prediction module (``params["mtp"]``) that module's
loss times ``cfg.mtp_loss_weight`` on top (:func:`mtp_loss`): the final hidden
states and the next token's embedding through one more layer of the stack's
last kind, with its own weights, to the token after next, through the main
model's embedding and head matrix.  The head then runs twice a step.  Every
loss here goes through ``models/head_loss.py: labelled_nll``'s tile loop and
hands it ``models/loss_tile.py: fused_tile`` as the tile's body
(:func:`_head_nll`); both are described where they live.

A layer is ``h = x + mixer(norm1(x)); x' = h + ffn(norm2(h))``, and where its
weights hold ``norm1_out`` and ``norm2_out`` it has four norms, each
sublayer's output normed before it is added: ``h = x + norm1_out(mixer(
norm1(x))); x' = h + norm2_out(ffn(norm2(h)))``, the last over the routed
experts' share and the shared expert summed.  After the last layer
a final norm and the head: ``params["head"]`` [h, vocab] where there is one,
else the embedding (a tied head).  ``params["buffers"]``, where a family has
it, is state that no gradient and no optimizer touches (``models/train.py:
_adamw_step``); ``buffers["layers"][i]`` belongs to layer ``i`` (what it holds
under ``"mixer"`` reaches the layer's mixer beside its weights) and
``buffers["mtp"]`` to the prediction module's layer.

A layer whose mixer shares a state runs two row loops where the others run
one: the first, where the layer is a source, makes the state a row at a time;
the second hands each row of the layer's input and of the state to the mixer.
The state is the first loop's result and the second's argument, so a source is
computed once in the forward pass, what it publishes is what the backward pass
keeps of it (the second loop's checkpoint keeps its arguments: the same
array, no second copy), and the cotangent of the state adds up over every
layer that read it before the first loop's transpose takes it.  A family that
shares nothing walks the one loop, and its step's program does not change by
what a sharing family needs.

A mixer's softmax attention is ``models/attention.py``'s:
:func:`softmax_attention` and :func:`latent_attention` make a layer's raw
query, key and value and hand them over (``attention_operands``,
``causal_attention``); which kernel a shape gets, the kernels, their layouts
and their ``jnp`` twins are that module's and described there.  What the stack
knows of it: the output comes back token-major for ``w_o``, and the names of
what the backward pass keeps (``attention.ATTN_KEPT``), which
:func:`_row_by_row`'s checkpoint holds on to.

The model may be one chip's share of an expert-parallel job: ``experts_held``
says which of the ``num_experts`` live here (``parallel/moe.py: held_experts``)
and ``vocab_size`` is the slice of the vocabulary the embedding, the head and
the loss are over.  Parallelism: dp over rows; everything else is replicated.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from lakesoul_tpu.models.attention import ATTN_KEPT, _rotary, attention_operands, causal_attention, mixer_counts
from lakesoul_tpu.models.head_loss import head_tile, labelled_nll
from lakesoul_tpu.models.loss_tile import fused_tile, tile_takes
from lakesoul_tpu.parallel.mesh import spec_axes
from lakesoul_tpu.parallel.moe import EXPERTS_SCOPE, ROUTE_SCOPE, SHARED_SCOPE, held_experts, shared_expert

ATTN_SCOPE = "lakesoul.lm.attn"
MLA_SCOPE = "lakesoul.lm.mla"  # inside ATTN_SCOPE: what latent attention adds around the kernels
MTP_SCOPE = "lakesoul.lm.mtp"  # the whole prediction module, its layer's and its head's scopes inside
MLP_SCOPE = "lakesoul.lm.mlp"
HEAD_SCOPE = "lakesoul.lm.head"
EMBED_SCOPE = "lakesoul.lm.embed"  # the token lookup and, through its transpose, the scatter-add of its gradient
EXIT_SCOPE = "lakesoul.lm.exit"    # a looped model's exit gate, its distribution over the passes and the expected loss
EXIT_MASS_UNIT = 1024  # :func:`exit_loss` counts the exit distribution's mass in this fraction of a position


def normal_init(key, *shape):
    """A weight matrix from a key: normal(0, 0.02), float32."""
    return (jax.random.normal(key, shape) * 0.02).astype(jnp.float32)


def causal_conv(x, w):
    """Depthwise causal convolution, no bias, no activation: x [B, T, C],
    w [C, K]; tap ``K-1`` sits on the current token, zeros left of the row."""
    taps = w.shape[1]
    t = x.shape[1]
    xp = jnp.pad(x, ((0, 0), (taps - 1, 0), (0, 0)))
    return sum(xp[:, j:j + t].astype(jnp.float32) * w[:, j] for j in range(taps)).astype(x.dtype)


# ------------------------------------------------------------- the mixers


def softmax_attention(x, p, *, heads: int, kv_heads: int, head_dim: int, rotary_dim: int | None,
                      theta: float, eps: float, centred: bool, gated: bool, window: int | None = None):
    """The grouped-query softmax-attention mixer: x [B, T, h] (normed) →
    [B, T, h].  ``eps`` and ``centred`` are the family's RMS norm's, here over
    a head's channels (``q_norm``, ``k_norm``; where the weights hold neither,
    the heads are not normed: plain attention); ``rotary_dim`` of them are
    rotated, none where it is None (a layer that sees no positions).
    ``window``: ``causal_attention``'s.  A gate's sigmoid scales each head's
    output before ``w_o``: ``gated``: ``w_q`` holds per head the query, then
    the gate (the Qwen3-Next family); else a matrix of the gate's own where
    the weights hold one (``w_gate`` [h, heads x D]), else none.

    Between the projections and the kernels the query and the key are normed,
    turned, the query scaled, and all three laid out heads first:
    ``models/attention.py: attention_operands``."""
    dtype = x.dtype
    f32 = jnp.float32
    b, t, _ = x.shape
    d = head_dim
    q = (x @ p["w_q"].astype(dtype)).reshape(b, t, heads, (2 if gated else 1) * d)
    gate = None
    if gated:
        q, gate = q[..., :d], q[..., d:]
    elif "w_gate" in p:
        gate = (x @ p["w_gate"].astype(dtype)).reshape(b, t, heads, d)
    k = (x @ p["w_k"].astype(dtype)).reshape(b, t, kv_heads, d)
    v = (x @ p["w_v"].astype(dtype)).reshape(b, t, kv_heads, d)
    q, k, v = attention_operands(
        q, k, v, p.get("q_norm"), p.get("k_norm"), eps=eps, centred=centred, rotary_dim=rotary_dim, theta=theta
    )
    # the gate and ``w_o`` over [B, T, heads x D], no head axis: on [.., heads, D] arrays XLA lays the gate's
    # passes tokens-minor for the weight-gradient products and copies the kernels' row-major o and do across
    o = causal_attention(q, k, v, window).reshape(b, t, heads * d)
    if gate is not None:
        o = (o.astype(f32) * jax.nn.sigmoid(gate.reshape(b, t, heads * d).astype(f32))).astype(dtype)
    return o @ p["w_o"].astype(dtype)


def latent_attention(x, p, *, heads: int, nope_dim: int, rope_dim: int, theta: float, norm):
    """The latent-attention mixer (multi-head latent attention, unabsorbed: a
    training step's form, whose gradients reach the up-projections): x
    [B, T, h] (normed) → [B, T, h].

    ``c_q = norm(x W_dq)``, a head's query ``[q_nope | q_rope] = c_q W_uq``;
    ``[c_kv | k_r] = x W_dkv``, ``c_kv`` normed, a head's ``[k_nope | v] = c_kv
    W_ukv``; ``q_rope`` and ``k_r`` rotated over all their ``rope_dim``
    channels, and ``k_r`` is ONE head that every query head's key ends in.
    Scores over ``nope_dim + rope_dim`` channels, scaled by their root; values
    as wide (``w_ukv`` says how wide: :func:`causal_attention` is one head
    size).  No norm over a head, no gate.  ``norm(a, w)`` is the family's RMS
    norm (``q_norm``, ``kv_norm`` over the latents)."""
    dtype = x.dtype
    f32 = jnp.float32
    b, t, _ = x.shape
    d = nope_dim + rope_dim
    with jax.named_scope(MLA_SCOPE):
        c_q = norm(x @ p["w_dq"].astype(dtype), p["q_norm"]).astype(dtype)
        q = (c_q @ p["w_uq"].astype(dtype)).reshape(b, t, heads, d)
        down = x @ p["w_dkv"].astype(dtype)
        latent = p["kv_norm"].shape[0]
        c_kv = norm(down[..., :latent], p["kv_norm"]).astype(dtype)
        kv = (c_kv @ p["w_ukv"].astype(dtype)).reshape(b, t, heads, -1)
        k_nope, v = kv[..., :nope_dim], kv[..., nope_dim:]
        positions = jnp.arange(t)
        q = q.astype(f32)
        q = jnp.concatenate([q[..., :nope_dim], _rotary(q[..., nope_dim:], positions, rope_dim, theta)], axis=-1)
        q = (q * d**-0.5).astype(dtype)
        k_rope = _rotary(down[..., None, latent:].astype(f32), positions, rope_dim, theta).astype(dtype)
        k = jnp.concatenate([k_nope, jnp.broadcast_to(k_rope, (b, t, heads, rope_dim))], axis=-1)
    # [B, T, heads, D] → [B, heads, 1, T, D]: every head has its own keys and values
    q = q.transpose(0, 2, 1, 3)[:, :, None]
    k, v = (a.transpose(0, 2, 1, 3) for a in (k, v))
    return causal_attention(q, k, v).reshape(b, t, -1) @ p["w_o"].astype(dtype)


# ------------------------------------------------------------- the stack


def _row_by_row(mixer, x, p, batch_sharding, kept=()):
    """``mixer(x, p)`` one row of ``x`` [B, T, h] at a time, each row
    rematerialised: a mixer's intermediates at 8k tokens are gigabytes a row
    and no row needs another's.  Of a row the backward pass keeps its input
    and what the attention kernels name (:data:`ATTN_KEPT`: 34 MB a row at 32
    heads of 64) and what the family's own kernels do (``kept``: its
    ``checkpoint_name``s).  On a mesh every device takes its own rows.  ``x``
    and the result may be trees of arrays with the rows first (a layer's
    input beside a shared state, :func:`lm_layer`)."""

    keep = jax.checkpoint_policies.save_only_these_names(*ATTN_KEPT, *kept)

    def local(x, p):
        def a_row(row):
            return jax.tree.map(lambda a: a[0], mixer(jax.tree.map(lambda a: a[None], row), p))

        return jax.lax.map(jax.checkpoint(a_row, policy=keep), x)

    if batch_sharding is None:
        return local(x, p)
    spec = batch_sharding.spec
    return jax.shard_map(
        local, mesh=batch_sharding.mesh, in_specs=(spec, P()), out_specs=spec, check_vma=False
    )(x, p)


def dense_mlp(x, p):
    """The dense SwiGLU feed-forward: x [..., h] (normed) → [..., h]."""
    dtype = x.dtype
    mid = jax.nn.silu(x @ p["w_gate"].astype(dtype)) * (x @ p["w_up"].astype(dtype))
    return mid @ p["w_down"].astype(dtype)


def _shares(cfg, kind: str):
    """``cfg.shares(kind)``; None for a family without shared states."""
    return cfg.shares(kind) if hasattr(cfg, "shares") else None


def _mixer_weights(lp, kind: str, buffers):
    """A layer's mixer's weights as its mixer is handed them: ``lp[kind]``,
    and beside them what the layer's buffers hold under ``"mixer"`` (numbers
    of the layer that nothing trains)."""
    held = (buffers or {}).get("mixer")
    return lp[kind] if held is None else {**lp[kind], **held}


def lm_layer(x, lp, buffers, *, kind: str, ffn: str, cfg, batch_sharding=None, shared=None):
    """One layer, its weights ``lp`` and its buffers (``None`` or a dict): x
    [B, T, h] → (x, the expert layer's counts; None after a dense
    feed-forward).  ``shared``: what the layers before this one published, by
    name (:func:`_layers`' dict; a layer whose mixer makes a shared state
    adds its own), read only where ``cfg.shares(kind)`` says so.  The mixer is rematerialised a row at a time; the
    dense feed-forward with its norm; of a routed layer norm, routing and the
    shared expert together, and the held experts' tile loop not (its backward
    pass needs its inputs alone).  What the backward pass keeps of a layer:
    its input, the mixer's output, and of a routed layer the experts' normed
    input and the routing.

    Where the weights hold ``norm1_out`` and ``norm2_out`` the layer has four
    norms: each sublayer's output is normed before it is added,
    ``h = x + norm(mixer(norm(x)))``, ``x' = h + norm(ffn(norm(h)))``, the
    second over the routed experts' share and the shared expert summed."""
    dtype = x.dtype
    mixer, scope = cfg.mixer(kind)

    def normed_out(out, w):
        return out if w is None else cfg.norm(out, w).astype(dtype)

    def normed_in(x, p):
        return cfg.norm(x, p["norm"]).astype(dtype)

    def mix(x, p):
        return x + normed_out(mixer(normed_in(x, p), p["mixer"]), p.get("out"))

    def mix_with(x_state, p):
        x, state = x_state
        return x + normed_out(mixer(normed_in(x, p), p["mixer"], state), p.get("out"))

    @jax.checkpoint
    def dense(x, norm, p, out):
        return normed_out(dense_mlp(cfg.norm(x, norm).astype(dtype), p), out)

    @jax.checkpoint
    def routed(x, norm, router, bias, shared):
        """Norm, routing and the shared expert: cheap to compute again."""
        y32 = cfg.norm(x, norm)
        top_e, w, moved = cfg.route(y32, router, bias)
        y = y32.astype(dtype)
        return y, top_e, w, moved, None if shared is None else shared_expert(y, shared)

    mixed = {"norm": lp["norm1"], "mixer": _mixer_weights(lp, kind, buffers)}
    if "norm1_out" in lp:
        mixed["out"] = lp["norm1_out"]
    share = _shares(cfg, kind)
    kept = getattr(cfg, "kept", ())
    # a scope stands around the call, not inside the checkpointed function: what the checkpoint itself
    # writes (the copies it keeps its inputs in) and each residual add are then the feed-forward's too
    with jax.named_scope(scope):
        if share is None:
            x = _row_by_row(mix, x, mixed, batch_sharding, kept)
        else:
            # the state is a row loop's result and the next one's argument: computed once, held once (what
            # the second loop's checkpoint keeps of it is the array the first one wrote), and its cotangent
            # comes back into the first loop's transpose from every layer that read it
            name, make = share
            if make is not None:
                shared[name] = _row_by_row(lambda x, p: make(normed_in(x, p), p["mixer"]), x, mixed, batch_sharding, kept)
            x = _row_by_row(mix_with, (x, shared[name]), mixed, batch_sharding, kept)
    if ffn == "dense":
        with jax.named_scope(MLP_SCOPE):
            return x + dense(x, lp["norm2"], lp["mlp"], lp.get("norm2_out")), None
    p = lp["moe"]
    with jax.named_scope(ROUTE_SCOPE):  # the norm and the routing; the shared expert's scope is inside
        y, top_e, w, moved, shared = routed(
            x, lp["norm2"], p["router"], (buffers or {}).get("expert_bias"), p.get("shared")
        )
    out, counts = held_experts(
        y, top_e, w, p, n_experts=cfg.num_experts, held=cfg.experts_held, batch_sharding=batch_sharding
    )
    counts = dict(counts, moe_bias_moved=moved)
    if "norm2_out" in lp:  # the two shares meet before their norm
        with jax.named_scope(EXPERTS_SCOPE):
            return x + jax.checkpoint(normed_out)(out if shared is None else out + shared, lp["norm2_out"]), counts
    with jax.named_scope(EXPERTS_SCOPE):
        x = x + out
    if shared is not None:
        with jax.named_scope(SHARED_SCOPE):
            x = x + shared
    return x, counts


def layer_attention_counts(cfg, kind: str, x, p, state=None) -> dict:
    """:func:`mixer_counts` of one layer's mixer (its weights ``p``) over
    the batch ``x`` [B, T, h], as the counts a loss returns; ``state``: the
    shapes of a row's shared state, where the mixer reads one."""
    row = jax.ShapeDtypeStruct((1, *x.shape[1:]), x.dtype)
    mixer = cfg.mixer(kind)[0]
    if state is not None:
        row, mixer = (row, state), lambda x_state, p, mixer=mixer: mixer(x_state[0], p, x_state[1])
    return {key: x.shape[0] * n for key, n in mixer_counts(mixer, row, p).items()}


def _sum_counts(totals: dict | None, counts: dict | None) -> dict:
    """``totals`` with ``counts`` added, key by key."""
    totals = dict(totals or {})
    for key, n in (counts or {}).items():
        totals[key] = totals[key] + n if key in totals else n
    return totals


def _embedded(params, ids, *, cfg):
    """ids [B, T] → the stack's input [B, T, h] in ``cfg.dtype``: the
    embedding's rows, multiplied by ``cfg.embed_scale`` where the
    configuration has one."""
    with jax.named_scope(EMBED_SCOPE):
        x = params["embed"][ids]
        if getattr(cfg, "embed_scale", None) is not None:
            x = x * cfg.embed_scale
        return x.astype(jnp.dtype(cfg.dtype))


def _layers(params, x, *, cfg, batch_sharding=None):
    """One walk through ``params["layers"]``: x [B, T, h] → (x, the expert
    layers' counts summed over the routed layers, or None)."""
    kinds, ffns = cfg.layer_kinds(), cfg.ffn_kinds()
    buffers = params.get("buffers", {}).get("layers", [None] * len(kinds))
    totals, shared = None, {}
    for lp, held, kind, ffn in zip(params["layers"], buffers, kinds, ffns, strict=True):
        x, counts = lm_layer(x, lp, held, kind=kind, ffn=ffn, cfg=cfg, batch_sharding=batch_sharding, shared=shared)
        if counts is not None:
            with jax.named_scope(EXPERTS_SCOPE):
                totals = counts if totals is None else jax.tree.map(jnp.add, totals, counts)
    return x, totals


def _attention_counts(params, x, *, cfg) -> dict:
    """:func:`layer_attention_counts` summed over one walk through the layers
    for the batch ``x`` [B, T, h], traced once a kind (a kind's layers have
    one shape): Python integers, no operation of the program.  Of a family
    with shared states also ``shared_reads``: the rows of the layers that read
    a state an EARLIER layer published."""
    kinds = cfg.layer_kinds()
    buffers = params.get("buffers", {}).get("layers", [None] * len(kinds))
    row = jax.ShapeDtypeStruct((1, *x.shape[1:]), x.dtype)
    of_kind, tiles, states, reads = {}, None, {}, 0
    for lp, held, kind in zip(params["layers"], buffers, kinds, strict=True):
        share = _shares(cfg, kind)
        p = _mixer_weights(lp, kind, held)
        if share is not None and share[1] is not None:
            states[share[0]] = jax.eval_shape(share[1], row, p)
        elif share is not None:
            reads += x.shape[0]
        if kind not in of_kind:
            of_kind[kind] = layer_attention_counts(cfg, kind, x, p, None if share is None else states[share[0]])
        tiles = _sum_counts(tiles, of_kind[kind])
    return dict(tiles, shared_reads=reads) if hasattr(cfg, "shares") else tiles


def lm_hidden(params, ids, *, cfg, batch_sharding=None):
    """ids [B, T] → (final hidden states [B, T, h] before the final norm,
    counts: the expert layers' summed over the routed layers, and the
    attention kernels' grid steps and the rows by what made their operands
    over every layer, :func:`_attention_counts`).  The embedding's output is
    multiplied by ``cfg.embed_scale`` where the configuration has one."""
    x = _embedded(params, ids, cfg=cfg)
    tiles = _attention_counts(params, x, cfg=cfg)
    x, totals = _layers(params, x, cfg=cfg, batch_sharding=batch_sharding)
    return x, _sum_counts(totals, tiles)


def loop_hidden(params, ids, *, cfg, batch_sharding=None):
    """The stack run ``cfg.loop_passes`` times over ONE set of weights (a
    looped, weight-shared model): ids [B, T] → (the state after each pass
    [R, B, T, h], every one AFTER the final norm, which is applied between
    passes: pass ``t + 1`` starts from pass ``t``'s normed state, and that is
    also what the head and the exit gate read; counts as :func:`lm_hidden`'s,
    over all the passes, and ``loop_layers_run`` and ``loop_layers``: rows x
    layers x passes and rows x layers, Python integers).

    With more than one pass the passes are one ``lax.scan`` whose body is
    :func:`_layers` and the norm: the step program holds each layer's body
    once, the shared weights' gradients add up in the scan's transpose, and
    the backward pass keeps of each pass what it keeps of a stack
    (:func:`lm_layer`).  One pass is the plain walk."""
    passes = cfg.loop_passes
    dtype = jnp.dtype(cfg.dtype)

    def one_pass(x, _=None):
        x, totals = _layers(params, x, cfg=cfg, batch_sharding=batch_sharding)
        with jax.named_scope(HEAD_SCOPE):
            x = cfg.norm(x, params["final_norm"]).astype(dtype)
        return x, (x, totals)

    x = _embedded(params, ids, cfg=cfg)
    tiles = {key: passes * n for key, n in _attention_counts(params, x, cfg=cfg).items()}
    if passes == 1:
        _, (state, totals) = one_pass(x)
        states = state[None]
    else:
        _, (states, totals) = jax.lax.scan(one_pass, x, None, length=passes)
        with jax.named_scope(EXPERTS_SCOPE):
            totals = jax.tree.map(lambda n: jnp.sum(n, axis=0), totals)
    layer_rows = ids.shape[0] * len(params["layers"])
    return states, dict(_sum_counts(totals, tiles), loop_layers_run=passes * layer_rows, loop_layers=layer_rows)


def head_params(params) -> dict:
    """The leaves :func:`lm_head` reads: the final norm and the head, or the
    embedding where the head is tied to it."""
    return {k: params[k] for k in ("final_norm", "head" if "head" in params else "embed")}


def lm_head(head, x, *, cfg):
    """Logits over the held vocabulary, float32: x [..., h] → [..., vocab];
    the final norm first where ``head`` holds one."""
    dtype = jnp.dtype(cfg.dtype)
    with jax.named_scope(HEAD_SCOPE):
        # a looped model's states come normed (:func:`loop_hidden`): its head holds no norm
        y = cfg.norm(x, head["final_norm"]).astype(dtype) if "final_norm" in head else x
        if "head" in head:
            return jnp.dot(y, head["head"].astype(dtype), preferred_element_type=jnp.float32)
        return jnp.einsum("...h,vh->...v", y, head["embed"].astype(dtype), preferred_element_type=jnp.float32)




def _head_nll(head_fn, head, x, labels, batch_sharding, weights=None):
    """:func:`labelled_nll` with :func:`fused_tile` as its tile body → (what
    it returns, the rows it was handed by the body that runs their tiles:
    ``loss_rows_fused`` and ``loss_rows_compiler``, host integers known when
    the step is traced and no operation of it, by the rule the body itself
    reads at a shard's tile)."""
    axes = () if batch_sharding is None else spec_axes(batch_sharding.spec)
    shards = math.prod(batch_sharding.mesh.shape[axis] for axis in axes)
    tile = jax.ShapeDtypeStruct((head_tile(labels.size // shards), x.shape[-1]), x.dtype)
    fused = tile_takes(*jax.eval_shape(head_fn, head, tile).shape)
    rows = {"loss_rows_fused": labels.size if fused else 0, "loss_rows_compiler": 0 if fused else labels.size}
    return labelled_nll(head_fn, head, x, labels, batch_sharding, weights, fused_tile), rows


def lm_logits(params, ids, *, cfg):
    x, _ = lm_hidden(params, ids, cfg=cfg)
    return lm_head(head_params(params), x, cfg=cfg)


def mtp_hidden(params, x, labels, *, cfg, batch_sharding=None):
    """The multi-token-prediction module up to its head (one module, depth 1:
    DeepSeek-V3 report, arXiv:2412.19437, section 2.2): ``x`` [B, T, h] the
    main stack's final hidden states, ``labels`` the row's next tokens (-100
    where there is none) → (the module's hidden states [B, T, h] before its
    head norm, its expert layer's counts or None).

    ``h'_i = [norm_e(Emb(t_{i+1})) ; norm_h(final_norm(h_i))] W_eh``, then one
    layer of the stack's last kind with the module's own weights and buffers.
    The embedding is the main model's.  The next token is read off ``labels``;
    where a row has none (its last position) token 0 stands in, and that
    position has no label two ahead either."""
    p = params["mtp"]
    dtype = jnp.dtype(cfg.dtype)

    @jax.checkpoint
    def merged(x, embed, final_norm, p):
        e = cfg.norm(embed[jnp.maximum(labels, 0)].astype(dtype), p["enorm"])
        h = cfg.norm(cfg.norm(x, final_norm).astype(dtype), p["hnorm"])
        return jnp.concatenate([e, h], axis=-1).astype(dtype) @ p["eh_proj"].astype(dtype)

    h = merged(x, params["embed"], params["final_norm"], {k: p[k] for k in ("enorm", "hnorm", "eh_proj")})
    return lm_layer(
        h, p["layer"], params.get("buffers", {}).get("mtp"), kind=cfg.layer_kinds()[-1],
        ffn=cfg.ffn_kinds()[-1], cfg=cfg, batch_sharding=batch_sharding,
    )


def mtp_head_params(params) -> dict:
    """:func:`head_params` of the prediction module: its own norm and the
    main model's head matrix (one copy, gradients from both uses)."""
    return dict(head_params(params), final_norm=params["mtp"]["shared_head_norm"])


def mtp_loss(params, x, labels, *, cfg, batch_sharding=None):
    """The prediction module's loss: the mean cross-entropy of the token after
    next over the positions that have one → (loss, the module's layer's
    counts: its expert layer's, its attention kernels' grid steps; those
    positions' count)."""
    with jax.named_scope(MTP_SCOPE):
        after_next = jnp.concatenate([labels[:, 1:], jnp.full_like(labels[:, :1], -100)], axis=1)
        h, counts = mtp_hidden(params, x, labels, cfg=cfg, batch_sharding=batch_sharding)
        kind = cfg.layer_kinds()[-1]
        counts = _sum_counts(counts, layer_attention_counts(cfg, kind, x, params["mtp"]["layer"][kind]))
        (loss, _), rows = _head_nll(
            functools.partial(lm_head, cfg=cfg), mtp_head_params(params), h, after_next, batch_sharding
        )
        return loss, _sum_counts(counts, rows), jnp.sum(after_next >= 0, dtype=jnp.int32)


def lm_loss(params, ids, labels, *, cfg, batch_sharding=None):
    """Next-token cross-entropy, float32, mean over the positions with
    ``labels >= 0`` (-100 elsewhere), and where the weights hold a prediction
    module ``cfg.mtp_loss_weight`` times :func:`mtp_loss` on top → (loss,
    counts).  ``counts``: the expert layers' (summed over layers, the
    module's among them), ``tokens``, and the positions with a label,
    ``head_all`` over both losses and ``head_mtp`` the module's (0 without
    one), int32; ``loss_main`` and ``loss_mtp``, the two terms, float32; and
    ten Python integers, known when the step is traced and no operation of
    it: ``models/attention.py: mixer_counts``' eight over rows and layers, and
    :func:`_head_nll`'s two over both losses."""
    x, counts = lm_hidden(params, ids, cfg=cfg, batch_sharding=batch_sharding)
    with jax.named_scope(HEAD_SCOPE):  # the loss's own loop over tiles of positions around the head
        (loss, _), rows = _head_nll(functools.partial(lm_head, cfg=cfg), head_params(params), x, labels, batch_sharding)
        counts = _sum_counts(counts, rows)
        labelled = jnp.sum(labels >= 0, dtype=jnp.int32)
    terms = {"loss_main": loss, "loss_mtp": jnp.float32(0.0)}
    second = jnp.int32(0)
    if "mtp" in params:
        terms["loss_mtp"], more, second = mtp_loss(params, x, labels, cfg=cfg, batch_sharding=batch_sharding)
        with jax.named_scope(MTP_SCOPE):
            loss = loss + cfg.mtp_loss_weight * terms["loss_mtp"]
            labelled = labelled + second
            counts = _sum_counts(counts, more)
    return loss, dict(counts, **terms, tokens=jnp.int32(ids.size), head_all=labelled, head_mtp=second)


def exit_distribution(gate, states):
    """A looped model's exit gate over the passes' normed states [R, ..., h] →
    the distribution over the pass a position exits after, [R, ...] float32
    (Ouro, arXiv:2510.25741, section 3): ``lambda^(t) = sigmoid(z^(t) . w +
    b)``; the survival ``S^(0) = 1``, ``S^(t) = S^(t-1) (1 - lambda^(t))``;
    ``p(t) = lambda^(t) S^(t-1)`` before the last pass and ``p(R) = S^(R-1)``:
    what is left exits there, so the ``R`` terms sum to 1.  Float32, the
    product with ``w`` [h] a multiply and a sum (no matrix unit's rounding)."""
    z = states.astype(jnp.float32)
    lam = jax.nn.sigmoid(jnp.sum(z[:-1] * gate["w"], axis=-1) + gate["b"])
    ones = jnp.ones_like(z[:1, ..., 0])
    survived = jnp.concatenate([ones, jnp.cumprod(1.0 - lam, axis=0)])  # S^(0) .. S^(R-1)
    return jnp.concatenate([lam * survived[:-1], survived[-1:]])


def exit_loss(params, states, labels, *, cfg, batch_sharding=None):
    """A looped model's objective over its passes' normed states [R, B, T, h]
    (:func:`loop_hidden`) → (loss, counts): the expected next-token loss under
    the exit distribution less ``cfg.exit_beta`` times that distribution's
    entropy (a uniform prior), mean over the positions with a label,

        ``mean_i [ sum_t p_i(t) nll_i^(t) + beta sum_t p_i(t) log p_i(t) ]``

    with ``nll_i^(t)`` the float32 cross-entropy of pass ``t``'s logits
    ``z^(t) W_head`` (one head matrix, no second norm: the states come
    normed) and ``p`` :func:`exit_distribution` by ``params["exit"]``.

    The ``R`` passes go through the head and the loss's tile loop as ONE call
    over the stacked states (:func:`labelled_nll` with a weight a position:
    one float32 weight-gradient accumulator of the head, not ``R``), and the
    product rule gives both gradients from it: the weighted NLL with ``p``
    held constant carries the gradient into the head and the stack, ``p``
    times the returned NLL held constant the gradient into the gate (and
    through ``z`` into the stack).  ``counts``: ``head_all`` the labelled
    positions over all ``R`` losses, ``head_loop`` those of the passes
    before the last and ``head_mtp`` 0 (no prediction module), int32; ``loss_pass`` [R] each pass's mean NLL and
    ``exit_mass`` [R] the mean ``p(t)``, float32; ``exit_mass_<t>`` the summed
    ``p(t)`` over the labelled positions in 1,024ths, int32 (what the step's
    gauge counts); ``tokens``; ``loss_rows_fused`` and ``loss_rows_compiler``,
    the stacked rows by the body that runs their tiles (:func:`_head_nll`:
    Python integers)."""
    passes = states.shape[0]
    f32 = jnp.float32
    with jax.named_scope(EXIT_SCOPE):
        p = exit_distribution(params["exit"], states)
        labelled = (labels >= 0).astype(f32)
        n = jnp.maximum(jnp.sum(labelled), 1.0)
        log_p = jnp.log(jnp.maximum(p, jnp.finfo(f32).tiny))  # p log p → 0 as p → 0
        neg_entropy = jnp.sum(p * log_p * labelled) / n
        weights = jax.lax.stop_gradient(p) * (labelled / n)
    with jax.named_scope(HEAD_SCOPE):  # rows first: a mesh splits the stacked states by their rows
        (expected, _, nll), rows = _head_nll(
            functools.partial(lm_head, cfg=cfg), {k: v for k, v in head_params(params).items() if k != "final_norm"},
            jnp.moveaxis(states, 0, 1), jnp.broadcast_to(labels[:, None], (labels.shape[0], passes, labels.shape[1])),
            batch_sharding, jnp.moveaxis(weights, 0, 1),
        )
    with jax.named_scope(EXIT_SCOPE):
        nll = jax.lax.stop_gradient(jnp.moveaxis(nll, 1, 0))  # [R, B, T]
        through_gate = jnp.sum(p * nll * labelled) / n  # equal to ``expected``; its gradient is the gate's
        loss = expected + (through_gate - jax.lax.stop_gradient(through_gate)) + cfg.exit_beta * neg_entropy
        mass = jnp.sum(p * labelled, axis=(1, 2))
        count = jnp.sum(labels >= 0, dtype=jnp.int32)
        counts = {
            "loss_pass": jnp.sum(nll * labelled, axis=(1, 2)) / n, "exit_mass": mass / n,
            "head_all": passes * count, "head_loop": (passes - 1) * count, "head_mtp": jnp.int32(0),
            "tokens": jnp.int32(labels.size), **rows,
            **{f"exit_mass_{t}": jnp.round(mass[t] * EXIT_MASS_UNIT).astype(jnp.int32) for t in range(passes)},
        }
    return loss, counts


def loop_loss(params, ids, labels, *, cfg, batch_sharding=None):
    """The loss of a looped model (``cfg.loop_passes`` passes of the stack
    over one set of weights, a loss after every pass through one head, an exit
    gate): :func:`exit_loss` over :func:`loop_hidden` → (loss, counts: both
    functions')."""
    states, counts = loop_hidden(params, ids, cfg=cfg, batch_sharding=batch_sharding)
    loss, more = exit_loss(params, states, labels, cfg=cfg, batch_sharding=batch_sharding)
    return loss, dict(counts, **more)
