"""Consumer adaptor: next-token training of the hybrid causal LM
(``lakesoul_tpu/models/qwen3_next.py``) through ``models/train.py``.

What the trainer driver needs of a model kind (see ``consumers/bert_mlm.py``),
and this cell's comparison with the plain reference: the driver gates
``correct`` on ``|system - plain| <= reference_loss_tolerance`` alone, so
:meth:`Consumer.losses_on` also compares logits and gradients and hands the
driver ``nan`` for the plain loss when one of them is outside its limit.

The program's model is imported at the top of this file: laid over a program
that lacks it (the parent of the PR that added this cell), the run fails at
import, within seconds, and not after a table build.
"""

from __future__ import annotations

import functools
import json
import math
import os
import re
import sys
import time

import numpy as np

from lakesoul_tpu.models.bert import labelled_nll
from lakesoul_tpu.models.qwen3_next import Qwen3NextConfig, lm_head, lm_hidden

STEP_MODULE = "jit_train_step"  # the step program's name in a device trace
LOGIT_POSITIONS = 256           # positions of the held row whose logits are compared
SCOPES_FILE = "step_scopes.json"  # instruction → scope of the compiled step, beside the trace


def _log(message: str) -> None:
    print(f"[qwen3_next_clm] {message}", file=sys.stderr, flush=True)


def model_config(config: dict) -> Qwen3NextConfig:
    m = config["model"]
    return Qwen3NextConfig.from_published(
        m, experts_held=(m["first_expert_held"], m["num_experts_held"]), dtype=m["compute_dtype"]
    )


def flops_per_row(config: dict) -> float:
    """Forward and backward operations one row (one sequence) requires.

    Per token, forward, 2 operations a multiply-add over the parameters a token
    touches: a Gated DeltaNet mixer's projections (``in_proj_qkvz``,
    ``in_proj_ba``, ``out_proj``: 33.69 M) or a gated-attention mixer's (27.26
    M); in every layer the router (1.05 M), the shared expert (3.15 M) and the
    routed experts at the expected ``top_k x held / experts`` of one expert
    (10 x 32/512 x 3.15 M = 1.97 M: what lands on this chip under even routing,
    not the worst case); the head over the held vocabulary (38.9 M).  Then the
    causal scores, half of ``4 T d`` over the query width (67.1 M operations at
    8,192 tokens), and the DeltaNet state, three ``dk x dv`` products a value
    head and token (3.1 M a layer).  At the published widths with one period,
    32 experts and 18,992 vocabulary rows that is 0.460 GFLOP a token forward.
    Backward costs twice the forward.  The embedding lookup, norms, the
    convolution, softmax, routing and the optimizer are left out, as is every
    recomputation (each layer, each block of attention rows and each DeltaNet
    chunk is computed again in the backward pass)."""
    m = config["model"]
    seq = config["table"]["seq"]
    h = m["hidden_size"]
    kinds = model_config(config).layer_kinds()
    key_dim = m["linear_num_key_heads"] * m["linear_key_head_dim"]
    value_dim = m["linear_num_value_heads"] * m["linear_value_head_dim"]
    gdn = h * (2 * key_dim + 2 * value_dim) + h * 2 * m["linear_num_value_heads"] + value_dim * h
    q_width = m["num_attention_heads"] * m["head_dim"]
    kv_width = m["num_key_value_heads"] * m["head_dim"]
    attn = h * 2 * q_width + 2 * h * kv_width + q_width * h
    expert = 3 * h * m["moe_intermediate_size"]
    routed = m["num_experts_per_tok"] * m["num_experts_held"] / m["num_experts"] * expert
    per_layer = h * m["num_experts"] + 3 * h * m["shared_expert_intermediate_size"] + routed
    params = sum(gdn if kind == "gdn" else attn for kind in kinds) + len(kinds) * per_layer + h * m["vocab_size"]
    scores = kinds.count("attn") * 4 * seq * q_width / 2
    state = kinds.count("gdn") * m["linear_num_value_heads"] * 3 * 2 * m["linear_key_head_dim"] * m["linear_value_head_dim"]
    return 3.0 * seq * (2 * params + scores + state)


def transform(config: dict, seed: int):
    """Host transform for the loader: token rows to (ids, labels), the labels
    the tokens shifted left by one, -100 at the last position."""
    del config, seed  # a causal LM masks nothing

    def collate(batch: dict) -> dict:
        tokens = batch["tokens"]
        labels = np.full_like(tokens, -100)
        labels[:, :-1] = tokens[:, 1:]
        return {"ids": tokens, "labels": labels}

    return collate


def _picked(tree: dict, cfg: Qwen3NextConfig) -> dict:
    """One leaf of each new kind, by what the comparison calls it: the first
    DeltaNet layer's ``A_log`` and convolution, the first layer's router and
    its first held expert's ``W_down``, and the attention gate's columns of the
    first attention layer's ``q_proj``."""
    kinds = cfg.layer_kinds()
    gdn = tree["layers"][kinds.index("gdn")]
    attn = tree["layers"][kinds.index("attn")]["attn"]
    d = cfg.head_dim
    return {
        "A_log": gdn["gdn"]["A_log"],
        "conv": gdn["gdn"]["conv"],
        "router": gdn["moe"]["router"],
        "expert_w_down": gdn["moe"]["w_down"][0],
        "attn_gate": attn["w_q"].reshape(cfg.hidden_size, cfg.num_attention_heads, 2 * d)[..., d:],
    }


class Consumer:
    """State and step on a mesh plan, built the way a training job builds
    them: ``make_lm_train_state`` makes the weights on the device from the
    seed, ``make_lm_train_step`` jits the step."""

    def __init__(self, config: dict, plan, seed: int):
        from lakesoul_tpu.models.train import make_lm_train_state, make_lm_train_step

        self.config = config
        self.cfg = model_config(config)
        self.params, self.opt_state, tx, shardings = make_lm_train_state(
            self.cfg, plan, lr=config["learning_rate"], seed=seed
        )
        self._step = make_lm_train_step(self.cfg, plan, tx, shardings)
        self._batch_shape = None

    def step(self, batch: dict):
        """Dispatch one optimizer step; returns the loss (a device array)."""
        self._batch_shape = batch["ids"].shape
        self.params, self.opt_state, loss = self._step(
            self.params, self.opt_state, batch["ids"], batch["labels"]
        )
        return loss

    # ------------------------------------------------------------ correct

    def _program(self, params, ids, labels, positions):
        """(loss, logits at ``positions``) as the timed path computes them."""
        x, _ = lm_hidden(params, ids, cfg=self.cfg)
        head = {k: params[k] for k in ("final_norm", "head")}
        loss, _ = labelled_nll(functools.partial(lm_head, cfg=self.cfg), head, x, labels, None)
        return loss, lm_head(head, x[:, positions], cfg=self.cfg)

    def compare(self, host_batch: dict, *, reference_dtype=None) -> dict:
        """The program against the plain reference on the same rows with the
        weights as they stand, at the timed width and length: the loss, the
        logits at ``LOGIT_POSITIONS`` positions spread over the row (largest
        absolute difference) and the gradient of one leaf of each new kind
        (norm of the difference over the reference's norm).  ``reference_dtype``
        computes the reference in a lower precision instead (how the limits
        were set)."""
        import jax
        import jax.numpy as jnp

        from reference import qwen3_next_f32 as plain

        m = self.config["model"]
        held = (m["first_expert_held"], m["num_experts_held"])
        ids, labels = jnp.asarray(host_batch["ids"]), jnp.asarray(host_batch["labels"])
        positions = jnp.asarray(np.linspace(0, ids.shape[1] - 1, LOGIT_POSITIONS).astype(np.int32))

        def both(fn):
            def run(params):
                (loss, logits), grads = jax.value_and_grad(fn, has_aux=True)(params)
                return loss, logits, _picked(grads, self.cfg)

            return jax.jit(run)

        t0 = time.perf_counter()
        got = both(lambda p: self._program(p, ids, labels, positions))(self.params)
        kwargs = {} if reference_dtype is None else {"dtype": reference_dtype}
        with jax.default_matmul_precision("highest"):
            want = both(
                lambda p: plain.lm_loss(p, ids, labels, cfg=m, held=held, logits_at=positions, **kwargs)
            )(self.params)
        got, want = jax.device_get((got, want))
        # per compared position, the largest difference over the vocabulary
        apart = np.max(np.abs(got[1].astype(np.float32) - want[1].astype(np.float32)), axis=(0, 2))
        out = {
            "system_loss": float(got[0]), "plain_loss": float(want[0]),
            "loss": abs(float(got[0]) - float(want[0])),
            "logits_p50": float(np.quantile(apart, 0.5)), "logits_p90": float(np.quantile(apart, 0.9)),
            "logits_max": float(apart.max()),
        }
        for name, ref_grad in want[2].items():
            ref_grad = ref_grad.astype(np.float32)
            diff = np.linalg.norm(got[2][name].astype(np.float32) - ref_grad)
            out["grad_" + name] = float(diff / max(np.linalg.norm(ref_grad), 1e-30))
        out["seconds"] = time.perf_counter() - t0
        return out

    def losses_on(self, host_batch: dict, *, reference_dtype=None) -> tuple[float, float]:
        """(the program's loss, the plain float32 reference's loss), the
        second ``nan`` when the logits or a named gradient are outside their
        limits (``guarantees`` in the configuration file).  ``reference_dtype``
        as :meth:`compare` takes it: the precision control, which a run has to
        report as not correct."""
        self._write_step_scopes(host_batch)
        found = self.compare(host_batch, reference_dtype=reference_dtype)
        limits = self.config["guarantees"]
        ok = True
        for name, value in found.items():
            limit = limits.get(f"reference_{name}_tolerance")
            if limit is None:
                _log(f"reference comparison {name}: {value:.6g}")
                continue
            inside = math.isfinite(value) and value <= limit
            _log(f"reference comparison {name}: {value:.6g} (limit {limit:g}){'' if inside else '  OUTSIDE'}")
            ok = ok and (inside or name == "loss")  # the driver holds the loss to its limit itself
        return found["system_loss"], (found["plain_loss"] if ok else float("nan"))

    # ------------------------------------------------------------- scopes

    def _write_step_scopes(self, host_batch: dict) -> None:
        """Where this process has traced, write ``{instruction: scope}`` of the
        compiled step beside the trace: a TPU profile names each operation by
        its HLO instruction and carries no ``jax.named_scope``; the compiled
        program's text has both (``chipbench/scopes.py`` reads the file)."""
        from chipbench import program_spans

        path = program_spans.newest_xplane()
        if path is None or os.path.getmtime(path) < _STARTED or self._batch_shape is None:
            return
        import jax

        batch = jax.ShapeDtypeStruct(self._batch_shape, host_batch["ids"].dtype)
        text = self._step.lower(self.params, self.opt_state, batch, batch).compile().as_text()
        logdir = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(path))))
        with open(os.path.join(logdir, SCOPES_FILE), "w") as f:
            json.dump(scopes_of(text), f)


_STARTED = time.time()
_SCOPE = re.compile(r"lakesoul\.lm\.[a-z.]*[a-z]")
_DEFINED = re.compile(r"^\s*(ROOT\s+)?%?([\w.\-]+)\s*=")
_CALLS = re.compile(r"calls=%?([\w.\-]+)")
_COMPUTATION = re.compile(r"^%?([\w.\-]+)\s.*\{\s*$")


def scopes_of(hlo_text: str) -> dict[str, str]:
    """``{instruction name: "lakesoul.lm...."}`` for the instructions of a
    compiled module whose metadata carries one of the program's scopes.  A
    fusion is charged to the scope of its fused computation's root, and to its
    own metadata where the root has none."""

    def scope_in(line: str) -> str | None:
        at = line.find('op_name="')
        found = _SCOPE.findall(line[at: line.find('"', at + 9)]) if at >= 0 else []
        return found[-1] if found else None

    roots: dict[str, str] = {}   # computation → its root's scope
    fusions: list[tuple[str, str | None, str | None]] = []
    out: dict[str, str] = {}
    computation = None
    for line in hlo_text.splitlines():
        if not line.startswith((" ", "\t")):
            opened = _COMPUTATION.match(line)
            computation = opened.group(1) if opened else computation
            continue
        defined = _DEFINED.match(line)
        if not defined:
            continue
        scope = scope_in(line)
        if defined.group(1) and scope and computation:
            roots[computation] = scope
        called = _CALLS.search(line) if " fusion(" in line else None
        if called:
            fusions.append((defined.group(2), called.group(1), scope))
        elif scope:
            out[defined.group(2)] = scope
    for name, called, own in fusions:
        scope = roots.get(called) or own
        if scope:
            out[name] = scope
    return out


def build(config: dict, plan, seed: int) -> Consumer:
    return Consumer(config, plan, seed)
