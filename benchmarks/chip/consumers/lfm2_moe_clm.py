"""Consumer adaptor: next-token training of the LFM2-MoE family
(``lakesoul_tpu/models/lfm2_moe.py`` on the shared stack of
``models/causal_lm.py``) through ``models/train.py``.

What ``consumers/qwen3_next_clm.py`` is to its model: what the trainer driver
needs of a model kind, and this cell's comparison with the plain reference.
The driver gates ``correct`` on ``|system - plain| <= reference_loss_tolerance``
alone, so :meth:`Consumer.losses_on` also compares logits and gradients and
hands the driver ``nan`` for the plain loss when one of them is outside its
limit.  The host transform and the reading of a compiled step's scopes are the
other causal-LM adaptor's, loaded from its file: both consumers feed the same
``(ids, labels)`` and write the same ``step_scopes.json``.

The program's model is imported at the top of this file: laid over a program
that lacks it (the parent of the PR that added this cell), the run fails at
import, within seconds, and not after a table build.
"""

from __future__ import annotations

import functools
import json
import math
import os
import sys
import time

import numpy as np

from lakesoul_tpu.models.bert import labelled_nll
from lakesoul_tpu.models.causal_lm import head_params, lm_head, lm_hidden
from lakesoul_tpu.models.lfm2_moe import Lfm2MoeConfig

from chipbench.spec import load_module

_clm = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)), "qwen3_next_clm.py"))
transform = _clm.transform    # token rows → (ids, labels shifted left by one)
scopes_of = _clm.scopes_of    # a compiled step's {instruction: "lakesoul.lm...."}

STEP_MODULE = "jit_train_step"  # the step program's name in a device trace
LOGIT_POSITIONS = 256           # positions of the held row whose logits are compared
SCOPES_FILE = "step_scopes.json"  # instruction → scope of the compiled step, beside the trace


def _log(message: str) -> None:
    print(f"[lfm2_moe_clm] {message}", file=sys.stderr, flush=True)


def model_config(config: dict) -> Lfm2MoeConfig:
    m = config["model"]
    return Lfm2MoeConfig.from_published(
        m, experts_held=(m["first_expert_held"], m["num_experts_held"]), dtype=m["compute_dtype"]
    )


def flops_per_row(config: dict) -> float:
    """Forward and backward operations one row (one sequence) requires.

    Per token, forward, 2 operations a multiply-add over the parameters a token
    touches: a convolution mixer's two projections (``W_in`` 2,048 x 6,144 and
    ``W_out``: 16.78 M; four such layers, 67.1 M) or the attention mixer's
    four (10.49 M); the dense feed-forward (3 x 2,048 x 7,168 = 44.04 M); in
    each routed layer the router (0.066 M) and the routed experts at the
    expected ``top_k x held / experts`` of one expert (4 x 8/32 x 11.01 M =
    11.01 M: what lands on this chip under even routing, not the worst case;
    four layers, 44.04 M); the tied head over the held vocabulary (33.55 M):
    199.5 M multiply-adds.  Then the causal scores, half of ``4 T d`` over the
    query width (33.6 M operations at 8,192 tokens).  At the published widths
    with layers 1 to 5, 8 experts and 16,384 vocabulary rows that is 0.4327
    GFLOP a token forward, 1.298 trained: backward costs twice the forward.
    The embedding lookup, norms, the convolution's three taps and two gates,
    rotary positions, softmax, routing and the optimizer are left out, as is
    every recomputation (each mixer, the dense feed-forward, each routed
    layer's norm and routing and each block of attention rows is computed
    again in the backward pass)."""
    m = config["model"]
    seq = config["table"]["seq"]
    h = m["hidden_size"]
    cfg = model_config(config)
    q_width = m["num_attention_heads"] * cfg.head_dim
    kv_width = m["num_key_value_heads"] * cfg.head_dim
    mixers = {"conv": h * 3 * h + h * h, "attn": h * q_width + 2 * h * kv_width + q_width * h}
    expert = 3 * h * m["moe_intermediate_size"]
    ffns = {
        "dense": 3 * h * m["intermediate_size"],
        "moe": h * m["num_experts"] + m["num_experts_per_tok"] * m["num_experts_held"] / m["num_experts"] * expert,
    }
    kinds = cfg.layer_kinds()
    params = sum(mixers[k] for k in kinds) + sum(ffns[f] for f in cfg.ffn_kinds()) + h * m["vocab_size"]
    scores = kinds.count("attn") * 4 * seq * q_width / 2
    return 3.0 * seq * (2 * params + scores)


def _picked(tree: dict, cfg: Lfm2MoeConfig) -> dict:
    """One leaf of each new kind, by what the comparison calls it: the first
    routed convolution layer's kernel and the ``B`` columns of its ``W_in``,
    the attention layer's ``q_layernorm``, that layer's router and its first
    held expert's ``W2``, and the dense layer's ``W2``."""
    kinds, ffns = cfg.layer_kinds(), cfg.ffn_kinds()
    conv = next(lp["conv"] for lp, k, f in zip(tree["layers"], kinds, ffns) if (k, f) == ("conv", "moe"))
    attn = tree["layers"][kinds.index("attn")]
    return {
        "conv": conv["conv"],
        "w_in_b": conv["w_in"][:, : cfg.hidden_size],
        "q_layernorm": attn["attn"]["q_norm"],
        "router": attn["moe"]["router"],
        "expert_w2": attn["moe"]["w_down"][0],
        "dense_w2": tree["layers"][ffns.index("dense")]["mlp"]["w_down"],
    }


class Consumer:
    """State and step on a mesh plan, built the way a training job builds
    them: ``make_lm_train_state`` makes the weights and the bias on the device
    from the seed, ``make_lm_train_step`` jits the step."""

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
        head = head_params(params)
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

        from reference import lfm2_moe_f32 as plain

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
        # the window is over and nothing steps again: AdamW's moments (4.1 GB) make room for the
        # reference's backward pass (5.5 GB of scratch in float32, 9.3 in the bfloat16 control)
        self.opt_state = None
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
        compiled step beside the trace (``chipbench/scopes.py`` reads the
        file; ``conv_step_share_pct`` and ``mlp_step_share_pct`` through it)."""
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


def build(config: dict, plan, seed: int) -> Consumer:
    return Consumer(config, plan, seed)
