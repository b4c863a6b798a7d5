"""Consumer adaptor: next-token training of the ``afmoe`` family
(``lakesoul_tpu/models/afmoe.py`` on the shared stack of
``models/causal_lm.py``: Trinity-Mini) through ``models/train.py``.

What ``consumers/lfm2_moe_clm.py`` is to its model, and built on it: state,
step, the program's loss and logits, the limits of ``guarantees`` and the
scope file are that adaptor's ``Consumer``, loaded from its file; this file
brings what the family changes: its configuration, its operation count (the
scores and values over the pairs each layer's mask lets through), the
comparison with the plain reference (loss, logits, gradients of one leaf of
each new kind: ``losses_on`` hands the driver ``nan`` for the plain loss when
one of them is outside its limit).  The host transform and the scope map are
the causal-LM adaptors' own: all four feed the same ``(ids, labels)`` and
write the same ``step_scopes.json``, an instruction charged to its innermost
scope, so the window layers' mixers read ``lakesoul.lm.swa`` and the full
layers' ``lakesoul.lm.attn``.

The program's model is imported at the top of this file: laid over a program
that lacks it (the parent of the PR that added this cell), the run fails at
import, within seconds, and not after a table build.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

from lakesoul_tpu.models.afmoe import AfmoeConfig

from chipbench.spec import load_module

_HERE = os.path.dirname(os.path.abspath(__file__))
# a copy of the LFM2 adaptor that is this file's alone (``load_module`` shares none)
_lfm2 = load_module(os.path.join(_HERE, "lfm2_moe_clm.py"))
# (query, key) pairs of one head's row that a mask lets through: the kernels' cost functions' own count
visible_pairs = load_module(os.path.join(os.path.dirname(_HERE), "kernels", "flash_attention.py")).visible_pairs
transform = _lfm2.transform   # token rows → (ids, labels shifted left by one)
scopes_of = _lfm2.scopes_of   # a compiled step's {instruction: its innermost "lakesoul.lm...."}

STEP_MODULE = "jit_train_step"  # the step program's name in a device trace
LOGIT_POSITIONS = 256           # positions of the held row whose logits are compared


def _log(message: str) -> None:
    print(f"[afmoe_clm] {message}", file=sys.stderr, flush=True)


def model_config(config: dict) -> AfmoeConfig:
    m = config["model"]
    return AfmoeConfig.from_published(
        m, experts_held=(m["first_expert_held"], m["num_experts_held"]), dtype=m["compute_dtype"]
    )


def flops_per_row(config: dict) -> float:
    """Forward and backward operations one row (one sequence) requires.

    Per token, forward, 2 operations a multiply-add over the parameters a token
    touches.  An attention mixer's five projections (``W_q`` and the gate's
    ``W_g`` 2,048 x 4,096 each, ``W_k`` and ``W_v`` 2,048 x 512, ``W_o`` 4,096 x
    2,048: 27.26 M; five mixers, 136.3 M); the dense feed-forward (3 x 2,048 x
    6,144 = 37.75 M); in each of the four routed layers the router (0.26 M),
    the shared expert (6.29 M) and the routed experts at the expected ``top_k
    x held / experts`` of one expert (8 x 16/128 x 6.29 M = 6.29 M: what lands
    on this chip under even routing, not the worst case); the head over the
    held vocabulary (51.25 M): 276.7 M multiply-adds.  Then the scores and
    values, ``4 x head_dim`` operations a query head and visible (query, key)
    pair: a full layer's row has 33.56 M pairs a head (550 GFLOP over 32 heads
    of 128), a window layer's 14.68 M (241 GFLOP): what the mask lets through,
    not the tiles a kernel runs.  At the published widths with layers 1 to 5,
    16 experts and 25,024 vocabulary rows that is 6.05 TFLOP a row forward,
    18.1 trained: backward costs twice the forward.  The embedding lookup and
    its scale, norms, rotary positions, the gate's sigmoid, softmax, routing
    and the optimizer are left out, as is every recomputation (each mixer, the
    dense feed-forward, each routed layer's norm, routing and shared expert
    are computed again in the backward pass)."""
    m = config["model"]
    seq = config["table"]["seq"]
    h = m["hidden_size"]
    cfg = model_config(config)
    q_width = m["num_attention_heads"] * m["head_dim"]
    kv_width = m["num_key_value_heads"] * m["head_dim"]
    mixer = 2 * h * q_width + 2 * h * kv_width + q_width * h
    expert = 3 * h * m["moe_intermediate_size"]
    ffns = {
        "dense": 3 * h * m["intermediate_size"],
        "moe": (h * m["num_experts"] + m["num_shared_experts"] * expert
                + m["num_experts_per_tok"] * m["num_experts_held"] / m["num_experts"] * expert),
    }
    kinds = cfg.layer_kinds()
    params = len(kinds) * mixer + sum(ffns[f] for f in cfg.ffn_kinds()) + h * m["vocab_size"]
    pairs = sum(visible_pairs(seq, m["sliding_window"] if kind == "swa" else None) for kind in kinds)
    return 3.0 * (seq * 2 * params + 4 * q_width * pairs)


def _picked(tree: dict, cfg: AfmoeConfig) -> dict:
    """One leaf of each new kind, by what the comparison calls it: of the first
    sparse window layer the query and gate matrices, the mixer's output norm,
    the router, the shared expert's and the first held expert's ``w_down`` and
    the feed-forward's output norm (over the two summed); of the full layer
    its key matrix and ``W_o``; the dense layer's ``w_down``."""
    kinds, ffns = cfg.layer_kinds(), cfg.ffn_kinds()
    window = next(lp for lp, k, f in zip(tree["layers"], kinds, ffns) if (k, f) == ("swa", "moe"))
    full = tree["layers"][kinds.index("attn")]
    return {
        "swa_w_q": window["swa"]["w_q"], "swa_w_gate": window["swa"]["w_gate"], "norm1_out": window["norm1_out"],
        "router": window["moe"]["router"], "shared_w_down": window["moe"]["shared"]["w_down"],
        "expert_w_down": window["moe"]["w_down"][0], "norm2_out": window["norm2_out"],
        "full_w_k": full["attn"]["w_k"], "full_w_o": full["attn"]["w_o"],
        "dense_w_down": tree["layers"][ffns.index("dense")]["mlp"]["w_down"],
    }


# the two names through which the copy's ``Consumer`` reaches its family: with these it builds this
# family's state and step and logs under this file's name
_lfm2.model_config, _lfm2._log = model_config, _log


class Consumer(_lfm2.Consumer):
    """The LFM2 adaptor's consumer (``make_lm_train_state`` and
    ``make_lm_train_step`` as a training job calls them, ``step``, the
    program's loss and logits, ``losses_on`` against ``guarantees``, the scope
    file) with this family's comparison."""

    def compare(self, host_batch: dict, *, reference_dtype=None) -> dict:
        """The program against the plain reference on the same rows with the
        weights as they stand, at the timed width and length: the loss, the
        logits at ``LOGIT_POSITIONS`` positions spread over the row (largest
        absolute difference at each) and the gradient of one leaf of each new
        kind (norm of the difference over the reference's norm).
        ``reference_dtype`` computes the reference in a lower precision
        instead (how the limits were set)."""
        import jax
        import jax.numpy as jnp

        from reference import afmoe_f32 as plain

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
        got = jax.device_get(both(lambda p: self._program(p, ids, labels, positions))(self.params))
        kwargs = {} if reference_dtype is None else {"dtype": reference_dtype}
        with jax.default_matmul_precision("highest"):
            want = jax.device_get(both(
                lambda p: plain.lm_loss(p, ids, labels, cfg=m, held=held, logits_at=positions, **kwargs)
            )(self.params))
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


def build(config: dict, plan, seed: int) -> Consumer:
    return Consumer(config, plan, seed)
