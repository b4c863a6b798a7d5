"""Consumer adaptor: training of the ``ouro`` family
(``lakesoul_tpu/models/ouro.py`` on the shared stack of
``models/causal_lm.py``: Ouro-2.6B, a looped language model) through
``models/train.py``.

What ``consumers/afmoe_clm.py`` is to its model, and built the same way on the
LFM2 adaptor's ``Consumer``, loaded from its file: state, step, ``losses_on``
against the limits of ``guarantees`` and the scope file are that adaptor's;
this file brings what the family changes: its configuration, its operation
count (``R x L`` layer passes by the causal mask's visible pairs and ``R`` head
passes), and the comparison with the plain reference: the looped objective,
each pass's loss, the exit distribution, the logits of the first and of the
last pass, the gradients of one leaf of each kind the loop touches differently
(``losses_on`` hands the driver ``nan`` for the plain loss when one of them is
outside its limit).  The host transform and the scope map are the causal-LM
adaptors' own: all five feed the same ``(ids, labels)`` and write the same
``step_scopes.json``, an instruction charged to its innermost scope; the pass
loop's body is a computation like any other, so the attention kernels inside
it read ``lakesoul.lm.attn`` and the gate and the objective
``lakesoul.lm.exit``.

The program's model is imported at the top of this file: laid over a program
that lacks it (the parent of the PR that added this cell), the run fails at
import, within seconds, and not after a table build.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

from lakesoul_tpu.models.ouro import OuroConfig

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
FIRST_AND_LAST = np.array([0, -1])  # the passes whose logits are compared, off the stacked axis
EXIT_MASS = "lakesoul_train_loop_exit_mass"  # the step's gauge of the exit distribution


def _log(message: str) -> None:
    print(f"[ouro_clm] {message}", file=sys.stderr, flush=True)


def model_config(config: dict) -> OuroConfig:
    m = config["model"]
    return OuroConfig.from_published(m, dtype=m["compute_dtype"])  # ``exit_beta`` rides in ``model``


def flops_per_row(config: dict) -> float:
    """Forward and backward operations one row (one sequence) requires.

    Per token, forward, 2 operations a multiply-add over the parameters a token
    touches, and every layer ``total_ut_steps`` times: a layer's four attention
    projections (4 x 2,048 x 2,048 = 16.78 M) and its SwiGLU (3 x 2,048 x 5,632
    = 34.60 M): 51.38 M, six layers 308.3 M a pass; the head over the whole
    vocabulary (2,048 x 49,152 = 100.7 M), once a pass.  Then the scores and
    values, ``4 x head_dim`` operations a query head and visible (query, key)
    pair: 33.56 M pairs a head under the causal mask (275 GFLOP over 16 heads
    of 128 a layer and pass).  At the published widths with six layers and
    four passes that is 8.35 TFLOP a pass forward (6.70 in the layers, 1.65 in
    the head), 33.4 a row, 100.2 trained: backward costs twice the forward
    (the backward kernel's second run of the scores is a recomputation).
    The embedding lookup, norms, rotary positions, softmax, the exit gate, the
    objective and the optimizer are left out, as is every recomputation (each
    mixer and each dense feed-forward is computed again in the backward
    pass)."""
    m = config["model"]
    seq = config["table"]["seq"]
    h = m["hidden_size"]
    q_width = m["num_attention_heads"] * m["head_dim"]
    kv_width = m["num_key_value_heads"] * m["head_dim"]
    layer = 2 * h * q_width + 2 * h * kv_width + 3 * h * m["intermediate_size"]
    a_pass = m["num_hidden_layers"] * (seq * 2 * layer + 4 * q_width * visible_pairs(seq, None)) \
        + seq * 2 * h * m["vocab_size"]
    return 3.0 * m["total_ut_steps"] * a_pass


def _picked(tree: dict) -> dict:
    """One leaf of each kind the loop touches differently, by what the
    comparison calls it: the first layer's query matrix (the deepest below the
    last loss: its gradient crosses every pass), the last layer's ``w_down``,
    a mixer's output norm, the final norm (between passes and before every
    head), the head (four uses), the embedding (the first pass alone), the exit
    gate's vector and bias."""
    return {
        "first_w_q": tree["layers"][0]["attn"]["w_q"], "last_w_down": tree["layers"][-1]["mlp"]["w_down"],
        "norm1_out": tree["layers"][0]["norm1_out"], "final_norm": tree["final_norm"], "head": tree["head"],
        "embed": tree["embed"], "w_exit": tree["exit"]["w"], "b_exit": tree["exit"]["b"],
    }


# the two names through which the copy's ``Consumer`` reaches its family: with these it builds this
# family's state and step and logs under this file's name
_lfm2.model_config, _lfm2._log = model_config, _log


class Consumer(_lfm2.Consumer):
    """The LFM2 adaptor's consumer (``make_lm_train_state`` and
    ``make_lm_train_step`` as a training job calls them, ``step``,
    ``losses_on`` against ``guarantees``, the scope file) with this family's
    program and comparison."""

    def _program(self, params, ids, labels, positions):
        """(the objective, what the comparison reads beside it) as the timed
        path computes them: ``cfg.loss``'s own pieces, and the first and the
        last pass's logits at ``positions``."""
        from lakesoul_tpu.models.causal_lm import exit_loss, lm_head, loop_hidden

        states, _ = loop_hidden(params, ids, cfg=self.cfg)
        loss, counts = exit_loss(params, states, labels, cfg=self.cfg)
        logits = lm_head({"head": params["head"]}, states[FIRST_AND_LAST][:, :, positions], cfg=self.cfg)
        return loss, {"loss_pass": counts["loss_pass"], "exit_mass": counts["exit_mass"], "logits": logits}

    def losses_on(self, host_batch: dict, *, reference_dtype=None) -> tuple[float, float]:
        """The LFM2 adaptor's, after a line for the log: the gauge
        ``lakesoul_train_loop_exit_mass`` over every step this process ran
        (the mean share of the exit distribution on each pass: sums to 1)."""
        from lakesoul_tpu.obs import registry

        mass = {k: round(float(v), 6) for k, v in sorted(registry().snapshot().items()) if k.startswith(EXIT_MASS)}
        _log(f"exit mass over the run's steps: {mass}, sum {sum(mass.values()):.6f}")
        return super().losses_on(host_batch, reference_dtype=reference_dtype)

    def compare(self, host_batch: dict, *, reference_dtype=None) -> dict:
        """The program against the plain reference on the same rows with the
        weights as they stand, at the timed width and length: the objective,
        each pass's mean NLL and the exit distribution (largest absolute
        difference over the passes), the first and the last pass's logits at
        ``LOGIT_POSITIONS`` positions spread over the row (largest absolute
        difference at each position: median, 90th percentile, largest) and the
        gradient of one leaf of each kind (norm of the difference over the
        reference's norm).  ``reference_dtype`` computes the reference in a
        lower precision instead (how the limits were set)."""
        import jax
        import jax.numpy as jnp

        from reference import ouro_f32 as plain

        m = self.config["model"]
        ids, labels = jnp.asarray(host_batch["ids"]), jnp.asarray(host_batch["labels"])
        positions = jnp.asarray(np.linspace(0, ids.shape[1] - 1, LOGIT_POSITIONS).astype(np.int32))

        def both(fn):
            def run(params):
                (loss, aux), grads = jax.value_and_grad(fn, has_aux=True)(params)
                return loss, aux, _picked(grads)

            return jax.jit(run)

        kwargs = {} if reference_dtype is None else {"dtype": reference_dtype}

        def reference(p):
            loss, aux = plain.lm_loss(p, ids, labels, cfg=m, beta=m["exit_beta"], logits_at=positions, **kwargs)
            return loss, dict(aux, logits=aux["logits"][FIRST_AND_LAST])

        t0 = time.perf_counter()
        got = jax.device_get(both(lambda p: self._program(p, ids, labels, positions))(self.params))
        with jax.default_matmul_precision("highest"):
            want = jax.device_get(both(reference)(self.params))
        out = {
            "system_loss": float(got[0]), "plain_loss": float(want[0]),
            "loss": abs(float(got[0]) - float(want[0])),
        }
        for name in ("loss_pass", "exit_mass"):
            system, plain_value = (np.asarray(x[1][name], np.float32) for x in (got, want))
            _log(f"{name}: program {system.tolist()}, reference {plain_value.tolist()}")
            out[name] = float(np.max(np.abs(system - plain_value)))
        for which, name in ((0, "first"), (1, "last")):
            # per compared position, the largest difference over the vocabulary
            apart = np.max(np.abs(got[1]["logits"][which].astype(np.float32)
                                  - want[1]["logits"][which].astype(np.float32)), axis=(0, 2))
            out.update({f"logits_{name}_p50": float(np.quantile(apart, 0.5)),
                        f"logits_{name}_p90": float(np.quantile(apart, 0.9)),
                        f"logits_{name}_max": float(apart.max())})
        for name, ref_grad in want[2].items():
            ref_grad = ref_grad.astype(np.float32)
            diff = np.linalg.norm(got[2][name].astype(np.float32) - ref_grad)
            out["grad_" + name] = float(diff / max(np.linalg.norm(ref_grad), 1e-30))
        out["seconds"] = time.perf_counter() - t0
        return out


def build(config: dict, plan, seed: int) -> Consumer:
    return Consumer(config, plan, seed)
