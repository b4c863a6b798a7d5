"""Consumer adaptor: next-token and next-but-one-token training of the
``glm4_moe_lite`` family (``lakesoul_tpu/models/glm4_moe_lite.py`` on the shared
stack of ``models/causal_lm.py``) through ``models/train.py``.

What ``consumers/lfm2_moe_clm.py`` is to its model, and built on it: state,
step, the limits of ``guarantees`` and the scope file are that adaptor's
``Consumer``, loaded from its file; this file brings what the family changes:
its configuration, its operation count, the comparison with the plain
reference (both loss terms, both heads' logits, gradients of the new kinds of
leaf: ``losses_on`` hands the driver ``nan`` for the plain loss when one of
them is outside its limit) and its scope map.  The host transform is the
causal-LM adaptors' one: all three feed the same ``(ids, labels)`` and write
the same ``step_scopes.json``.

The scope map is this file's own (:func:`scopes_of`).  The prediction module
holds a whole decoder layer, whose operations carry the layer's scopes
(``attn``, ``mla``, ``moe.*``, ``head``) inside ``lakesoul.lm.mtp``: by the
other adaptors' innermost-scope rule the module would scatter over them.  Here
an instruction whose ``op_name`` carries ``lakesoul.lm.mtp`` at any depth is
the module's, and every other instruction its innermost scope's: ``attn``,
``mla``, ``moe.*`` and ``head`` read the main stack, ``mtp`` what the second
loss costs.

The program's model is imported at the top of this file: laid over a program
that lacks it (the parent of the PR that added this cell), the run fails at
import, within seconds, and not after a table build.
"""

from __future__ import annotations

import os
import re
import sys
import time

import numpy as np

from lakesoul_tpu.models.glm4_moe_lite import Glm4MoeLiteConfig

from chipbench.spec import load_module

# a copy of the LFM2 adaptor that is this file's alone (``load_module`` shares none)
_lfm2 = load_module(os.path.join(os.path.dirname(os.path.abspath(__file__)), "lfm2_moe_clm.py"))
transform = _lfm2.transform   # token rows → (ids, labels shifted left by one)

STEP_MODULE = "jit_train_step"  # the step program's name in a device trace
LOGIT_POSITIONS = 256           # positions of the held row whose logits are compared
MTP_SCOPE = "lakesoul.lm.mtp"   # the prediction module's scope: wins over any scope inside it


def _log(message: str) -> None:
    print(f"[glm4_moe_lite_clm] {message}", file=sys.stderr, flush=True)


def model_config(config: dict) -> Glm4MoeLiteConfig:
    m = config["model"]
    return Glm4MoeLiteConfig.from_published(
        m, experts_held=(m["first_expert_held"], m["num_experts_held"]), dtype=m["compute_dtype"]
    )


def flops_per_row(config: dict) -> float:
    """Forward and backward operations one row (one sequence) requires.

    Per token, forward, 2 operations a multiply-add over the parameters a token
    touches.  A latent-attention mixer's five projections (``W_dq`` 2,048 x 768,
    ``W_uq`` 768 x 5,120, ``W_dkv`` 2,048 x 576, ``W_ukv`` 512 x 8,960, ``W_o``
    5,120 x 2,048: 21.76 M; six mixers, the module's among them: 130.5 M); the
    dense feed-forward (3 x 2,048 x 10,240 = 62.9 M); in each of the five routed
    layers (the module's among them) the router (0.13 M), the shared expert
    (9.44 M) and the routed experts at the expected ``top_k x held / experts``
    of one expert (4 x 8/64 x 9.44 M = 4.72 M: what lands on this chip under
    even routing, not the worst case); the module's ``eh_proj`` (8.39 M); the
    head over the held vocabulary, twice (2 x 39.65 M).  Then the causal
    scores and values, half of ``4 T d`` over the 5,120 query channels a mixer
    (83.9 M operations at 8,192 tokens).  At the published widths with layers
    0 to 4, the module, 8 experts and 19,360 vocabulary rows that is 1.210
    GFLOP a token forward, 3.63 trained: backward costs twice the forward.
    The embedding lookups, norms, rotary positions, softmax, routing and the
    optimizer are left out, as is every recomputation (each mixer, the dense
    feed-forward, each routed layer's norm, routing and shared expert are
    computed again in the backward pass)."""
    m = config["model"]
    seq = config["table"]["seq"]
    h = m["hidden_size"]
    cfg = model_config(config)
    heads, nope, rope, v = m["num_attention_heads"], m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    mixer = (h * m["q_lora_rank"] + m["q_lora_rank"] * heads * (nope + rope) + h * (m["kv_lora_rank"] + rope)
             + m["kv_lora_rank"] * heads * (nope + v) + heads * v * h)
    expert = 3 * h * m["moe_intermediate_size"]
    ffns = {
        "dense": 3 * h * m["intermediate_size"],
        "moe": (h * m["n_routed_experts"] + m["n_shared_experts"] * expert
                + m["num_experts_per_tok"] * m["num_experts_held"] / m["n_routed_experts"] * expert),
    }
    kinds = list(cfg.ffn_kinds())
    heads_run = 1
    params = 0.0
    if m["num_nextn_predict_layers"]:
        kinds.append(kinds[-1])  # the module's layer
        heads_run = 2
        params += 2 * h * h      # eh_proj
    params += len(kinds) * mixer + sum(ffns[f] for f in kinds) + heads_run * h * m["vocab_size"]
    scores = len(kinds) * 4 * seq * heads * (nope + rope) / 2
    return 3.0 * seq * (2 * params + scores)


_innermost_scopes_of = _lfm2.scopes_of  # the other adaptors' rule: an instruction's innermost scope
_OP_NAME = re.compile(r'op_name="[^"]*"')
_SCOPE = re.compile(r"lakesoul\.lm\.[a-z.]*[a-z]")


def scopes_of(hlo_text: str) -> dict[str, str]:
    """``{instruction name: "lakesoul.lm...."}`` as the other adaptors'
    ``scopes_of`` reads a compiled module (a fusion charged to its root's
    scope), over a text in which every ``op_name`` that carries
    ``lakesoul.lm.mtp`` at any depth carries no other scope: the module's
    instructions are the module's, every other its innermost scope's."""

    def whole(match):
        name = match.group(0)
        return _SCOPE.sub(MTP_SCOPE, name) if MTP_SCOPE in name else name

    return _innermost_scopes_of(_OP_NAME.sub(whole, hlo_text))


def _picked(tree: dict) -> dict:
    """One leaf of each new kind, by what the comparison calls it: the five
    matrices of the first sparse layer's latent attention, the module's
    ``eh_proj`` and its layer's router, and of the first sparse layer the
    shared expert's and the first held expert's ``w_down``."""
    sparse = next(lp for lp in tree["layers"] if "moe" in lp)
    return {
        **{name: sparse["mla"][name] for name in ("w_dq", "w_uq", "w_dkv", "w_ukv", "w_o")},
        "eh_proj": tree["mtp"]["eh_proj"],
        "mtp_router": tree["mtp"]["layer"]["moe"]["router"],
        "shared_w_down": sparse["moe"]["shared"]["w_down"],
        "expert_w_down": sparse["moe"]["w_down"][0],
    }


# the three names through which the copy's ``Consumer`` reaches its family: with these it builds this
# family's state and step, logs under this file's name and writes the scope map that charges the module whole
_lfm2.model_config, _lfm2.scopes_of, _lfm2._log = model_config, scopes_of, _log


class Consumer(_lfm2.Consumer):
    """The LFM2 adaptor's consumer (``make_lm_train_state`` and
    ``make_lm_train_step`` as a training job calls them, ``step``,
    ``losses_on`` against ``guarantees``, the scope file) with this family's
    program and comparison."""

    def _program(self, params, ids, labels, positions):
        """(loss, its two terms and both heads' logits at ``positions``) as
        the timed path computes them: ``cfg.loss`` is the step's own loss, the
        logits are the same layers run once more."""
        from lakesoul_tpu.models import causal_lm as lm

        cfg = self.cfg
        loss, counts = cfg.loss(params, ids, labels)
        x, _ = lm.lm_hidden(params, ids, cfg=cfg)
        h, _ = lm.mtp_hidden(params, x, labels, cfg=cfg)
        return loss, {
            "loss_main": counts["loss_main"], "loss_mtp": counts["loss_mtp"],
            "logits": lm.lm_head(lm.head_params(params), x[:, positions], cfg=cfg),
            "logits_mtp": lm.lm_head(lm.mtp_head_params(params), h[:, positions], cfg=cfg),
        }

    def compare(self, host_batch: dict, *, reference_dtype=None) -> dict:
        """The program against the plain reference on the same rows with the
        weights as they stand, at the timed width and length: the loss and its
        two terms, both heads' logits at ``LOGIT_POSITIONS`` positions spread
        over the row (largest absolute difference at each) and the gradient
        of one leaf of each new kind (norm of the difference over the
        reference's norm).  ``reference_dtype`` computes the reference in a
        lower precision instead (how the limits were set)."""
        import jax
        import jax.numpy as jnp

        from reference import glm4_moe_lite_f32 as plain

        m = self.config["model"]
        held = (m["first_expert_held"], m["num_experts_held"])
        ids, labels = jnp.asarray(host_batch["ids"]), jnp.asarray(host_batch["labels"])
        positions = jnp.asarray(np.linspace(0, ids.shape[1] - 1, LOGIT_POSITIONS).astype(np.int32))

        def both(fn):
            def run(params):
                (loss, aux), grads = jax.value_and_grad(fn, has_aux=True)(params)
                return loss, aux, _picked(grads)

            return jax.jit(run)

        t0 = time.perf_counter()
        got = jax.device_get(both(lambda p: self._program(p, ids, labels, positions))(self.params))
        kwargs = {} if reference_dtype is None else {"dtype": reference_dtype}
        with jax.default_matmul_precision("highest"):
            want = jax.device_get(both(
                lambda p: plain.lm_loss(p, ids, labels, cfg=m, held=held, logits_at=positions, **kwargs)
            )(self.params))
        out = {"system_loss": float(got[0]), "plain_loss": float(want[0]), "loss": abs(float(got[0]) - float(want[0]))}
        for term in ("loss_main", "loss_mtp"):
            out[term] = abs(float(got[1][term]) - float(want[1][term]))
        for name in ("logits", "logits_mtp"):
            # per compared position, the largest difference over the vocabulary
            apart = np.max(np.abs(got[1][name].astype(np.float32) - want[1][name].astype(np.float32)), axis=(0, 2))
            out.update({f"{name}_p50": float(np.quantile(apart, 0.5)), f"{name}_p90": float(np.quantile(apart, 0.9)),
                        f"{name}_max": float(apart.max())})
        for name, ref_grad in want[2].items():
            ref_grad = ref_grad.astype(np.float32)
            diff = np.linalg.norm(got[2][name].astype(np.float32) - ref_grad)
            out["grad_" + name] = float(diff / max(np.linalg.norm(ref_grad), 1e-30))
        out["seconds"] = time.perf_counter() - t0
        return out


def build(config: dict, plan, seed: int) -> Consumer:
    return Consumer(config, plan, seed)
