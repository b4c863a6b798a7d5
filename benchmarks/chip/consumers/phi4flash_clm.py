"""Consumer adaptor: next-token training of the ``phi4flash`` family
(``lakesoul_tpu/models/phi4flash.py`` on the shared stack of
``models/causal_lm.py``: Phi-4-mini-flash-reasoning, a decoder-hybrid-decoder
model whose second half reads the first half's state) through
``models/train.py``.

What ``consumers/ouro_clm.py`` is to its model, and built the same way on the
LFM2 adaptor's ``Consumer``, loaded from its file: state, step, ``_program``
(the hidden states, the loss's tile loop and the logits at the compared
positions as the timed path computes them), ``losses_on`` against the limits
of ``guarantees`` and the scope file are that adaptor's; this file brings what
the family changes: its configuration, its operation count and the comparison
with the plain reference (the loss, the logits, the gradient of one leaf of
each new kind: ``losses_on`` hands the driver ``nan`` for the plain loss when
one of them is outside its limit).  The host transform and the scope map are
the causal-LM adaptors' own: an instruction is charged to its innermost
``lakesoul.lm.*`` scope, so the scan kernels read ``lakesoul.lm.ssm``, the
window layers' attention kernels ``lakesoul.lm.swa``, the full source's
``lakesoul.lm.attn`` and the cross layers' ``lakesoul.lm.xattn``.

The program's model is imported at the top of this file: laid over a program
that lacks it (the parent of the PR that added this cell), the run fails at
import, within seconds, and not after a table build.
"""

from __future__ import annotations

import os
import sys
import time

import numpy as np

from lakesoul_tpu.models.phi4flash import Phi4FlashConfig

from chipbench.spec import load_module

_HERE = os.path.dirname(os.path.abspath(__file__))
# a copy of the LFM2 adaptor that is this file's alone (``load_module`` shares none)
_lfm2 = load_module(os.path.join(_HERE, "lfm2_moe_clm.py"))
_KERNELS = os.path.join(os.path.dirname(_HERE), "kernels")
# (query, key) pairs of one head's row that a mask lets through: the attention kernels' cost functions' own count
visible_pairs = load_module(os.path.join(_KERNELS, "flash_attention.py")).visible_pairs
scan_cost = load_module(os.path.join(_KERNELS, "selective_scan.py")).cost
transform = _lfm2.transform   # token rows → (ids, labels shifted left by one)
scopes_of = _lfm2.scopes_of   # a compiled step's {instruction: its innermost "lakesoul.lm...."}

STEP_MODULE = "jit_train_step"  # the step program's name in a device trace
LOGIT_POSITIONS = 256           # positions of the held row whose logits are compared


def _log(message: str) -> None:
    print(f"[phi4flash_clm] {message}", file=sys.stderr, flush=True)


def model_config(config: dict) -> Phi4FlashConfig:
    m = config["model"]
    return Phi4FlashConfig.from_published(m, dtype=m["compute_dtype"])  # ``layers_held`` and ``mamba_*`` ride in ``model``


def flops_per_row(config: dict) -> float:
    """Forward and backward operations one row (one sequence) requires.

    Per token, forward, 2 operations a multiply-add over the parameters a token
    touches: every layer's SwiGLU (3 x 2,560 x 10,240 = 78.64 M); a Mamba
    mixer's four products (``W_in`` 26.21 M, ``W_x`` 0.98 M, ``W_dt`` 0.82 M,
    ``W_out`` 13.11 M: 41.12 M); an attention mixer's four (19.66 M); a gated
    memory unit's two (26.21 M); a cross layer's two (13.11 M); the tied head
    over the held vocabulary (64.02 M).  Then what is no matrix product: the
    scan's recurrence as its multiply-adds (``kernels/selective_scan.py``:
    ``3 N + 2`` a token and channel, 4.2 GFLOP a layer and row), and the
    differential scores and values, ``12 d`` operations a query PAIR and
    visible (query, key) pair (two score maps of ``2 d`` and two products with
    a value ``2 d`` wide of ``4 d``): 20 pairs of heads over the window's band
    (4.06 M pairs) in a window layer, over the causal triangle (33.56 M) in
    the full source and in a cross layer.  No score map is credited twice,
    whatever the kernels run.  At the published widths with the six held
    layers and 25,008 vocabulary rows that is 12.5 TFLOP a row forward, 37.6
    trained: backward costs twice the forward.  The embedding lookup, the
    norms, the convolution's taps, softplus, the exponentials, the gates, the
    softmaxes, lambda and the optimizer are left out, as is every
    recomputation (each mixer and each dense feed-forward is computed again in
    the backward pass)."""
    m = config["model"]
    seq = config["table"]["seq"]
    cfg = model_config(config)
    h, e, d = cfg.hidden_size, cfg.inner, cfg.head_dim
    q_width, kv_width = cfg.num_attention_heads * d, cfg.num_key_value_heads * d
    attention = 2 * h * q_width + 2 * h * kv_width
    products = {  # multiply-adds a token
        "ssm": h * 2 * e + e * (cfg.mamba_dt_rank + 2 * cfg.mamba_d_state) + cfg.mamba_dt_rank * e + e * h,
        "swa": attention, "attn": attention, "gmu": 2 * h * e, "xattn": 2 * h * q_width,
    }
    pairs = cfg.num_attention_heads // 2
    maps = {  # operations a row that are no matrix product
        "ssm": scan_cost(kernel="fwd", seq=seq, channels=e, states=cfg.mamba_d_state)[0],
        "swa": 12 * d * pairs * visible_pairs(seq, cfg.sliding_window), "gmu": 0,
    }
    maps["attn"] = maps["xattn"] = 12 * d * pairs * visible_pairs(seq, None)
    kinds = cfg.layer_kinds()
    a_token = sum(products[k] for k in kinds) + len(kinds) * 3 * h * cfg.intermediate_size + h * m["vocab_size"]
    return 3.0 * (seq * 2 * a_token + sum(maps[k] for k in kinds))


def _picked(tree: dict, cfg: Phi4FlashConfig) -> dict:
    """One leaf of each new kind, by what the comparison calls it: the memory
    source's ``W_x``, ``W_dt``, ``A_log`` and ``W_out`` (the first three reach
    the loss through the scan alone, and through every gated memory unit
    after it); the gated memory unit's ``W_1``; the cross layer's ``W_q``;
    the key-value source's ``W_k`` and ``W_v`` (their gradients arrive through
    the cross layer too), one ``lq1`` and one ``g_sub``."""
    kinds = cfg.layer_kinds()

    def last(kind):
        return tree["layers"][len(kinds) - 1 - kinds[::-1].index(kind)][kind]

    source, kv_source = last("ssm"), last("attn")
    return {
        "w_x": source["w_x"], "w_dt": source["w_dt"], "a_log": source["A_log"], "w_out": source["w_out"],
        "gmu_w1": last("gmu")["w_1"], "cross_w_q": last("xattn")["w_q"],
        "w_k": kv_source["w_k"], "w_v": kv_source["w_v"], "lq1": kv_source["lq1"], "g_sub": last("xattn")["g_sub"],
    }


# the two names through which the copy's ``Consumer`` reaches its family: with these it builds this
# family's state and step and logs under this file's name
_lfm2.model_config, _lfm2._log = model_config, _log


class Consumer(_lfm2.Consumer):
    """The LFM2 adaptor's consumer (``make_lm_train_state`` and
    ``make_lm_train_step`` as a training job calls them, ``step``,
    ``_program``, ``losses_on`` against ``guarantees``, the scope file) with
    this family's comparison."""

    def compare(self, host_batch: dict, *, reference_dtype=None) -> dict:
        """The program against the plain reference on the same rows with the
        weights as they stand, at the timed width and length: the loss, the
        largest logit difference at each of ``LOGIT_POSITIONS`` positions
        spread over the row (median, 90th percentile, largest) and the
        gradient of one leaf of each new kind (norm of the difference over the
        reference's norm).  ``reference_dtype`` computes the reference in a
        lower precision instead (how the limits were set)."""
        import jax
        import jax.numpy as jnp

        from reference import phi4flash_f32 as plain

        m = self.config["model"]
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
            want = jax.device_get(
                both(lambda p: plain.lm_loss(p, ids, labels, cfg=m, logits_at=positions, **kwargs))(self.params)
            )
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
