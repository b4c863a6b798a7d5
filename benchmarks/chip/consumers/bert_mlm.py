"""Consumer adaptor: BERT masked-LM training through ``models/train.py``.

An adaptor tells the trainer driver what one kind of model needs: how to build
its state and step through the program's own entry points, which host
transform turns table rows into its batch, how many operations a row costs,
and how to compare the program's loss with the plain reference.  A new model
kind is a new file here plus a configuration that names it.
"""

from __future__ import annotations

import functools

import numpy as np

MASK_ID = 103  # [MASK] in the BERT vocabulary
STEP_MODULE = "jit_train_step"  # the step program's name in a device trace


def model_config(config: dict):
    from lakesoul_tpu.models.bert import BertConfig

    m = config["model"]
    return BertConfig(
        vocab_size=m["vocab_size"], hidden=m["hidden_size"], layers=m["num_hidden_layers"],
        heads=m["num_attention_heads"], ff=m["intermediate_size"],
        max_len=m["max_position_embeddings"], dtype=m["compute_dtype"],
    )


def flops_per_row(config: dict) -> float:
    """Forward and backward operations one row (one sequence) requires.

    Per token and layer: four ``h x h`` projections and two ``h x ff`` products
    (2 operations per multiply-add), and ``QK^T`` and ``PV`` at ``2 T h`` each.
    The output head is counted at the masked positions only, because the loss
    needs no other logits; the program computes all of them, and that surplus
    is not credited.  Backward costs twice the forward.  Embedding lookups,
    layer norms, softmax and the optimizer are left out, as is recomputation."""
    m = config["model"]
    h, ff, layers, vocab = (m["hidden_size"], m["intermediate_size"],
                            m["num_hidden_layers"], m["vocab_size"])
    seq = config["table"]["seq"]
    per_token_layer = 2 * (4 * h * h + 2 * h * ff) + 4 * seq * h
    head = 2 * h * vocab * config["mlm_probability"]
    return 3.0 * seq * (layers * per_token_layer + head)


def transform(config: dict, seed: int):
    """Host transform for the loader: token rows to (ids, labels, mask) with
    ``mlm_probability`` of the positions masked; labels are -100 elsewhere.
    (A copy of ``chip_smoke.py``'s ``mlm_collate``.)"""
    rng = np.random.default_rng(seed)
    p = config["mlm_probability"]

    def collate(batch: dict) -> dict:
        tokens = batch["tokens"]
        masked = rng.random(tokens.shape) < p
        return {
            "ids": np.where(masked, np.int32(MASK_ID), tokens),
            "labels": np.where(masked, tokens, np.int32(-100)),
            "mask": np.ones(tokens.shape, np.bool_),
        }

    return collate


class Consumer:
    """State and step on a mesh plan, built the way a training job builds
    them: ``make_bert_train_state`` makes the weights on the device from the
    seed, ``make_bert_train_step`` jits the step."""

    def __init__(self, config: dict, plan, seed: int):
        from lakesoul_tpu.models.train import make_bert_train_state, make_bert_train_step

        self.cfg = model_config(config)
        self.params, self.opt_state, tx, shardings = make_bert_train_state(
            self.cfg, plan, lr=config["learning_rate"], seed=seed
        )
        self._step = make_bert_train_step(self.cfg, plan, tx, shardings)

    def step(self, batch: dict):
        """Dispatch one optimizer step; returns the loss (a device array)."""
        self.params, self.opt_state, loss = self._step(
            self.params, self.opt_state, batch["ids"], batch["labels"], batch["mask"]
        )
        return loss

    def losses_on(self, host_batch: dict) -> tuple[float, float]:
        """(the program's loss, the plain float32 reference's loss) on the
        same rows with the weights as they stand."""
        import jax

        from lakesoul_tpu.models.bert import bert_mlm_loss
        from reference.bert_mlm_f32 import mlm_loss

        args = (self.params, host_batch["ids"], host_batch["labels"], host_batch["mask"])
        system = jax.jit(functools.partial(bert_mlm_loss, cfg=self.cfg))(*args)
        with jax.default_matmul_precision("highest"):
            plain = jax.jit(functools.partial(mlm_loss, heads=self.cfg.heads))(*args)
        return float(system), float(plain)


def build(config: dict, plan, seed: int) -> Consumer:
    return Consumer(config, plan, seed)
