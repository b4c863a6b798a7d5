"""Share of the train step's device time inside the gated-attention mixers
(``jax.named_scope("lakesoul.lm.attn")``: norm, projections, rotary positions,
the blockwise causal attention, both passes and every recomputation), as
``gdn_step_share_pct`` is read (``chipbench/scopes.py``)."""

from chipbench import scopes


def read(sample):
    return scopes.share_pct(sample, "attn")
