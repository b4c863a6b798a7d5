"""Share of the train step's device time inside the Gated DeltaNet mixers
(``jax.named_scope("lakesoul.lm.gdn")``: norm, projections, convolution, the
chunked scan, both passes and every recomputation): self time of the step's
``XLA Ops`` events whose instruction carries the scope, over the step's busy
time (``chipbench/scopes.py``)."""

from chipbench import scopes


def read(sample):
    return scopes.share_pct(sample, "gdn")
