"""Share of the train step's device time inside the gated short-convolution
mixers (``jax.named_scope("lakesoul.lm.conv")``: norm, ``W_in``, the two
gates, the depthwise causal convolution, ``W_out``, both passes and every
recomputation), as ``gdn_step_share_pct`` is read (``chipbench/scopes.py``).
A program whose step carries no such scope, or a run without the scope map,
gives nothing."""

from chipbench import scopes

SCOPE = "conv"


def read(sample):
    result = scopes.of_run(sample)
    seconds = None if result is None else result["seconds"].get(scopes.PREFIX + SCOPE)
    if seconds is None or not result["step_s"]:
        return None
    return 100.0 * seconds / result["step_s"]
