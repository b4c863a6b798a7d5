"""Share of the train step's device time inside the Mamba mixers
(``jax.named_scope("lakesoul.lm.ssm")``: norm, ``W_in``, the convolution,
``W_x``, ``W_dt``, softplus, the selective-scan kernels, the gate and
``W_out``, both passes and every recomputation), as ``gdn_step_share_pct`` is
read (``chipbench/scopes.py``).  A program whose step carries no such scope,
or a run without the scope map, gives nothing."""

from chipbench import scopes

SCOPE = "ssm"


def read(sample):
    result = scopes.of_run(sample)
    seconds = None if result is None else result["seconds"].get(scopes.PREFIX + SCOPE)
    if seconds is None or not result["step_s"]:
        return None
    return 100.0 * seconds / result["step_s"]
