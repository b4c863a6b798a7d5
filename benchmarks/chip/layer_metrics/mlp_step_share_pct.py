"""Share of the train step's device time inside the dense feed-forward of the
leading layers (``jax.named_scope("lakesoul.lm.mlp")``: norm and the SwiGLU's
three products, forward, the rematerialisation and backward), as
``gdn_step_share_pct`` is read (``chipbench/scopes.py``).  A program whose
step carries no such scope, or a run without the scope map, gives nothing."""

from chipbench import scopes

SCOPE = "mlp"


def read(sample):
    result = scopes.of_run(sample)
    seconds = None if result is None else result["seconds"].get(scopes.PREFIX + SCOPE)
    if seconds is None or not result["step_s"]:
        return None
    return 100.0 * seconds / result["step_s"]
