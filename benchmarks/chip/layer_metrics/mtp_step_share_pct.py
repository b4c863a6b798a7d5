"""Share of the train step's device time in the multi-token-prediction module
(``jax.named_scope("lakesoul.lm.mtp")``: its two norms, ``eh_proj``, its whole
decoder layer, its head and loss, both passes and every recomputation), as
``gdn_step_share_pct`` is read (``chipbench/scopes.py``): what the second loss
costs.  The module's layer carries the layer's own scopes inside the module's;
``consumers/glm4_moe_lite_clm.py: scopes_of`` charges an instruction to the
module wherever its ``op_name`` carries the module's scope at any depth.  A
program whose step carries no such scope, or a run without the scope map, gives
nothing."""

from chipbench import scopes

SCOPE = "mtp"


def read(sample):
    result = scopes.of_run(sample)
    seconds = None if result is None else result["seconds"].get(scopes.PREFIX + SCOPE)
    if seconds is None or not result["step_s"]:
        return None
    return 100.0 * seconds / result["step_s"]
