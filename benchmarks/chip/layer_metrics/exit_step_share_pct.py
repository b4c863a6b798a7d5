"""Share of the train step's device time in a looped model's exit gate and
objective (``jax.named_scope("lakesoul.lm.exit")``, ``models/causal_lm.py:
exit_loss``: the gate's product with the stacked states and its sigmoid, the
survival products, the distribution over the passes, its entropy, the weighted
sums, both passes), as ``mla_step_share_pct`` is read
(``chipbench/scopes.py``).  The head's four runs and the loss's tile loop stay
under ``head_step_share_pct``.  With it the looped cell's shares are the step
whole: ``attn + mlp + head + exit + optim + embed + unscoped``.  A program
whose step carries no such scope, or a run without the scope map, gives
nothing."""

from chipbench import scopes

SCOPE = "exit"


def read(sample):
    result = scopes.of_run(sample)
    seconds = None if result is None else result["seconds"].get(scopes.PREFIX + SCOPE)
    if seconds is None or not result["step_s"]:
        return None
    return 100.0 * seconds / result["step_s"]
