"""Share of the train step's device time in the token lookup
(``jax.named_scope("lakesoul.lm.embed")``, ``models/causal_lm.py: lm_hidden``:
the gather of the rows' embeddings with its cast, and through the transpose the
scatter-add of their gradient into a float32 matrix the size of the table), as
``gdn_step_share_pct`` is read (``chipbench/scopes.py``).  In a tied family the
head's use of the same matrix stays ``head_step_share_pct``'s; the GLM
prediction module's lookup is the module's (``mtp_step_share_pct``).  A program
whose step carries no such scope, or a run without the scope map, gives
nothing."""

from chipbench import scopes

SCOPE = "embed"


def read(sample):
    result = scopes.of_run(sample)
    seconds = None if result is None else result["seconds"].get(scopes.PREFIX + SCOPE)
    if seconds is None or not result["step_s"]:
        return None
    return 100.0 * seconds / result["step_s"]
