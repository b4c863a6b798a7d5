"""Share of the train step's device time in the final norm, the head and the
loss over the held vocabulary (``jax.named_scope("lakesoul.lm.head")``,
``models/causal_lm.py: lm_head``: both passes, the head's tile bodies and its
weight-gradient add; in a tied family the head's use of the embedding matrix),
as ``gdn_step_share_pct`` is read (``chipbench/scopes.py``).  The scope is as
old as the scope map and ``chipbench/scopes.py`` has logged it since; this
reader returns it.  In the GLM cell the prediction module's second head is the
module's (``mtp_step_share_pct``).  A program whose step carries no such scope,
or a run without the scope map, gives nothing."""

from chipbench import scopes

SCOPE = "head"


def read(sample):
    result = scopes.of_run(sample)
    seconds = None if result is None else result["seconds"].get(scopes.PREFIX + SCOPE)
    if seconds is None or not result["step_s"]:
        return None
    return 100.0 * seconds / result["step_s"]
