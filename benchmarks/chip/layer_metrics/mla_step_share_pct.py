"""Share of the train step's device time in what latent attention adds around
the attention kernels (``jax.named_scope("lakesoul.lm.mla")``, inside
``lakesoul.lm.attn``: both down-projections, the latent norms, both
up-projections, the rotary of the decoupled parts and assembling the per-head
queries and keys, both passes and every recomputation), as
``gdn_step_share_pct`` is read (``chipbench/scopes.py``).  The kernels, ``W_o``
and the layout copies stay under ``attn_step_share_pct``; the prediction
module's mixer is under ``mtp_step_share_pct``.  A program whose step carries no
such scope, or a run without the scope map, gives nothing."""

from chipbench import scopes

SCOPE = "mla"


def read(sample):
    result = scopes.of_run(sample)
    seconds = None if result is None else result["seconds"].get(scopes.PREFIX + SCOPE)
    if seconds is None or not result["step_s"]:
        return None
    return 100.0 * seconds / result["step_s"]
