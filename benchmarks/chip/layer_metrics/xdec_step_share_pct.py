"""Share of the train step's device time inside the second decoder's mixers,
the layers that read another layer's state: the gated memory units
(``jax.named_scope("lakesoul.lm.gmu")``) and the cross-attention layers
(``"lakesoul.lm.xattn"``: norm, ``W_q``, the attention kernels over the
source's keys and values, the difference and its norm, ``W_o``), both passes
and every recomputation, as ``swa_step_share_pct`` is read
(``chipbench/scopes.py``).  A program whose step carries neither scope, or a
run without the scope map, gives nothing."""

from chipbench import scopes

SCOPES = ("gmu", "xattn")


def read(sample):
    result = scopes.of_run(sample)
    if result is None or not result["step_s"]:
        return None
    found = [result["seconds"][scopes.PREFIX + s] for s in SCOPES if scopes.PREFIX + s in result["seconds"]]
    return 100.0 * sum(found) / result["step_s"] if found else None
