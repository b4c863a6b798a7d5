"""Share of the train step's device time inside the window-attention mixers
(``jax.named_scope("lakesoul.lm.swa")``: norm, the four projections and the
gate's, the head norms, rotary positions, the attention kernels on their
banded tile lists, the gate, ``W_o`` and the mixer's output norm, both passes
and every recomputation), as ``mla_step_share_pct`` is read
(``chipbench/scopes.py``).  The full-attention layers of the same stack stay
under ``attn_step_share_pct``: the two add up to the mixers'.  A program whose
step carries no such scope, or a run without the scope map, gives nothing."""

from chipbench import scopes

SCOPE = "swa"


def read(sample):
    result = scopes.of_run(sample)
    seconds = None if result is None else result["seconds"].get(scopes.PREFIX + SCOPE)
    if seconds is None or not result["step_s"]:
        return None
    return 100.0 * seconds / result["step_s"]
