"""Share of the train step's device time in the optimizer
(``jax.named_scope("lakesoul.lm.optim")``, ``models/train.py: _adamw_step``:
``tx.update`` and ``optax.apply_updates`` over every trained leaf, AdamW's
28 B a parameter read and written in float32, and ``_CountedStep``'s count
limbs), as ``gdn_step_share_pct`` is read (``chipbench/scopes.py``).  A program
whose step carries no such scope (one from before the scope), or a run without
the scope map, gives nothing."""

from chipbench import scopes

SCOPE = "optim"


def read(sample):
    result = scopes.of_run(sample)
    seconds = None if result is None else result["seconds"].get(scopes.PREFIX + SCOPE)
    if seconds is None or not result["step_s"]:
        return None
    return 100.0 * seconds / result["step_s"]
