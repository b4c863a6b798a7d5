"""Share of the train step's device time inside the expert layers: routing
(``lakesoul.lm.moe.route``: norm, router, softmax, top-k), the held experts'
grouped products (``lakesoul.lm.moe.experts``: sort, tiles, gathers and
scatters) and the shared expert (``lakesoul.lm.moe.shared``), as
``gdn_step_share_pct`` is read (``chipbench/scopes.py``)."""

from chipbench import scopes


def read(sample):
    return scopes.share_pct(sample, "moe.route", "moe.experts", "moe.shared")
