"""Share of the held assignments whose products ran in the grouped kernels:
``lakesoul_train_moe_assignments_total{kind="grouped"}`` over ``{kind="held"}``
(``models/train.py``; ``parallel/moe.py`` counts both from the tile plan on
the device), deltas over the window.  Where a routed layer's shapes go to the
kernels (``parallel/moe.py: _grouped``: an expert's widths and the tile whole
128-lane tiles, its three matrices twice within the kernels' VMEM) a segment
of tiles is one Pallas kernel a pass (``experts_fwd``, ``experts_bwd``) that
gathers its own rows, skips the row blocks past an expert's last row and adds
its rows to the sums itself; elsewhere a turn of the tile loop runs a tile.
100 where every routed layer goes through the kernels, 0 where every one
keeps the loop.  A program without the ``grouped`` series (before PR 53) gives
nothing, and so does a window in which no assignment was held."""

from chipbench.counters import family_sum

COUNTER = "lakesoul_train_moe_assignments_total"


def read(sample):
    counters = sample["counters"]
    if not any(key.startswith(COUNTER) and 'kind="grouped"' in key for key in counters):
        return None
    held = family_sum(counters, COUNTER, kind="held")
    if not held:
        return None
    return 100.0 * family_sum(counters, COUNTER, kind="grouped") / held
