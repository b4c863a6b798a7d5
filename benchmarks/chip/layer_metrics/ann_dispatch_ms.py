"""Host milliseconds per plane dispatch (``AnnPlane.batch_search``, all shards):
``lakesoul_ann_ragged_dispatch_seconds`` sum over count, deltas."""

from chipbench.counters import family_sum

FAMILY = "lakesoul_ann_ragged_dispatch_seconds"


def read(sample):
    count = family_sum(sample["counters"], FAMILY, ":count")
    if not count:
        return None
    return 1e3 * family_sum(sample["counters"], FAMILY, ":sum") / count
