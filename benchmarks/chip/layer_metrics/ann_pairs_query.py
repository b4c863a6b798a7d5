"""(query, cluster) pairs scored per query:
``lakesoul_ann_ragged_pairs_total`` over ``_queries_total``, deltas.  It is the
mean ``nprobe`` of the requests served and repeats from a seed."""

from chipbench.counters import family_sum


def read(sample):
    queries = family_sum(sample["counters"], "lakesoul_ann_ragged_queries_total")
    if not queries:
        return None
    return family_sum(sample["counters"], "lakesoul_ann_ragged_pairs_total") / queries
