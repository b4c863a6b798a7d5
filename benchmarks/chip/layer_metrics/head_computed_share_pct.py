"""Share of the batches' positions at which the train step ran the MLM head:
``lakesoul_train_head_positions_total{kind="computed"}`` over ``{kind="all"}``
(``models/train.py``), deltas over the window.  The loss needs the labelled
positions only (15% here), so 100 means the head ran everywhere; a program
without the counter gives nothing."""

from chipbench.counters import family_sum

COUNTER = "lakesoul_train_head_positions_total"


def read(sample):
    every = family_sum(sample["counters"], COUNTER, kind="all")
    if not every:
        return None
    return 100.0 * family_sum(sample["counters"], COUNTER, kind="computed") / every
