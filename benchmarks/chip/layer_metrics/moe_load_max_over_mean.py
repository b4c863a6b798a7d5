"""How uneven the held experts' load is: the fullest held expert's assignments
over the mean held expert's, each summed over the window's steps and layers
(``lakesoul_train_moe_expert_load{stat="max"|"mean"}``, ``models/train.py``).
1 is even; the tiles of the grouped products pad every expert up to a whole
tile, so the ratio says how far the last tile of the fullest expert runs
ahead of the rest.  A program without the counter gives nothing."""

from chipbench.counters import family_sum

COUNTER = "lakesoul_train_moe_expert_load"


def read(sample):
    mean = family_sum(sample["counters"], COUNTER, stat="mean")
    if not mean:
        return None
    return family_sum(sample["counters"], COUNTER, stat="max") / mean
