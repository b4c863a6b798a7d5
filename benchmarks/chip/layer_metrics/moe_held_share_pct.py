"""Share of the window's expert assignments that landed on experts this chip
holds: ``lakesoul_train_moe_assignments_total{kind="held"}`` over
``{kind="all"}`` (``models/train.py``), deltas over the window.  A gauge of the
cut, not of speed: 100 x held / experts under even routing (6.25 with 32 of
512), and every point more is more grouped products on this chip, which is why
``better`` says lower.  A program without the counter gives nothing."""

from chipbench.counters import family_sum

COUNTER = "lakesoul_train_moe_assignments_total"


def read(sample):
    every = family_sum(sample["counters"], COUNTER, kind="all")
    if not every:
        return None
    return 100.0 * family_sum(sample["counters"], COUNTER, kind="held") / every
