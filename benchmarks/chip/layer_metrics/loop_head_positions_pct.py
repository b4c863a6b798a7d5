"""Share of the step's labelled positions that are the losses' of a looped
model's passes BEFORE the last:
``lakesoul_train_head_positions_total{kind="loop"}`` over ``{kind="all"}``
(``models/train.py: make_lm_train_step``; the positions with a label, of the
passes before the last and of every pass's loss), deltas over the window.  75
with four losses through one head; a program that thins or drops the losses
before the last moves it, and a family that does not loop reads 0.  A program
without the series gives nothing."""

from chipbench.counters import family_sum

COUNTER = "lakesoul_train_head_positions_total"
LOOP = f'{COUNTER}{{kind="loop"}}'


def read(sample):
    every = family_sum(sample["counters"], COUNTER, kind="all")
    if not every or LOOP not in sample["counters"]:
        return None
    return 100.0 * sample["counters"][LOOP] / every
