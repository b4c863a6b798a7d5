"""Share of the step's labelled positions that are the multi-token-prediction
module's: ``lakesoul_train_head_positions_total{kind="mtp"}`` over
``{kind="all"}`` (``models/train.py: make_lm_train_step``; the positions with a
label, of the module's loss and of every loss the step sums), deltas over the
window.  About 50 with one module (a row of T tokens has T - 1 next tokens and
T - 2 tokens after next); a program that thins or drops the second loss moves
it, and a family without a module reads 0.  A program without the series gives
nothing."""

from chipbench.counters import family_sum

COUNTER = "lakesoul_train_head_positions_total"
MTP = f'{COUNTER}{{kind="mtp"}}'


def read(sample):
    every = family_sum(sample["counters"], COUNTER, kind="all")
    if not every or MTP not in sample["counters"]:
        return None
    return 100.0 * sample["counters"][MTP] / every
