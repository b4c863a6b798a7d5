"""Share of the expert tile loop's slots that carried an assignment:
``lakesoul_train_moe_assignments_total{kind="held"}`` over
``{kind="tile_rows"}`` (``models/train.py``; ``parallel/moe.py`` counts a
tile's rows for every tile it runs), deltas over the window.  The loop runs
one fixed tile of one expert's rows at a time, so an expert's last tile is
part padding: every slot is fetched and multiplied whether it holds an
assignment or not (only the write back skips the empty ones), and this share
says how much of that work was useful: 62.5 where each held expert's 320
assignments fill one tile of 512.  How many experts need a second tile is the
seed's routing, which is why the step time follows the seed.  A program
without the ``tile_rows`` series (before PR 31) gives nothing."""

from chipbench.counters import family_sum

COUNTER = "lakesoul_train_moe_assignments_total"


def read(sample):
    slots = family_sum(sample["counters"], COUNTER, kind="tile_rows")
    if not slots:
        return None
    return 100.0 * family_sum(sample["counters"], COUNTER, kind="held") / slots
