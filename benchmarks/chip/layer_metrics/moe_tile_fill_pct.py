"""Share of the held experts' multiplied slots that carried an assignment:
``lakesoul_train_moe_assignments_total{kind="held"}`` over
``{kind="tile_rows"}`` (``models/train.py``; ``parallel/moe.py`` counts the
rows it moves and multiplies, forward), deltas over the window.  What a slot
is follows the path the program takes.  Since PR 53 the four routed cells run
the grouped kernels (``moe.py: experts_fwd``), which walk an expert's rows in
blocks of ``GROUP_ROWS`` (128) and skip the blocks past its last row: a slot
is a row of a block the kernels multiply, ``tile_rows`` counts those blocks'
rows, and only an expert's last block is part padding (320 assignments fill
three blocks of 128: 83.3).  On the tile loop, the kernels' twin and the path
of the shapes they refuse, a slot is a row of a whole tile of one expert's
rows (512 in these cells), fetched and multiplied whether it holds an
assignment or not: 62.5 at 320 of 512.  How many experts need another block
or tile is the seed's routing, which is why the step time follows the seed.
A program without the ``tile_rows`` series (before PR 31) gives nothing."""

from chipbench.counters import family_sum

COUNTER = "lakesoul_train_moe_assignments_total"


def read(sample):
    slots = family_sum(sample["counters"], COUNTER, kind="tile_rows")
    if not slots:
        return None
    return 100.0 * family_sum(sample["counters"], COUNTER, kind="held") / slots
