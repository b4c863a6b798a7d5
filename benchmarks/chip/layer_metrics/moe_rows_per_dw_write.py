"""Slots of the expert tile loop for each time the backward pass writes an
expert's weight-gradient sum: ``lakesoul_train_moe_assignments_total
{kind="tile_rows"}`` over ``{kind="dw_writes"}`` (``models/train.py``;
``parallel/moe.py`` counts both from the tile plan on the device), deltas over
the window.  Where an expert's rows fill several tiles the backward loop leaves
a tile's operands in row buffers and one kernel a matrix (``expert_dw``) sums
an expert's consecutive tiles in VMEM, a segment of tiles at a time: an
expert's float32 sum crosses HBM once for each write counted here.  Where they
fill a tile or so the sums ride the loop and are written once a tile: 512.
About 4,000 where a held expert sees 4,100 rows a layer.  A program without the
``dw_writes`` series (before PR 39) gives nothing."""

from chipbench.counters import family_sum

COUNTER = "lakesoul_train_moe_assignments_total"


def read(sample):
    writes = family_sum(sample["counters"], COUNTER, kind="dw_writes")
    if not writes:
        return None
    return family_sum(sample["counters"], COUNTER, kind="tile_rows") / writes
