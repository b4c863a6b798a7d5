"""Host busy milliseconds of the loader (rebatch + collate with the transform
+ device_put dispatch) per 1,000 rows delivered."""

from chipbench.stages import LOADER, rows_delivered, stage_seconds


def read(sample):
    rows = rows_delivered(sample["counters"])
    if not rows:
        return None
    return stage_seconds(sample["counters"], LOADER) * 1e3 / (rows / 1e3)
