"""Host busy milliseconds of the scan (decode + merge + fill) per 1,000 rows
the loader delivered, from ``lakesoul_scan_stage_seconds`` deltas over the
window.  Busy time summed over the scan's threads, not elapsed time."""

from chipbench.stages import SCAN, rows_delivered, stage_seconds


def read(sample):
    rows = rows_delivered(sample["counters"])
    if not rows:
        return None
    return stage_seconds(sample["counters"], SCAN) * 1e3 / (rows / 1e3)
