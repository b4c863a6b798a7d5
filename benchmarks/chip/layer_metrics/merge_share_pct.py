"""The merge-on-read stage's share of the scan's busy time (merge over decode
+ merge + fill), same deltas as ``scan_ms_krow``."""

from chipbench.stages import SCAN, stage_seconds


def read(sample):
    scan = stage_seconds(sample["counters"], SCAN)
    if not scan:
        return None
    return 100.0 * stage_seconds(sample["counters"], ("merge",)) / scan
