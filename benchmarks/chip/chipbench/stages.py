"""Sums over the program's ``lakesoul_scan_stage_seconds`` family, shared by
the scan and loader readers."""

from __future__ import annotations

from chipbench.counters import family_sum

FAMILY = "lakesoul_scan_stage_seconds"
SCAN = ("decode", "merge", "fill")
LOADER = ("rebatch", "collate", "device_put")


def stage_seconds(deltas: dict, stages) -> float:
    return sum(family_sum(deltas, FAMILY, ":sum", stage=s) for s in stages)


def rows_delivered(deltas: dict) -> float:
    return family_sum(deltas, "lakesoul_loader_rows_total")
