"""Share of the window the step loop waited on the loader's queue:
``lakesoul_loader_stall_seconds_total`` delta over window seconds."""

from chipbench.counters import family_sum


def read(sample):
    if "rows" not in sample:
        return None
    stall = family_sum(sample["counters"], "lakesoul_loader_stall_seconds_total")
    return 100.0 * stall / sample["window_s"]
