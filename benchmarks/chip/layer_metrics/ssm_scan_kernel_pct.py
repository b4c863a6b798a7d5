"""Share of the step's selective-scan rows that took the kernel pair:
``lakesoul_train_ssm_scan_rows_total{path="kernel"}`` over ``{path="kernel"}
+ {path="twin"}`` (``models/train.py``; host integers off
``models/selective_scan.py: scan_takes``, rows x the layers that scan, summed
over the window's steps), deltas over the window.  100 where the shape is one
the kernels take; a change that sends the scan to its ``lax.scan`` twin moves
it.  A program without the series, or a step without a scan (both 0), gives
nothing."""

from chipbench.counters import family_sum

COUNTER = "lakesoul_train_ssm_scan_rows_total"


def read(sample):
    kernel = family_sum(sample["counters"], COUNTER, path="kernel")
    twin = family_sum(sample["counters"], COUNTER, path="twin")
    if not kernel + twin:
        return None
    return 100.0 * kernel / (kernel + twin)
