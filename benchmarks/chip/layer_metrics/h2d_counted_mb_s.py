"""Megabytes per second placed on the device as the program counts them:
``lakesoul_tensorplane_h2d_bytes_total`` (``tensorplane/dlpack.py: deliver``)
delta over window seconds.  ``h2d_mb_s`` is the same quantity counted from
outside; a program without the counter gives nothing."""

from chipbench.counters import family_sum

COUNTER = "lakesoul_tensorplane_h2d_bytes_total"


def read(sample):
    if not any(key.partition("{")[0] == COUNTER for key in sample["counters"]):
        return None
    return family_sum(sample["counters"], COUNTER) / sample["window_s"] / 1e6
