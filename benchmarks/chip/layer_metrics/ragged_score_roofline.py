"""The ``ragged_score`` kernel's share of its roofline: the least time the
peaks table allows for each traced call (operations and bytes from the call's
shapes, ``kernels/ragged_score.py``) over the time it took.  The kernel moves
half an operation per byte, so the memory bound applies."""

import os

from chipbench import trace
from chipbench.peaks import least_seconds
from chipbench.spec import load_module

KERNEL = "ragged_score"
_COST = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "kernels", "ragged_score.py")


def read(sample):
    if sample["trace_plain"] is None:
        return None
    cost = load_module(_COST)
    peaks = sample["peaks"]
    least = took = 0.0
    for name, seconds in trace.kernel_events(sample["trace_plain"], KERNEL):
        flops, moved = cost.from_event(name)
        bound, _which = least_seconds(
            flops=flops, bytes_moved=moved,
            flops_peak=peaks["f32_flops"], bytes_peak=peaks["hbm_bytes_per_s"],
        )
        least += bound
        took += seconds
    return 100.0 * least / took if took else None
