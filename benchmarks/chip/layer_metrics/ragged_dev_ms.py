"""Device milliseconds in the ``ragged_score`` kernel per plane dispatch: the
kernel's events in the trace, summed, over the dispatches that ended in the
traced part of the window."""

from chipbench import trace
from chipbench.counters import family_sum

KERNEL = "ragged_score"


def read(sample):
    if sample["trace_plain"] is None or not sample.get("traced_counters"):
        return None
    events = trace.kernel_events(sample["trace_plain"], KERNEL)
    dispatches = family_sum(sample["traced_counters"], "lakesoul_ann_ragged_dispatch_seconds", ":count")
    if not events or not dispatches:
        return None
    return 1e3 * sum(seconds for _, seconds in events) / dispatches
