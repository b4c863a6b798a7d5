"""The part of ``idle_queue_ms_step`` during which a ``lakesoul.scan.merge``
span was open on a producer thread: the device idle while the consumer waited
and a merge ran (``chipbench/program_spans.py``).  Not exclusive of the other
stages open meanwhile."""

from chipbench import program_spans


def read(sample):
    return program_spans.blame_ms_step(sample, "lakesoul.scan.merge")
