"""Device idle milliseconds a step, outside any device program, while the
consumer thread was inside ``lakesoul.train.dispatch``: the
jitted step's dispatch, the host side of the call, exposed
(``models/train.py: _CountedStep.__call__``; ``chipbench/program_spans.py``).
Until the span existed this time was ``idle_unowned_ms_step``'s.  A program
without the span gives nothing."""

from chipbench import program_spans

SPAN = "lakesoul.train.dispatch"


def read(sample):
    result = program_spans.of_run(sample)
    if result is None or SPAN not in result["owner_s"]:
        return None
    return program_spans.owner_ms_step(sample, SPAN)
