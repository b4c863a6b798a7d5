"""Device idle milliseconds a step, outside any device program, while no
``lakesoul.*`` span was open on the consumer thread: step dispatch, the loss
read, the loop itself (``chipbench/program_spans.py``)."""

from chipbench import program_spans


def read(sample):
    return program_spans.unowned_ms_step(sample)
