"""Device idle milliseconds a step, outside any device program, while the
consumer thread was inside ``lakesoul.loader.device_put``: the dispatch of the
host-to-device transfer exposed (``chipbench/program_spans.py``)."""

from chipbench import program_spans


def read(sample):
    return program_spans.owner_ms_step(sample, "lakesoul.loader.device_put")
