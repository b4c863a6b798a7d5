"""Wall time of the process's first ``jax.devices()`` call, seconds: the
backend loads the TPU's library and attaches the chips, and no code of this
tree runs in it (``run.py: start_runtime`` takes it around the call alone and
hands it to the driver).  ``setup_s`` leaves it out since PR 54, so ``setup_s
+ runtime_start_s`` is process start to window start, what ``setup_s`` was
before.  A driver called without the span (a program that never asked the
backend) gives nothing."""


def read(sample):
    return sample.get("runtime_start_s") or None
