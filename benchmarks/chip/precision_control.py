#!/usr/bin/env python3
"""The precision control of a cell whose adaptor compares against a plain
reference: one run of the cell exactly as ``run.py`` makes it, with the
adaptor's ``losses_on`` comparing against the same reference computed wholly
in bfloat16, the precision below the one the configuration states.

    python3 benchmarks/chip/precision_control.py --workload <cell> --seed <n> --seconds 20 --trace 0

The result line has to say ``"correct": false``: a limit in the
configuration's ``guarantees`` that a lower precision passes tells nothing
about precision.  Whoever changes a tolerance, the reference or the
comparison runs this on a few seeds and writes the smallest control reading
beside the largest sound one (``PERF.md`` section 6).  It takes ``run.py``'s
arguments and needs an adaptor whose ``Consumer.losses_on`` accepts
``reference_dtype`` (``consumers/qwen3_next_clm.py``).
"""

import functools
import os
import runpy
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
for path in (HERE, REPO):
    if path not in sys.path:
        sys.path.insert(0, path)

from chipbench import spec  # noqa: E402

_load_module = spec.load_module


def _load_with_control(path: str):
    module = _load_module(path)
    if os.path.dirname(path) == os.path.join(HERE, "consumers"):
        import jax.numpy as jnp

        module.Consumer.losses_on = functools.partialmethod(
            module.Consumer.losses_on, reference_dtype=jnp.bfloat16
        )
    return module


if __name__ == "__main__":
    spec.load_module = _load_with_control  # the adaptor is loaded by path, anew each time
    runpy.run_path(os.path.join(HERE, "run.py"), run_name="__main__")
