#!/usr/bin/env python3
"""CPU self-test of ``layer_metrics/attn_operands_fused_pct.py``.

    python3 benchmarks/chip/selftest/operand_rows.py

``selftest/afmoe_readers.py`` checks the readers the Trinity-Mini cell added
and is not edited by later PRs, so the reader PR 43 added is checked here: its
arithmetic on hand counts (the three cells that list it), and that a program
without the series, or without a softmax-attention mixer, gives nothing (the
parent of PR 43, the GLM cell, every BERT cell).  Nothing here reports a
device metric.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from chipbench.spec import load_module  # noqa: E402

read = load_module(os.path.join(BENCH, "layer_metrics", "attn_operands_fused_pct.py")).read
FAMILY = "lakesoul_train_attn_operand_rows_total"


def _counters(kernel, xla):
    return {f'{FAMILY}{{path="kernel"}}': float(kernel), f'{FAMILY}{{path="xla"}}': float(xla)}


def test_share_of_hand_counts():
    # 38 steps of 2 rows over five attention layers at a head of 128, positions over the whole head or none
    assert read({"counters": _counters(38 * 2 * 5, 0)}) == 100.0
    # 47 steps of 2 rows, one attention layer whose positions cover 64 of 256 channels: the jnp lines
    assert read({"counters": _counters(0, 47 * 2)}) == 0.0
    # a stack of both: four layers the rule takes, one it does not
    assert read({"counters": _counters(30 * 2 * 4, 30 * 2)}) == 80.0


def test_nothing_without_the_series():
    assert read({"counters": {'lakesoul_train_attn_key_tiles_total{kind="run"}': 66560.0}}) is None  # the program before PR 43
    assert read({"counters": _counters(0, 0)}) is None  # latent attention: no softmax_attention mixer
    assert read({"counters": {f'{FAMILY}{{path="kernel"}}': 380.0}}) is None  # half a family is no reading
    assert read({"counters": {'lakesoul_loader_rows_total{consumer="local"}': 80.0}}) is None  # a BERT cell


TESTS = [test_share_of_hand_counts, test_nothing_without_the_series]


def main() -> int:
    failed = 0
    for test in TESTS:
        try:
            test()
        except Exception:  # a self-test reports every failure, not the first
            import traceback

            failed += 1
            print(f"FAIL {test.__name__}")
            traceback.print_exc()
        else:
            print(f"ok   {test.__name__}")
    print(f"{len(TESTS) - failed} passed, {failed} failed")
    return 1 if failed else 0


if __name__ == "__main__":
    sys.exit(main())
