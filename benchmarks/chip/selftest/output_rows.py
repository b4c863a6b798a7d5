#!/usr/bin/env python3
"""CPU self-test of ``layer_metrics/attn_out_token_major_pct.py``.

    python3 benchmarks/chip/selftest/output_rows.py

Beside ``selftest/operand_rows.py`` (later PRs add files here and edit none):
the reader's arithmetic on hand counts (the five causal-LM cells that list
it), and that a program without the series, or without attention of the
stack's, gives nothing (the parent of PR 45, every BERT cell).  Nothing here
reports a device metric.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from chipbench.spec import load_module  # noqa: E402

read = load_module(os.path.join(BENCH, "layer_metrics", "attn_out_token_major_pct.py")).read
FAMILY = "lakesoul_train_attn_output_rows_total"


def _counters(tokens, heads):
    return {f'{FAMILY}{{layout="tokens"}}': float(tokens), f'{FAMILY}{{layout="heads"}}': float(heads)}


def test_share_of_hand_counts():
    # 42 steps of 2 rows over five attention layers at a head of 128: every row token-major
    assert read({"counters": _counters(42 * 2 * 5, 0)}) == 100.0
    # 25 steps of 1 row, six layers run four times, a head of 128
    assert read({"counters": _counters(25 * 1 * 6 * 4, 0)}) == 100.0
    # 37 steps of 4 rows, one attention layer at a head of 64: heads first, transposed after
    assert read({"counters": _counters(0, 37 * 4)}) == 0.0
    # a stack of both: three layers the rule takes, one it does not
    assert read({"counters": _counters(30 * 2 * 3, 30 * 2)}) == 75.0


def test_nothing_without_the_series():
    before = {'lakesoul_train_attn_operand_rows_total{path="kernel"}': 380.0,
              'lakesoul_train_attn_operand_rows_total{path="xla"}': 0.0}
    assert read({"counters": before}) is None  # the program before PR 45
    assert read({"counters": _counters(0, 0)}) is None  # a step that runs none of the stack's attention
    assert read({"counters": {f'{FAMILY}{{layout="tokens"}}': 380.0}}) is None  # half a family is no reading
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
