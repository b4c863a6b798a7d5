#!/usr/bin/env python3
"""CPU self-test of ``layer_metrics/moe_rows_per_dw_write.py``.

    python3 benchmarks/chip/selftest/dw_writes.py

The reader PR 39 added, checked as ``selftest/tile_fill.py`` checks PR 31's:
its arithmetic on hand counts, and that a program without the series gives
nothing (the parent of PR 39, and every BERT cell).  Nothing here reports a
device metric.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from chipbench.spec import load_module  # noqa: E402

read = load_module(os.path.join(BENCH, "layer_metrics", "moe_rows_per_dw_write.py")).read
FAMILY = "lakesoul_train_moe_assignments_total"


def test_rows_a_write_of_hand_counts():
    # 40 steps of 4 layers: 8 held experts of 9 tiles of 512, 72 tiles a layer in two segments
    # of 64 and 8, the boundary inside the eighth expert: 9 writes a layer
    counters = {
        f'{FAMILY}{{kind="held"}}': 40 * 4 * 8 * 4100.0,
        f'{FAMILY}{{kind="tile_rows"}}': 40 * 4 * 72 * 512.0,
        f'{FAMILY}{{kind="dw_writes"}}': 40 * 4 * 9.0,
    }
    assert read({"counters": counters}) == 4096.0
    # an expert a tile: every tile is a write
    counters[f'{FAMILY}{{kind="dw_writes"}}'] = 40 * 4 * 72.0
    assert read({"counters": counters}) == 512.0


def test_nothing_without_the_series():
    before = {f'{FAMILY}{{kind="held"}}': 40 * 40960.0, f'{FAMILY}{{kind="tile_rows"}}': 40 * 65536.0}
    assert read({"counters": before}) is None  # the program before PR 39
    assert read({"counters": {'lakesoul_loader_rows_total{consumer="local"}': 80.0}}) is None  # a BERT cell
    assert read({"counters": {f'{FAMILY}{{kind="dw_writes"}}': 0.0}}) is None  # no tile ran


TESTS = [test_rows_a_write_of_hand_counts, test_nothing_without_the_series]


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
