#!/usr/bin/env python3
"""CPU self-test of ``layer_metrics/moe_tile_fill_pct.py``.

    python3 benchmarks/chip/selftest/tile_fill.py

``selftest/scopes.py`` checks the counter readers PR 28 added and is not
edited by later PRs, so the reader PR 31 added is checked here: its arithmetic
on hand counts, and that a program without the series gives nothing (the
parent of PR 31, and every BERT cell).  Nothing here reports a device metric.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from chipbench.spec import load_module  # noqa: E402

read = load_module(os.path.join(BENCH, "layer_metrics", "moe_tile_fill_pct.py")).read
FAMILY = "lakesoul_train_moe_assignments_total"


def test_fill_of_hand_counts():
    # 40 steps of 4 layers: 32 held experts of 320 assignments, each one tile of 512
    counters = {
        f'{FAMILY}{{kind="held"}}': 40 * 4 * 32 * 320.0,
        f'{FAMILY}{{kind="all"}}': 40 * 655360.0,
        f'{FAMILY}{{kind="tile_rows"}}': 40 * 4 * 32 * 512.0,
    }
    assert read({"counters": counters}) == 62.5
    # every expert's last row spills into a second tile
    counters[f'{FAMILY}{{kind="tile_rows"}}'] *= 2
    assert read({"counters": counters}) == 31.25


def test_nothing_without_the_series():
    before = {f'{FAMILY}{{kind="held"}}': 40 * 40960.0, f'{FAMILY}{{kind="all"}}': 40 * 655360.0}
    assert read({"counters": before}) is None  # the program before PR 31
    assert read({"counters": {'lakesoul_loader_rows_total{consumer="local"}': 80.0}}) is None  # a BERT cell
    assert read({"counters": {f'{FAMILY}{{kind="tile_rows"}}': 0.0}}) is None  # no tile ran


TESTS = [test_fill_of_hand_counts, test_nothing_without_the_series]


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
