#!/usr/bin/env python3
"""CPU self-test of ``layer_metrics/moe_grouped_pct.py``.

    python3 benchmarks/chip/selftest/grouped_experts.py

``selftest/tile_fill.py`` and ``selftest/dw_writes.py`` check the readers PRs
31 and 39 added and are not edited by later PRs, so the reader PR 53 added is
checked here: its arithmetic on hand counts, that a layer whose shapes keep
the tile loop reads 0 and not nothing, and that a program without the series
gives nothing (the parent of PR 53, and every cell without a routed layer).
It also holds ``moe_tile_fill_pct`` to what a slot is since PR 53: with the
kernels ``{kind="tile_rows"}`` counts the row blocks multiplied, not whole
tiles, and the accepted reader's share rises by itself.
Nothing here reports a device metric.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from chipbench.spec import load_module  # noqa: E402

read = load_module(os.path.join(BENCH, "layer_metrics", "moe_grouped_pct.py")).read
fill = load_module(os.path.join(BENCH, "layer_metrics", "moe_tile_fill_pct.py")).read
FAMILY = "lakesoul_train_moe_assignments_total"


HELD = 40 * 4 * 32 * 320.0  # 40 steps of 4 layers of 32 held experts of 320 assignments


def _counters(tile_rows, grouped=None, held=HELD):
    counters = {
        f'{FAMILY}{{kind="held"}}': held,
        f'{FAMILY}{{kind="all"}}': 40 * 655360.0,
        f'{FAMILY}{{kind="tile_rows"}}': tile_rows,
        f'{FAMILY}{{kind="dw_writes"}}': 40 * 4 * 32.0,
    }
    if grouped is not None:
        counters[f'{FAMILY}{{kind="grouped"}}'] = grouped
    return counters


def test_share_of_hand_counts():
    slots = 40 * 4 * 32 * 512.0  # a tile of 512 slots an expert
    assert read({"counters": _counters(slots, HELD)}) == 100.0      # every routed layer through the kernels
    assert read({"counters": _counters(slots, HELD / 2)}) == 50.0   # half of the layers
    assert read({"counters": _counters(slots, 0.0)}) == 0.0         # shapes that keep the tile loop: a reading, not nothing


def test_nothing_without_the_series():
    assert read({"counters": _counters(40 * 4 * 32 * 512.0)}) is None  # the program before PR 53
    assert read({"counters": {'lakesoul_loader_rows_total{consumer="local"}': 80.0}}) is None  # a BERT cell
    assert read({"counters": _counters(0.0, 0.0, held=0.0)}) is None  # no assignment held in the window


def test_tile_fill_rises_with_the_blocks_skipped():
    # 320 assignments an expert: the loop multiplies a tile of 512 slots, the kernels two blocks of 256 rows
    # (of 128: three), and the accepted reader divides the held assignments by whichever the program counts
    assert fill({"counters": _counters(40 * 4 * 32 * 512.0)}) == 62.5
    assert fill({"counters": _counters(40 * 4 * 32 * 512.0, HELD)}) == 62.5  # blocks of 256: 320 rows fill two
    assert abs(fill({"counters": _counters(40 * 4 * 32 * 384.0, HELD)}) - 83.3333) < 1e-3  # blocks of 128: three


TESTS = [test_share_of_hand_counts, test_nothing_without_the_series, test_tile_fill_rises_with_the_blocks_skipped]


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
