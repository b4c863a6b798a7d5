#!/usr/bin/env python3
"""CPU self-test of ``layer_metrics/attn_pair_maps_run_pct.py``.

    python3 benchmarks/chip/selftest/pair_maps.py

Beside ``selftest/afmoe_readers.py`` and ``selftest/output_rows.py`` (later
PRs add files here and edit none): the reader's arithmetic on hand counts
(the Phi-4-mini-flash cell's three paired layers), and that a program without
the series, or a family without head pairs, gives nothing (the parent of
PR 51, every other cell).  Nothing here reports a device metric.
"""

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from chipbench.spec import load_module  # noqa: E402

read = load_module(os.path.join(BENCH, "layer_metrics", "attn_pair_maps_run_pct.py")).read
FAMILY = "lakesoul_train_attn_pair_key_tiles_total"
# a row of 8,192 tokens in tiles of 512 x 512: a causal list's steps a key-value head, and a window of 512's
CAUSAL, WINDOW = 136, 31


def _counters(run, required):
    return {f'{FAMILY}{{kind="run"}}': float(run), f'{FAMILY}{{kind="required"}}': float(required)}


def test_share_of_hand_counts():
    # 47 steps of 1 row, a window layer, a full and a cross one, 10 key-value pairs: two maps a pair
    required = 47 * 2 * 10 * (WINDOW + 2 * CAUSAL)
    assert read({"counters": _counters(required, required)}) == 100.0
    # each map beside each half of its value: four key-value heads a pair
    assert read({"counters": _counters(2 * required, required)}) == 200.0
    # one layer of three, a full one, laid out the old way
    mixed = read({"counters": _counters(47 * 2 * 10 * (WINDOW + 3 * CAUSAL), required)})
    assert abs(mixed - 100.0 * (WINDOW + 3 * CAUSAL) / (WINDOW + 2 * CAUSAL)) < 1e-9


def test_nothing_without_the_series():
    before = {'lakesoul_train_attn_key_tiles_total{kind="run"}': 569870.0,
              'lakesoul_train_attn_key_tiles_total{kind="causal"}': 767040.0}
    assert read({"counters": before}) is None  # the program before PR 51
    assert read({"counters": _counters(0, 0)}) is None  # a family without head pairs
    assert read({"counters": {f'{FAMILY}{{kind="run"}}': 380.0}}) is None  # half a family is no reading
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
