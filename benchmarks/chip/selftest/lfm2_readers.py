#!/usr/bin/env python3
"""CPU self-test of the three readers the LFM2-MoE cell added:
``layer_metrics/conv_step_share_pct.py``, ``mlp_step_share_pct.py`` and
``moe_bias_moved_pct.py``.

    python3 benchmarks/chip/selftest/lfm2_readers.py

``selftest/scopes.py`` checks ``chipbench/scopes.py`` itself and is not edited
by later PRs; here the two new scope readers run over a hand-made trace of one
step (the device's ``XLA Ops`` events and the adaptor's scope map, the run's
``.xplane.pb`` stood in for), the adaptor's ``scopes_of`` reads a compiled
module's text that carries the two new scopes, and the counter reader runs on
hand counts.  Each reader gives nothing on a program without its scope or
series (the parent of the PR that added them, and every other cell).  Nothing
here reports a device metric.
"""

import os
import sys
from unittest import mock

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(os.path.dirname(BENCH))
for path in (BENCH, REPO):
    if path not in sys.path:
        sys.path.insert(0, path)

from chipbench import scopes  # noqa: E402
from chipbench import trace as T  # noqa: E402
from chipbench.spec import load_module  # noqa: E402

STEP = "jit_train_step"
FAMILY = "lakesoul_train_moe_assignments_total"
SCOPE_READERS = ("conv_step_share_pct", "mlp_step_share_pct", "attn_step_share_pct", "moe_step_share_pct")

HLO = """\
HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (p0: bf16[8,8]) -> bf16[8,8] {
  %p0 = bf16[8,8]{1,0} parameter(0)
  ROOT %m.1 = bf16[8,8]{1,0} multiply(%p0, %p0), metadata={op_name="jit(train_step)/jvp(lakesoul.lm.conv)/while/body/checkpoint/mul"}
}

ENTRY %main (x: bf16[8,8]) -> bf16[8,8] {
  %x = bf16[8,8]{1,0} parameter(0)
  %fusion.1 = bf16[8,8]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %dot.2 = bf16[8,8]{1,0} dot(%fusion.1, %x), metadata={op_name="jit(train_step)/transpose(jvp(checkpoint))/rematted_computation/lakesoul.lm.mlp/dot_general"}
  ROOT %add.3 = bf16[8,8]{1,0} add(%dot.2, %x), metadata={op_name="jit(train_step)/jit(main)/add"}
}
"""


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", name + ".py")).read


def hand_step():
    """One step of 100 us: conv 30 (a loop of 40 whose body's attention
    operation takes 10), mlp 20, attn 10, experts 15, head 5, no scope 10, and
    10 idle; an operation of another program after it."""
    us = 1000
    ops = [
        ["%while.1 = (...) while(...)", 0, 40 * us],
        ["%fusion.2 = bf16[8,8] fusion(...)", 5 * us, 10 * us],
        ["%dot.3 = bf16[8,8] dot(...)", 40 * us, 20 * us],
        ["%fusion.4 = f32[8] fusion(...)", 60 * us, 15 * us],
        ["%fusion.5 = f32[8] fusion(...)", 75 * us, 5 * us],
        ["%copy.6 = f32[8] copy(...)", 80 * us, 10 * us],
        ["%dot.3 = bf16[8,8] dot(...)", 150 * us, 20 * us],
    ]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": T.MODULES_LINE, "events": [[STEP + "(7)", 0, 100 * us], ["jit_other(5)", 140 * us, 40 * us]]},
        {"name": T.OPS_LINE, "events": ops},
    ]}]}
    scope_of = {
        "while.1": "lakesoul.lm.conv", "fusion.2": "lakesoul.lm.attn", "dot.3": "lakesoul.lm.mlp",
        "fusion.4": "lakesoul.lm.moe.experts", "fusion.5": "lakesoul.lm.head",
    }
    return trace, scope_of


def _run(result):
    """Stands ``scopes.of_run`` on a hand-made result for the readers' sake."""
    return mock.patch.object(scopes, "of_run", lambda sample: result)


def test_scope_shares_of_a_hand_step():
    trace, scope_of = hand_step()
    result = scopes.shares(trace, scope_of, STEP)
    assert result["steps"] == 1 and abs(result["step_s"] * 1e6 - 90.0) < 1e-6
    sample = {"trace_plain": trace, "step_module": STEP}
    with _run(result):
        got = {name: reader(name)(sample) for name in SCOPE_READERS}
    want = {"conv_step_share_pct": 30 / 0.9, "mlp_step_share_pct": 20 / 0.9, "attn_step_share_pct": 10 / 0.9,
            "moe_step_share_pct": 15 / 0.9}
    assert all(abs(got[name] - want[name]) < 1e-9 for name in want), got
    rest = 100 * (result["seconds"]["lakesoul.lm.head"] + result["seconds"][scopes.UNATTRIBUTED]) / result["step_s"]
    assert abs(sum(got.values()) + rest - 100) < 1e-9  # the four shares, the head and the rest are the step


def test_scope_readers_give_nothing_without_their_scope():
    trace, scope_of = hand_step()
    for name, scope in (("conv_step_share_pct", "lakesoul.lm.conv"), ("mlp_step_share_pct", "lakesoul.lm.mlp")):
        read = reader(name)
        # no trace, and a driver that names no step program (the real ``of_run``)
        assert read({"trace_plain": None, "step_module": STEP}) is None
        assert read({"trace_plain": {"planes": []}}) is None
        # a step that carries no such scope: the other causal-LM cell, or this cell on an older program
        without = {k: v for k, v in scope_of.items() if v != scope}
        with _run(scopes.shares(trace, without, STEP)):
            assert read({"trace_plain": trace, "step_module": STEP}) is None
        with _run(None):  # traced, and the step never ran or left no scope map
            assert read({"trace_plain": trace, "step_module": STEP}) is None


def test_scopes_of_reads_the_two_new_scopes():
    found = load_module(os.path.join(BENCH, "consumers", "lfm2_moe_clm.py")).scopes_of(HLO)
    assert found == {"m.1": "lakesoul.lm.conv", "fusion.1": "lakesoul.lm.conv", "dot.2": "lakesoul.lm.mlp"}, found


def test_bias_moved_of_hand_counts():
    read = reader("moe_bias_moved_pct")
    # 30 steps of 4 routed layers, 32,768 tokens top-4; one assignment in sixteen moved
    every = 30 * 4 * 32768 * 4.0
    counters = {f'{FAMILY}{{kind="all"}}': every, f'{FAMILY}{{kind="held"}}': every / 4,
                f'{FAMILY}{{kind="bias_moved"}}': every / 16}
    assert read({"counters": counters}) == 6.25
    counters[f'{FAMILY}{{kind="bias_moved"}}'] = 0.0  # a zero bias, or a family without one: a reading of 0
    assert read({"counters": counters}) == 0.0


def test_bias_moved_gives_nothing_without_the_series():
    read = reader("moe_bias_moved_pct")
    before = {f'{FAMILY}{{kind="all"}}': 655360.0, f'{FAMILY}{{kind="held"}}': 40960.0}
    assert read({"counters": before}) is None  # the program before this series
    assert read({"counters": {'lakesoul_loader_rows_total{consumer="local"}': 80.0}}) is None  # a BERT cell
    assert read({"counters": {f'{FAMILY}{{kind="bias_moved"}}': 0.0, f'{FAMILY}{{kind="all"}}': 0.0}}) is None  # no step ran


TESTS = [
    test_scope_shares_of_a_hand_step, test_scope_readers_give_nothing_without_their_scope,
    test_scopes_of_reads_the_two_new_scopes, test_bias_moved_of_hand_counts,
    test_bias_moved_gives_nothing_without_the_series,
]


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
