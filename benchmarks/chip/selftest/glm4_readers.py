#!/usr/bin/env python3
"""CPU self-test of the three readers the GLM-4.7-Flash cell added
(``layer_metrics/mla_step_share_pct.py``, ``mtp_step_share_pct.py``,
``mtp_head_positions_pct.py``) and of the rule by which its adaptor charges the
prediction module (``consumers/glm4_moe_lite_clm.py: scopes_of``).

    python3 benchmarks/chip/selftest/glm4_readers.py

As ``selftest/lfm2_readers.py``: the scope readers run over a hand-made trace
of one step and the adaptor's scope map, the adaptor's ``scopes_of`` reads a
compiled module's text whose prediction module carries a layer's scopes inside
its own, and the counter reader runs on hand counts.  Each reader gives nothing
on a program without its scope or series (the parent of the PR that added them,
and every other cell).  Nothing here reports a device metric.
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
FAMILY = "lakesoul_train_head_positions_total"
SCOPE_READERS = ("mla_step_share_pct", "mtp_step_share_pct", "attn_step_share_pct", "moe_step_share_pct",
                 "mlp_step_share_pct")

# a main-stack mixer (attn, and mla inside it), the module's mixer (the same two scopes inside mtp), the
# module's head (head inside mtp, the root of a fusion), the main head, and an operation with no scope
HLO = """\
HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  ROOT %e.1 = f32[8,8]{1,0} exponential(%p0), metadata={op_name="jit(train_step)/jvp(lakesoul.lm.mtp)/lakesoul.lm.head/exp"}
}

ENTRY %main (x: bf16[8,8]) -> bf16[8,8] {
  %x = bf16[8,8]{1,0} parameter(0)
  %dot.1 = bf16[8,8]{1,0} dot(%x, %x), metadata={op_name="jit(train_step)/jvp(lakesoul.lm.attn)/while/body/checkpoint/lakesoul.lm.mla/dot_general"}
  %copy.2 = bf16[8,8]{1,0} copy(%dot.1), metadata={op_name="jit(train_step)/jvp(lakesoul.lm.attn)/while/body/checkpoint/transpose"}
  %dot.3 = bf16[8,8]{1,0} dot(%x, %x), metadata={op_name="jit(train_step)/transpose(jvp(lakesoul.lm.mtp))/lakesoul.lm.attn/while/body/checkpoint/lakesoul.lm.mla/dot_general"}
  %custom-call.4 = bf16[8,8]{1,0} custom-call(%dot.3), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/jvp(lakesoul.lm.mtp)/lakesoul.lm.attn/while/body/checkpoint/flash_attention_fwd"}
  %dot.5 = bf16[8,8]{1,0} dot(%x, %x), metadata={op_name="jit(train_step)/jvp(lakesoul.lm.mtp)/lakesoul.lm.moe.experts/while/body/dot_general"}
  %fusion.6 = f32[8,8]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.1
  %dot.7 = f32[8,8]{1,0} dot(%x, %x), metadata={op_name="jit(train_step)/jvp(lakesoul.lm.head)/dot_general"}
  ROOT %add.8 = bf16[8,8]{1,0} add(%dot.5, %x), metadata={op_name="jit(train_step)/jit(main)/add"}
}
"""


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", name + ".py")).read


def hand_step():
    """One step of 100 us: attn 25 (a loop of 40 whose body's latent
    projections take 15), mla 15, mtp 20, mlp 10, experts 10, head 5, no scope
    5, and 10 idle; an operation of another program after it."""
    us = 1000
    ops = [
        ["%while.1 = (...) while(...)", 0, 40 * us],
        ["%fusion.2 = bf16[8,8] fusion(...)", 5 * us, 15 * us],
        ["%while.3 = (...) while(...)", 40 * us, 20 * us],
        ["%dot.4 = bf16[8,8] dot(...)", 60 * us, 10 * us],
        ["%fusion.5 = f32[8] fusion(...)", 70 * us, 10 * us],
        ["%fusion.6 = f32[8] fusion(...)", 80 * us, 5 * us],
        ["%copy.7 = f32[8] copy(...)", 85 * us, 5 * us],
        ["%dot.4 = bf16[8,8] dot(...)", 150 * us, 20 * us],
    ]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": T.MODULES_LINE, "events": [[STEP + "(7)", 0, 100 * us], ["jit_other(5)", 140 * us, 40 * us]]},
        {"name": T.OPS_LINE, "events": ops},
    ]}]}
    scope_of = {
        "while.1": "lakesoul.lm.attn", "fusion.2": "lakesoul.lm.mla", "while.3": "lakesoul.lm.mtp",
        "dot.4": "lakesoul.lm.mlp", "fusion.5": "lakesoul.lm.moe.experts", "fusion.6": "lakesoul.lm.head",
    }
    return trace, scope_of


def _run(result):
    """Stands ``scopes.of_run`` on a hand-made result for the readers' sake."""
    return mock.patch.object(scopes, "of_run", lambda sample: result)


def test_the_module_is_charged_wherever_its_scope_stands():
    adaptor = load_module(os.path.join(BENCH, "consumers", "glm4_moe_lite_clm.py"))
    found = adaptor.scopes_of(HLO)
    assert found == {
        "e.1": "lakesoul.lm.mtp", "fusion.6": "lakesoul.lm.mtp",  # the module's head: not the main head's
        "dot.1": "lakesoul.lm.mla", "copy.2": "lakesoul.lm.attn",   # the main stack: the innermost scope
        "dot.3": "lakesoul.lm.mtp", "custom-call.4": "lakesoul.lm.mtp", "dot.5": "lakesoul.lm.mtp",
        "dot.7": "lakesoul.lm.head",
    }, found
    # the other adaptors' rule (the innermost scope) would scatter the module over its layer's scopes
    innermost = load_module(os.path.join(BENCH, "consumers", "qwen3_next_clm.py")).scopes_of(HLO)
    assert innermost["dot.3"] == "lakesoul.lm.mla" and innermost["custom-call.4"] == "lakesoul.lm.attn"
    assert innermost["fusion.6"] == "lakesoul.lm.head" and "lakesoul.lm.mtp" not in innermost.values()
    assert {k: v for k, v in innermost.items() if found[k] != "lakesoul.lm.mtp"} == \
        {k: v for k, v in found.items() if v != "lakesoul.lm.mtp"}


def test_scope_shares_of_a_hand_step():
    trace, scope_of = hand_step()
    result = scopes.shares(trace, scope_of, STEP)
    assert result["steps"] == 1 and abs(result["step_s"] * 1e6 - 90.0) < 1e-6
    sample = {"trace_plain": trace, "step_module": STEP}
    with _run(result):
        got = {name: reader(name)(sample) for name in SCOPE_READERS}
    want = {"mla_step_share_pct": 15 / 0.9, "mtp_step_share_pct": 20 / 0.9, "attn_step_share_pct": 25 / 0.9,
            "moe_step_share_pct": 10 / 0.9, "mlp_step_share_pct": 10 / 0.9}
    assert all(abs(got[name] - want[name]) < 1e-9 for name in want), got
    rest = 100 * (result["seconds"]["lakesoul.lm.head"] + result["seconds"][scopes.UNATTRIBUTED]) / result["step_s"]
    assert abs(sum(got.values()) + rest - 100) < 1e-9  # the five shares, the head and the rest are the step


def test_scope_readers_give_nothing_without_their_scope():
    trace, scope_of = hand_step()
    for name, scope in (("mla_step_share_pct", "lakesoul.lm.mla"), ("mtp_step_share_pct", "lakesoul.lm.mtp")):
        read = reader(name)
        # no trace, and a driver that names no step program (the real ``of_run``)
        assert read({"trace_plain": None, "step_module": STEP}) is None
        assert read({"trace_plain": {"planes": []}}) is None
        # a step that carries no such scope: another causal-LM cell, or this cell on an older program
        without = {k: v for k, v in scope_of.items() if v != scope}
        with _run(scopes.shares(trace, without, STEP)):
            assert read({"trace_plain": trace, "step_module": STEP}) is None
        with _run(None):  # traced, and the step never ran or left no scope map
            assert read({"trace_plain": trace, "step_module": STEP}) is None


def test_head_positions_of_hand_counts():
    read = reader("mtp_head_positions_pct")
    # 30 steps of one row of 8,192 tokens: 8,191 next tokens and 8,190 tokens after next
    counters = {f'{FAMILY}{{kind="all"}}': 30 * (8191 + 8190.0), f'{FAMILY}{{kind="mtp"}}': 30 * 8190.0}
    assert abs(read({"counters": counters}) - 100 * 8190 / 16381) < 1e-9
    counters[f'{FAMILY}{{kind="mtp"}}'] = 0.0  # a family without a module: a reading of 0
    assert read({"counters": counters}) == 0.0


def test_head_positions_give_nothing_without_the_series():
    read = reader("mtp_head_positions_pct")
    bert = {f'{FAMILY}{{kind="all"}}': 245760.0, f'{FAMILY}{{kind="computed"}}': 40960.0}
    assert read({"counters": bert}) is None  # a BERT cell, and the causal-LM step before this series
    assert read({"counters": {'lakesoul_loader_rows_total{consumer="local"}': 80.0}}) is None
    assert read({"counters": {f'{FAMILY}{{kind="mtp"}}': 0.0, f'{FAMILY}{{kind="all"}}': 0.0}}) is None  # no step ran


TESTS = [
    test_the_module_is_charged_wherever_its_scope_stands, test_scope_shares_of_a_hand_step,
    test_scope_readers_give_nothing_without_their_scope, test_head_positions_of_hand_counts,
    test_head_positions_give_nothing_without_the_series,
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
