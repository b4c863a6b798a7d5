#!/usr/bin/env python3
"""CPU self-test of the three readers the looped cell added
(``layer_metrics/exit_step_share_pct.py``, ``loop_passes_per_layer.py``,
``loop_head_positions_pct.py``), of the scope charging inside the pass loop's
body (``consumers/qwen3_next_clm.py: scopes_of``, which every causal-LM
adaptor uses, over a hand-written compiled module) and of the accepted flash
roofline readers at this cell's shape (16 key-value heads of one query head).

    python3 benchmarks/chip/selftest/ouro_readers.py

As ``selftest/afmoe_readers.py``: the scope reader runs over a hand-made trace
of one step and a scope map, the counter readers on hand counts.  Each reader
gives nothing on a program without its scope or its series (the parent of the
PR that added them, and every other cell).  Nothing here reports a device
metric.
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

from chipbench import flash_roofline, scopes  # noqa: E402
from chipbench import trace as T  # noqa: E402
from chipbench.peaks import peaks_for  # noqa: E402
from chipbench.spec import load_module  # noqa: E402

STEP = "jit_train_step"
PASSES = "lakesoul_train_loop_layer_passes_total"
HEAD = "lakesoul_train_head_positions_total"
MODEL = {"num_attention_heads": 16, "num_key_value_heads": 16, "head_dim": 128, "sliding_window": None}
SEQ = 8192
PEAKS = peaks_for("TPU v5 lite")
cost = load_module(os.path.join(BENCH, "kernels", "flash_attention.py"))
scopes_of = load_module(os.path.join(BENCH, "consumers", "qwen3_next_clm.py")).scopes_of


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", name + ".py")).read


def hand_step():
    """One step of 100 us: a pass loop of 60 (inside it attn 35, of which a
    kernel 20, mlp 20, the norm between passes 5 under head), head 15, exit 5,
    optim 5, embed 2, no scope 3, and 10 idle."""
    us = 1000
    ops = [
        ["%while.1 = (...) while(...)", 0, 60 * us],
        ["%while.2 = (...) while(...)", 0, 35 * us],
        ["%flash_attention_fwd.3 = (bf16[16,1,8192,128], f32[16,1,1,8192]) custom-call(...)", 5 * us, 20 * us],
        ["%fusion.4 = bf16[8192,5632] fusion(...)", 35 * us, 20 * us],
        ["%fusion.5 = bf16[1,8192,2048] fusion(...)", 55 * us, 5 * us],
        ["%while.6 = (...) while(...)", 60 * us, 15 * us],
        ["%fusion.7 = f32[4,1,8192] fusion(...)", 75 * us, 5 * us],
        ["%fusion.8 = f32[2048,5632] fusion(...)", 80 * us, 5 * us],
        ["%scatter.9 = f32[49152,2048] scatter(...)", 85 * us, 2 * us],
        ["%copy.10 = f32[8] copy(...)", 87 * us, 3 * us],
    ]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": T.MODULES_LINE, "events": [[STEP + "(7)", 0, 100 * us]]},
        {"name": T.OPS_LINE, "events": ops},
    ]}]}
    scope_of = {
        "while.2": "lakesoul.lm.attn", "flash_attention_fwd.3": "lakesoul.lm.attn", "fusion.4": "lakesoul.lm.mlp",
        "fusion.5": "lakesoul.lm.head", "while.6": "lakesoul.lm.head", "fusion.7": "lakesoul.lm.exit",
        "fusion.8": "lakesoul.lm.optim", "scatter.9": "lakesoul.lm.embed",
    }  # ``while.1``, the pass loop itself, bears no scope: its self time is nobody's, and here it has none
    return trace, scope_of


def _run(result):
    """Stands ``scopes.of_run`` on a hand-made result for the readers' sake."""
    return mock.patch.object(scopes, "of_run", lambda sample: result)


def test_the_looped_steps_shares_are_the_step_whole():
    trace, scope_of = hand_step()
    result = scopes.shares(trace, scope_of, STEP)
    sample = {"trace_plain": trace, "step_module": STEP}
    names = ("attn", "mlp", "head", "exit", "optim", "embed", "unscoped")
    with _run(result):
        got = {name: reader(name + "_step_share_pct")(sample) for name in names}
    want = {"attn": 35, "mlp": 20, "head": 20, "exit": 5, "optim": 5, "embed": 2, "unscoped": 3}
    for name in names:
        assert abs(got[name] - want[name] / 0.9) < 1e-9, (name, got[name])
    assert abs(sum(got.values()) - 100.0) < 1e-9


def test_exit_share_gives_nothing_without_its_scope():
    trace, scope_of = hand_step()
    read = reader("exit_step_share_pct")
    assert read({"trace_plain": None, "step_module": STEP}) is None
    assert read({"trace_plain": {"planes": []}}) is None
    # a step that carries no such scope: every other causal-LM cell
    without = {k: v for k, v in scope_of.items() if v != "lakesoul.lm.exit"}
    with _run(scopes.shares(trace, without, STEP)):
        assert read({"trace_plain": trace, "step_module": STEP}) is None
    with _run(None):  # traced, and the step never ran or left no scope map
        assert read({"trace_plain": trace, "step_module": STEP}) is None


def test_passes_per_layer_of_hand_counts():
    read = reader("loop_passes_per_layer")
    # 14 steps of 1 row over 6 layers, four passes each
    counters = {f'{PASSES}{{kind="run"}}': 14 * 6 * 4.0, f'{PASSES}{{kind="layers"}}': 14 * 6.0}
    assert read({"counters": counters}) == 4.0
    counters[f'{PASSES}{{kind="run"}}'] = 14 * 6 * 3.0  # a program that skips the last pass
    assert read({"counters": counters}) == 3.0


def test_passes_per_layer_gives_nothing_without_the_series():
    read = reader("loop_passes_per_layer")
    assert read({"counters": {'lakesoul_train_tokens_total': 114688.0}}) is None  # the program before this series
    assert read({"counters": {f'{PASSES}{{kind="run"}}': 0.0, f'{PASSES}{{kind="layers"}}': 0.0}}) is None  # no loop
    assert read({"counters": {f'{PASSES}{{kind="run"}}': 24.0}}) is None


def test_loop_head_positions_of_hand_counts():
    read = reader("loop_head_positions_pct")
    labelled = 14 * 8191.0  # 14 steps of one row: every position but the last has a next token
    counters = {f'{HEAD}{{kind="all"}}': 4 * labelled, f'{HEAD}{{kind="loop"}}': 3 * labelled, f'{HEAD}{{kind="mtp"}}': 0.0}
    assert read({"counters": counters}) == 75.0
    counters[f'{HEAD}{{kind="loop"}}'] = 0.0  # a family that does not loop
    assert read({"counters": counters}) == 0.0


def test_loop_head_positions_gives_nothing_without_the_series():
    read = reader("loop_head_positions_pct")
    assert read({"counters": {f'{HEAD}{{kind="all"}}': 100.0, f'{HEAD}{{kind="mtp"}}': 0.0}}) is None  # the parent
    assert read({"counters": {f'{HEAD}{{kind="loop"}}': 0.0}}) is None
    assert read({"counters": {}}) is None


HLO = '''HloModule jit_train_step

%fused_norm (p: bf16[1,8192,2048]) -> bf16[1,8192,2048] {
  %p = bf16[1,8192,2048] parameter(0)
  ROOT %m = bf16[1,8192,2048] multiply(%p, %p), metadata={op_name="jit(train_step)/while/body/lakesoul.lm.head/mul"}
}

%row_body (r: (s32[], bf16[16,1,8192,128])) -> (s32[], bf16[16,1,8192,128]) {
  %r = (s32[], bf16[16,1,8192,128]) parameter(0)
  %flash_attention_fwd.11 = (bf16[16,1,8192,128], f32[16,1,1,8192]) custom-call(%r), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/while/body/lakesoul.lm.attn/while/body/checkpoint/flash_attention_fwd"}
  %attn_operands_fwd.12 = (bf16[1,16,1,8192,128]) custom-call(%r), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/while/body/lakesoul.lm.attn/while/body/checkpoint/attn_operands_fwd"}
  ROOT %t = (s32[], bf16[16,1,8192,128]) tuple(%r)
}

%pass_body (c: (s32[], bf16[1,8192,2048])) -> (s32[], bf16[1,8192,2048]) {
  %c = (s32[], bf16[1,8192,2048]) parameter(0)
  %while.20 = (s32[], bf16[16,1,8192,128]) while(%c), body=%row_body, metadata={op_name="jit(train_step)/while/body/lakesoul.lm.attn/while"}
  %fusion.21 = bf16[1,8192,2048] fusion(%c), kind=kLoop, calls=%fused_norm
  %copy.22 = bf16[1,8192,2048] copy(%fusion.21)
  ROOT %u = (s32[], bf16[1,8192,2048]) tuple(%c)
}

ENTRY %main (a: bf16[1,8192,2048]) -> f32[] {
  %a = bf16[1,8192,2048] parameter(0)
  %while.30 = (s32[], bf16[1,8192,2048]) while(%a), body=%pass_body
  %fusion.31 = f32[4,1,8192] fusion(%a), kind=kLoop, calls=%fused_norm, metadata={op_name="jit(train_step)/lakesoul.lm.exit/logistic"}
  ROOT %s = f32[] constant(0), metadata={op_name="jit(train_step)/lakesoul.lm.optim/add"}
}
'''


def test_the_kernels_inside_the_pass_loops_body_are_charged_to_the_mixers_scope():
    """An instruction's scope is in its own metadata wherever its computation
    is called from: the pass loop's body is a computation like any other, and
    the flash pair and the operand pair inside the loop over rows inside it
    read ``lakesoul.lm.attn``; the pass loop itself, whose ``while`` bears no
    scope, is nobody's; a fusion takes its root's scope before its own."""
    scope_of = scopes_of(HLO)
    assert scope_of["flash_attention_fwd.11"] == scope_of["attn_operands_fwd.12"] == "lakesoul.lm.attn"
    assert scope_of["while.20"] == "lakesoul.lm.attn" and "while.30" not in scope_of and "copy.22" not in scope_of
    assert scope_of["fusion.21"] == "lakesoul.lm.head"  # the norm between passes, by its root
    assert scope_of["fusion.31"] == "lakesoul.lm.head"  # a fusion's root speaks before the fusion's own metadata


def test_the_flash_rooflines_read_this_cells_shape():
    """16 key-value heads of ONE query head at 128 under the causal mask: the
    accepted readers' work for a call, from the configuration's ``model``;
    every event of the step stands under ``lakesoul.lm.attn``."""
    sizes = dict(heads=16, kv_heads=16, head_dim=128, seq=SEQ)
    flops, moved = cost.cost(kernel="fwd", window=None, **sizes)
    assert flops == 4.0 * 128 * 16 * 33_558_528 and moved == 2.0 * 4 * 16 * SEQ * 128 + 4.0 * 16 * SEQ
    least = flops / PEAKS["bf16_flops"]  # the products bound it
    events = [("%flash_attention_fwd.11 = (bf16[...", 2 * least), ("%flash_attention_fwd.13 = (bf16[...", 2 * least)]
    scope_of = {"flash_attention_fwd.11": "lakesoul.lm.attn", "flash_attention_fwd.13": "lakesoul.lm.attn"}
    assert abs(flash_roofline.share_pct(events, scope_of, MODEL, SEQ, PEAKS, "fwd") - 50.0) < 1e-9
    assert flash_roofline.share_pct(events, {}, MODEL, SEQ, PEAKS, "fwd") is None  # no scope map entry: nothing


TESTS = [
    test_the_looped_steps_shares_are_the_step_whole, test_exit_share_gives_nothing_without_its_scope,
    test_passes_per_layer_of_hand_counts, test_passes_per_layer_gives_nothing_without_the_series,
    test_loop_head_positions_of_hand_counts, test_loop_head_positions_gives_nothing_without_the_series,
    test_the_kernels_inside_the_pass_loops_body_are_charged_to_the_mixers_scope,
    test_the_flash_rooflines_read_this_cells_shape,
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
