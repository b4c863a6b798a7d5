#!/usr/bin/env python3
"""CPU self-test of the four readers the Trinity-Mini cell added
(``layer_metrics/swa_step_share_pct.py``, ``attn_tiles_run_pct.py``,
``flash_fwd_roofline_pct.py``, ``flash_bwd_roofline_pct.py``) and of the
kernels' cost functions (``kernels/flash_attention.py``).

    python3 benchmarks/chip/selftest/afmoe_readers.py

As ``selftest/glm4_readers.py``: the scope reader runs over a hand-made trace
of one step and a scope map, the counter reader on hand counts, the roofline
readers on hand-made kernel events whose least times are written out here.
Each reader gives nothing on a program without its scope, its series or its
events (the parent of the PR that added them, and every other cell).  Nothing
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

from chipbench import flash_roofline, scopes  # noqa: E402
from chipbench import trace as T  # noqa: E402
from chipbench.peaks import peaks_for  # noqa: E402
from chipbench.spec import load_module  # noqa: E402

STEP = "jit_train_step"
FAMILY = "lakesoul_train_attn_key_tiles_total"
MODEL = {"num_attention_heads": 32, "num_key_value_heads": 4, "head_dim": 128, "sliding_window": 2048}
SEQ = 8192
PEAKS = peaks_for("TPU v5 lite")
cost = load_module(os.path.join(BENCH, "kernels", "flash_attention.py"))


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", name + ".py")).read


def hand_step():
    """One step of 100 us: swa 40 (a loop whose body's kernel takes 25 of
    them), attn 20, mlp 10, experts 10, head 5, no scope 5, and 10 idle."""
    us = 1000
    ops = [
        ["%while.1 = (...) while(...)", 0, 40 * us],
        ["%flash_attention_fwd.2 = (bf16[4,8,8192,128], f32[4,8,1,8192]) custom-call(...)", 5 * us, 25 * us],
        ["%while.3 = (...) while(...)", 40 * us, 20 * us],
        ["%dot.4 = bf16[8,8] dot(...)", 60 * us, 10 * us],
        ["%fusion.5 = f32[8] fusion(...)", 70 * us, 10 * us],
        ["%fusion.6 = f32[8] fusion(...)", 80 * us, 5 * us],
        ["%copy.7 = f32[8] copy(...)", 85 * us, 5 * us],
    ]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": T.MODULES_LINE, "events": [[STEP + "(7)", 0, 100 * us]]},
        {"name": T.OPS_LINE, "events": ops},
    ]}]}
    scope_of = {
        "while.1": "lakesoul.lm.swa", "flash_attention_fwd.2": "lakesoul.lm.swa", "while.3": "lakesoul.lm.attn",
        "dot.4": "lakesoul.lm.mlp", "fusion.5": "lakesoul.lm.moe.experts", "fusion.6": "lakesoul.lm.head",
    }
    return trace, scope_of


def _run(result):
    """Stands ``scopes.of_run`` on a hand-made result for the readers' sake."""
    return mock.patch.object(scopes, "of_run", lambda sample: result)


def test_window_share_of_a_hand_step():
    trace, scope_of = hand_step()
    result = scopes.shares(trace, scope_of, STEP)
    sample = {"trace_plain": trace, "step_module": STEP}
    with _run(result):
        swa, attn = reader("swa_step_share_pct")(sample), reader("attn_step_share_pct")(sample)
    assert abs(swa - 40 / 0.9) < 1e-9 and abs(attn - 20 / 0.9) < 1e-9  # the two are the mixers': 60 of 90 busy us


def test_window_share_gives_nothing_without_its_scope():
    trace, scope_of = hand_step()
    read = reader("swa_step_share_pct")
    assert read({"trace_plain": None, "step_module": STEP}) is None
    assert read({"trace_plain": {"planes": []}}) is None
    # a step that carries no such scope: another causal-LM cell, whose attention stands under ``attn``
    without = {k: ("lakesoul.lm.attn" if v == "lakesoul.lm.swa" else v) for k, v in scope_of.items()}
    with _run(scopes.shares(trace, without, STEP)):
        assert read({"trace_plain": trace, "step_module": STEP}) is None
    with _run(None):  # traced, and the step never ran or left no scope map
        assert read({"trace_plain": trace, "step_module": STEP}) is None


def test_tiles_run_of_hand_counts():
    read = reader("attn_tiles_run_pct")
    # 30 steps of 2 rows over 4 key-value heads: four window layers of 280 steps and one full layer of 544
    per_head = 30 * 2 * 4
    counters = {f'{FAMILY}{{kind="run"}}': per_head * (4 * 280 + 544.0), f'{FAMILY}{{kind="causal"}}': per_head * 5 * 544.0}
    assert abs(read({"counters": counters}) - 100 * 1664 / 2720) < 1e-9 and round(read({"counters": counters}), 1) == 61.2
    counters[f'{FAMILY}{{kind="run"}}'] = counters[f'{FAMILY}{{kind="causal"}}']  # a family without a window
    assert read({"counters": counters}) == 100.0


def test_tiles_run_gives_nothing_without_the_series():
    read = reader("attn_tiles_run_pct")
    assert read({"counters": {'lakesoul_train_tokens_total': 245760.0}}) is None  # the program before this series
    assert read({"counters": {f'{FAMILY}{{kind="run"}}': 0.0, f'{FAMILY}{{kind="causal"}}': 0.0}}) is None  # no tile listed
    assert read({"counters": {f'{FAMILY}{{kind="causal"}}': 10.0}}) is None


def test_a_window_of_the_row_length_counts_as_the_causal_mask():
    sizes = dict(heads=32, kv_heads=4, head_dim=128, seq=SEQ)
    assert cost.visible_pairs(SEQ, None) == SEQ * (SEQ + 1) // 2 == 33_558_528
    assert cost.visible_pairs(SEQ, SEQ) == cost.visible_pairs(SEQ, 10 * SEQ) == cost.visible_pairs(SEQ, None)
    assert cost.visible_pairs(SEQ, 2048) == sum(min(i + 1, 2048) for i in range(SEQ)) == 14_681_088
    assert cost.visible_pairs(SEQ, 1) == SEQ  # a query sees itself alone
    for kernel in ("fwd", "bwd"):
        assert cost.cost(kernel=kernel, window=SEQ, **sizes) == cost.cost(kernel=kernel, window=None, **sizes)
    flops, moved = cost.cost(kernel="fwd", window=2048, **sizes)
    assert flops == 4 * 128 * 32 * 14_681_088 and round(flops / 1e9, 1) == 240.5   # the issue's 241 GFLOP a layer-row
    assert moved == 2 * (2 * 32 + 2 * 4) * SEQ * 128 + 4 * 32 * SEQ              # q, o; k, v; the log-sum-exp
    assert round(cost.cost(kernel="fwd", window=None, **sizes)[0] / 1e9, 1) == 549.8
    back, moved = cost.cost(kernel="bwd", window=2048, **sizes)
    assert back == 2.5 * flops                                                     # five products for two
    assert moved == 2 * (3 * 32 + 2 * 4) * SEQ * 128 + 4 * (2 * 32 * SEQ + 2 * 4 * SEQ * 128)


def _kernel_events(kernel):
    """Four window layers' calls of 2 ms and one full layer's of 4 ms (a
    row's; the backward kernel's 5 and 10), and an event of another kernel
    that no reader of these may count."""
    name = "%flash_attention_{}.{} = (bf16[4,8,8192,128]{{3,2,1,0}}) custom-call(%a, %b)"
    ms = 1_000_000 if kernel == "fwd" else 2_500_000
    events = [[name.format(kernel, n), n * 20_000_000, 2 * ms] for n in (1, 2, 3, 4)]
    events.append([name.format(kernel, 5), 100_000_000, 4 * ms])
    events.append(["%expert_dw.9 = f32[8,2048,1024]{2,1,0} custom-call(%a)", 60_000_000, 1_000_000])
    scope_of = {f"flash_attention_{kernel}.{n}": "lakesoul.lm.swa" for n in (1, 2, 3, 4)}
    scope_of[f"flash_attention_{kernel}.5"] = "lakesoul.lm.attn"
    return {"planes": [{"name": "/device:TPU:0", "lines": [{"name": T.OPS_LINE, "events": events}]}]}, scope_of


def test_roofline_shares_of_hand_events():
    for kernel, per_pair in (("fwd", 4), ("bwd", 10)):
        trace, scope_of = _kernel_events(kernel)
        sample = {"trace_plain": trace, "peaks": PEAKS, "config": {"model": MODEL, "table": {"seq": SEQ}}}
        with mock.patch.object(flash_roofline, "scope_map", lambda scope_of=scope_of: scope_of):
            got = reader(f"flash_{kernel}_roofline_pct")(sample)
        # the products bound every call: operations over the bfloat16 peak, 4 window calls and 1 full over 12 (30) ms
        least = per_pair * 128 * 32 * (4 * 14_681_088 + 33_558_528) / PEAKS["bf16_flops"]
        assert abs(got - 100 * least / (12e-3 * per_pair / 4)) < 1e-9, (kernel, got)
        assert 0 < got < 100
        # were every call charged the triangle, as a count off a causal tile list would, the share would be higher
        causal = dict(scope_of, **{k: "lakesoul.lm.attn" for k in scope_of})
        with mock.patch.object(flash_roofline, "scope_map", lambda causal=causal: causal):
            assert reader(f"flash_{kernel}_roofline_pct")(sample) > got


def test_roofline_readers_give_nothing_without_their_events():
    for kernel in ("fwd", "bwd"):
        read = reader(f"flash_{kernel}_roofline_pct")
        trace, scope_of = _kernel_events(kernel)
        config = {"model": MODEL, "table": {"seq": SEQ}}
        assert read({"trace_plain": None, "peaks": PEAKS, "config": config}) is None  # an untraced run
        other, _ = _kernel_events("bwd" if kernel == "fwd" else "fwd")
        with mock.patch.object(flash_roofline, "scope_map", lambda scope_of=scope_of: scope_of):
            assert read({"trace_plain": other, "peaks": PEAKS, "config": config}) is None  # no event of this kernel
        with mock.patch.object(flash_roofline, "scope_map", lambda: None):  # no scope map beside the trace
            assert read({"trace_plain": trace, "peaks": PEAKS, "config": config}) is None
        # a program whose attention carries neither scope (the prediction module's ``mtp``, say): no guess
        foreign = dict(scope_of, **{f"flash_attention_{kernel}.5": "lakesoul.lm.mtp"})
        with mock.patch.object(flash_roofline, "scope_map", lambda foreign=foreign: foreign):
            assert read({"trace_plain": trace, "peaks": PEAKS, "config": config}) is None


TESTS = [
    test_window_share_of_a_hand_step, test_window_share_gives_nothing_without_its_scope,
    test_tiles_run_of_hand_counts, test_tiles_run_gives_nothing_without_the_series,
    test_a_window_of_the_row_length_counts_as_the_causal_mask, test_roofline_shares_of_hand_events,
    test_roofline_readers_give_nothing_without_their_events,
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
