#!/usr/bin/env python3
"""CPU self-test of the six readers the decoder-hybrid-decoder cell added
(``layer_metrics/ssm_step_share_pct.py``, ``xdec_step_share_pct.py``,
``ssm_scan_fwd_roofline_pct.py``, ``ssm_scan_bwd_roofline_pct.py``,
``ssm_scan_kernel_pct.py``, ``shared_reads_step.py``), of
``kernels/selective_scan.py``'s counts, of the adaptor's operation count and
of the scope charging of the scan kernels inside the row loop
(``consumers/qwen3_next_clm.py: scopes_of``, which every causal-LM adaptor
uses, over a hand-written compiled module).

    python3 benchmarks/chip/selftest/phi4flash_readers.py

As ``selftest/ouro_readers.py``: the scope readers run over a hand-made trace
of one step and a scope map, the counter readers on hand counts, the
rooflines on hand-made events.  Each reader gives nothing on a program
without its scope, its series or its kernel (the parent of the PR that added
them, and every other cell).  Nothing here reports a device metric.
"""

import json
import os
import sys
from unittest import mock

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(os.path.dirname(BENCH))
for path in (BENCH, REPO):
    if path not in sys.path:
        sys.path.insert(0, path)

from chipbench import scopes, ssm_roofline  # noqa: E402
from chipbench import trace as T  # noqa: E402
from chipbench.peaks import peaks_for  # noqa: E402
from chipbench.spec import load_module  # noqa: E402

STEP = "jit_train_step"
SCAN_ROWS = "lakesoul_train_ssm_scan_rows_total"
SHARED_READS = "lakesoul_train_shared_state_reads_total"
SEQ, CHANNELS, STATES = 8192, 5120, 16
PEAKS = peaks_for("TPU v5 lite")
cost = load_module(os.path.join(BENCH, "kernels", "selective_scan.py")).cost
scopes_of = load_module(os.path.join(BENCH, "consumers", "qwen3_next_clm.py")).scopes_of


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", name + ".py")).read


def cell_config() -> dict:
    with open(os.path.join(BENCH, "configs", "phi4_mini_flash_clm_pk.json")) as f:
        return json.load(f)


def hand_step():
    """One step of 100 us: ssm 30 (a row loop of 30 whose two scan kernels
    take 6 and 12), swa 8, attn 10, gmu 4, xattn 8, mlp 20, head 10, optim 5,
    embed 2, no scope 3."""
    us = 1000
    ops = [
        ["%while.1 = (...) while(...)", 0, 30 * us],
        ["%selective_scan_fwd.2 = (bf16[1,8192,5120], f32[1,64,16,5120]) custom-call(...)", 2 * us, 6 * us],
        ["%selective_scan_bwd.3 = (bf16[1,8192,5120], f32[1,8192,5120]) custom-call(...)", 10 * us, 12 * us],
        ["%while.4 = (...) while(...)", 30 * us, 8 * us],
        ["%while.5 = (...) while(...)", 38 * us, 10 * us],
        ["%fusion.6 = bf16[1,8192,2560] fusion(...)", 48 * us, 4 * us],
        ["%while.7 = (...) while(...)", 52 * us, 8 * us],
        ["%fusion.8 = bf16[8192,10240] fusion(...)", 60 * us, 20 * us],
        ["%while.9 = (...) while(...)", 80 * us, 10 * us],
        ["%fusion.10 = f32[2560,10240] fusion(...)", 90 * us, 5 * us],
        ["%scatter.11 = f32[25008,2560] scatter(...)", 95 * us, 2 * us],
        ["%copy.12 = f32[8] copy(...)", 97 * us, 3 * us],
    ]
    trace = {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": T.MODULES_LINE, "events": [[STEP + "(7)", 0, 100 * us]]},
        {"name": T.OPS_LINE, "events": ops},
    ]}]}
    scope_of = {
        "while.1": "lakesoul.lm.ssm", "selective_scan_fwd.2": "lakesoul.lm.ssm", "selective_scan_bwd.3": "lakesoul.lm.ssm",
        "while.4": "lakesoul.lm.swa", "while.5": "lakesoul.lm.attn", "fusion.6": "lakesoul.lm.gmu",
        "while.7": "lakesoul.lm.xattn", "fusion.8": "lakesoul.lm.mlp", "while.9": "lakesoul.lm.head",
        "fusion.10": "lakesoul.lm.optim", "scatter.11": "lakesoul.lm.embed",
    }
    return trace, scope_of


def _run(result):
    """Stands ``scopes.of_run`` on a hand-made result for the readers' sake."""
    return mock.patch.object(scopes, "of_run", lambda sample: result)


def test_the_hybrid_steps_shares_are_the_step_whole():
    trace, scope_of = hand_step()
    result = scopes.shares(trace, scope_of, STEP)
    sample = {"trace_plain": trace, "step_module": STEP}
    names = ("ssm", "swa", "attn", "xdec", "mlp", "head", "optim", "embed", "unscoped")
    with _run(result):
        got = {name: reader(name + "_step_share_pct")(sample) for name in names}
    want = {"ssm": 30, "swa": 8, "attn": 10, "xdec": 12, "mlp": 20, "head": 10, "optim": 5, "embed": 2, "unscoped": 3}
    for name in names:
        assert abs(got[name] - want[name]) < 1e-9, (name, got[name])
    assert abs(sum(got.values()) - 100.0) < 1e-9


def test_the_new_shares_give_nothing_without_their_scopes():
    trace, scope_of = hand_step()
    for name, gone in (("ssm_step_share_pct", ("lakesoul.lm.ssm",)), ("xdec_step_share_pct", ("lakesoul.lm.gmu", "lakesoul.lm.xattn"))):
        read = reader(name)
        assert read({"trace_plain": None, "step_module": STEP}) is None
        assert read({"trace_plain": {"planes": []}}) is None
        # a step that carries no such scope: every other causal-LM cell, and the parent
        without = {k: v for k, v in scope_of.items() if v not in gone}
        with _run(scopes.shares(trace, without, STEP)):
            assert read({"trace_plain": trace, "step_module": STEP}) is None
        with _run(None):  # traced, and the step never ran or left no scope map
            assert read({"trace_plain": trace, "step_module": STEP}) is None
    # one of the second decoder's two scopes alone still reads
    with _run(scopes.shares(trace, {k: v for k, v in scope_of.items() if v != "lakesoul.lm.gmu"}, STEP)):
        assert abs(reader("xdec_step_share_pct")({"trace_plain": trace, "step_module": STEP}) - 8.0) < 1e-9


def test_the_scans_required_work():
    """``T E (3 N + 2)`` multiply-adds forward and twice that backward; the
    bytes of the operands and results once; the bytes bound both passes."""
    fwd, bwd = cost(kernel="fwd", seq=SEQ, channels=CHANNELS, states=STATES), cost(kernel="bwd", seq=SEQ, channels=CHANNELS, states=STATES)
    tokens = SEQ * CHANNELS
    assert fwd[0] == 2.0 * tokens * 50 and bwd[0] == 2 * fwd[0]
    small = 4.0 * (2 * SEQ * STATES + CHANNELS * STATES + CHANNELS)
    assert fwd[1] == 8.0 * tokens + small and bwd[1] == 14.0 * tokens + 2 * small
    for flops, moved in (fwd, bwd):
        assert moved / PEAKS["hbm_bytes_per_s"] > flops / PEAKS["f32_flops"]
    # no [T, E, N] tensor is among the bytes
    assert bwd[1] < 4.0 * tokens * STATES / 4


def test_the_scan_rooflines_of_hand_events():
    model = {"mamba_expand": 2, "hidden_size": 2560, "mamba_d_state": 16}
    for kernel in ("fwd", "bwd"):
        _, moved = cost(kernel=kernel, seq=SEQ, channels=CHANNELS, states=STATES)
        least = moved / PEAKS["hbm_bytes_per_s"]
        events = [(f"%selective_scan_{kernel}.2 = (bf16[...", 4 * least), (f"%selective_scan_{kernel}.9 = (bf16[...", 6 * least)]
        assert abs(ssm_roofline.share_pct(events, model, SEQ, PEAKS, kernel) - 20.0) < 1e-9
        us = 1000
        trace = {"planes": [{"name": "/device:TPU:0", "lines": [
            {"name": T.OPS_LINE, "events": [[f"%selective_scan_{kernel}.2 = (bf16[1,8192,5120]) custom-call(...)", 0, int(5 * least * 1e9)],
                                           ["%fusion.3 = bf16[8] fusion(...)", 0, 7 * us]]},
        ]}]}
        sample = {"trace_plain": trace, "peaks": PEAKS, "config": {"model": model, "table": {"seq": SEQ}}}
        assert abs(reader(f"ssm_scan_{kernel}_roofline_pct")(sample) - 20.0) < 1e-4
        # no trace; a configuration without the sizes (every other cell); a trace without the kernel (the parent)
        assert reader(f"ssm_scan_{kernel}_roofline_pct")(dict(sample, trace_plain=None)) is None
        assert reader(f"ssm_scan_{kernel}_roofline_pct")(dict(sample, config={"model": {"hidden_size": 2048}, "table": {"seq": SEQ}})) is None
        assert reader(f"ssm_scan_{kernel}_roofline_pct")(dict(sample, trace_plain={"planes": []})) is None


def test_scan_kernel_share_of_hand_counts():
    read = reader("ssm_scan_kernel_pct")
    # 40 steps of 1 row over 2 Mamba layers
    counters = {f'{SCAN_ROWS}{{path="kernel"}}': 80.0, f'{SCAN_ROWS}{{path="twin"}}': 0.0}
    assert read({"counters": counters}) == 100.0
    counters = {f'{SCAN_ROWS}{{path="kernel"}}': 40.0, f'{SCAN_ROWS}{{path="twin"}}': 40.0}  # one layer's shape refused
    assert read({"counters": counters}) == 50.0
    assert read({"counters": {'lakesoul_train_tokens_total': 114688.0}}) is None  # the program before this series
    assert read({"counters": {f'{SCAN_ROWS}{{path="kernel"}}': 0.0, f'{SCAN_ROWS}{{path="twin"}}': 0.0}}) is None  # no scan


def test_shared_reads_of_hand_counts():
    read = reader("shared_reads_step")
    assert read({"counters": {SHARED_READS: 80.0}, "steps": 40}) == 2.0  # 1 row x (a gated memory unit + a cross layer)
    assert read({"counters": {SHARED_READS: 0.0}, "steps": 40}) is None    # a family that shares nothing
    assert read({"counters": {}, "steps": 40}) is None                     # the parent
    assert read({"counters": {SHARED_READS: 80.0}, "steps": 0}) is None


def test_the_adaptors_operations_a_row():
    """The required products and maps of the cell's six layers, by hand: no
    score map is credited twice, and the scan is its multiply-adds."""
    adaptor = load_module(os.path.join(BENCH, "consumers", "phi4flash_clm.py"))
    config = cell_config()
    h, ff, e, d, vocab = 2560, 10240, 5120, 64, 25008
    mamba = h * 2 * e + e * 192 + 160 * e + e * h
    attention = 2 * h * h + 2 * h * 1280
    a_token = 6 * 3 * h * ff + 2 * mamba + 2 * attention + 2 * h * e + 2 * h * h + h * vocab
    band, triangle = 512 * 513 // 2 + (SEQ - 512) * 512, SEQ * (SEQ + 1) // 2
    maps = 2 * 2.0 * SEQ * e * 50 + 12 * d * 20 * (band + 2 * triangle)
    want = 3.0 * (SEQ * 2 * a_token + maps)
    got = adaptor.flops_per_row(config)
    assert abs(got - want) <= 1e-9 * want, (got, want)
    assert 37.5e12 < got < 37.6e12  # 34.3 in the products, 3.3 in the score maps and their values, 0.03 in the scan


HLO = '''HloModule jit_train_step

%row_body (r: (s32[], bf16[1,8192,5120])) -> (s32[], bf16[1,8192,5120]) {
  %r = (s32[], bf16[1,8192,5120]) parameter(0)
  %selective_scan_fwd.11 = (bf16[1,8192,5120], f32[1,64,16,5120]) custom-call(%r), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/lakesoul.lm.ssm/while/body/checkpoint/selective_scan_fwd"}
  ROOT %t = (s32[], bf16[1,8192,5120]) tuple(%r)
}

%cross_body (c: (s32[], bf16[40,2,8192,64])) -> (s32[], bf16[40,2,8192,64]) {
  %c = (s32[], bf16[40,2,8192,64]) parameter(0)
  %flash_attention_fwd.12 = (bf16[40,2,8192,64], f32[40,2,1,8192]) custom-call(%c), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/lakesoul.lm.xattn/while/body/checkpoint/flash_attention_fwd"}
  ROOT %u = (s32[], bf16[40,2,8192,64]) tuple(%c)
}

ENTRY %main (a: bf16[1,8192,2560]) -> f32[] {
  %a = bf16[1,8192,2560] parameter(0)
  %while.20 = (s32[], bf16[1,8192,5120]) while(%a), body=%row_body, metadata={op_name="jit(train_step)/lakesoul.lm.ssm/while"}
  %while.21 = (s32[], bf16[40,2,8192,64]) while(%a), body=%cross_body, metadata={op_name="jit(train_step)/lakesoul.lm.xattn/while"}
  %selective_scan_bwd.22 = (bf16[1,8192,5120], f32[1,8192,5120]) custom-call(%a), custom_call_target="tpu_custom_call", metadata={op_name="jit(train_step)/transpose(jvp(lakesoul.lm.ssm))/while/body/checkpoint/selective_scan_bwd"}
  ROOT %s = f32[] constant(0), metadata={op_name="jit(train_step)/lakesoul.lm.optim/add"}
}
'''


def test_the_scan_kernels_are_charged_to_the_mamba_scope():
    """The scan pair inside the row loop reads ``lakesoul.lm.ssm`` in both
    passes, and a cross layer's attention kernel ``lakesoul.lm.xattn``."""
    scope_of = scopes_of(HLO)
    assert scope_of["selective_scan_fwd.11"] == scope_of["selective_scan_bwd.22"] == scope_of["while.20"] == "lakesoul.lm.ssm"
    assert scope_of["flash_attention_fwd.12"] == scope_of["while.21"] == "lakesoul.lm.xattn"


TESTS = [
    test_the_hybrid_steps_shares_are_the_step_whole, test_the_new_shares_give_nothing_without_their_scopes,
    test_the_scans_required_work, test_the_scan_rooflines_of_hand_events, test_scan_kernel_share_of_hand_counts,
    test_shared_reads_of_hand_counts, test_the_adaptors_operations_a_row,
    test_the_scan_kernels_are_charged_to_the_mamba_scope,
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
