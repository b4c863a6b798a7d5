#!/usr/bin/env python3
"""CPU self-test of ``chipbench/scopes.py`` and of the map its adaptor writes
(``consumers/qwen3_next_clm.py: scopes_of``).

    python3 benchmarks/chip/selftest/scopes.py

``selftest/run.py`` is not edited by later PRs, so what PR 28 added is checked
here: the arithmetic on a hand-made trace, the parsing of a compiled module's
text, and both on a recorded fixture: one execution of the causal-LM step cut
out of a traced run of ``qwen3_next_a3b_clm_pk.seq8k_mor_stream`` on a v5e
(``scope_fixtures/lm_seq8k_1step.json.gz``: the ``XLA Ops`` events of the step
with names cut to 40 characters and times from the step's start, the scope of
every instruction among them, and what the reader gave for the whole run).
Nothing here reports a device metric.
"""

import gzip
import json
import os
import sys
import time

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(os.path.dirname(BENCH))
for path in (BENCH, REPO):
    if path not in sys.path:
        sys.path.insert(0, path)

from chipbench import scopes  # noqa: E402
from chipbench import trace as T  # noqa: E402
from chipbench.spec import load_module  # noqa: E402

FIXTURE = os.path.join(HERE, "scope_fixtures", "lm_seq8k_1step.json.gz")
STEP = "jit_train_step"

HLO = """\
HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (p0: bf16[8,8]) -> bf16[8,8] {
  %p0 = bf16[8,8]{1,0} parameter(0)
  %m.1 = bf16[8,8]{1,0} multiply(%p0, %p0), metadata={op_name="jit(train_step)/jvp(lakesoul.lm.gdn)/mul"}
  ROOT %t.1 = bf16[8,8]{1,0} tanh(%m.1), metadata={op_name="jit(train_step)/jit(main)/checkpoint/rematted_computation/lakesoul.lm.attn/tanh"}
}

%fused_computation.2 (p0: bf16[8,8]) -> bf16[8,8] {
  %p0.1 = bf16[8,8]{1,0} parameter(0)
  ROOT %n.1 = bf16[8,8]{1,0} negate(%p0.1)
}

ENTRY %main (x: bf16[8,8]) -> bf16[8,8] {
  %x = bf16[8,8]{1,0} parameter(0)
  %fusion.1 = bf16[8,8]{1,0} fusion(%x), kind=kLoop, calls=%fused_computation.1, metadata={op_name="jit(train_step)/jvp(lakesoul.lm.gdn)/mul"}
  %fusion.2 = bf16[8,8]{1,0} fusion(%fusion.1), kind=kLoop, calls=%fused_computation.2, metadata={op_name="jit(train_step)/transpose(jvp(lakesoul.lm.moe.experts))/while/body/neg"}
  %copy.3 = bf16[8,8]{1,0} copy(%fusion.2)
  ROOT %add.4 = bf16[8,8]{1,0} add(%copy.3, %x), metadata={op_name="jit(train_step)/jit(main)/add"}
}
"""


def adaptor():
    return load_module(os.path.join(BENCH, "consumers", "qwen3_next_clm.py"))


def test_scopes_of_a_compiled_module():
    """A fusion takes its root's scope, its own where the root has none; an
    instruction without one of the program's scopes is left out."""
    found = adaptor().scopes_of(HLO)
    assert found["fusion.1"] == "lakesoul.lm.attn", found       # the root's, not its own
    assert found["fusion.2"] == "lakesoul.lm.moe.experts", found  # its own: the root has none
    assert "copy.3" not in found and "add.4" not in found
    assert found["m.1"] == "lakesoul.lm.gdn" and found["t.1"] == "lakesoul.lm.attn"


def hand_trace():
    us = 1000
    ops = [
        ["%fusion.1 = bf16[8,8] fusion(...)", 0, 10 * us],
        ["%while.9 = (...) while(...)", 10 * us, 60 * us],      # spans its body
        ["%fusion.2 = bf16[8,8] fusion(...)", 12 * us, 20 * us],
        ["%copy.3 = bf16[8,8] copy(...)", 35 * us, 5 * us],
        ["%add.4 = bf16[8,8] add(...)", 80 * us, 10 * us],
        ["%fusion.1 = bf16[8,8] fusion(...)", 200 * us, 10 * us],  # outside the step
    ]
    return {"planes": [{"name": "/device:TPU:0", "lines": [
        {"name": T.MODULES_LINE, "events": [[STEP + "(123)", 0, 100 * us], ["jit_other(5)", 195 * us, 20 * us]]},
        {"name": T.OPS_LINE, "events": ops},
    ]}]}


def test_shares_by_hand():
    scope_of = {"fusion.1": "lakesoul.lm.gdn", "fusion.2": "lakesoul.lm.moe.experts", "while.9": "lakesoul.lm.moe.experts"}
    got = scopes.shares(hand_trace(), scope_of, STEP)
    assert got["steps"] == 1
    sec = {k: round(v * 1e6, 6) for k, v in got["seconds"].items()}
    # the while's self time is 60 less its body's 20 + 5; the copy and the add carry no scope
    assert sec == {"lakesoul.lm.gdn": 10.0, "lakesoul.lm.moe.experts": 35.0 + 20.0, scopes.UNATTRIBUTED: 15.0}, sec
    assert abs(got["step_s"] * 1e6 - 80.0) < 1e-6
    assert scopes.shares(hand_trace(), scope_of, "jit_absent") is None
    assert scopes.instruction_name("%fusion.12 = f32[8]{0} fusion(") == "fusion.12"
    assert scopes.instruction_name("copy-start.3 = (bf16[2]") == "copy-start.3"


def test_recorded_step():
    with gzip.open(FIXTURE, "rt") as f:
        fixture = json.load(f)
    got = scopes.shares(fixture["trace"], fixture["scopes"], STEP)
    assert got["steps"] == 1
    total = got["step_s"]
    # every scope the readers ask for is there, and the self times are the step's busy time
    for scope in ("gdn", "attn", "moe.route", "moe.experts", "moe.shared", "head"):
        assert got["seconds"].get(scopes.PREFIX + scope, 0.0) > 0, scope
    busy_ms = T.module_busy_ms(fixture["trace"], STEP)
    assert len(busy_ms) == 1 and abs(busy_ms[0] / 1e3 - total) < 0.01 * total, (busy_ms, total)
    share = lambda *names: 100 * sum(got["seconds"].get(scopes.PREFIX + n, 0.0) for n in names) / total  # noqa: E731
    unattributed = 100 * got["seconds"].get(scopes.UNATTRIBUTED, 0.0) / total
    assert unattributed < 15, unattributed
    parts = share("gdn") + share("attn") + share("moe.route", "moe.experts", "moe.shared") + share("head")
    assert abs(parts + unattributed - 100) < 1e-6
    # one step is the run: each share within two points of what the reader gave for the whole trace
    whole = fixture["whole_run"]
    for scope, sec in whole["seconds"].items():
        assert abs(100 * sec / whole["step_s"] - 100 * got["seconds"].get(scope, 0.0) / total) < 2.0, scope


def test_readers_give_nothing_without_a_trace():
    for name in ("gdn_step_share_pct", "attn_step_share_pct", "moe_step_share_pct"):
        read = load_module(os.path.join(BENCH, "layer_metrics", name + ".py")).read
        assert read({"trace_plain": None, "step_module": STEP}) is None
        assert read({"trace_plain": {"planes": []}}) is None  # a driver that names no step program


def test_counter_readers():
    counters = {
        'lakesoul_train_tokens_total': 40 * 16384.0,
        'lakesoul_train_moe_assignments_total{kind="held"}': 40 * 40960.0,
        'lakesoul_train_moe_assignments_total{kind="all"}': 40 * 655360.0,
        'lakesoul_train_moe_expert_load{stat="max"}': 40 * 4 * 360.0,
        'lakesoul_train_moe_expert_load{stat="mean"}': 40 * 4 * 320.0,
    }
    sample = {"counters": counters, "window_s": 20.0, "chips": 1}
    read = lambda name: load_module(os.path.join(BENCH, "layer_metrics", name + ".py")).read  # noqa: E731
    assert read("moe_held_share_pct")(sample) == 6.25
    assert read("moe_load_max_over_mean")(sample) == 1.125
    assert read("train_tokens_s_chip")(sample) == 40 * 16384 / 20.0
    bare = {"counters": {'lakesoul_loader_rows_total{consumer="local"}': 80.0}, "window_s": 20.0, "chips": 1}
    for name in ("moe_held_share_pct", "moe_load_max_over_mean", "train_tokens_s_chip"):
        assert read(name)(bare) is None  # a program without the counters: nothing to read


TESTS = [
    test_scopes_of_a_compiled_module, test_shares_by_hand, test_recorded_step,
    test_readers_give_nothing_without_a_trace, test_counter_readers,
]


def main() -> int:
    failed = 0
    for test in TESTS:
        t0 = time.perf_counter()
        try:
            test()
        except Exception:  # a self-test reports every failure, not the first
            import traceback

            failed += 1
            print(f"FAIL {test.__name__}")
            traceback.print_exc()
        else:
            print(f"ok   {test.__name__} ({time.perf_counter() - t0:.1f} s)")
    print(f"{len(TESTS) - failed} passed, {failed} failed")
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main())
