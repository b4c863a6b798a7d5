#!/usr/bin/env python3
"""CPU self-test of the six readers that name the train step whole:
``layer_metrics/head_step_share_pct.py``, ``optim_step_share_pct.py``,
``embed_step_share_pct.py``, ``unscoped_step_share_pct.py`` (device time inside
the step by ``jax.named_scope``, ``chipbench/scopes.py``) and
``idle_place_ms_step.py``, ``idle_dispatch_ms_step.py`` (device idle time under
the step wrapper's two spans, ``chipbench/program_spans.py``).

    python3 benchmarks/chip/selftest/train_step_readers.py

``selftest/scopes.py`` and ``selftest/program_spans.py`` check the two modules
themselves and are not edited by later PRs.  Here the readers run over

- ``scope_fixtures/step_named_whole.json``: a hand-made step in which every
  scope the readers know stands, the optimizer's and the embedding's among them,
  beside events under no scope; each share is known by construction and with the
  unscoped rest they are 100;
- ``scope_fixtures/lm_seq8k_1step.json.gz``, recorded on a v5e before the two
  new scopes: the head's share and the rest are read, the two new give nothing;
- a hand-made trace of two devices and two steps in which the wrapper's spans
  own idle time by arithmetic;
- ``span_fixtures/bert_mor_stream_3steps.json.gz``, recorded on a v5e before
  the two spans, alone (the parent: the two readers give nothing) and with
  ``span_fixtures_train/bert_mor_stream_3steps.train_spans.json`` laid into
  its consumer line: the new owners take exactly what nobody owned, and the
  parts still add up to ``trace.reduce_trace``'s idle time outside programs.

(``span_fixtures_train/`` is a directory of its own: ``selftest/program_spans.py``
reads every file of ``span_fixtures/`` as a whole trace.)  Each adaptor's
``scopes_of`` reads the two new scopes off a compiled module's text, the GLM
adaptor's charging the prediction module's own lookup to the module.  Nothing
here reports a device metric.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import copy  # noqa: E402
import gzip  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from unittest import mock  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(os.path.dirname(BENCH))
for path in (BENCH, REPO):
    if path not in sys.path:
        sys.path.insert(0, path)

from chipbench import program_spans as P  # noqa: E402
from chipbench import scopes  # noqa: E402
from chipbench import trace as T  # noqa: E402
from chipbench.spec import load_module  # noqa: E402

STEP = "jit_train_step"
PLACE, DISPATCH = "lakesoul.train.place", "lakesoul.train.dispatch"
QUEUE, PUT = "lakesoul.loader.queue", "lakesoul.loader.device_put"
SCOPE_READERS = ("head_step_share_pct", "optim_step_share_pct", "embed_step_share_pct", "unscoped_step_share_pct")
SPAN_READERS = {"idle_place_ms_step": PLACE, "idle_dispatch_ms_step": DISPATCH}

HLO = """\
HloModule jit_train_step, is_scheduled=true

%fused_computation.1 (p0: f32[8,8]) -> f32[8,8] {
  %p0 = f32[8,8]{1,0} parameter(0)
  ROOT %m.1 = f32[8,8]{1,0} multiply(%p0, %p0), metadata={op_name="jit(train_step)/lakesoul.lm.optim/mul"}
}

ENTRY %main (x: f32[8,8], ids: s32[4]) -> f32[8,8] {
  %x = f32[8,8]{1,0} parameter(0)
  %ids = s32[4]{0} parameter(1)
  %gather.1 = f32[4,8]{1,0} gather(%x, %ids), metadata={op_name="jit(train_step)/jvp(lakesoul.lm.embed)/gather"}
  %scatter.2 = f32[8,8]{1,0} scatter(%x, %ids, %gather.1), metadata={op_name="jit(train_step)/transpose(jvp(lakesoul.lm.embed))/scatter-add"}
  %gather.3 = f32[4,8]{1,0} gather(%x, %ids), metadata={op_name="jit(train_step)/jvp(lakesoul.lm.mtp)/checkpoint/lakesoul.lm.embed/gather"}
  %fusion.4 = f32[8,8]{1,0} fusion(%scatter.2), kind=kLoop, calls=%fused_computation.1
  %copy.5 = f32[8,8]{1,0} copy(%fusion.4)
  ROOT %add.6 = f32[8,8]{1,0} add(%copy.5, %x), metadata={op_name="jit(train_step)/transpose(jvp())/add_any"}
}
"""


def close(a: float, b: float, rel: float = 1e-9) -> bool:
    return abs(a - b) <= rel * max(abs(a), abs(b)) + 1e-18


def reader(name):
    return load_module(os.path.join(BENCH, "layer_metrics", name + ".py")).read


def _scopes_run(result):
    """Stands ``scopes.of_run`` on a result for the readers' sake."""
    return mock.patch.object(scopes, "of_run", lambda sample: result)


def _spans_run(trace):
    """Stands the run's newest ``.xplane.pb`` on a trace for the readers' sake."""
    return mock.patch.multiple(P, newest_xplane=lambda: "fixture", _split_file=lambda path: P.split(trace))


def _fixture(*parts):
    path = os.path.join(HERE, *parts)
    with (gzip.open(path, "rt") if path.endswith(".gz") else open(path)) as f:
        return json.load(f)


# ---------------------------------------------------------------- scopes


def test_shares_of_a_step_named_whole():
    fixture = _fixture("scope_fixtures", "step_named_whole.json")
    result = scopes.shares(fixture["trace"], fixture["scopes"], STEP)
    assert result["steps"] == 1 and close(result["step_s"] * 1e6, fixture["step_us"]), result
    sample = {"trace_plain": fixture["trace"], "step_module": STEP}
    with _scopes_run(result):
        got = {name: reader(name)(sample) for name in fixture["share_pct"]}
    assert all(close(got[name], want) for name, want in fixture["share_pct"].items()), got
    assert set(SCOPE_READERS) <= set(got) and close(sum(got.values()), 100.0), got  # the step, whole


def test_scope_readers_on_the_parent():
    """A program from before the two scopes: the optimizer and the embedding
    are in the unattributed rest, which reads that much higher; their readers
    give nothing.  No scope map, no trace or no step: all four give nothing."""
    fixture = _fixture("scope_fixtures", "step_named_whole.json")
    sample = {"trace_plain": fixture["trace"], "step_module": STEP}
    older = {k: v for k, v in fixture["scopes"].items() if v not in ("lakesoul.lm.optim", "lakesoul.lm.embed")}
    with _scopes_run(scopes.shares(fixture["trace"], older, STEP)):
        assert reader("optim_step_share_pct")(sample) is None and reader("embed_step_share_pct")(sample) is None
        assert close(reader("head_step_share_pct")(sample), fixture["share_pct"]["head_step_share_pct"])
        assert close(reader("unscoped_step_share_pct")(sample), fixture["unscoped_pct_without_optim_and_embed"])
    headless = {k: v for k, v in older.items() if v != "lakesoul.lm.head"}
    with _scopes_run(scopes.shares(fixture["trace"], headless, STEP)):
        assert reader("head_step_share_pct")(sample) is None
    for name in SCOPE_READERS:
        read = reader(name)
        assert read({"trace_plain": None, "step_module": STEP}) is None, name   # an untraced run
        assert read({"trace_plain": {"planes": []}}) is None, name               # a driver that names no step program
        with _scopes_run(None):  # traced, and the step never ran or the adaptor wrote no map (a BERT cell)
            assert read(sample) is None, name


def test_recorded_step_from_before_the_scopes():
    """One execution of the Qwen cell's step recorded on a v5e by PR 28: it
    has the head's scope and an unattributed rest, neither of the new two."""
    fixture = _fixture("scope_fixtures", "lm_seq8k_1step.json.gz")
    result = scopes.shares(fixture["trace"], fixture["scopes"], STEP)
    sample = {"trace_plain": fixture["trace"], "step_module": STEP}
    with _scopes_run(result):
        head, rest = reader("head_step_share_pct")(sample), reader("unscoped_step_share_pct")(sample)
        assert reader("optim_step_share_pct")(sample) is None and reader("embed_step_share_pct")(sample) is None
        older = sum(reader(name)(sample) for name in ("gdn_step_share_pct", "attn_step_share_pct", "moe_step_share_pct"))
    assert 0 < head < 10 and 5 < rest < 15, (head, rest)
    assert close(older + head + rest, 100.0, rel=1e-6)


def test_scopes_of_reads_the_two_new_scopes():
    want = {"gather.1": "lakesoul.lm.embed", "scatter.2": "lakesoul.lm.embed", "gather.3": "lakesoul.lm.embed",
            "m.1": "lakesoul.lm.optim", "fusion.4": "lakesoul.lm.optim"}
    for adaptor in ("qwen3_next_clm", "lfm2_moe_clm"):
        found = load_module(os.path.join(BENCH, "consumers", adaptor + ".py")).scopes_of(HLO)
        assert found == want, (adaptor, found)  # the copy and the add_any bear no scope
    found = load_module(os.path.join(BENCH, "consumers", "glm4_moe_lite_clm.py")).scopes_of(HLO)
    assert found == dict(want, **{"gather.3": "lakesoul.lm.mtp"}), found  # the module's own lookup is the module's


# ----------------------------------------------------------------- spans


def hand_trace() -> dict:
    """Times in ns, window 0 to 1000, two steps, two devices.

    Device 0 runs programs 0-100, 420-700 and 900-1000: idle outside programs
    100-420 and 700-900.  Device 1 runs 0-150 and 400-1000: idle 150-400.

    Consumer thread: ``queue`` 110-130, ``device_put`` 140-200, then inside
    ``bench.step`` ``place`` 210-260 and ``dispatch`` 270-440; second round
    ``device_put`` 720-760, ``place`` 770-790, ``dispatch`` 800-920.

    Device 0: queue 20; put 60 + 40 = 100; place 50 + 20 = 70; dispatch
    270-420 and 800-900 = 250; nobody 100-110, 130-140, 200-210, 260-270,
    700-720, 760-770, 790-800 = 80.  Device 1 is busy through the queue
    stall: put 150-200 = 50; place 50; dispatch 270-400 = 130; nobody 200-210
    and 260-270 = 20."""
    fusion = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
    dev0 = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [[STEP + "(1)", 0, 100], [STEP + "(1)", 420, 280], [STEP + "(1)", 900, 100]]},
        {"name": "XLA Ops", "events": [[fusion, 0, 100], [fusion, 420, 280], [fusion, 900, 100]]},
    ]}
    dev1 = {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Modules", "events": [[STEP + "(1)", 0, 150], [STEP + "(1)", 400, 600]]},
        {"name": "XLA Ops", "events": [[fusion, 0, 150], [fusion, 400, 600]]},
    ]}
    host = {"name": "/host:CPU", "lines": [{"name": "python3", "events": [
        ["bench.next_batch", 105, 100], [QUEUE, 110, 20], [PUT, 140, 60],
        ["bench.step", 205, 240], [PLACE, 210, 50], [DISPATCH, 270, 170],
        ["bench.next_batch", 710, 55], [PUT, 720, 40],
        ["bench.step", 765, 160], [PLACE, 770, 20], [DISPATCH, 800, 120],
    ]}]}
    return {"planes": [dev0, dev1, host]}


def test_new_owners_by_hand():
    trace = hand_trace()
    r = P.split(trace)
    ns = {name: s * 1e9 for name, s in r["owner_s"].items()}
    assert r["devices"] == 2 and r["steps"] == 2, r
    assert close(ns[PLACE], 120) and close(ns[DISPATCH], 380) and close(ns[QUEUE], 20) and close(ns[PUT], 150), ns
    assert close(r["unowned_s"] * 1e9, 100) and close(r["outside_s"] * 1e9, 770), r
    reduced = T.reduce_trace(trace)
    P.check(r, reduced)  # queue + put + place + dispatch + nobody = the reduction's idle time outside programs
    sample = {"trace_plain": trace, "trace": reduced}
    with _spans_run(trace):
        assert close(reader("idle_place_ms_step")(sample), 120e-6 / 4)      # ms a device and step
        assert close(reader("idle_dispatch_ms_step")(sample), 380e-6 / 4)
        assert close(reader("idle_unowned_ms_step")(sample), 100e-6 / 4)
        parts = sum(reader(name)(sample) for name in ("idle_queue_ms_step", "idle_put_ms_step", "idle_place_ms_step",
                                                      "idle_dispatch_ms_step", "idle_unowned_ms_step"))
    assert close(parts, 1e3 * P.outside_programs_s(reduced) / 4)


def _with_train_spans(base: dict, extension: dict) -> dict:
    trace = copy.deepcopy(base)
    host = [line for p in trace["planes"] if p["name"] == T.HOST_PLANE for line in p["lines"]]
    (consumer,) = [line for line in host if any(n == P.CONSUMER_MARK for n, _, _ in line["events"])]
    consumer["events"] = consumer["events"] + extension["consumer_events"]
    return trace


def test_recorded_trace_with_the_two_owners():
    """The BERT cell's recorded trace with the wrapper's spans laid into every
    ``bench.step``: what they own is what nobody owned before, no other owner
    moves, and the total is still the reduction's."""
    extension = _fixture("span_fixtures_train", "bert_mor_stream_3steps.train_spans.json")
    base = _fixture(extension["extends"])
    before, trace = P.split(base), _with_train_spans(base, extension)
    after = P.split(trace)
    reduced = T.reduce_trace(trace)
    P.check(after, reduced)
    assert close(after["outside_s"], before["outside_s"]) and close(after["outside_s"], P.outside_programs_s(reduced), rel=1e-6)
    place, dispatch = after["owner_s"][PLACE], after["owner_s"][DISPATCH]
    # the device idled through the first step's dispatch only (a loss read had drained its queue): 60 us of
    # placing, then from 70 us into ``bench.step`` to the next program's start
    assert close(place, 60e-6, rel=1e-6) and 0.4e-3 < dispatch < 0.7e-3, (place, dispatch)
    assert close(before["unowned_s"] - after["unowned_s"], place + dispatch, rel=1e-9), (before, after)
    assert all(close(after["owner_s"][name], s) for name, s in before["owner_s"].items())
    sample = {"trace_plain": trace, "trace": reduced}
    with _spans_run(trace):
        per = after["devices"] * after["steps"]
        assert close(reader("idle_place_ms_step")(sample), 1e3 * place / per)
        assert close(reader("idle_dispatch_ms_step")(sample), 1e3 * dispatch / per)


def test_span_readers_on_the_parent():
    """A program from before the two spans (the recorded trace as it is), an
    untraced run, a trace without a device: nothing to read; and
    ``idle_unowned_ms_step`` still holds the wrapper's time."""
    base = _fixture("span_fixtures", "bert_mor_stream_3steps.json.gz")
    sample = {"trace_plain": base, "trace": T.reduce_trace(base)}
    no_device = {"planes": [p for p in base["planes"] if p["name"] == T.HOST_PLANE]}
    for name in SPAN_READERS:
        read = reader(name)
        with _spans_run(base):
            assert read(sample) is None, name
        assert read({"trace_plain": None, "trace": None}) is None, name
        assert read({"trace_plain": no_device, "trace": {"idle_gaps": []}}) is None, name
    with _spans_run(base):
        assert reader("idle_unowned_ms_step")(sample) > 0


TESTS = [
    test_shares_of_a_step_named_whole, test_scope_readers_on_the_parent, test_recorded_step_from_before_the_scopes,
    test_scopes_of_reads_the_two_new_scopes, test_new_owners_by_hand, test_recorded_trace_with_the_two_owners,
    test_span_readers_on_the_parent,
]


def main(argv: list[str]) -> int:
    chosen = [t for t in TESTS if not argv or t.__name__ in argv or t.__name__.removeprefix("test_") in argv]
    failed = 0
    for test in chosen:
        t0 = time.perf_counter()
        try:
            test()
        except Exception:  # a self-test reports every failure, not the first
            failed += 1
            print(f"FAIL {test.__name__}\n{traceback.format_exc()}", flush=True)
        else:
            print(f"ok   {test.__name__} ({time.perf_counter() - t0:.1f} s)", flush=True)
    print(f"{len(chosen) - failed} passed, {failed} failed")
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
