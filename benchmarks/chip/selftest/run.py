#!/usr/bin/env python3
"""Self-test of the chip benchmark's own code, on the CPU:

    python3 benchmarks/chip/selftest/run.py [name ...]

- the trace reduction (busy union, idle share, kernel time, exposed
  collectives, gap naming) on a hand-made trace with hand counts and on a
  small trace recorded on a v5e (``fixtures/``);
- the operation and byte functions against hand counts; the peaks table;
- the traffic generator and the load generator against a stub server;
- the two drivers called as functions at tiny sizes, the ANN plane with the
  Pallas interpreter, the way ``tests/test_chip_smoke.py`` calls the smoke's
  stages;
- a cell, a configuration, a consumer adaptor and a per-layer metric added as
  new files in a temporary overlay and found by name, no existing file changed;
- ``run.py``'s own ``main`` with the chip and the driver stubbed: the order of
  its stamps around the backend's start and ``device.runtime_start_s`` in both
  result lines.

This is the one place in the benchmark that runs without a chip, and it
reports no device metric: what the drivers print here is checked for shape and
for correctness, never for speed.  It works in a temporary directory.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")
os.environ.setdefault("XLA_FLAGS", "--xla_force_host_platform_device_count=4")

import dataclasses  # noqa: E402
import gzip  # noqa: E402
import hashlib  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402
from concurrent.futures import Future  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
REPO = os.path.dirname(os.path.dirname(BENCH))
for path in (BENCH, REPO):
    if path not in sys.path:
        sys.path.insert(0, path)

import numpy as np  # noqa: E402

from chipbench import trace as T  # noqa: E402
from chipbench.overlay import add_cell, add_pending, make_overlay  # noqa: E402
from chipbench.peaks import UnknownDevice, least_seconds, peaks_for  # noqa: E402
from chipbench.spec import SpecError, load_cell, load_module  # noqa: E402


def close(a: float, b: float, rel: float = 1e-9) -> bool:
    return abs(a - b) <= rel * max(abs(a), abs(b)) + 1e-18


# ------------------------------------------------------------------- trace

AR = "%all-reduce.7 = f32[1024]{0:T(1024)} all-reduce(f32[1024]{0:T(1024)} %fusion.3), replica_groups={{0,1,2,3}}"
KERNEL = ("%_ragged_score_pallas_call.1 = f32[4096,1,128]{2,1,0:T(1,128)} custom-call(s32[4096]{0:T(1024)}"
          " %item_q.1, s32[4096]{0:T(1024)} %item_tile.1, f32[4096]{0:T(1024)} %csq.1, f32[4096]{0:T(1024)}"
          " %csum.1, f32[256,1,512]{2,1,0:T(1,128)S(1)} %b.1, f32[143616,512]{1,0:T(8,128)} %codes.1,"
          " f32[1,143616]{1,0:T(1,128)S(1)} %reshape.3), custom_call_target=\"tpu_custom_call\"")


def hand_trace() -> dict:
    """Two devices, times in ns.  Device 0: a program from 100 to 500 holding a
    ``while`` (100-400) with two fusions inside (100-200, 250-400), then an
    all-reduce 400-500; a second program 800-900 with the kernel.  Device 1:
    one fusion 100-300.  Host: ``bench.step`` 0-150, ``bench.next_batch``
    500-850, nothing 850-1000 where ``bench.step`` 900-1000 closes the window."""
    dev0 = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_train_step(1)", 100, 400], ["jit__ragged_score_pallas_call(2)", 800, 100]]},
        {"name": "XLA Ops", "events": [
            ["%while.1 = (s32[]) while((s32[]) %tuple.1), condition=%c, body=%b", 100, 300],
            ["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 100, 100],
            ["%fusion.2 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 250, 150],
            [AR, 400, 100],
            [KERNEL, 800, 100],
        ]},
    ]}
    dev1 = {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Modules", "events": [["jit_train_step(1)", 100, 200]]},
        {"name": "XLA Ops", "events": [["%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop", 100, 200]]},
    ]}
    host = {"name": "/host:CPU", "lines": [{"name": "python", "events": [
        ["bench.step", 0, 150], ["bench.next_batch", 500, 350], ["bench.step", 900, 100],
    ]}]}
    return {"planes": [dev0, dev1, host]}


def test_trace_hand_counts():
    r = T.reduce_trace(hand_trace())
    assert r["devices"] == 2 and close(r["window_s"], 1000e-9), r
    # device 0 is busy 100-500 and 800-900 (the while covers its inner gap); device 1 100-300
    assert close(r["per_device"][0]["busy_s"], 500e-9) and close(r["per_device"][1]["busy_s"], 200e-9), r
    assert close(r["busy_s"], 350e-9), r
    idle_share = 1 - r["busy_s"] / r["window_s"]
    assert close(idle_share, 0.65), idle_share
    # exposed collectives: the all-reduce sits in the core's own stream for 100 ns; worst device
    assert close(r["collective_exposed_s_worst"], 100e-9), r
    ops = dict(r["device_ops"])
    # self time: the while is 300 less its two fusions (100 + 150) = 50; fusion.1 runs on both devices
    assert close(ops["while.1 s32[]"], 50e-9) and close(ops["fusion.1 f32[8]"], 300e-9), ops
    assert close(ops["all-reduce.7 f32[1024]"], 100e-9), ops
    gaps = dict(r["idle_gaps"])
    # device 0: 0-100 under bench.step; 500-800 under bench.next_batch; 900-1000 under bench.step
    # device 1: 0-100 bench.step; 300-1000: 300-500 nothing open... 150-500 is between spans
    assert close(gaps["bench.next_batch"], (300 + 350) * 1e-9), gaps
    assert close(gaps["bench.step"], (100 + 100 + 100 + 100) * 1e-9), gaps
    assert close(gaps["(between bench spans)"], (200 + 50) * 1e-9), gaps
    assert "(inside a device program)" not in gaps, gaps
    assert close(sum(gaps.values()), 2 * 1000e-9 - 700e-9), gaps
    assert T.module_busy_ms(hand_trace(), "jit_train_step") == [400e-6, 200e-6]
    kernel = T.kernel_events(hand_trace(), "ragged_score")
    assert len(kernel) == 1 and close(kernel[0][1], 100e-9)


def test_trace_names():
    assert T.instruction(AR) == ("all-reduce", "all-reduce") and T.is_collective(AR)
    assert T.is_collective("%all-gather-start.2 = (f32[8]{0}, f32[32]{0}) all-gather-start(f32[8]{0} %x)")
    assert not T.is_collective("%fusion.479 = f32[768]{0:T(1024)S(1)} fusion(f32[12,768]{1,0} %g), kind=kLoop")
    assert T.instruction(KERNEL) == ("_ragged_score_pallas_call", "custom-call")
    assert T.op_label(KERNEL) == "_ragged_score_pallas_call.1 custom-call f32[4096,1,128]"
    assert T.union([(0, 2), (1, 3), (5, 6)]) == [(0, 3), (5, 6)]
    assert T.gaps([(1, 2), (4, 5)], 0, 6) == [(0, 1), (2, 4), (5, 6)]


def _sweep_busy(events):
    """Busy time by another route: sort the edges and count what is open."""
    edges = sorted([(s, 1) for _, s, d in events] + [(s + d, -1) for _, s, d in events])
    busy, open_now, last = 0.0, 0, 0.0
    for t, step in edges:
        if open_now > 0:
            busy += t - last
        last, open_now = t, open_now + step
    return busy


def test_trace_recorded():
    """The reduction on traces recorded on a TPU v5e in PR 22 (cut to a few
    steps and dispatches): the union agrees with an edge sweep, the step
    program and the kernel are found by name, idle gaps have owners."""
    seen = 0
    for name in sorted(os.listdir(os.path.join(HERE, "fixtures"))):
        if not name.endswith(".json.gz"):
            continue
        with gzip.open(os.path.join(HERE, "fixtures", name), "rt") as f:
            plain = json.load(f)
        r = T.reduce_trace(plain)
        assert r["devices"] >= 1 and r["window_s"] > 0 and 0 < r["busy_s"] <= r["window_s"], (name, r)
        for plane, per in zip([p for p in plain["planes"] if p["name"].startswith(T.DEVICE_PREFIX)], r["per_device"]):
            ops = T._line(plane, T.OPS_LINE)
            assert close(per["busy_s"] * 1e9, _sweep_busy(ops), rel=1e-6), name
        total_gap = sum(s for _, s in T.reduce_trace(plain, top=1000)["idle_gaps"])
        assert close(total_gap, r["devices"] * r["window_s"] - sum(p["busy_s"] for p in r["per_device"]), rel=1e-6), name
        if name.startswith("bert"):
            steps = T.module_busy_ms(plain, "jit_train_step")
            assert steps and all(10 < ms < 200 for ms in steps), steps
            if "dp4" in name:
                assert r["devices"] == 4 and r["collective_exposed_s_worst"] > 0, r
            else:
                assert r["collective_exposed_s_worst"] == 0
        if name.startswith("ann"):
            cost = load_module(os.path.join(BENCH, "kernels", "ragged_score.py"))
            events = T.kernel_events(plain, "ragged_score")
            assert events, name
            peaks = peaks_for("TPU v5 lite")
            for event, seconds in events:
                flops, moved = cost.from_event(event)
                least, which = least_seconds(flops=flops, bytes_moved=moved, flops_peak=peaks["f32_flops"],
                                             bytes_peak=peaks["hbm_bytes_per_s"])
                assert which == "memory" and 0.05 < least / seconds <= 1.0, (least, seconds)
            assert any(owner.startswith("bench.") for owner, _ in r["idle_gaps"]), r["idle_gaps"]
        seen += 1
    assert seen >= 2, "no recorded fixtures found"


# ------------------------------------------------------- counts and peaks


def test_operation_counts():
    config = json.load(open(os.path.join(BENCH, "configs", "bert_base_mlm_pk.json")))
    adaptor = load_module(os.path.join(BENCH, "consumers", "bert_mlm.py"))
    # by hand, per token: a layer is 2 * (4 * 768^2 + 2 * 768 * 3072) + 4 * 128 * 768 = 14,548,992
    # twelve of them 174,587,904; the head at 15% of the positions 2 * 768 * 30522 * 0.15 = 7,032,268.8
    forward_token = 12 * 14_548_992 + 7_032_268.8
    assert close(adaptor.flops_per_row(config), 3 * 128 * forward_token), adaptor.flops_per_row(config)
    cost = load_module(os.path.join(BENCH, "kernels", "ragged_score.py"))
    # one item: a 128 x 512 float32 block times one row
    flops, moved = cost.cost(items=1, tile=128, d=512)
    assert flops == 2 * 128 * 512 + 4 * 128 and moved == 128 * 512 * 4 + 512 * 4 + 4 * 128 * 4
    assert cost.from_event(KERNEL) == cost.cost(items=4096, tile=128, d=512)


def test_peaks_table():
    v5e = peaks_for("TPU v5 lite")
    assert v5e["bf16_flops"] == 197e12 and v5e["hbm_bytes_per_s"] == 819e9 and v5e["hbm_bytes"] == 16e9
    assert "TPU v5e" in v5e["source"]
    try:
        peaks_for("cpu")
    except UnknownDevice:
        pass
    else:
        raise AssertionError("an unknown device kind must be an error")
    assert least_seconds(flops=197e12, bytes_moved=819e9 / 2, flops_peak=197e12, bytes_peak=819e9) == (1.0, "compute")
    assert least_seconds(flops=1.0, bytes_moved=819e9, flops_peak=197e12, bytes_peak=819e9) == (1.0, "memory")


# ------------------------------------------------------------- traffic


def test_traffic_and_loadgen():
    from chipbench.loadgen import LoadGenerator
    from chipbench.traffic import Schedule

    workload = {"loop": "open", "rate_per_s": 200, "arrivals": {"process": "gamma", "shape": 1.0},
                "query_pool": 8, "mix": {"nprobe": {"values": [1, 2], "weights": [0.75, 0.25]}}}
    a = Schedule(workload, seed=5, horizon_s=10.0, query_count=16)
    b = Schedule(workload, seed=5, horizon_s=10.0, query_count=16)
    c = Schedule(workload, seed=6, horizon_s=10.0, query_count=16)
    assert np.array_equal(a.due, b.due) and np.array_equal(a.params["nprobe"], b.params["nprobe"])
    assert not np.array_equal(a.due, c.due)
    assert 1700 < a.n < 2300 and np.all(np.diff(a.due) >= 0) and a.due[-1] < 10.0
    assert 0.7 < np.mean(a.params["nprobe"] == 1) < 0.8 and a.query.max() < 8
    bursty = Schedule(dict(workload, arrivals={"process": "gamma", "shape": 0.25}), seed=5,
                      horizon_s=10.0, query_count=16)
    cv = lambda s: np.std(np.diff(s.due)) / np.mean(np.diff(s.due))  # noqa: E731
    assert 0.9 < cv(a) < 1.1 and 1.6 < cv(bursty) < 2.5, (cv(a), cv(bursty))

    class Refused(Exception):
        pass

    answered: list[Future] = []

    def submit(i):
        if i == 3:
            raise Refused()
        fut = Future()
        answered.append(fut)
        fut.set_result((np.array([i]), None))
        return fut

    short = Schedule(dict(workload, rate_per_s=400), seed=1, horizon_s=0.25, query_count=16)
    gen = LoadGenerator(short, submit, rejected=Refused)
    gen.start()
    time.sleep(0.4)
    gen.stop()
    assert gen.issued == short.n and gen.status[3] == 3
    assert np.all(gen.status[np.arange(short.n) != 3] == 2)
    assert np.nanmax(gen.sent - gen.due) < 0.05

    closed = Schedule({"loop": "closed", "in_flight": 4, "closed_loop_requests": 50}, seed=1,
                      horizon_s=1.0, query_count=16)
    pending: list[Future] = []
    gen = LoadGenerator(closed, lambda i: (pending.append(Future()), pending[-1])[1], rejected=Refused)
    gen.start()
    time.sleep(0.05)
    assert len(pending) == 4  # no more than in_flight outstanding
    pending[0].set_result((np.array([0]), None))
    time.sleep(0.05)
    assert len(pending) == 5
    gen.stop()


# --------------------------------------------------------------- drivers


def tiny(cell, root):
    config = json.loads(json.dumps(cell.config))
    workload = json.loads(json.dumps(cell.workload))
    if config["kind"] == "ann":
        config["corpus_rows"] = 20000
        config["data"].update(dim=64, components=64, queries=64)
        config["plane"].update(nlist=16, shard_budget_bytes=6000 * 600)
        workload.update(in_flight=16, rate_per_s=20, grace_seconds=5,
                        mix={"nprobe": {"values": [4, 8], "weights": [0.5, 0.5]}},
                        warmup={"seconds": 1.0, "ladder": {"batch_sizes": [8], "nprobes": [4]}})
    else:
        config["table_rows"] = 2048
        config["table"].update(seq=32, hash_buckets=4)
        config["model"].update(vocab_size=2048, hidden_size=64, num_hidden_layers=2,
                               num_attention_heads=4, intermediate_size=128, max_position_embeddings=64)
        workload["per_chip_batch"] = 8
    return dataclasses.replace(cell, config=config, workload=workload, root=root)


def drive(name: str, root: str, *, trace: bool, spec_root: str = REPO, **kw) -> tuple[dict, dict]:
    cell = tiny(load_cell(name, root=spec_root), root)
    out = cell.driver().run(cell, seed=1, seconds=2.0, trace=trace, process_start=time.perf_counter(),
                            log=lambda m: None, **kw)
    run = load_module(os.path.join(BENCH, "run.py"))
    metrics, _extras, _breakdown = run.layer_metrics(cell, out, peaks_for("TPU v5 lite"), 0)
    return out, metrics


def test_trainer_driver():
    with tempfile.TemporaryDirectory(prefix="chipbench_selftest_") as root:
        # the first cell is driven with a stated ``runtime_start_s``, the second
        # without one, as a caller that never timed the backend's start
        for name, trace, stated in (("bert_base_mlm_pk.mor_stream", False, {"runtime_start_s": 0.25}),
                                    ("bert_base_mlm_pk.dp4_mor_stream", True, {})):
            out, metrics = drive(name, root, trace=trace, **stated)
            assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 3, out["detail"]
            # set-up is process start to window start less the stated span, to the digit
            opened = out["detail"]["window_start_s"]
            assert out["end_to_end"]["setup_s"] == opened - stated.get("runtime_start_s", 0.0), out["end_to_end"]
            if stated:
                assert out["end_to_end"]["setup_s"] + 0.25 == opened
                assert metrics["runtime_start_s"] == {"value": 0.25, "unit": "s"}, metrics
            else:
                assert "runtime_start_s" not in metrics, metrics
            assert out["detail"]["table_check"]["rows_delivered"] == 2048
            assert abs(out["detail"]["system_loss"] - out["detail"]["plain_loss"]) < 0.01
            assert out["end_to_end"]["train_rows_s_chip"] > 0 and out["end_to_end"]["setup_s"] > 0
            for must in ("scan_ms_krow", "merge_share_pct", "loader_ms_krow", "loader_wait_pct",
                         "h2d_mb_s", "train_mfu_pct", "compiles_in_window", "peak_hbm_gb"):
                assert must in metrics, (must, metrics)
            assert metrics["compiles_in_window"]["value"] == 0
            assert 0 < metrics["merge_share_pct"]["value"] < 100
            # no device plane in a CPU trace: the device readers return nothing and are left out
            assert "step_device_ms" not in metrics


def test_ann_driver():
    """The ANN cells are not in ``BENCHMARK.json`` yet (``pending/``): they are
    run from an overlay that adds their entries, which is also how a later PR
    brings them in."""
    with tempfile.TemporaryDirectory(prefix="chipbench_selftest_") as root:
        overlay = make_overlay(os.path.join(root, "overlay"), REPO)
        names = add_pending(overlay, os.path.join(BENCH, "pending", "ann_laion_clip512.json"))
        assert names == ["ann_laion_clip512.open_steady", "ann_laion_clip512.batch_closed"]
        for name in names:
            out, metrics = drive(name, root, trace=False, spec_root=overlay, pallas_interpret=True,
                                 runtime_start_s=0.5)
            assert out["correct"] is True and out["failed"] == 0 and out["attempted"] > 10, out["detail"]
            assert out["end_to_end"]["setup_s"] == out["detail"]["window_start_s"] - 0.5
            assert metrics["runtime_start_s"]["value"] == 0.5
            assert out["end_to_end"]["ann_recall10"] >= 0.9 and out["end_to_end"]["ann_p99_ms"] > 0
            for must in ("ann_mean_batch", "ann_dispatch_ms", "ann_pairs_query", "compiles_in_window"):
                assert must in metrics, (must, metrics)
            assert 4 <= metrics["ann_pairs_query"]["value"] <= 8
            assert ("gen_late_ms" in metrics) == name.endswith("open_steady")


# ---------------------------------------------------------------- run.py


def test_run_times_the_runtime_start():
    """``run.py: main`` with the chip, the cell's driver and the program's
    entry points stubbed: the cell is loaded and stamped, then ``jax.devices()``
    is called once between two stamps and nothing else, its wall time goes to
    the driver as ``runtime_start_s``, and both result lines, traced and not,
    carry it as ``device.runtime_start_s`` (the traced one as a per-layer
    metric too)."""
    import contextlib
    import io
    from unittest import mock

    import jax

    import chipbench.spec
    import lakesoul_tpu.native
    import lakesoul_tpu.utils.compile_cache

    run = load_module(os.path.join(BENCH, "run.py"))
    events: list = []

    class Chip:
        platform, device_kind = "tpu", "TPU v5 lite"

        def memory_stats(self):
            return {"peak_bytes_in_use": 3, "peak_bytes_reserved": 4}

    def devices():
        events.append("jax.devices()")
        time.sleep(0.2)
        return [Chip()]

    class Driver:
        @staticmethod
        def run(cell, **kw):
            events.append(("driver", kw["process_start"], kw["runtime_start_s"]))
            return {"correct": True, "attempted": 7, "failed": 0, "tracer": None, "devices": [Chip()], "detail": {},
                    "end_to_end": {"train_rows_s_chip": 5.0, "setup_s": 2.0},
                    "sample": {"runtime_start_s": kw["runtime_start_s"]}}

    real = load_cell("bert_base_mlm_pk.mor_stream")
    only = tuple(m for m in real.per_layer if m.name == "runtime_start_s")

    def stub_cell(name):
        events.append("load_cell")
        cell = mock.Mock(wraps=real, chips=1, end_to_end=real.end_to_end, per_layer=only, config={}, workload={})
        cell.name = name
        cell.driver.return_value = Driver
        return cell

    def configure():
        events.append("compile cache")
        return "(stub)"

    lines = {}
    for trace in (0, 1):
        del events[:]
        out = io.StringIO()
        with mock.patch.object(chipbench.spec, "load_cell", stub_cell), \
                mock.patch.object(lakesoul_tpu.utils.compile_cache, "configure_compile_cache", configure), \
                mock.patch.object(lakesoul_tpu.native, "available", lambda: True), \
                mock.patch.object(jax, "devices", devices), \
                mock.patch.object(run, "log", lambda m: events.append(("log", m))), \
                contextlib.redirect_stdout(out):
            rc = run.main(["--workload", "bert_base_mlm_pk.mor_stream", "--seed", str(2**31 + 5),
                           "--seconds", "1", "--trace", str(trace)])
        assert rc == 0, events
        lines[trace] = json.loads(out.getvalue().splitlines()[-1])
        at = events.index("jax.devices()")
        assert events.count("jax.devices()") == 1 and events.index("load_cell") < events.index("compile cache") < at
        # a stamp on either side of the call and nothing between: the span holds the call alone
        assert events[at - 1] == ("log", "jax imported"), events
        assert events[at + 1][0] == "log" and events[at + 1][1].startswith("jax.devices() returned"), events
        assert events[1] == ("log", "cell bert_base_mlm_pk.mor_stream loaded"), events
        driver = next(e for e in events if e[0] == "driver")
        assert events.index(driver) > at + 1 and driver[1] == run.PROCESS_START
        span = lines[trace]["device"]["runtime_start_s"]
        assert span == driver[2] and 0.2 <= span < 0.3, (span, driver)
        assert lines[trace]["device"]["memory_peak_bytes"] == 7 and lines[trace]["correct"] is True
    assert lines[0]["metrics"] == {"train_rows_s_chip": {"value": 5.0, "unit": "rows/s/chip"},
                                   "setup_s": {"value": 2.0, "unit": "s"}}
    assert lines[1]["metrics"] == {"runtime_start_s": {"value": lines[1]["device"]["runtime_start_s"], "unit": "s"}}


def test_study_reads_both_definitions():
    """``chipbench/study.py``: a run's stamps out of its log, and ``setup_s``
    beside its sum with ``device.runtime_start_s`` (the reading before PR 54)."""
    from chipbench import study

    log = ("warning: not ours\n[bench    0.41s] cell a.b loaded\n[bench   12.30s] jax.devices() returned after 10.500 s"
           " (runtime_start_s)\n[bench   40.00s] detail {\"steps\": 3}\n")
    assert study.stamps(log) == [[0.41, "cell a.b loaded"],
                                 [12.3, "jax.devices() returned after 10.500 s (runtime_start_s)"]]
    runs = [{"metrics": {"setup_s": {"value": v, "unit": "s"}}, "device": {"runtime_start_s": r}}
            for v, r in ((5.0, 10.5), (5.25, 13.0), (4.75, 11.0))] + [{"rc": 2}]
    values = study.values_by_metric([study.with_old_setup(r) for r in runs])
    assert values == {"setup_s": [5.0, 5.25, 4.75], "runtime_start_s": [10.5, 13.0, 11.0],
                      "setup_s_with_runtime": [15.5, 18.25, 15.75]}
    assert "setup_s_with_runtime" not in runs[0]["metrics"]  # the runs as recorded are left alone
    assert study.span_over_median(values["setup_s"]) == 0.1
    # the quartiles are Python's default ones, as the driver takes them: 4.75 and 5.25 of three values
    assert study.spread(values["setup_s"]) == (5.0, 0.1)
    assert close(study.spread([1.0, 2.0, 3.0, 4.0, 5.0, 6.0])[1], (5.25 - 1.75) / 3.5)


# --------------------------------------------------------------- overlay


def _digests(root: str) -> dict[str, str]:
    out = {}
    for base, _dirs, files in os.walk(root):
        if "__pycache__" in base:
            continue
        for f in files:
            path = os.path.join(base, f)
            if not os.path.islink(path):
                out[os.path.relpath(path, root)] = hashlib.sha256(open(path, "rb").read()).hexdigest()
    return out


def test_overlay_adds_files_only():
    """A new configuration, consumer adaptor, cell and per-layer metric are
    files and entries added; every file that was there keeps its bytes."""
    with tempfile.TemporaryDirectory(prefix="chipbench_overlay_") as tmp:
        root = make_overlay(os.path.join(tmp, "overlay"), REPO)
        bench_dir = os.path.join(root, "benchmarks", "chip")
        before = _digests(bench_dir)

        config = json.load(open(os.path.join(bench_dir, "configs", "bert_base_mlm_pk.json")))
        config.update(consumer="toy_consumer", table_rows=1024)
        with open(os.path.join(bench_dir, "configs", "toy_rows.json"), "x") as f:
            json.dump(config, f)
        with open(os.path.join(bench_dir, "consumers", "toy_consumer.py"), "x") as f:
            f.write("STEP_MODULE = 'jit_toy_step'\n\ndef flops_per_row(config):\n    return 42.0\n")
        with open(os.path.join(bench_dir, "layer_metrics", "toy_rows_per_step.py"), "x") as f:
            f.write("def read(sample):\n    return sample['rows'] / sample['steps'] if sample.get('steps') else None\n")
        workload = json.load(open(os.path.join(bench_dir, "workloads", "bert_base_mlm_pk.mor_stream.json")))
        workload.update(traffic="compacted_stream", table={"compacted": True})
        path = os.path.join(root, "BENCHMARK.json")
        bench = json.load(open(path))
        bench["configs"].append({"name": "toy_rows", "source": "none", "file": "benchmarks/chip/configs/toy_rows.json",
                                 "reduced": [], "why": "overlay self-test"})
        bench["per_layer"].append({"name": "toy_rows_per_step", "unit": "rows", "better": "higher",
                                   "source": "program_counter", "layer": "loader: data/jax_iter.py",
                                   "moves": "train_rows_s_chip", "workloads": ["toy_rows.compacted_stream"]})
        json.dump(bench, open(path, "w"))
        add_cell(root, name="toy_rows.compacted_stream", like="bert_base_mlm_pk.mor_stream",
                 workload=workload, config="toy_rows")

        cell = load_cell("toy_rows.compacted_stream", root=root)
        assert cell.config["table_rows"] == 1024 and cell.workload["table"]["compacted"] is True
        assert cell.consumer().flops_per_row(cell.config) == 42.0
        names = [m.name for m in cell.per_layer]
        assert "toy_rows_per_step" in names and "scan_ms_krow" in names and "ann_mean_batch" not in names
        toy = next(m for m in cell.per_layer if m.name == "toy_rows_per_step")
        assert cell.layer_reader(toy)({"rows": 128, "steps": 2}) == 64.0
        assert [m.name for m in cell.end_to_end] == ["train_rows_s_chip", "setup_s"]
        assert cell.driver().__name__.endswith("drivers_trainer")
        # the cells that were there are read as before, and no file changed
        assert load_cell("bert_base_mlm_pk.mor_stream", root=root).config["table_rows"] == 262144
        after = _digests(bench_dir)
        assert {k: after[k] for k in before} == before
        assert sorted(set(after) - set(before)) == sorted([
            "configs/toy_rows.json", "consumers/toy_consumer.py", "layer_metrics/toy_rows_per_step.py",
            "workloads/toy_rows.compacted_stream.json"])
        try:
            load_cell("no_such.cell", root=root)
        except SpecError:
            pass
        else:
            raise AssertionError("an unknown cell must be an error")


def test_benchmark_json_names_files():
    """Every cell, configuration and metric of the real ``BENCHMARK.json``,
    and of the pending entries merged into a copy, resolves to its files."""
    with tempfile.TemporaryDirectory(prefix="chipbench_names_") as tmp:
        root = make_overlay(os.path.join(tmp, "overlay"), REPO)
        for pending in sorted(os.listdir(os.path.join(BENCH, "pending"))):
            add_pending(root, os.path.join(BENCH, "pending", pending))
        _check_names(root)
    _check_names(REPO)


def _check_names(root: str) -> None:
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    assert {c["name"] for c in bench["configs"]} == {w["config"] for w in bench["workloads"]}
    for w in bench["workloads"]:
        cell = load_cell(w["name"], root=root)
        assert cell.driver().run and all(cell.layer_reader(m) for m in cell.per_layer)
        assert any(m.name == "setup_s" for m in cell.end_to_end) and len(cell.end_to_end) >= 2
        assert cell.per_layer
        reported = {m.name for m in cell.end_to_end}
        assert all(m.moves in reported for m in cell.per_layer), cell.name
        # what ``setup_s`` leaves out since PR 54 stays in sight in every cell
        assert [m.moves for m in cell.per_layer if m.name == "runtime_start_s"] == ["setup_s"], cell.name


def test_contract_shape():
    """``BENCHMARK.json`` against the limits of the builder's contract that a
    file can be checked for: keys, names, units, lengths, bounds, the share of
    four-chip cells, files under ``paths``."""
    import re

    bench = json.load(open(os.path.join(REPO, "BENCHMARK.json")))
    assert set(bench) == {"command", "paths", "run_seconds", "configs", "workloads", "end_to_end", "per_layer"}
    name_ok = re.compile(r"^[A-Za-z0-9_][A-Za-z0-9_.\-]{0,63}$").match
    unit_ok = re.compile(r"^[A-Za-z0-9_/%.\-]{1,16}$").match
    line_ok = lambda t: 1 <= len(t) <= 200 and "\n" not in t and "\t" not in t  # noqa: E731
    assert 1 <= len(bench["paths"]) <= 16 and len(bench["command"]) <= 32
    assert all(line_ok(word) for word in bench["command"])
    assert isinstance(bench["run_seconds"], int) and 1 <= bench["run_seconds"] <= 51
    assert os.path.getsize(os.path.join(REPO, "BENCHMARK.json")) <= 64 * 1024
    under_paths = lambda f: any(f.startswith(p + "/") for p in bench["paths"])  # noqa: E731
    assert 1 <= len(bench["configs"]) <= 24
    for c in bench["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}, c
        assert name_ok(c["name"]) and line_ok(c["source"]) and line_ok(c["why"]) and under_paths(c["file"])
        assert len(c["reduced"]) <= 16 and all(name_ok(k) for k in c["reduced"])
        held = json.load(open(os.path.join(REPO, c["file"])))
        assert all(k in held for k in c["reduced"]), "reduced names keys of the configuration's file"
        assert not any(k.endswith(("_dim", "_rank")) or "hidden" in k or "intermediate" in k for k in c["reduced"])
    cells = bench["workloads"]
    assert 2 <= len(cells) <= 24 and len({(w["config"], w["traffic"]) for w in cells}) == len(cells)
    for w in cells:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}, w
        assert name_ok(w["name"]) and name_ok(w["traffic"]) and w["chips"] in (1, 4) and line_ok(w["why"])
    assert sum(w["chips"] == 4 for w in cells) <= max(1, len(cells) // 4)
    names = [m["name"] for m in bench["end_to_end"] + bench["per_layer"]]
    assert len(set(names)) == len(names) and len({w["name"] for w in cells}) == len(cells)
    sources = {"device_trace", "program_span", "program_counter", "host_clock"}
    assert any(m["name"] == "setup_s" and m["bound"] <= 0.1 for m in bench["end_to_end"])
    for m in bench["end_to_end"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "bound", "source"}, m
        assert 0.01 <= m["bound"] <= 0.1 and m["source"] in ("host_clock", "device_trace")
    for m in bench["per_layer"]:
        assert set(m) - {"workloads"} == {"name", "unit", "better", "source", "layer", "moves"}, m
        assert line_ok(m["layer"]) and m["moves"] in names and m["source"] in sources
    assert {"name": "runtime_start_s", "unit": "s", "better": "lower", "source": "host_clock",
            "layer": "device", "moves": "setup_s"} in bench["per_layer"]  # no ``workloads``: every cell
    assert {"name": "setup_s", "unit": "s", "better": "lower", "bound": 0.1,
            "source": "host_clock"} in bench["end_to_end"]
    for m in bench["end_to_end"] + bench["per_layer"]:
        assert name_ok(m["name"]) and unit_ok(m["unit"]) and m["better"] in ("lower", "higher"), m
        assert all(n in {w["name"] for w in cells} for n in m.get("workloads", ())), m
    for base, _dirs, files in os.walk(BENCH):
        for f in files:
            if "__pycache__" not in base:
                assert re.match(r"^[A-Za-z0-9_.\-]+$", f), f


TESTS = [
    test_trace_hand_counts, test_trace_names, test_trace_recorded, test_operation_counts,
    test_peaks_table, test_traffic_and_loadgen, test_contract_shape, test_benchmark_json_names_files,
    test_overlay_adds_files_only, test_run_times_the_runtime_start,
    test_study_reads_both_definitions, test_trainer_driver, test_ann_driver,
]


def main(argv: list[str]) -> int:
    chosen = [t for t in TESTS if not argv or t.__name__ in argv or t.__name__.removeprefix("test_") in argv]
    failed = 0
    for test in chosen:
        t0 = time.perf_counter()
        try:
            test()
        except Exception:
            failed += 1
            print(f"FAIL {test.__name__}\n{traceback.format_exc()}", flush=True)
        else:
            print(f"ok   {test.__name__} ({time.perf_counter() - t0:.1f} s)", flush=True)
    print(f"{len(chosen) - failed} passed, {failed} failed")
    return 1 if failed else 0


if __name__ == "__main__":
    raise SystemExit(main(sys.argv[1:]))
