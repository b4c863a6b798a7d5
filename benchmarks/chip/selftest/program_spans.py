#!/usr/bin/env python3
"""Self-test of ``chipbench/program_spans.py`` and its six readers, on the CPU:

    python3 benchmarks/chip/selftest/program_spans.py [name ...]

- the split of idle time by owner and by what a queue stall waited for, on a
  hand-made trace whose numbers are known by arithmetic: two devices, a
  consumer thread and two producer threads, a ``queue`` stall that overlaps a
  ``merge`` (with its ``fill``) and a ``decode``, gaps under ``device_put``,
  under a span nested in it, and under nothing;
- the same split on traces recorded on a TPU v5e and cut to a few steps
  (``span_fixtures/``; a directory of its own, because ``run.py`` applies its
  checks to every file of ``fixtures/`` by name), held to the identity with
  ``trace.reduce_trace``'s own idle gaps;
- the readers on samples with nothing to read: no trace, a trace without a
  device, a program without the spans or the counter.

To cut a new recording: ``--cut <load()'s structure as .json.gz> <steps> <out>``.
"""

import os

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import gzip  # noqa: E402
import json  # noqa: E402
import sys  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
if BENCH not in sys.path:
    sys.path.insert(0, BENCH)

from chipbench import program_spans as P  # noqa: E402
from chipbench import trace as T  # noqa: E402
from chipbench.spec import load_module  # noqa: E402

FIXTURES = os.path.join(HERE, "span_fixtures")
QUEUE, PUT = "lakesoul.loader.queue", "lakesoul.loader.device_put"
MERGE, DECODE, FILL, COLLATE = ("lakesoul.scan.merge", "lakesoul.scan.decode", "lakesoul.scan.fill",
                                "lakesoul.loader.collate")
COPY = "lakesoul.tensorplane.copy"  # no such span today: any other owner on the consumer thread


def close(a: float, b: float, rel: float = 1e-9) -> bool:
    return abs(a - b) <= rel * max(abs(a), abs(b)) + 1e-18


def hand_trace() -> dict:
    """Times in ns, window 0 to 1000, two steps.

    Device 0 runs programs 0-60, 300-520 (no operation 400-450: idle inside
    the program) and 720-1000, so it idles outside programs 60-300 and
    520-720.  Device 1 runs 0-100 and 350-1000 and idles 100-350.

    Consumer thread: ``queue`` 50-250 and 510-530, ``device_put`` 260-380 and
    540-690 with another span nested 600-650.  Producers: ``merge`` 100-200
    with ``fill`` 120-140 and ``collate`` 210-240 on one thread, ``decode``
    60-150 on another.

    Device 0: queue 60-250 and 520-530 = 200; put 260-300 and 540-600 and
    650-690 = 140; the nested span 50; nobody 250-260, 530-540, 690-720 = 50.
    Device 1: queue 100-250 = 150; put 260-350 = 90; nobody 250-260 = 10.
    While queue owned the idle device: merge 100 + 100, decode 90 + 50, fill
    20 + 20, collate 30 + 30."""
    fusion = "%fusion.1 = f32[8]{0} fusion(f32[8]{0} %p), kind=kLoop"
    dev0 = {"name": "/device:TPU:0", "lines": [
        {"name": "XLA Modules", "events": [["jit_train_step(1)", 0, 60], ["jit_train_step(1)", 300, 220],
                                           ["jit_train_step(1)", 720, 280]]},
        {"name": "XLA Ops", "events": [[fusion, 0, 60], [fusion, 300, 100], [fusion, 450, 70], [fusion, 720, 280]]},
    ]}
    dev1 = {"name": "/device:TPU:1", "lines": [
        {"name": "XLA Modules", "events": [["jit_train_step(1)", 0, 100], ["jit_train_step(1)", 350, 650]]},
        {"name": "XLA Ops", "events": [[fusion, 0, 100], [fusion, 350, 650]]},
    ]}
    host = {"name": "/host:CPU", "lines": [
        {"name": "python3", "events": [
            ["bench.next_batch", 0, 400], [QUEUE, 50, 200], [PUT, 260, 120], ["bench.step", 400, 100],
            ["bench.next_batch", 500, 200], [QUEUE, 510, 20], [PUT, 540, 150], [COPY, 600, 50],
            ["bench.step", 700, 300],
        ]},
        {"name": "python3", "events": [[MERGE, 100, 100], [FILL, 120, 20], [COLLATE, 210, 30]]},
        {"name": "python3", "events": [[DECODE, 60, 90]]},
    ]}
    return {"planes": [dev0, dev1, host]}


def test_hand_split():
    r = P.split(hand_trace())
    assert r["devices"] == 2 and r["steps"] == 2, r
    ns = lambda s: s * 1e9  # noqa: E731
    assert close(ns(r["owner_s"][QUEUE]), 350) and close(ns(r["owner_s"][PUT]), 230), r["owner_s"]
    assert close(ns(r["owner_s"][COPY]), 50) and set(r["owner_s"]) == {QUEUE, PUT, COPY}, r["owner_s"]
    assert close(ns(r["unowned_s"]), 60) and close(ns(r["outside_s"]), 690), r
    blame = {name: ns(s) for name, s in r["blame_s"].items()}
    assert close(blame[MERGE], 200) and close(blame[DECODE], 140), blame
    assert close(blame[FILL], 40) and close(blame[COLLATE], 60) and len(blame) == 4, blame


def test_hand_identity():
    """The owners and nobody are what ``trace.reduce_trace`` calls idle less
    the part inside a program (device 0, 400-450); a total that is off by
    more than 1% is refused."""
    trace = hand_trace()
    reduced = T.reduce_trace(trace)
    gaps = dict(reduced["idle_gaps"])
    assert close(gaps[P.INSIDE_PROGRAM], 50e-9), gaps
    assert close(P.outside_programs_s(reduced), 690e-9), gaps
    r = P.split(trace)
    P.check(r, reduced)
    try:
        P.check(dict(r, outside_s=r["outside_s"] * 1.02), reduced)
    except ValueError:
        pass
    else:
        raise AssertionError("a split that disagrees with the reduction must be refused")


def test_innermost():
    owned = P.innermost([["a", 0, 100], ["b", 10, 30], ["c", 20, 10], ["b", 60, 50], ["d", 200, 10]])
    # the second b outlasts its parent: the parent's own time ends where b starts
    assert owned == {"a": [(0, 10), (40, 60)], "b": [(10, 20), (30, 40), (60, 110)], "c": [(20, 30)],
                     "d": [(200, 210)]}, owned
    assert P._common([(0, 10), (20, 30)], [(5, 25), (28, 40)]) == [(5, 10), (20, 25), (28, 30)]


def test_nothing_to_read():
    """A parent from before the seam has no ``lakesoul.*`` span and no
    counter; a CPU trace has no device: every reader returns ``None``."""
    bare = hand_trace()
    for line in bare["planes"][2]["lines"]:
        line["events"] = [e for e in line["events"] if not e[0].startswith(P.PROGRAM_PREFIX)]
    assert P.split(bare) is None
    no_device = {"planes": hand_trace()["planes"][2:]}
    assert P.split(no_device) is None
    for name in ("idle_queue_ms_step", "idle_put_ms_step", "idle_unowned_ms_step", "idle_merge_ms_step",
                 "idle_decode_ms_step"):
        read = load_module(os.path.join(BENCH, "layer_metrics", name + ".py")).read
        assert read({"trace_plain": None, "trace": None}) is None, name
        assert read({"trace_plain": no_device, "trace": {"idle_gaps": []}}) is None, name
    read = load_module(os.path.join(BENCH, "layer_metrics", "h2d_counted_mb_s.py")).read
    assert read({"counters": {"lakesoul_loader_rows_total": 64.0}, "window_s": 2.0}) is None
    assert read({"counters": {"lakesoul_tensorplane_h2d_bytes_total": 4e6}, "window_s": 2.0}) == 2.0


def test_readers_on_a_run():
    """The five span readers through :func:`program_spans.of_run`, with the
    hand trace standing in for the run's newest trace: milliseconds a device
    and step, so 350 ns of queue over two devices and two steps is 350e-6 / 4."""
    trace = hand_trace()
    sample = {"trace_plain": trace, "trace": T.reduce_trace(trace)}
    newest, split_file = P.newest_xplane, P._split_file
    P.newest_xplane, P._split_file = (lambda: "hand"), (lambda path: P.split(trace))
    try:
        want = {"idle_queue_ms_step": 350e-6 / 4, "idle_put_ms_step": 230e-6 / 4, "idle_unowned_ms_step": 60e-6 / 4,
                "idle_merge_ms_step": 200e-6 / 4, "idle_decode_ms_step": 140e-6 / 4}
        for name, value in want.items():
            read = load_module(os.path.join(BENCH, "layer_metrics", name + ".py")).read
            assert close(read(sample), value), (name, read(sample))
    finally:
        P.newest_xplane, P._split_file = newest, split_file


def test_load_a_recorded_session():
    """:func:`program_spans.load` on an ``.xplane.pb`` written here, where
    ``Tracer`` would put it: the newest trace under the root is found, the
    ``lakesoul.*`` and ``bench.*`` spans are kept with their threads apart,
    anything else on the host is dropped."""
    import tempfile
    import threading

    import jax

    with tempfile.TemporaryDirectory(prefix="chipbench_spans_") as root:
        assert P.newest_xplane(root) is None
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2

        def produce():
            with jax.profiler.TraceAnnotation(MERGE), jax.profiler.TraceAnnotation(FILL):
                time.sleep(0.002)

        for cell in ("older.cell", "newer.cell"):
            jax.profiler.start_trace(os.path.join(root, ".bench_data", "chip", "trace", cell),
                                     profiler_options=options)
            producer = threading.Thread(target=produce)
            with jax.profiler.TraceAnnotation(P.CONSUMER_MARK):
                producer.start()
                with jax.profiler.TraceAnnotation(QUEUE):
                    producer.join()
                with jax.profiler.TraceAnnotation("something.else"):
                    pass
            jax.profiler.stop_trace()
        path = P.newest_xplane(root)
        assert os.sep + "newer.cell" + os.sep in path, path
        trace = P.load(path)
    lines = [line for p in trace["planes"] if p["name"] == T.HOST_PLANE for line in p["lines"]]
    names = sorted(sorted(n for n, _, _ in line["events"]) for line in lines)
    assert names == [sorted([P.CONSUMER_MARK, QUEUE]), sorted([MERGE, FILL])], names
    (mark,) = [e for line in lines for e in line["events"] if e[0] == P.CONSUMER_MARK]
    assert trace["window_ns"] == [mark[1], mark[1] + mark[2]], trace["window_ns"]
    assert P.split(trace) is None  # no device plane on the CPU


def test_recorded():
    """Traces recorded on a TPU v5e in PR 24, cut to a few steps: the program's
    spans are there on their threads, the split adds up to the reduction's own
    idle time outside programs, and nothing is negative."""
    seen = 0
    for name in sorted(os.listdir(FIXTURES)):
        if not name.endswith(".json.gz"):
            continue
        with gzip.open(os.path.join(FIXTURES, name), "rt") as f:
            trace = json.load(f)
        r = P.split(trace)
        assert r is not None and r["steps"] >= 2 and r["devices"] >= 1, (name, r)
        reduced = T.reduce_trace(trace)
        P.check(r, reduced)
        assert close(r["outside_s"], P.outside_programs_s(reduced), rel=1e-6), name
        assert PUT in r["owner_s"] and QUEUE in r["owner_s"], (name, r["owner_s"])
        # a scan unit feeds 16,384 rows, a quarter of a minute of steps: a few steps show the loader's threads only
        assert {COLLATE, "lakesoul.loader.rebatch"} <= set(r["blame_s"]), (name, r["blame_s"])
        values = list(r["owner_s"].values()) + list(r["blame_s"].values()) + [r["unowned_s"]]
        assert all(v >= 0 for v in values) and r["outside_s"] > 0, (name, r)
        # what a stall waited for is part of the stall
        assert all(s <= r["owner_s"][QUEUE] + 1e-12 for s in r["blame_s"].values()), (name, r)
        seen += 1
    assert seen >= 1, "no recorded fixture found"


# ---------------------------------------------------------------- cutting


def cut(trace: dict, steps: int) -> dict:
    """``steps`` executions of the step program around the longest wait of the
    first device between two of them (a loss read: the only place where the
    host, which otherwise runs ahead, holds the device up), every event
    clipped to that window so busy and idle time inside it stay what they
    were.  ``window_ns`` is dropped: the cut's window is its own events'."""
    device = next(p for p in trace["planes"] if p["name"].startswith(T.DEVICE_PREFIX))
    modules = sorted((s, s + d) for _, s, d in T._line(device, T.MODULES_LINE))
    waits = [modules[i][0] - modules[i - 1][1] for i in range(1, len(modules) - steps + 1)]
    first = waits.index(max(waits))  # the wait is before modules[first + 1]
    lo, hi = modules[first][0], modules[first + steps][0]
    planes = []
    for plane in trace["planes"]:
        lines = []
        for line in plane["lines"]:
            events = [[n, max(s, lo), min(s + d, hi) - max(s, lo)] for n, s, d in line["events"]
                      if s + d > lo and s < hi]
            if events:
                lines.append({"name": line["name"], "events": events})
        planes.append({"name": plane["name"], "lines": lines})
    return {"planes": planes}


TESTS = [test_hand_split, test_hand_identity, test_innermost, test_nothing_to_read, test_readers_on_a_run,
         test_load_a_recorded_session, test_recorded]


def main(argv: list[str]) -> int:
    if argv[:1] == ["--cut"]:
        source, steps, out = argv[1], int(argv[2]), argv[3]
        with gzip.open(source, "rt") as f:
            trace = cut(json.load(f), steps)
        with gzip.open(out, "wt") as f:
            json.dump(trace, f, separators=(",", ":"))
        print(f"{out}: {os.path.getsize(out)} bytes")
        return 0
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
