#!/usr/bin/env python3
"""Run one cell of the chip benchmark once.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell, its configuration, its traffic and its metrics are named in
``BENCHMARK.json`` and found as files under this directory
(``chipbench/spec.py``).  The last line of standard output is the result: one
JSON object with ``correct``, ``attempted``, ``failed``, ``metrics`` and
``device`` (and ``breakdown`` when traced).  ``--trace 0`` reports the cell's
end-to-end metrics with the profiler off; ``--trace 1`` profiles the last
seconds of the window and reports the cell's per-layer metrics.  Progress goes
to standard error.

There is no CPU mode.  The command exits 2 and prints no result unless
``jax.devices()[0].platform`` is ``tpu`` and there are as many chips as the
cell asks for; it never sets ``JAX_PLATFORMS``; a ``device_kind`` that is not
in ``chipbench/peaks.json`` is an error.  It needs the repository around it:
in a directory that holds only ``BENCHMARK.json`` and this directory the
import of ``lakesoul_tpu`` fails and nothing is printed.

Set-up is counted from ``PROCESS_START`` to the window's opening, less
``runtime_start_s``: the wall time of the first ``jax.devices()`` call, in
which the backend loads the TPU's library and attaches the chips and no code
of this tree runs (it follows the machine and the order of the runs, not the
tree; ``PERF.md`` section 6, PR 54).  Every import, ``jax``'s too, stays in
``setup_s``.  The span is reported as ``device.runtime_start_s`` in every
result line and as a per-layer metric of its own, so ``setup_s +
runtime_start_s`` is the reading the gate had before PR 54.
"""

import time

PROCESS_START = time.perf_counter()  # set-up is counted from here

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
REPO = os.path.dirname(os.path.dirname(HERE))
for path in (HERE, REPO):
    if path not in sys.path:
        sys.path.insert(0, path)


def log(message: str) -> None:
    print(f"[bench {time.perf_counter() - PROCESS_START:7.2f}s] {message}", file=sys.stderr, flush=True)


def layer_metrics(cell, outcome, peaks, peak_bytes) -> tuple[dict, dict, dict | None]:
    """Read every per-layer metric of the cell with its own reader.  Returns
    ``(metrics, device extras, breakdown)``."""
    sample = dict(outcome["sample"])
    sample.update(peaks=peaks, config=cell.config, workload=cell.workload,
                  peak_hbm_bytes=peak_bytes, trace=None, trace_plain=None)
    extras, breakdown = {}, None
    tracer = outcome.get("tracer")
    if tracer is not None:
        plain, reduced = tracer.reduce()
        sample.update(trace=reduced, trace_plain=plain)
        extras = {"busy_s": reduced["busy_s"], "window_s": reduced["window_s"]}
        breakdown = {"device_ops": reduced["device_ops"], "idle_gaps": reduced["idle_gaps"]}
    metrics = {}
    for metric in cell.per_layer:
        value = cell.layer_reader(metric)(sample)
        if value is None:  # nothing to read in this run: the metric is left out
            log(f"per-layer metric {metric.name}: nothing to read")
            continue
        metrics[metric.name] = {"value": float(value), "unit": metric.unit}
    return metrics, extras, breakdown


def start_runtime(jax) -> tuple[list, float]:
    """The process's first ``jax.devices()`` and its wall time, taken around
    the call alone: no thread of the benchmark or the program is running yet."""
    t0 = time.perf_counter()
    devices = jax.devices()
    return devices, time.perf_counter() - t0


def main(argv: list[str] | None = None) -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("--workload", required=True)
    parser.add_argument("--seed", type=int, required=True)
    parser.add_argument("--seconds", type=float, required=True)
    parser.add_argument("--trace", type=int, choices=(0, 1), required=True)
    args = parser.parse_args(argv)

    from chipbench.peaks import peaks_for
    from chipbench.runtime import memory_peak_bytes
    from chipbench.spec import load_cell

    cell = load_cell(args.workload)
    log(f"cell {cell.name} loaded")

    from lakesoul_tpu import native
    from lakesoul_tpu.utils.compile_cache import configure_compile_cache

    log("lakesoul_tpu.native imported")
    cache_dir = configure_compile_cache()  # imports jax itself where no directory is given from outside
    import jax

    log("jax imported")
    devices, runtime_start_s = start_runtime(jax)
    log(f"jax.devices() returned after {runtime_start_s:.3f} s (runtime_start_s)")
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind, "count": len(devices),
              "runtime_start_s": runtime_start_s}
    log(f"jax {jax.__version__} sees {device}; compile cache at {cache_dir}")
    if device["platform"] != "tpu":
        log("no TPU: this benchmark has no CPU mode (its self-test has: selftest/run.py)")
        return 2
    if len(devices) < cell.chips:
        log(f"cell {cell.name} needs {cell.chips} chips, JAX sees {len(devices)}")
        return 2
    peaks = peaks_for(device["kind"])
    if not native.available():
        log("the native library did not build or load; the host stages would be the numpy fallbacks")
        return 3

    outcome = cell.driver().run(
        cell, seed=args.seed, seconds=args.seconds, trace=bool(args.trace),
        process_start=PROCESS_START, runtime_start_s=runtime_start_s, log=log,
    )
    peak_bytes = memory_peak_bytes(outcome["devices"])
    device["memory_peak_bytes"] = peak_bytes
    log(f"detail {json.dumps(outcome['detail'], default=float)}")
    result = {
        "correct": outcome["correct"],
        "attempted": outcome["attempted"],
        "failed": outcome["failed"],
    }
    if args.trace:
        metrics, extras, breakdown = layer_metrics(cell, outcome, peaks, peak_bytes)
        device.update(extras)
        result.update(metrics=metrics, device=device)
        if breakdown is not None:
            result["breakdown"] = breakdown
    else:
        measured = outcome["end_to_end"]
        missing = [m.name for m in cell.end_to_end if m.name not in measured]
        if missing:
            log(f"the driver did not measure {missing}")
            return 4
        result.update(
            metrics={m.name: {"value": float(measured[m.name]), "unit": m.unit} for m in cell.end_to_end},
            device=device,
        )
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
