"""Run cells as the driver does, from a parent that stays off JAX, and
summarise the results: shared by ``spread_study.py`` and ``sweep_knee.py``."""

from __future__ import annotations

import json
import os
import re
import statistics
import subprocess
import sys
import time


def run_cell(root: str, cell: str, *, seed: int, seconds: float, trace: int,
             timeout: float = 1500.0) -> dict:
    """One run of ``<root>/benchmarks/chip/run.py`` in a process of its own.
    Returns the result line as a dict, plus ``wall_s``, ``rc`` and the tail of
    standard error."""
    bench = json.load(open(os.path.join(root, "BENCHMARK.json")))
    command = [sys.executable if c == "python3" else c for c in bench["command"]]
    command += ["--workload", cell, "--seed", str(seed), "--seconds", str(seconds), "--trace", str(trace)]
    t0 = time.perf_counter()
    proc = subprocess.run(command, cwd=root, capture_output=True, text=True, timeout=timeout)
    wall = time.perf_counter() - t0
    lines = [ln for ln in proc.stdout.splitlines() if ln.strip()]
    out: dict = {}
    if proc.returncode == 0 and lines:
        try:
            out = json.loads(lines[-1])
        except json.JSONDecodeError:
            out = {}
    out.update(cell=cell, seed=seed, trace=trace, rc=proc.returncode, wall_s=wall,
               stderr_tail=proc.stderr[-3000:], log=stamps(proc.stderr))
    return out


_STAMP = re.compile(r"^\[bench\s+([0-9.]+)s\] (.*)$")


def stamps(stderr: str) -> list[list]:
    """``run.py``'s own log lines as ``[seconds since process start, message]``:
    where a run's set-up went, stamp to stamp (the ``detail`` line is left to
    ``stderr_tail``)."""
    found = (_STAMP.match(ln) for ln in stderr.splitlines())
    return [[float(m.group(1)), m.group(2)[:160]] for m in found if m and not m.group(2).startswith("detail ")]


def with_old_setup(run: dict) -> dict:
    """The run with ``runtime_start_s`` and ``setup_s_with_runtime`` beside
    ``setup_s``: ``device.runtime_start_s`` and its sum with ``setup_s``,
    process start to window start, which is what ``setup_s`` was before PR 54.
    A study then reads both definitions from the same runs."""
    metrics = dict(run.get("metrics") or {})
    span = (run.get("device") or {}).get("runtime_start_s")
    if "setup_s" in metrics and span is not None:
        metrics["runtime_start_s"] = {"value": span, "unit": "s"}
        metrics["setup_s_with_runtime"] = {"value": metrics["setup_s"]["value"] + span, "unit": "s"}
    return dict(run, metrics=metrics)


def span_over_median(values: list[float]) -> float:
    """``(max - min) / median``: every run counts, the far-off one too."""
    med = statistics.median(values)
    return (max(values) - min(values)) / abs(med) if med else float("inf")


def spread(values: list[float]) -> tuple[float, float]:
    """``(median, distance between the quartiles over the median)``."""
    med = statistics.median(values)
    if len(values) < 2:
        return med, 0.0
    q = statistics.quantiles(values, n=4)  # the default method: the driver's; "inclusive" lies closer
    return med, (q[2] - q[0]) / abs(med) if med else float("inf")


def values_by_metric(results: list[dict]) -> dict[str, list[float]]:
    """Each metric's values over the runs that printed a result, in run order."""
    by_metric: dict[str, list[float]] = {}
    for r in results:
        for name, m in (r.get("metrics") or {}).items():
            by_metric.setdefault(name, []).append(m["value"])
    return by_metric
