"""A copy of the benchmark in which files can be added without touching the
checkout: ``BENCHMARK.json`` and ``benchmarks/chip`` copied, the program and
the data cache linked.  ``sweep_knee.py`` adds cells at other rates there; the
self-test adds a cell, a configuration, a consumer and a metric there to show
that the harness takes them as files."""

from __future__ import annotations

import json
import os
import shutil


def make_overlay(dst: str, repo: str) -> str:
    shutil.rmtree(dst, ignore_errors=True)
    os.makedirs(dst)
    shutil.copy(os.path.join(repo, "BENCHMARK.json"), dst)
    shutil.copytree(
        os.path.join(repo, "benchmarks", "chip"), os.path.join(dst, "benchmarks", "chip"),
        ignore=shutil.ignore_patterns("__pycache__"),
    )
    for shared in ("lakesoul_tpu", ".bench_data"):
        src = os.path.join(repo, shared)
        if os.path.exists(src):
            os.symlink(src, os.path.join(dst, shared))
    return dst


def add_pending(root: str, pending: str) -> list[str]:
    """Merge a file of entries that are not in ``BENCHMARK.json`` yet
    (``pending/<configuration>.json``) into the overlay's copy; returns the
    names of the cells it added."""
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    entries = json.load(open(pending))
    for key in ("configs", "workloads", "end_to_end", "per_layer"):
        bench[key].extend(entries.get(key, ()))
    with open(path, "w") as f:
        json.dump(bench, f, indent=1)
    return [w["name"] for w in entries.get("workloads", ())]


def add_cell(root: str, *, name: str, like: str, workload: dict, config: str | None = None) -> None:
    """Add a cell to the overlay's ``BENCHMARK.json`` and its workload file.
    It reports every metric that ``like`` reports."""
    path = os.path.join(root, "BENCHMARK.json")
    bench = json.load(open(path))
    base = next(w for w in bench["workloads"] if w["name"] == like)
    entry = dict(base, name=name, traffic=workload["traffic"])
    if config is not None:
        entry["config"] = config
    bench["workloads"].append(entry)
    for metric in bench["end_to_end"] + bench["per_layer"]:
        if "workloads" in metric and like in metric["workloads"]:
            metric["workloads"].append(name)
    with open(path, "w") as f:
        json.dump(bench, f, indent=1)
    target = os.path.join(root, bench["paths"][0], "workloads", name + ".json")
    with open(target, "x") as f:  # "x": an overlay adds files, it never replaces one
        json.dump(workload, f, indent=2)
