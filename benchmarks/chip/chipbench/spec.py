"""Find a cell's files by name.

``BENCHMARK.json`` at the root names cells, configurations and metrics; every
one of them is a file under ``benchmarks/chip`` that nothing else lists:

    configs/<configuration>.json     sizes, data, guarantees; ``kind`` picks
                                     the driver, ``consumer`` the model adaptor
    workloads/<cell>.json            the traffic mix, all parameters
    drivers/<kind>.py                ``run(cell, seed, seconds, trace, ...) -> dict``
    consumers/<consumer>.py          state, step, batch leaves, operations
    layer_metrics/<metric>.py        ``read(sample) -> float | None``

So a new cell, configuration, model or metric is added files and added entries
in ``BENCHMARK.json``; no file that exists is edited.  ``root`` is the directory
that holds ``BENCHMARK.json``, which lets the self-test point the same code at
a temporary overlay.
"""

from __future__ import annotations

import importlib.util
import json
import os
from dataclasses import dataclass

HERE = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))  # benchmarks/chip
REPO = os.path.dirname(os.path.dirname(HERE))


class SpecError(Exception):
    """The benchmark's files do not say what the harness needs."""


def _read_json(path: str) -> dict:
    try:
        with open(path) as f:
            return json.load(f)
    except FileNotFoundError:
        raise SpecError(f"missing file {path}") from None


def load_module(path: str):
    """Import one file by path under a name of its own (never cached in
    ``sys.modules``: two overlays may hold different files of one name)."""
    if not os.path.exists(path):
        raise SpecError(f"missing file {path}")
    name = "chipbench_file_" + os.path.relpath(path, "/").replace("/", "_").removesuffix(".py")
    spec = importlib.util.spec_from_file_location(name, path)
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


@dataclass(frozen=True)
class Metric:
    name: str
    unit: str
    better: str
    source: str
    layer: str | None      # per-layer metrics only
    moves: str | None      # per-layer metrics only
    cells: tuple[str, ...] | None  # None: every cell

    def in_cell(self, cell: str) -> bool:
        return self.cells is None or cell in self.cells


@dataclass(frozen=True)
class Cell:
    """One entry of ``workloads`` with its files read."""

    name: str
    chips: int
    config_name: str
    config: dict
    workload: dict
    end_to_end: tuple[Metric, ...]
    per_layer: tuple[Metric, ...]
    bench_dir: str   # .../benchmarks/chip of the root in use
    root: str        # the directory that holds BENCHMARK.json

    def driver(self):
        return load_module(os.path.join(self.bench_dir, "drivers", self.config["kind"] + ".py"))

    def consumer(self):
        return load_module(os.path.join(self.bench_dir, "consumers", self.config["consumer"] + ".py"))

    def layer_reader(self, metric: Metric):
        return load_module(os.path.join(self.bench_dir, "layer_metrics", metric.name + ".py")).read


def _metrics(entries, cell: str) -> tuple[Metric, ...]:
    out = []
    for e in entries:
        cells = e.get("workloads")
        m = Metric(e["name"], e["unit"], e["better"], e["source"], e.get("layer"),
                   e.get("moves"), None if cells is None else tuple(cells))
        if m.in_cell(cell):
            out.append(m)
    return tuple(out)


def load_cell(name: str, root: str = REPO) -> Cell:
    bench = _read_json(os.path.join(root, "BENCHMARK.json"))
    entry = next((w for w in bench["workloads"] if w["name"] == name), None)
    if entry is None:
        known = ", ".join(w["name"] for w in bench["workloads"])
        raise SpecError(f"no cell named {name!r} in BENCHMARK.json (cells: {known})")
    cfg_entry = next((c for c in bench["configs"] if c["name"] == entry["config"]), None)
    if cfg_entry is None:
        raise SpecError(f"cell {name!r} names configuration {entry['config']!r}, which is not listed")
    bench_dir = os.path.join(root, bench["paths"][0])
    workload = _read_json(os.path.join(bench_dir, "workloads", name + ".json"))
    if workload.get("traffic") != entry["traffic"]:
        raise SpecError(
            f"workloads/{name}.json says traffic {workload.get('traffic')!r},"
            f" BENCHMARK.json says {entry['traffic']!r}"
        )
    return Cell(
        name=name, chips=int(entry["chips"]), config_name=entry["config"],
        config=_read_json(os.path.join(root, cfg_entry["file"])), workload=workload,
        end_to_end=_metrics(bench["end_to_end"], name),
        per_layer=_metrics(bench["per_layer"], name),
        bench_dir=bench_dir, root=root,
    )
