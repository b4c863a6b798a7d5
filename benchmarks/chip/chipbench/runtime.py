"""What every driver shares: the data cache's place, the profiler around the
traced part of the window and the device's memory peak."""

from __future__ import annotations

import gzip
import hashlib
import json
import os
import shutil
import time

from chipbench import trace as trace_mod


def sleep_until(t: float) -> None:
    while True:
        wait = t - time.perf_counter()
        if wait <= 0:
            return
        time.sleep(min(wait, 0.05))


def data_dir(cell, *parts: dict) -> str:
    """``<root>/.bench_data/chip/<configuration>-<digest>``: the digest covers
    exactly the fields that decide the bytes on disk, so two cells of one
    configuration share one table or one plane, and a cell that changes a
    field gets a directory of its own."""
    digest = hashlib.sha256(json.dumps(parts, sort_keys=True).encode()).hexdigest()[:12]
    return os.path.join(cell.root, ".bench_data", "chip", f"{cell.config_name}-{digest}")


def build_once(final: str, build) -> bool:
    """Run ``build(final)`` unless an earlier run finished it there; returns
    whether it built.  The data is built in place, because a table's metadata
    holds the absolute paths of its files, and a marker written last says it is
    whole: a run that died half way leaves no marker and the next run starts
    again from nothing.  One run at a time uses a checkout."""
    marker = os.path.join(final, "READY")
    if os.path.exists(marker):
        return False
    shutil.rmtree(final, ignore_errors=True)
    os.makedirs(final)
    build(final)
    with open(marker, "w") as f:
        f.write("built by benchmarks/chip\n")
    return True


class Tracer:
    """The profiler around the last seconds of the window of a ``--trace 1``
    run.  Host tracing stays at the level that records ``TraceAnnotation``
    spans; the Python tracer is off, it would slow the host it measures."""

    def __init__(self, cell):
        self.logdir = os.path.join(cell.root, ".bench_data", "chip", "trace", cell.name)
        self.started: float | None = None
        self.stopped: float | None = None

    def start(self) -> None:
        import jax

        shutil.rmtree(self.logdir, ignore_errors=True)
        options = jax.profiler.ProfileOptions()
        options.python_tracer_level = 0
        options.host_tracer_level = 2
        jax.profiler.start_trace(self.logdir, profiler_options=options)
        self.started = time.perf_counter()

    def stop(self) -> None:
        import jax

        self.stopped = time.perf_counter()
        jax.profiler.stop_trace()

    def reduce(self) -> tuple[dict, dict]:
        """``(plain trace, reduction)``; the plain trace is also kept beside the
        profiler's own files, for whoever wants to look at it by hand."""
        plain = trace_mod.load_xplane(trace_mod.find_xplane(self.logdir))
        with gzip.open(os.path.join(self.logdir, "plain.json.gz"), "wt") as f:
            json.dump(plain, f)
        return plain, trace_mod.reduce_trace(plain)


def span(name: str):
    import jax

    return jax.profiler.TraceAnnotation(name)


def memory_peak_bytes(devices) -> int:
    """Peak device memory on the fullest chip, as JAX reports it.

    On this runtime ``peak_bytes_in_use`` counts buffers (weights, optimizer
    state, batches, uploaded codes) and leaves out the scratch space a running
    program reserves for its temporaries, which ``peak_bytes_reserved`` holds
    (BERT-base at 64 x 128: 1.33 GB in use, 8.95 GB reserved, against 1.31 +
    10.28 GB in XLA's own memory analysis of the step).  The peak is their sum."""
    worst = 0
    for d in devices:
        stats = d.memory_stats() or {}
        worst = max(worst, int(stats.get("peak_bytes_in_use", 0)) + int(stats.get("peak_bytes_reserved", 0)))
    return worst
