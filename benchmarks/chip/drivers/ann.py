"""Driver for configurations of kind ``ann``: a built plane served by
``ShardedAnnEndpoint`` under the cell's traffic.

The plane, the held-out queries and their exact top-k are built once per
checkout from the configuration's ``data_seed`` and cached
(``chipbench.runtime.data_dir``); a run opens them with ``AnnPlane.open(root)``
as a service opens an index it did not just build.  ``--seed`` draws the
traffic: arrival times, the ``nprobe`` of each request, the order of queries.
"""

from __future__ import annotations

import os
import time

import numpy as np

from chipbench import counters
from chipbench.loadgen import LoadGenerator
from chipbench.runtime import Tracer, build_once, data_dir, sleep_until, span
from chipbench.traffic import Schedule


def _plane_config(config: dict):
    from lakesoul_tpu.annplane import AnnPlaneConfig
    from lakesoul_tpu.vector.config import VectorIndexConfig

    data, plane = config["data"], config["plane"]
    index = VectorIndexConfig(
        column="emb", dim=data["dim"], nlist=plane["nlist"],
        total_bits=plane["total_bits"], rotator=plane["rotator"],
    )
    return AnnPlaneConfig(
        index=index, shard_budget_bytes=plane["shard_budget_bytes"], keep_raw=plane["keep_raw"],
    )


def prepare(cell, log) -> str:
    """Build the plane, the queries and their exact neighbours unless this
    checkout already has them; returns the directory."""
    from chipbench.datagen import mixture_corpus

    config = cell.config
    final = data_dir(cell, config["data"], config["plane"],
                     {"rows": config["corpus_rows"], "top_k": config["search"]["top_k"]})

    def build(tmp: str) -> None:
        from lakesoul_tpu.annplane import ShardedAnnBuilder

        data = config["data"]
        t0 = time.perf_counter()
        vectors, ids, queries = mixture_corpus(
            rows=config["corpus_rows"], dim=data["dim"], components=data["components"],
            spread=data["spread"], queries=data["queries"], seed=data["data_seed"],
        )
        log(f"corpus {vectors.shape} from the seed in {time.perf_counter() - t0:.1f} s")
        t0 = time.perf_counter()
        truth = cell_reference(cell).exact_topk_ids(vectors, ids, queries, config["search"]["top_k"])
        log(f"exact top-{config['search']['top_k']} of {len(queries)} queries in {time.perf_counter() - t0:.1f} s")
        t0 = time.perf_counter()
        chunk = 65536
        manifest = ShardedAnnBuilder(os.path.join(tmp, "plane"), _plane_config(config)).build(
            (vectors[lo:lo + chunk], ids[lo:lo + chunk]) for lo in range(0, len(vectors), chunk)
        )
        log(f"plane of {len(manifest['shards'])} shards built in {time.perf_counter() - t0:.1f} s")
        np.save(os.path.join(tmp, "queries.npy"), queries)
        np.save(os.path.join(tmp, "truth.npy"), truth)

    build_once(final, build)
    return final


def cell_reference(cell):
    from chipbench.spec import load_module

    return load_module(os.path.join(cell.bench_dir, "reference", "exact_topk.py"))


def dispatch_cycles(done_sorted: np.ndarray, *, same_batch_s: float = 0.005) -> np.ndarray:
    """``[[queries, seconds], ...]`` for each batch of answers after the first:
    the endpoint answers a whole batch at once, so completions come in clusters
    (callbacks of one batch fire within a millisecond or two); a cycle is the
    time from one cluster's last answer to the next one's."""
    if len(done_sorted) < 2:
        return np.zeros((0, 2))
    starts = np.flatnonzero(np.diff(done_sorted) > same_batch_s) + 1
    bounds = np.concatenate([[0], starts, [len(done_sorted)]])
    ends = done_sorted[bounds[1:] - 1]  # last answer of each cluster
    sizes = np.diff(bounds)
    return np.stack([sizes[1:], np.diff(ends)], axis=1).astype(float)


def warm_ladder(plane, params, queries: np.ndarray, ladder: dict) -> int:
    """Dispatch bracketing batches straight at the plane so that the
    power-of-two item and query shapes the window can meet are compiled before
    it opens: every listed batch size at every listed ``nprobe``."""
    n = 0
    for size in ladder.get("batch_sizes", ()):
        size = min(int(size), len(queries))
        for nprobe in ladder.get("nprobes", ()):
            plane.batch_search(queries[:size], params, nprobes=np.full(size, int(nprobe)))
            n += 1
    return n


def run(cell, *, seed: int, seconds: float, trace: bool, process_start: float, log,
        runtime_start_s: float = 0.0, pallas_interpret: bool = False) -> dict:
    """``runtime_start_s``: the backend's own start as ``run.py`` took it,
    left out of ``setup_s`` as in ``drivers/trainer.py`` (0.0: nothing is)."""
    import jax

    from lakesoul_tpu.annplane import AnnPlane, ShardedAnnEndpoint
    from lakesoul_tpu.errors import OverloadedError
    from lakesoul_tpu.vector.index import SearchParams

    config, workload = cell.config, cell.workload
    search = config["search"]
    root = prepare(cell, log)
    queries = np.load(os.path.join(root, "queries.npy"))
    truth = np.load(os.path.join(root, "truth.npy"))
    t0 = time.perf_counter()
    if pallas_interpret:
        plane = AnnPlane.open(os.path.join(root, "plane"), use_pallas=True, pallas_interpret=True)
    else:
        plane = AnnPlane.open(os.path.join(root, "plane"))
    log(f"plane open: {len(plane.shards)} shards, {plane.num_vectors} vectors,"
        f" use_pallas={plane.use_pallas} in {time.perf_counter() - t0:.1f} s")
    params = SearchParams(top_k=search["top_k"], nprobe=search["nprobe"],
                          rerank_depth=search["rerank_depth"])
    lowerings = counters.LoweringCounter()
    warm = workload.get("warmup", {})
    ladder_dispatches = warm_ladder(plane, params, queries, warm.get("ladder", {}))
    warm_s = float(warm.get("seconds", 0.0))
    trace_s = min(float(workload.get("trace_seconds", 4.0)), seconds / 2)
    grace_s = float(workload.get("grace_seconds", 10.0))
    schedule = Schedule(workload, seed=seed, horizon_s=warm_s + seconds + 1.0,
                        query_count=len(queries))
    nprobes = schedule.params.get("nprobe")
    tracer = Tracer(cell) if trace else None

    with ShardedAnnEndpoint(plane, params) as endpoint:
        def submit(i: int):
            nprobe = None if nprobes is None else int(nprobes[i])
            return endpoint.submit(queries[schedule.query[i]], nprobe=nprobe)

        gen = LoadGenerator(schedule, submit, rejected=OverloadedError,
                            span=span if trace else None)
        t_begin = gen.start() + warm_s
        t_end = t_begin + seconds
        sleep_until(t_begin)
        window_start_s = time.perf_counter() - process_start  # set-up ends where the window opens
        setup_s = window_start_s - runtime_start_s
        before = counters.snapshot()
        stats_before = endpoint.stats()
        lowered_before = lowerings.count
        traced_counters = None
        if tracer is not None:
            sleep_until(t_end - trace_s)
            at_trace = counters.snapshot()
            tracer.start()
        sleep_until(t_end)
        after = counters.snapshot()
        stats_after = endpoint.stats()
        compiles = lowerings.count - lowered_before
        if tracer is not None:
            tracer.stop()
            traced_counters = counters.delta(at_trace, after)
        gen.stop()
        issued = gen.issued
        in_window = np.flatnonzero(
            (gen.due[:issued] >= t_begin) & (gen.due[:issued] < t_end)
        )
        gen.wait_for(in_window, until=t_end + grace_s)
        # copy what the callbacks wrote before the endpoint drains the rest
        status = gen.status[:issued].copy()
        done = gen.done[:issued].copy()

    answered = in_window[status[in_window] == 2]
    failed = int(len(in_window) - len(answered))
    latency_ms = (done[answered] - gen.due[answered]) * 1e3
    worst = max(float(latency_ms.max()) if len(answered) else 0.0, grace_s * 1e3)
    all_latency_ms = np.concatenate([latency_ms, np.full(failed, worst)])
    done_in_window = np.sort(done[(status == 2) & (done >= t_begin) & (done < t_end)])
    cycles = dispatch_cycles(done_in_window)
    reference = cell_reference(cell)
    hits = sum(
        reference.recall_hits(truth[schedule.query[i]], gen.results[i][0]) for i in answered
    )
    recall = hits / max(1, truth.shape[1] * len(answered))
    lateness_ms = (gen.sent[in_window] - gen.due[in_window]) * 1e3

    end_to_end = {"ann_recall10": recall, "setup_s": setup_s}
    if len(cycles):
        end_to_end["ann_qps"] = float(np.median(cycles[:, 0] / cycles[:, 1]))
    if len(all_latency_ms):
        end_to_end["ann_p99_ms"] = float(np.percentile(all_latency_ms, 99))
    sample = {
        "window_s": seconds,
        "chips": cell.chips,
        "counters": counters.delta(before, after),
        "traced_counters": traced_counters,
        "endpoint": {k: stats_after[k] - stats_before[k] for k in ("requests", "rejected", "batches")},
        "lateness_ms": lateness_ms,
        "compiles_in_window": compiles,
        "runtime_start_s": runtime_start_s,
    }
    detail = {
        "window_start_s": window_start_s, "answered": int(len(answered)), "in_window": int(len(in_window)),
        "answered_in_window_per_s": len(done_in_window) / seconds,
        "cycles": int(len(cycles)),
        "cycle_ms_p10_p50_p90": [float(x) for x in np.percentile(cycles[:, 1] * 1e3, [10, 50, 90])] if len(cycles) else None,
        "p50_ms": float(np.percentile(latency_ms, 50)) if len(answered) else None,
        "recall": recall, "ladder_dispatches": ladder_dispatches,
        "rejected": int(np.sum(status[in_window] == 3)),
        "unanswered": int(np.sum(status[in_window] == 1)),
        "raised": int(np.sum(status[in_window] == 4)),
    }
    return {
        "correct": bool(recall >= config["guarantees"]["recall_at_10_floor"]),
        "attempted": int(len(in_window)),
        "failed": failed,
        "end_to_end": end_to_end,
        "sample": sample,
        "tracer": tracer,
        "devices": jax.devices(),
        "detail": detail,
    }
