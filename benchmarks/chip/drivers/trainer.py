"""Driver for configurations of kind ``trainer``: a primary-key table read
through ``table.scan().to_jax_iter()`` into a jitted train step.

The table is written once per checkout from the configuration's ``data_seed``
(appends, then upsert waves, optionally compacted) and cached; a run opens it
as a training job opens a table somebody else wrote.  ``--seed`` makes the
weights and the masking.  The model-specific parts come from the consumer
adaptor the configuration names (``consumers/<consumer>.py``).
"""

from __future__ import annotations

import math
import os
import time

import numpy as np

from chipbench import counters
from chipbench.runtime import Tracer, build_once, data_dir, span

TABLE_NAME = "bench_rows"


def _writes(config: dict):
    from chipbench.datagen import token_writes

    t = config["table"]
    return token_writes(
        rows=config["table_rows"], seq=t["seq"], vocab=config["model"]["vocab_size"],
        commits=t["commits"], upsert_waves=t["upsert_waves"],
        upsert_fraction=t["upsert_fraction"], seed=t["data_seed"],
    )


def prepare(cell, log) -> str:
    """Write the table unless this checkout already has it; returns the
    warehouse directory."""
    config = cell.config
    state = {"compacted": bool(cell.workload.get("table", {}).get("compacted", False))}
    final = data_dir(cell, config["table"], {"rows": config["table_rows"], "vocab": config["model"]["vocab_size"]}, state)

    def build(tmp: str) -> None:
        import pyarrow as pa

        from lakesoul_tpu import LakeSoulCatalog
        from lakesoul_tpu.tensorplane import tensor_field

        t = config["table"]
        schema = pa.schema([("id", pa.int64()), tensor_field("tokens", (t["seq"],), "int32")])
        t0 = time.perf_counter()
        table = LakeSoulCatalog(tmp).create_table(
            TABLE_NAME, schema, primary_keys=["id"], hash_bucket_num=t["hash_buckets"]
        )
        for kind, ids, tokens in _writes(config):
            batch = pa.table({
                "id": ids,
                "tokens": pa.FixedSizeListArray.from_arrays(
                    pa.array(tokens.ravel()), t["seq"]
                ).cast(schema.field("tokens").type),
            }, schema=schema)
            (table.upsert if kind == "upsert" else table.write_arrow)(batch)
        if state["compacted"]:
            table.compact()
        log(f"table of {config['table_rows']} rows written in {time.perf_counter() - t0:.1f} s")

    build_once(final, build)
    return final


def _sharding(plan, spec):
    return None if spec is None else plan.sharding(*spec)


def _platforms(tree) -> set[str]:
    import jax

    return {d.platform for leaf in jax.tree_util.tree_leaves(tree) for d in leaf.devices()}


class _Epochs:
    """The loader iterated epoch after epoch: a window may outlast the table."""

    def __init__(self, loader):
        self._loader = loader
        self._it = iter(loader)

    def next(self):
        try:
            return next(self._it)
        except StopIteration:
            self._it = iter(self._loader)
            return next(self._it)


def check_table(cell, table, plan, batch: int, reference, log) -> dict:
    """One epoch through ``to_jax_iter`` without the step: every key exactly
    once, every row equal to the last write for its key.  The comparison runs
    on the device against the plain merge of the regenerated writes.  The
    epoch is read in large batches (``check_batch`` rows): what is checked is
    the table's content, and 4,096 batches of 64 would take 17 s of every run."""
    import jax
    import jax.numpy as jnp

    config = cell.config
    rows, seq = config["table_rows"], config["table"]["seq"]
    t0 = time.perf_counter()
    # the key column has one axis, so a sharded cell's check shards the rows only
    spec = cell.workload["loader"].get("sharding")
    sharded = spec is not None
    merged = reference.merge_last_write_wins(_writes(config), rows=rows, seq=seq)
    # an argument of the jitted fold, placed once where the batches are: closed
    # over, the 134 MB table sat on one device and every call with a sharded
    # batch moved it again (40 s of each four-chip run)
    want = jax.device_put(merged, plan.replicated) if sharded else jnp.asarray(merged)

    @jax.jit
    def fold(want, seen, wrong, ids, tokens):
        ids = ids.astype(jnp.int32)
        seen = seen.at[ids].add(1)
        wrong = wrong + jnp.sum(jnp.any(want[ids] != tokens, axis=1))
        return seen, wrong

    seen = jnp.zeros(rows, jnp.int32)
    wrong = jnp.zeros((), jnp.int32)
    delivered = 0
    loader = table.scan().batch_size(batch).to_jax_iter(
        sharding=_sharding(plan, spec and spec[:1]), drop_remainder=False,
    )
    for got in loader:
        seen, wrong = fold(want, seen, wrong, got["id"], got["tokens"])
        delivered += int(got["id"].shape[0])
    seen = np.asarray(seen)
    out = {
        "rows_delivered": delivered,
        "keys_missing": int(np.sum(seen == 0)),
        "keys_repeated": int(np.sum(seen > 1)),
        "rows_wrong": int(wrong),
    }
    out["ok"] = delivered == rows and not (out["keys_missing"] or out["keys_repeated"] or out["rows_wrong"])
    log(f"table check {out} in {time.perf_counter() - t0:.1f} s")
    return out


def run(cell, *, seed: int, seconds: float, trace: bool, process_start: float, log,
        runtime_start_s: float = 0.0) -> dict:
    """``runtime_start_s``: the backend's own start as ``run.py: start_runtime``
    took it, which ``setup_s`` leaves out (0.0: nothing is, as before PR 54)."""
    import jax

    from lakesoul_tpu import LakeSoulCatalog
    from lakesoul_tpu.parallel.mesh import make_mesh

    from chipbench.spec import load_module

    config, workload = cell.config, cell.workload
    reference = load_module(os.path.join(cell.bench_dir, "reference", "lww_merge.py"))
    adaptor = cell.consumer()
    warehouse = prepare(cell, log)
    table = LakeSoulCatalog(warehouse).table(TABLE_NAME)
    if not workload.get("table", {}).get("compacted", False) and not any(
        len(u.data_files) > 1 for u in table.scan().scan_plan()
    ):
        raise RuntimeError("the upsert waves left nothing to merge on read")

    devices = jax.devices()[: cell.chips]
    plan = make_mesh(devices, **workload["mesh"])
    batch = int(workload["per_chip_batch"]) * plan.dp
    t0 = time.perf_counter()
    consumer = adaptor.build(config, plan, seed)
    jax.block_until_ready(consumer.params)
    log(f"state on {len(devices)} device(s), mesh dp={plan.dp} tp={plan.tp} sp={plan.sp},"
        f" batch {batch} in {time.perf_counter() - t0:.1f} s")

    loader_args = dict(workload["loader"])
    loader_args["sharding"] = _sharding(plan, loader_args.get("sharding"))
    fill_epochs = int(loader_args.pop("fill_epochs", 0))
    loader = table.scan().batch_size(batch).to_jax_iter(
        transform=adaptor.transform(config, seed), **loader_args
    )
    for _ in range(fill_epochs):  # e.g. cache="device": the first epoch pins the table
        for _batch in loader:
            pass
    epochs = _Epochs(loader)
    lowerings = counters.LoweringCounter()
    platforms: set[str] = set()

    # warm-up: the cell's one step shape, and the loss read the window makes
    t0 = time.perf_counter()
    first = epochs.next()
    log(f"first batch after {time.perf_counter() - t0:.2f} s")
    platforms |= _platforms(first)
    sample_rows = int(workload.get("reference_rows", 16))
    held = {k: np.asarray(v[:sample_rows]) for k, v in first.items()}
    bytes_per_batch = sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(first))
    loss = consumer.step(first)
    for _ in range(int(workload.get("warmup_steps", 3)) - 1):
        loss = consumer.step(epochs.next())
    float(loss)
    log(f"step built and warmed up {time.perf_counter() - t0:.2f} s after the loader was asked; the window opens")

    read_every = int(workload["loss_read_every"])
    trace_s = min(float(workload.get("trace_seconds", 4.0)), seconds / 2)
    tracer = Tracer(cell) if trace else None
    losses: list[float] = []
    raised = 0
    steps = 0
    t_begin = time.perf_counter()
    window_start_s = t_begin - process_start  # set-up ends where the window opens
    setup_s = window_start_s - runtime_start_s
    before = counters.snapshot()
    lowered_before = lowerings.count
    t_stop = t_begin + seconds
    while True:
        now = time.perf_counter()
        if now >= t_stop:
            break
        if tracer is not None and tracer.started is None and now >= t_stop - trace_s:
            tracer.start()
        with span("bench.next_batch"):
            delivered = epochs.next()
        with span("bench.step"):
            try:
                loss = consumer.step(delivered)
            except Exception as e:  # counted, reported, and the run is not correct
                raised += 1
                log(f"step {steps} raised {type(e).__name__}: {e}")
                break
        steps += 1
        if steps % read_every == 0:
            losses.append(float(loss))
    losses.append(float(loss))  # waits for the last step dispatched
    t_end = time.perf_counter()
    after = counters.snapshot()
    compiles = lowerings.count - lowered_before
    if tracer is not None:
        tracer.stop()
    platforms |= _platforms(delivered) | _platforms(loss)
    window_s = t_end - t_begin

    system_loss, plain_loss = consumer.losses_on(held)
    log(f"loss on {sample_rows} rows: program {system_loss:.5f}, plain float32 {plain_loss:.5f}")
    table_check = check_table(cell, table, plan, int(workload.get("check_batch", batch)), reference, log)
    nonfinite = sum(0 if math.isfinite(x) else 1 for x in losses)
    tolerance = float(config["guarantees"]["reference_loss_tolerance"])
    correct = (
        table_check["ok"] and nonfinite == 0 and raised == 0
        and abs(system_loss - plain_loss) <= tolerance
        and platforms == {devices[0].platform}
    )
    rows = steps * batch
    sample = {
        "window_s": window_s,
        "chips": cell.chips,
        "counters": counters.delta(before, after),
        "rows": rows,
        "steps": steps,
        "bytes_delivered": steps * bytes_per_batch,
        "flops_per_row": adaptor.flops_per_row(config),
        "step_module": adaptor.STEP_MODULE,
        "compiles_in_window": compiles,
        "runtime_start_s": runtime_start_s,
    }
    detail = {
        "steps": steps, "batch": batch, "window_start_s": window_start_s, "window_s": window_s, "losses": losses[:3] + losses[-2:],
        "system_loss": system_loss, "plain_loss": plain_loss, "table_check": table_check,
        "platforms": sorted(platforms),
    }
    return {
        "correct": bool(correct),
        "attempted": steps + raised,
        "failed": raised + nonfinite,
        "end_to_end": {"train_rows_s_chip": rows / window_s / cell.chips, "setup_s": setup_s},
        "sample": sample,
        "tracer": tracer,
        "devices": devices,
        "detail": detail,
    }
