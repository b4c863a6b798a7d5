"""The ``stage()`` seam of ``obs/stages.py`` under a real profiler session.

A small merge-on-read table goes through ``to_jax_iter`` three times: outside
any session, then not at all during an empty session, then inside a session
set up as the chip benchmark's ``Tracer`` sets it (host tracer level 2, the
Python tracer off).  Per stage: the span is in the host plane on the right
thread's line, the number of spans is the histogram's count delta, and the
spans' self time is its sum delta; outside a session the histograms move and
nothing is recorded.  The last test pins the two names the benchmark's device
readers search a trace for.
"""

from __future__ import annotations

import functools
import glob
import os
import re

import jax
import jax.numpy as jnp
import numpy as np
import pyarrow as pa
import pytest

from lakesoul_tpu.models.qwen3_next import Qwen3NextConfig
from lakesoul_tpu.obs import SCAN_STAGES, registry, stage, stage_counts, stage_seconds

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONSUMER_STAGES = ("queue", "device_put")
H2D_COUNTER = "lakesoul_tensorplane_h2d_bytes_total"


def _span_name(stage_name: str) -> str:
    layer = "scan" if stage_name in ("decode", "merge", "fill") else "loader"
    return f"lakesoul.{layer}.{stage_name}"


def _snapshot() -> dict:
    return {"seconds": stage_seconds(), "counts": stage_counts(),
            "h2d": registry().counter(H2D_COUNTER).value}


def _delta(before: dict, after: dict) -> dict:
    return {
        "seconds": {s: after["seconds"][s] - before["seconds"][s] for s in SCAN_STAGES},
        "counts": {s: after["counts"][s] - before["counts"][s] for s in SCAN_STAGES},
        "h2d": after["h2d"] - before["h2d"],
    }


def _session(logdir: str, body) -> list[list[tuple[str, float, float]]]:
    """Run ``body`` inside a profiler session; returns the host plane's lines,
    each the ``(name, start_ns, duration_ns)`` of its ``lakesoul.*`` and
    ``test.*`` events."""
    options = jax.profiler.ProfileOptions()
    options.python_tracer_level = 0
    options.host_tracer_level = 2
    jax.profiler.start_trace(logdir, profiler_options=options)
    try:
        body()
    finally:
        jax.profiler.stop_trace()
    path = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")))[-1]
    lines = []
    for plane in jax.profiler.ProfileData.from_file(path).planes:
        if plane.name != "/host:CPU":
            continue
        for line in plane.lines:
            events = [(e.name, e.start_ns, e.duration_ns) for e in line.events
                      if e.name.startswith(("lakesoul.", "test."))]
            if events:
                lines.append(events)
    return lines


def _self_ns(events) -> list[tuple[str, float]]:
    """``(name, self time)`` of each span of one line: its duration less the
    spans nested directly inside it."""
    out: list[list] = []
    open_: list[tuple[float, int]] = []  # (end, index into out)
    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        while open_ and start >= open_[-1][0]:
            open_.pop()
        if open_:
            out[open_[-1][1]][1] -= dur
        out.append([name, dur])
        open_.append((start + dur, len(out) - 1))
    return [(name, ns) for name, ns in out]


def _spans_and_histogram(recorded, stage_name):
    """(each span's self time in ns, the histogram's seconds over the same
    session, whether the two sums agree) of one stage."""
    name = _span_name(stage_name)
    selfs = [ns for events in recorded["lines"] for n, ns in _self_ns(events) if n == name]
    seconds = recorded["inside"]["seconds"][stage_name]
    return selfs, seconds, abs(sum(selfs) / 1e9 - seconds) <= 0.05 * seconds + 1e-3


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    from lakesoul_tpu import LakeSoulCatalog

    root = tmp_path_factory.mktemp("stage_spans")
    schema = pa.schema([("id", pa.int64()), ("x", pa.float32())])
    table = LakeSoulCatalog(str(root / "warehouse")).create_table(
        "rows", schema, primary_keys=["id"], hash_bucket_num=2
    )
    rng = np.random.default_rng(0)
    rows = 32768
    for part in np.split(np.arange(rows, dtype=np.int64), 4):
        table.write_arrow(pa.table({"id": part, "x": rng.random(len(part), np.float32)}, schema=schema))
    ids = np.sort(rng.choice(rows, rows // 4, replace=False)).astype(np.int64)
    table.upsert(pa.table({"id": ids, "x": rng.random(len(ids), np.float32)}, schema=schema))

    delivered = []

    def epoch():
        n = nbytes = 0
        for batch in table.scan().batch_size(512).to_jax_iter():
            n += int(batch["id"].shape[0])
            nbytes += sum(leaf.nbytes for leaf in jax.tree_util.tree_leaves(batch))
        delivered.append((n, nbytes))

    def consume():
        with jax.profiler.TraceAnnotation("test.consume"):
            epoch()

    epoch()  # the backend, the native library, the pool's threads
    before = _snapshot()
    epoch()
    outside = _delta(before, _snapshot())
    quiet_lines = _session(str(root / "quiet"), lambda: None)
    for attempt in ("traced", "traced_again"):
        before = _snapshot()
        lines = _session(str(root / attempt), consume)
        found = {"outside": outside, "quiet_lines": quiet_lines, "lines": lines,
                 "inside": _delta(before, _snapshot()), "bytes_epoch": delivered[-1][1]}
        # a span opens before its stage reads the histogram's clock and closes after it: a thread
        # descheduled in between (seen under six workers: 0.3 ms on one ``collate`` span) puts that
        # time into one span and not into the histogram.  That is the recording's accident, not the
        # program's, so one such recording is made again, and the second is what the tests hold
        if all(_spans_and_histogram(found, s)[2] for s in SCAN_STAGES):
            break
    assert {n for n, _ in delivered} == {rows}
    return found


def _consumer_line(lines) -> int:
    (index,) = [i for i, events in enumerate(lines) if any(n == "test.consume" for n, _, _ in events)]
    return index


@pytest.mark.parametrize("stage_name", SCAN_STAGES)
def test_span_is_on_the_right_threads_line(recorded, stage_name):
    lines, name = recorded["lines"], _span_name(stage_name)
    holding = {i for i, events in enumerate(lines) if any(n == name for n, _, _ in events)}
    assert holding, f"no {name} span in the host plane"
    consumer = _consumer_line(lines)
    if stage_name in CONSUMER_STAGES:
        assert holding == {consumer}
    else:
        assert consumer not in holding


@pytest.mark.parametrize("stage_name", SCAN_STAGES)
def test_spans_agree_with_the_histogram(recorded, stage_name):
    selfs, seconds, agree = _spans_and_histogram(recorded, stage_name)
    assert len(selfs) == recorded["inside"]["counts"][stage_name]
    assert agree, (sum(selfs) / 1e9, seconds)


def test_merge_self_time_excludes_its_fill_children(recorded):
    whole = nested_fill = 0.0
    for events in recorded["lines"]:
        merges = [(s, s + d) for n, s, d in events if n == "lakesoul.scan.merge"]
        whole += sum(e - s for s, e in merges)
        nested_fill += sum(d for n, s, d in events if n == "lakesoul.scan.fill"
                           and any(lo <= s and s + d <= hi for lo, hi in merges))
    assert nested_fill > 0, "every merge uniforms its runs: a fill inside"
    seconds = recorded["inside"]["seconds"]["merge"]
    assert abs((whole - nested_fill) / 1e9 - seconds) <= 0.05 * seconds + 1e-3
    assert seconds < whole / 1e9


@pytest.mark.parametrize("stage_name", SCAN_STAGES)
def test_outside_a_session_the_histogram_moves_and_nothing_is_recorded(recorded, stage_name):
    assert recorded["outside"]["counts"][stage_name] > 0
    assert recorded["outside"]["seconds"][stage_name] > 0
    # the same work as the traced epoch (queue: one more or less wait on the pump)
    slack = 1 if stage_name == "queue" else 0
    assert abs(recorded["outside"]["counts"][stage_name] - recorded["inside"]["counts"][stage_name]) <= slack
    assert not [n for events in recorded["quiet_lines"] for n, _, _ in events if n.startswith("lakesoul.")]


def test_merge_and_fill_stay_additive(monkeypatch):
    """``merge`` contains ``fill``: with the uniform step slowed to something a
    clock can see, the two stages still sum to the whole merge call."""
    import time

    from lakesoul_tpu.io import merge

    real = merge.uniform_table

    def slow_uniform(*args, **kwargs):
        time.sleep(0.02)
        return real(*args, **kwargs)

    monkeypatch.setattr(merge, "uniform_table", slow_uniform)
    runs = [pa.table({"id": pa.array([1, 2, 3], pa.int64()), "x": pa.array([v, v, v], pa.float32())})
            for v in (0.0, 1.0)]
    whole = registry().histogram("lakesoul_io_merge_seconds")
    before = _snapshot(), whole.value["sum"]
    merged = merge.merge_sorted_tables(runs, ["id"])
    stages, total = _delta(before[0], _snapshot())["seconds"], whole.value["sum"] - before[1]
    assert merged.column("x").to_pylist() == [1.0, 1.0, 1.0]
    assert stages["fill"] >= 0.04
    assert stages["merge"] + stages["fill"] == pytest.approx(total, abs=1e-4)
    assert stages["merge"] < total - 0.04 + 1e-4


def test_deliver_counts_the_bytes_it_placed(recorded):
    assert recorded["inside"]["h2d"] == recorded["outside"]["h2d"] == recorded["bytes_epoch"] > 0


def test_nested_stages_are_additive_and_any_name_is_taken():
    """Self time by nesting, on this thread's stack, and a name outside the
    seven (an ANN phase, later) goes through the same seam."""
    import time

    before = registry().histogram("lakesoul_scan_stage_seconds", stage="ann.upload").value
    with stage("ann.upload") as outer:
        time.sleep(0.02)
        with stage("ann.upload") as inner:
            time.sleep(0.03)
    after = registry().histogram("lakesoul_scan_stage_seconds", stage="ann.upload").value
    assert after["count"] - before["count"] == 2
    assert outer.elapsed >= inner.elapsed >= 0.03
    assert after["sum"] - before["sum"] == pytest.approx(outer.elapsed, abs=1e-4)


TRAIN_STAGES = ("train.place", "train.dispatch")  # ``models/train.py: _CountedStep.__call__``


@pytest.fixture(scope="module")
def recorded_steps(tmp_path_factory):
    """Five calls of a train step as a training job gets it (``make_lm_train_step``'s return
    value), compiled before the session: the host plane's lines, and what the two stages'
    histograms moved by meanwhile."""
    from lakesoul_tpu.models.train import make_lm_train_state, make_lm_train_step
    from lakesoul_tpu.parallel.mesh import make_mesh

    plan = make_mesh(jax.devices()[:1], dp=1, tp=1, sp=1)
    params, opt_state, tx, shardings = make_lm_train_state(LM_CFG, plan)
    step = make_lm_train_step(LM_CFG, plan, tx, shardings)
    ids = np.zeros((2, 16), np.int32)
    state = [params, opt_state]

    def steps(n=5):
        with jax.profiler.TraceAnnotation("test.consume"):
            for _ in range(n):
                *state[:], loss = step(*state, ids, ids)
            float(loss)

    steps(1)  # traces and compiles inside ``train.dispatch``: not what the session holds
    histograms = {s: registry().histogram("lakesoul_scan_stage_seconds", stage=s) for s in TRAIN_STAGES}
    for attempt in ("traced", "traced_again"):  # as ``recorded``: one descheduled span is the recording's accident
        before = {s: dict(h.value) for s, h in histograms.items()}
        lines = _session(str(tmp_path_factory.mktemp("step_spans") / attempt), steps)
        moved = {s: {k: h.value[k] - before[s][k] for k in ("sum", "count")} for s, h in histograms.items()}
        selfs = {s: [ns for events in lines for n, ns in _self_ns(events) if n == "lakesoul." + s] for s in TRAIN_STAGES}
        if all(abs(sum(selfs[s]) / 1e9 - moved[s]["sum"]) <= 0.05 * moved[s]["sum"] + 1e-3 for s in TRAIN_STAGES):
            break
    return {"lines": lines, "moved": moved, "selfs": selfs}


@pytest.mark.parametrize("stage_name", TRAIN_STAGES)
def test_step_wrapper_spans_agree_with_the_histogram(recorded_steps, stage_name):
    """``idle_place_ms_step`` and ``idle_dispatch_ms_step`` read the spans ``lakesoul.train.place``
    and ``lakesoul.train.dispatch`` off the line that holds the benchmark's ``bench.next_batch``:
    the thread that calls the step.  One span a call, on that line only, and its time is the
    histogram's."""
    lines, name = recorded_steps["lines"], "lakesoul." + stage_name
    holding = {i for i, events in enumerate(lines) if any(n == name for n, _, _ in events)}
    assert holding == {_consumer_line(lines)}
    selfs, moved = recorded_steps["selfs"][stage_name], recorded_steps["moved"][stage_name]
    assert len(selfs) == moved["count"] == 5
    assert abs(sum(selfs) / 1e9 - moved["sum"]) <= 0.05 * moved["sum"] + 1e-3, (sum(selfs) / 1e9, moved["sum"])
    with open(os.path.join(REPO, "benchmarks", "chip", "layer_metrics", f"idle_{stage_name[6:]}_ms_step.py")) as f:
        assert f'SPAN = "{name}"' in f.read()
    assert stage_name not in SCAN_STAGES  # ``stage_seconds()`` and the loader's readers keep summing the seven


def _step_module() -> str:
    """The step as a training job gets it, ``make_bert_train_step``'s return
    value called once; the factory jits on that first call, inside a closure,
    so ``jax.jit`` is watched for what it was handed."""
    from lakesoul_tpu.models.bert import BertConfig
    from lakesoul_tpu.models.train import make_bert_train_state, make_bert_train_step
    from lakesoul_tpu.parallel.mesh import make_mesh

    plan = make_mesh(jax.devices()[:1], dp=1, tp=1, sp=1)
    cfg = BertConfig.tiny()
    params, opt_state, tx, shardings = make_bert_train_state(cfg, plan, lr=1e-3)
    step = make_bert_train_step(cfg, plan, tx, shardings)
    ids = jnp.zeros((2, 16), jnp.int32)
    lowered: list[str] = []
    real_jit = jax.jit

    def watching_jit(fn, **kwargs):
        jitted = real_jit(fn, **kwargs)

        def call(*args):
            lowered.append(jitted.lower(*args).as_text())
            return jitted(*args)

        return call

    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(jax, "jit", watching_jit)
        step(params, opt_state, ids, ids, jnp.ones((2, 16), jnp.bool_))
    (text,) = lowered
    return text


def _kernel_module() -> str:
    from lakesoul_tpu.annplane import ragged

    f32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.float32)  # noqa: E731
    i32 = lambda *shape: jax.ShapeDtypeStruct(shape, jnp.int32)  # noqa: E731
    m, rows, d = 256, 1024, 128
    return ragged._ragged_score_pallas_call.trace(
        i32(m), i32(m), f32(m), f32(m), f32(8, d), f32(rows, d), f32(rows), f32(rows), f32(rows),
        tile=ragged.TILE, interpret=False,
    ).lower(lowering_platforms=("tpu",)).as_text()


@pytest.mark.parametrize("lower, module, reader, constant", [
    (_step_module, "jit_train_step", "consumers/bert_mlm.py", 'STEP_MODULE = "jit_train_step"'),
    (_kernel_module, "jit__ragged_score_pallas_call", "layer_metrics/ragged_dev_ms.py", 'KERNEL = "ragged_score"'),
])
def test_names_the_device_readers_search_for(lower, module, reader, constant):
    """``chipbench/trace.py`` finds the step program and the ragged kernel in a
    device trace by these names and nothing else pins them: a rename here has
    to be a rename there, which only a benchmark PR may make."""
    assert f"module @{module} " in lower()
    with open(os.path.join(REPO, "benchmarks", "chip", reader)) as f:
        assert constant in f.read()


LM_SCOPES = {
    "lakesoul.lm.gdn": ("layer_metrics/gdn_step_share_pct.py", '"gdn"'),
    "lakesoul.lm.attn": ("layer_metrics/attn_step_share_pct.py", '"attn"'),
    "lakesoul.lm.moe.route": ("layer_metrics/moe_step_share_pct.py", '"moe.route"'),
    "lakesoul.lm.moe.experts": ("layer_metrics/moe_step_share_pct.py", '"moe.experts"'),
    "lakesoul.lm.moe.shared": ("layer_metrics/moe_step_share_pct.py", '"moe.shared"'),
    "lakesoul.lm.head": ("layer_metrics/head_step_share_pct.py", 'SCOPE = "head"'),
    "lakesoul.lm.optim": ("layer_metrics/optim_step_share_pct.py", 'SCOPE = "optim"'),
    "lakesoul.lm.embed": ("layer_metrics/embed_step_share_pct.py", 'SCOPE = "embed"'),
}


LM_TOKENS = 128  # a row in the steps compiled for a v5e: one tile of the attention kernels
LM_CFG = Qwen3NextConfig(  # heads of 64, DeltaNet heads of 128: both mixers take their kernels at 128 tokens
    vocab_size=64, hidden_size=32, num_hidden_layers=4, num_attention_heads=8,
    num_key_value_heads=1, head_dim=64, linear_num_key_heads=2, linear_num_value_heads=4,
    linear_key_head_dim=128, linear_value_head_dim=128, num_experts=8, num_experts_per_tok=2,
    moe_intermediate_size=16, shared_expert_intermediate_size=16, experts_held=(0, 4),
)


@pytest.fixture(scope="module")
def lm_step_module() -> str:
    """The causal-LM step as a training job gets it, lowered with the
    locations that carry ``jax.named_scope``."""
    from lakesoul_tpu.models.train import make_lm_train_state, make_lm_train_step
    from lakesoul_tpu.parallel.mesh import make_mesh

    plan = make_mesh(jax.devices()[:1], dp=1, tp=1, sp=1)
    params, opt_state, tx, shardings = make_lm_train_state(LM_CFG, plan)
    step = make_lm_train_step(LM_CFG, plan, tx, shardings)
    ids = jnp.zeros((2, 16), jnp.int32)
    return step.lower(params, opt_state, ids, ids).as_text(debug_info=True)


@pytest.fixture(scope="module")
def lm_step_compiled_for_a_v5e() -> str:
    """The causal-LM step compiled for a described v5e (no chip: the TPU's
    compiler is installed here), as the text the adaptor's ``scopes_of``
    reads on the chip: the compiler inlines the calls, so an instruction's
    ``op_name`` carries the scopes of its callers."""
    import optax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from lakesoul_tpu.models import qwen3_next, train
    from lakesoul_tpu.utils import platform

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    one_chip = SingleDeviceSharding(topo.devices[0])
    tx = optax.adamw(1e-3)

    def init(seed):
        params = qwen3_next.init_lm_params(LM_CFG, jax.random.key(seed))
        return params, tx.init(params)

    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(init, np.uint32(0)),
    )
    ids = jax.ShapeDtypeStruct((2, LM_TOKENS), jnp.int32, sharding=one_chip)
    step = train._adamw_step(functools.partial(qwen3_next.lm_loss, cfg=LM_CFG), tx)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(platform, "on_tpu", lambda: True)  # the branch the chip takes
        return jax.jit(step).lower(*state, ids, ids).compile().as_text()


def _kernel_calls(compiled_text: str) -> list[str]:
    """The Pallas kernels' instructions in a compiled module, by name (one
    result or a tuple of them)."""
    return re.findall(r"^\s*%?([\w.\-]+) = .*? custom-call\(.*tpu_custom_call", compiled_text, re.MULTILINE)


def test_lm_step_holds_the_deltanet_kernels_under_the_gdn_scope(lm_step_compiled_for_a_v5e):
    """A device trace names a kernel's events by its instruction, which takes
    the kernel's name.  The chunk inverse and the recurrence's forward kernel
    stand twice a DeltaNet layer in the program (forward, and the row's
    rematerialisation, where the forward kernel also keeps each chunk's
    state), the recurrence's backward kernel once; each runs once a row.
    ``gdn_step_share_pct`` counts their time only if the step's scope map (the
    adaptor's ``scopes_of``) charges them to ``lakesoul.lm.gdn``: the reader
    sums that exact name, so a scope of a kernel's own would take the kernel
    out of the metric."""
    import importlib.util

    text = lm_step_compiled_for_a_v5e
    layers = LM_CFG.layer_kinds().count("gdn")
    calls = [name for name in _kernel_calls(text) if not name.startswith("flash_attention")]
    assert sorted(name.rsplit(".", 1)[0] for name in calls) == (
        ["gated_delta_bwd"] * layers + ["gated_delta_fwd"] * 2 * layers + ["unit_lower_inverse"] * 2 * layers
    ), calls
    spec = importlib.util.spec_from_file_location(
        "qwen3_next_clm", os.path.join(REPO, "benchmarks", "chip", "consumers", "qwen3_next_clm.py")
    )
    adaptor = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(adaptor)
    scope_of = adaptor.scopes_of(text)
    assert {scope_of.get(name) for name in calls} == {"lakesoul.lm.gdn"}
    assert sorted(set(scope_of.values())) == sorted(LM_SCOPES)  # and no scope the readers do not know


@pytest.mark.parametrize("n, f, tile, grouped", [(64, 16, 16, 0), (1024, 128, 128, 1)],
                         ids=["narrow-experts", "experts-and-tile-of-whole-lane-tiles"])
def test_expert_sums_by_dma_stay_under_the_experts_scope(n, f, tile, grouped):
    """The held experts' layer alone, value and gradient, at a width whose
    float32 sums move by DMA (the toy step above is narrower and indexes),
    compiled for the described v5e.  A device trace names the two kernels'
    events ``take_rows.<n>`` and ``put_rows.<n>``: in the forward loop, after
    it (the last tile's rows) and in the backward loop.  Where the experts'
    matrices and the tile are whole lane tiles the layer runs none of them:
    the grouped kernels ``experts_fwd.<n>`` and ``experts_bwd.<n>``, once a
    pass inside the loop over segments, move their own rows (``stage_rows.<n>``
    lays ``x`` and ``dy`` out for them once a pass, ``unstage_rows.<n>`` reads
    the sums back), and the weight-gradient sums are ``expert_dw.<n>``, once a
    matrix a segment.
    ``moe_step_share_pct`` keeps counting them only if the adaptor's
    ``scopes_of`` charges them to ``lakesoul.lm.moe.experts``, as a custom
    call's ``op_name`` lets it."""
    import importlib.util

    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from lakesoul_tpu.parallel import moe
    from lakesoul_tpu.utils import platform

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    one_chip = SingleDeviceSharding(topo.devices[0])
    h, k = 256, 2

    def shape(dims, dtype=jnp.float32):
        return jax.ShapeDtypeStruct(dims, dtype, sharding=one_chip)

    def loss(x, w, p, top_e):
        y, _ = moe.held_experts(x, top_e, w, p, n_experts=8, held=(0, 4), tile=tile)
        return jnp.sum(y.astype(jnp.float32) ** 2)

    p = {"w_gate": shape((4, h, f)), "w_up": shape((4, h, f)), "w_down": shape((4, f, h))}
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(platform, "on_tpu", lambda: True)  # the branch the chip takes
        text = jax.jit(jax.value_and_grad(loss, argnums=(0, 1, 2))).lower(
            shape((n, h), jnp.bfloat16), shape((n, k)), p, shape((n, k), jnp.int32)
        ).compile().as_text()
    calls = _kernel_calls(text)
    assert sorted(name.split(".")[0] for name in calls) == (
        ["expert_dw"] * 3 + ["experts_bwd", "experts_fwd"] + ["stage_rows"] * 2 + ["unstage_rows"] * 2 if grouped
        else ["put_rows"] * 3 + ["take_rows"] * 3
    ), calls
    spec = importlib.util.spec_from_file_location(
        "qwen3_next_clm", os.path.join(REPO, "benchmarks", "chip", "consumers", "qwen3_next_clm.py")
    )
    adaptor = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(adaptor)
    scope_of = adaptor.scopes_of(text)
    assert {scope_of.get(name) for name in calls} == {"lakesoul.lm.moe.experts"}


def test_lm_step_program_name_the_device_readers_search_for(lm_step_module):
    """``step_device_ms`` and ``chipbench/scopes.py`` find the causal-LM step in
    a device trace by the name its adaptor pins."""
    assert "module @jit_train_step " in lm_step_module
    with open(os.path.join(REPO, "benchmarks", "chip", "consumers", "qwen3_next_clm.py")) as f:
        assert 'STEP_MODULE = "jit_train_step"' in f.read()


@pytest.mark.parametrize("scope", sorted(LM_SCOPES))
def test_lm_scope_names_the_share_readers_search_for(lm_step_module, scope):
    """The three step-share readers charge device time by these
    ``jax.named_scope`` names (through ``consumers/qwen3_next_clm.py:
    scopes_of``): a rename in the program has to be a rename there."""
    assert f"{scope}/" in lm_step_module or f"{scope})" in lm_step_module or f'{scope}"' in lm_step_module
    reader, constant = LM_SCOPES[scope]
    with open(os.path.join(REPO, "benchmarks", "chip", reader)) as f:
        assert constant in f.read()
    with open(os.path.join(REPO, "benchmarks", "chip", "consumers", "qwen3_next_clm.py")) as f:
        assert r'lakesoul\.lm\.' in f.read()


@pytest.mark.parametrize("check", ["fill_of_hand_counts", "nothing_without_the_series"])
def test_tile_fill_reader(check):
    """``moe_tile_fill_pct`` through its own self-test, and the series it
    divides by under the name the LM step feeds."""
    import importlib.util

    from lakesoul_tpu.models.train import MOE_ASSIGNMENTS_FAMILY

    spec = importlib.util.spec_from_file_location(
        "chipbench_selftest_tile_fill", os.path.join(REPO, "benchmarks", "chip", "selftest", "tile_fill.py")
    )
    selftest = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(selftest)
    assert [t.__name__ for t in selftest.TESTS] == ["test_fill_of_hand_counts", "test_nothing_without_the_series"]
    getattr(selftest, "test_" + check)()
    assert selftest.FAMILY == MOE_ASSIGNMENTS_FAMILY
    with open(os.path.join(REPO, "benchmarks", "chip", "layer_metrics", "moe_tile_fill_pct.py")) as f:
        assert 'kind="tile_rows"' in f.read()
    with open(os.path.join(REPO, "lakesoul_tpu", "models", "train.py")) as f:
        assert '{"kind": "tile_rows"}' in f.read()


@pytest.mark.parametrize("check", ["rows_a_write_of_hand_counts", "nothing_without_the_series"])
def test_rows_per_dw_write_reader(check):
    """``moe_rows_per_dw_write`` through its own self-test, and the series it
    divides by under the name the LM step feeds."""
    import importlib.util

    from lakesoul_tpu.models.train import MOE_ASSIGNMENTS_FAMILY

    spec = importlib.util.spec_from_file_location(
        "chipbench_selftest_dw_writes", os.path.join(REPO, "benchmarks", "chip", "selftest", "dw_writes.py")
    )
    selftest = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(selftest)
    assert [t.__name__ for t in selftest.TESTS] == ["test_rows_a_write_of_hand_counts", "test_nothing_without_the_series"]
    getattr(selftest, "test_" + check)()
    assert selftest.FAMILY == MOE_ASSIGNMENTS_FAMILY
    with open(os.path.join(REPO, "benchmarks", "chip", "layer_metrics", "moe_rows_per_dw_write.py")) as f:
        assert 'kind="dw_writes"' in f.read()
    with open(os.path.join(REPO, "lakesoul_tpu", "models", "train.py")) as f:
        assert '{"kind": "dw_writes"}' in f.read()


@pytest.mark.parametrize("check", ["share_of_hand_counts", "nothing_without_the_series", "tile_fill_rises_with_the_blocks_skipped"])
def test_grouped_experts_reader(check):
    """``moe_grouped_pct`` through its own self-test, and the series it reads
    under the names the LM step feeds."""
    import importlib.util

    from lakesoul_tpu.models.train import MOE_ASSIGNMENTS_FAMILY

    spec = importlib.util.spec_from_file_location(
        "chipbench_selftest_grouped_experts", os.path.join(REPO, "benchmarks", "chip", "selftest", "grouped_experts.py")
    )
    selftest = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(selftest)
    assert [t.__name__ for t in selftest.TESTS] == [
        "test_share_of_hand_counts", "test_nothing_without_the_series", "test_tile_fill_rises_with_the_blocks_skipped"]
    getattr(selftest, "test_" + check)()
    assert selftest.FAMILY == MOE_ASSIGNMENTS_FAMILY
    with open(os.path.join(REPO, "benchmarks", "chip", "layer_metrics", "moe_grouped_pct.py")) as f:
        reader = f.read()
    assert 'kind="grouped"' in reader and 'kind="held"' in reader
    with open(os.path.join(REPO, "lakesoul_tpu", "models", "train.py")) as f:
        assert '{"kind": "grouped"}' in f.read()


# ------------------------------------------- the second causal-LM family

LFM2_SCOPES = {
    "lakesoul.lm.conv": ("layer_metrics/conv_step_share_pct.py", 'SCOPE = "conv"'),
    "lakesoul.lm.mlp": ("layer_metrics/mlp_step_share_pct.py", 'SCOPE = "mlp"'),
}


def _lfm2_cfg():
    from lakesoul_tpu.models.lfm2_moe import Lfm2MoeConfig

    return Lfm2MoeConfig(  # four heads of 64 on one key-value head: the attention layer takes its kernels at 128 tokens
        vocab_size=64, hidden_size=256, layer_types=("conv", "full_attention", "conv"), num_dense_layers=1,
        intermediate_size=48, num_attention_heads=4, num_key_value_heads=1, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=16, experts_held=(0, 4),
    )


def _adaptor(name: str):
    import importlib.util
    import sys

    bench = os.path.join(REPO, "benchmarks", "chip")
    sys.path[:0] = [p for p in (bench,) if p not in sys.path]  # the adaptor imports ``chipbench``
    spec = importlib.util.spec_from_file_location(name, os.path.join(bench, "consumers", name + ".py"))
    module = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(module)
    return module


def _family_step_compiled_for_a_v5e(cfg) -> str:
    """A family's step (``cfg.init``, ``cfg.loss``) compiled for a described
    v5e, as ``lm_step_compiled_for_a_v5e`` compiles the first family's: the
    text its adaptor's ``scopes_of`` reads on the chip."""
    import optax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from lakesoul_tpu.models import train
    from lakesoul_tpu.utils import platform

    try:
        topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    except Exception as e:
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    one_chip = SingleDeviceSharding(topo.devices[0])
    tx = optax.adamw(1e-3)

    def init(seed):
        params = cfg.init(jax.random.key(seed))
        return params, tx.init(train._split_buffers(params)[0])

    state = jax.tree.map(
        lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=one_chip),
        jax.eval_shape(init, np.uint32(0)),
    )
    ids = jax.ShapeDtypeStruct((2, LM_TOKENS), jnp.int32, sharding=one_chip)
    with pytest.MonkeyPatch.context() as patch:
        patch.setattr(platform, "on_tpu", lambda: True)  # the branch the chip takes
        return jax.jit(train._adamw_step(cfg.loss, tx)).lower(*state, ids, ids).compile().as_text()


@pytest.fixture(scope="module")
def lfm2_step_compiled_for_a_v5e() -> str:
    return _family_step_compiled_for_a_v5e(_lfm2_cfg())


@pytest.mark.parametrize("family", ["qwen3_next_clm", "lfm2_moe_clm"])
def test_lm_steps_hold_the_attention_kernels_under_the_attn_scope(family, request):
    """A device trace names the two kernels' events ``flash_attention_fwd.<n>``
    and ``flash_attention_bwd.<n>`` (what the ledger's ``breakdown.device_ops``
    prints), once each an attention layer in either family's step: the row's
    checkpoint keeps the output and the log-sum-exp, so the backward pass runs
    no second forward kernel.  ``attn_step_share_pct`` counts their time only if
    the step's scope map charges them to ``lakesoul.lm.attn``."""
    fixture = {"qwen3_next_clm": "lm_step_compiled_for_a_v5e", "lfm2_moe_clm": "lfm2_step_compiled_for_a_v5e"}[family]
    text = request.getfixturevalue(fixture)
    calls = [name for name in _kernel_calls(text) if name.startswith("flash_attention")]
    assert sorted(name.rsplit(".", 1)[0] for name in calls) == ["flash_attention_bwd", "flash_attention_fwd"], calls
    scope_of = _adaptor(family).scopes_of(text)
    assert {scope_of.get(name) for name in calls} == {"lakesoul.lm.attn"}


def test_lfm2_step_carries_its_two_scopes_on_a_v5e(lfm2_step_compiled_for_a_v5e):
    """``conv_step_share_pct`` and ``mlp_step_share_pct`` sum the self time of
    the instructions the adaptor's scope map charges to these exact names;
    attention, routing, experts and head stay under the scopes the other
    family's readers know, and the step has no scope that no reader sums."""
    scope_of = _adaptor("lfm2_moe_clm").scopes_of(lfm2_step_compiled_for_a_v5e)
    shared = set(LM_SCOPES) - {"lakesoul.lm.gdn", "lakesoul.lm.moe.shared"}
    assert set(scope_of.values()) == shared | set(LFM2_SCOPES)
    dots = re.findall(r"^\s*%?([\w.\-]+) = \S+ (?:convolution|fusion)\(", lfm2_step_compiled_for_a_v5e, re.MULTILINE)
    for scope in LFM2_SCOPES:  # products among them: forward, rematerialised and backward
        assert sum(scope_of.get(name) == scope for name in dots) >= 3, scope


@pytest.mark.parametrize("scope", sorted(LFM2_SCOPES))
def test_lfm2_scope_names_the_share_readers_search_for(scope):
    from lakesoul_tpu.models.train import make_lm_train_state, make_lm_train_step
    from lakesoul_tpu.parallel.mesh import make_mesh

    plan = make_mesh(jax.devices()[:1], dp=1, tp=1, sp=1)
    cfg = _lfm2_cfg()
    params, opt_state, tx, shardings = make_lm_train_state(cfg, plan)
    ids = jnp.zeros((2, 16), jnp.int32)
    text = make_lm_train_step(cfg, plan, tx, shardings).lower(params, opt_state, ids, ids).as_text(debug_info=True)
    assert "module @jit_train_step " in text
    assert f"{scope}/" in text or f"{scope})" in text or f'{scope}"' in text
    reader, constant = LFM2_SCOPES[scope]
    with open(os.path.join(REPO, "benchmarks", "chip", reader)) as f:
        assert constant in f.read()
    with open(os.path.join(REPO, "benchmarks", "chip", "consumers", "lfm2_moe_clm.py")) as f:
        assert 'STEP_MODULE = "jit_train_step"' in f.read()


LFM2_READER_CHECKS = [
    "scope_shares_of_a_hand_step", "scope_readers_give_nothing_without_their_scope",
    "scopes_of_reads_the_two_new_scopes", "bias_moved_of_hand_counts", "bias_moved_gives_nothing_without_the_series",
]


@pytest.mark.parametrize("check", LFM2_READER_CHECKS)
def test_lfm2_readers(check):
    """The three readers the LFM2-MoE cell added through their own self-test,
    and the series one of them divides under the name the LM step feeds."""
    import importlib.util

    from lakesoul_tpu.models.train import MOE_ASSIGNMENTS_FAMILY

    spec = importlib.util.spec_from_file_location(
        "chipbench_selftest_lfm2_readers", os.path.join(REPO, "benchmarks", "chip", "selftest", "lfm2_readers.py")
    )
    selftest = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(selftest)
    assert [t.__name__ for t in selftest.TESTS] == ["test_" + name for name in LFM2_READER_CHECKS]
    getattr(selftest, "test_" + check)()
    assert selftest.FAMILY == MOE_ASSIGNMENTS_FAMILY
    with open(os.path.join(REPO, "benchmarks", "chip", "layer_metrics", "moe_bias_moved_pct.py")) as f:
        assert 'kind="bias_moved"' in f.read()
    with open(os.path.join(REPO, "lakesoul_tpu", "models", "train.py")) as f:
        assert '{"kind": "bias_moved"}' in f.read()


# -------------------------------------------- the third causal-LM family

GLM_SCOPES = {
    "lakesoul.lm.mla": ("layer_metrics/mla_step_share_pct.py", 'SCOPE = "mla"'),
    "lakesoul.lm.mtp": ("layer_metrics/mtp_step_share_pct.py", 'SCOPE = "mtp"'),
}


def _glm_cfg():
    from lakesoul_tpu.models.glm4_moe_lite import Glm4MoeLiteConfig

    return Glm4MoeLiteConfig(  # two heads of the published 192 + 64 | 256: the mixers take their kernels at 128 tokens
        vocab_size=64, hidden_size=256, num_hidden_layers=2, first_k_dense_replace=1, intermediate_size=48,
        num_attention_heads=2, q_lora_rank=24, kv_lora_rank=16, n_routed_experts=8, num_experts_per_tok=2,
        moe_intermediate_size=16, experts_held=(0, 4),
    )


@pytest.fixture(scope="module")
def glm_step_compiled_for_a_v5e() -> str:
    """Two layers and the prediction module."""
    return _family_step_compiled_for_a_v5e(_glm_cfg())


def test_glm_step_charges_the_module_whole_and_the_main_stack_by_its_innermost_scope(glm_step_compiled_for_a_v5e):
    """Three latent-attention mixers (two layers and the module's), each with
    the forward and the backward kernel once: ``flash_attention_fwd.<n>`` and
    ``flash_attention_bwd.<n>`` in a device trace.  The adaptor's scope map
    charges the main stack's to ``lakesoul.lm.attn`` and the module's to
    ``lakesoul.lm.mtp``, and the step has no scope that no reader sums."""
    text = glm_step_compiled_for_a_v5e
    calls = [name for name in _kernel_calls(text) if name.startswith("flash_attention")]
    assert sorted(name.rsplit(".", 1)[0] for name in calls) == ["flash_attention_bwd"] * 3 + ["flash_attention_fwd"] * 3
    scope_of = _adaptor("glm4_moe_lite_clm").scopes_of(text)
    charged = sorted(scope_of.get(name) for name in calls)
    assert charged == ["lakesoul.lm.attn"] * 4 + ["lakesoul.lm.mtp"] * 2, charged
    shared = set(LM_SCOPES) - {"lakesoul.lm.gdn"}
    assert set(scope_of.values()) == shared | set(GLM_SCOPES) | {"lakesoul.lm.mlp"}
    # by the other adaptors' innermost rule nothing would be the module's but its norms and ``eh_proj``
    innermost = _adaptor("qwen3_next_clm").scopes_of(text)
    assert {innermost.get(name) for name in calls} == {"lakesoul.lm.attn"}
    moved = {name for name, scope in scope_of.items() if scope == "lakesoul.lm.mtp" and innermost.get(name) != scope}
    assert {innermost[name] for name in moved} >= {
        "lakesoul.lm.attn", "lakesoul.lm.mla", "lakesoul.lm.moe.experts", "lakesoul.lm.head"
    }
    dots = re.findall(r"^\s*%?([\w.\-]+) = \S+ (?:convolution|fusion)\(", text, re.MULTILINE)
    for scope in GLM_SCOPES:  # products among them: forward, rematerialised and backward
        assert sum(scope_of.get(name) == scope for name in dots) >= 3, scope


@pytest.mark.parametrize("scope", sorted(GLM_SCOPES))
def test_glm_scope_names_the_share_readers_search_for(scope):
    from lakesoul_tpu.models import causal_lm
    from lakesoul_tpu.models.train import make_lm_train_state, make_lm_train_step
    from lakesoul_tpu.parallel.mesh import make_mesh

    plan = make_mesh(jax.devices()[:1], dp=1, tp=1, sp=1)
    cfg = _glm_cfg()
    params, opt_state, tx, shardings = make_lm_train_state(cfg, plan)
    ids = jnp.zeros((2, 16), jnp.int32)
    text = make_lm_train_step(cfg, plan, tx, shardings).lower(params, opt_state, ids, ids).as_text(debug_info=True)
    assert "module @jit_train_step " in text
    assert f"{scope}/" in text or f"{scope})" in text or f'{scope}"' in text
    assert scope in (causal_lm.MLA_SCOPE, causal_lm.MTP_SCOPE)
    reader, constant = GLM_SCOPES[scope]
    with open(os.path.join(REPO, "benchmarks", "chip", reader)) as f:
        assert constant in f.read()
    with open(os.path.join(REPO, "benchmarks", "chip", "consumers", "glm4_moe_lite_clm.py")) as f:
        adaptor = f.read()
    assert 'STEP_MODULE = "jit_train_step"' in adaptor and f'MTP_SCOPE = "{causal_lm.MTP_SCOPE}"' in adaptor


GLM_READER_CHECKS = [
    "the_module_is_charged_wherever_its_scope_stands", "scope_shares_of_a_hand_step",
    "scope_readers_give_nothing_without_their_scope", "head_positions_of_hand_counts",
    "head_positions_give_nothing_without_the_series",
]


@pytest.mark.parametrize("check", GLM_READER_CHECKS)
def test_glm_readers(check):
    """The three readers the GLM-4.7-Flash cell added and its adaptor's
    charging rule through their own self-test, and the series one of them
    divides under the name the LM step feeds."""
    import importlib.util

    from lakesoul_tpu.models.train import HEAD_POSITIONS_FAMILY

    spec = importlib.util.spec_from_file_location(
        "chipbench_selftest_glm4_readers", os.path.join(REPO, "benchmarks", "chip", "selftest", "glm4_readers.py")
    )
    selftest = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(selftest)
    assert [t.__name__ for t in selftest.TESTS] == ["test_" + name for name in GLM_READER_CHECKS]
    getattr(selftest, "test_" + check)()
    assert selftest.FAMILY == HEAD_POSITIONS_FAMILY
    with open(os.path.join(REPO, "benchmarks", "chip", "layer_metrics", "mtp_head_positions_pct.py")) as f:
        assert 'kind="mtp"' in f.read()
    with open(os.path.join(REPO, "lakesoul_tpu", "models", "train.py")) as f:
        assert '{"kind": "mtp"}' in f.read()


# ------------------------------------------- the fourth causal-LM family

AFMOE_SCOPES = {
    "lakesoul.lm.swa": ("layer_metrics/swa_step_share_pct.py", 'SCOPE = "swa"'),
}


def _afmoe_cfg(**changed):
    from lakesoul_tpu.models.afmoe import AfmoeConfig

    # the held cut's pattern (a dense window layer, then window, full, window, window), four heads of 128 on one
    # key-value head: every mixer takes its kernels (the flash pair and the operand pair) at 128 tokens, the
    # window layers under a window of 64
    sizes = dict(
        vocab_size=64, hidden_size=256, num_hidden_layers=5, num_dense_layers=1, intermediate_size=48,
        layer_types=("sliding_attention", "sliding_attention", "full_attention", "sliding_attention", "sliding_attention"),
        num_attention_heads=4, num_key_value_heads=1, head_dim=128, sliding_window=64, num_experts=8,
        num_experts_per_tok=2, moe_intermediate_size=16, experts_held=(0, 4),
    )
    return AfmoeConfig(**(sizes | changed))


@pytest.fixture(scope="module")
def afmoe_step_compiled_for_a_v5e() -> str:
    return _family_step_compiled_for_a_v5e(_afmoe_cfg())


def test_afmoe_step_holds_the_kernels_under_swa_and_attn_and_every_scope_a_reader_sums(afmoe_step_compiled_for_a_v5e):
    """Five attention layers, each with the forward and the backward kernel
    once (``flash_attention_fwd.<n>`` and ``flash_attention_bwd.<n>`` in a
    device trace): four under ``lakesoul.lm.swa`` and one under
    ``lakesoul.lm.attn`` by the adaptor's scope map, which is how
    ``swa_step_share_pct`` and the two roofline readers tell a window layer
    from a full one.  The four norms and the gate bring no scope of their own:
    the step's scopes are the thirteen's subset a reader sums."""
    text = afmoe_step_compiled_for_a_v5e
    calls = [name for name in _kernel_calls(text) if name.startswith("flash_attention")]
    assert sorted(name.rsplit(".", 1)[0] for name in calls) == ["flash_attention_bwd"] * 5 + ["flash_attention_fwd"] * 5
    scope_of = _adaptor("afmoe_clm").scopes_of(text)
    for kernel in ("flash_attention_fwd", "flash_attention_bwd"):
        charged = sorted(scope_of.get(name) for name in calls if name.startswith(kernel))
        assert charged == ["lakesoul.lm.attn"] + ["lakesoul.lm.swa"] * 4, charged
    # the operand kernels at a head of 128 (``attn_operands_fwd.<n>``, ``attn_operands_bwd.<n>``): the forward
    # one twice a layer (forward, and the row's rematerialisation), the backward one once, under the mixer's
    # scope; no name of theirs holds ``flash_attention_``, which is what the two roofline readers search for
    operands = [name for name in _kernel_calls(text) if name.startswith("attn_operands")]
    assert sorted(name.rsplit(".", 1)[0] for name in operands) == ["attn_operands_bwd"] * 5 + ["attn_operands_fwd"] * 10
    charged = sorted(scope_of.get(name) for name in operands if name.startswith("attn_operands_bwd"))
    assert charged == ["lakesoul.lm.attn"] + ["lakesoul.lm.swa"] * 4, charged
    assert sorted(_kernel_calls(text)) == sorted(
        calls + operands + [n for n in _kernel_calls(text) if n.split("_")[0] in ("take", "put", "expert")]
    )
    shared = set(LM_SCOPES) - {"lakesoul.lm.gdn"}
    assert set(scope_of.values()) == shared | set(AFMOE_SCOPES) | {"lakesoul.lm.mlp"}
    assert len(set(LM_SCOPES) | set(LFM2_SCOPES) | set(GLM_SCOPES) | set(AFMOE_SCOPES)) == 13
    dots = re.findall(r"^\s*%?([\w.\-]+) = \S+ (?:convolution|fusion)\(", text, re.MULTILINE)
    assert sum(scope_of.get(name) == "lakesoul.lm.swa" for name in dots) >= 3  # forward, rematerialised, backward


@pytest.mark.parametrize("scope", sorted(AFMOE_SCOPES))
def test_afmoe_scope_names_the_share_readers_search_for(scope):
    from lakesoul_tpu.models import afmoe
    from lakesoul_tpu.models.train import make_lm_train_state, make_lm_train_step
    from lakesoul_tpu.parallel.mesh import make_mesh

    plan = make_mesh(jax.devices()[:1], dp=1, tp=1, sp=1)
    cfg = _afmoe_cfg()
    params, opt_state, tx, shardings = make_lm_train_state(cfg, plan)
    ids = jnp.zeros((2, 16), jnp.int32)
    text = make_lm_train_step(cfg, plan, tx, shardings).lower(params, opt_state, ids, ids).as_text(debug_info=True)
    assert "module @jit_train_step " in text
    assert f"{scope}/" in text or f"{scope})" in text or f'{scope}"' in text
    assert scope == afmoe.SWA_SCOPE
    reader, constant = AFMOE_SCOPES[scope]
    with open(os.path.join(REPO, "benchmarks", "chip", reader)) as f:
        assert constant in f.read()
    with open(os.path.join(REPO, "benchmarks", "chip", "consumers", "afmoe_clm.py")) as f:
        assert 'STEP_MODULE = "jit_train_step"' in f.read()
    with open(os.path.join(REPO, "benchmarks", "chip", "chipbench", "flash_roofline.py")) as f:
        assert 'scopes.PREFIX + "swa", scopes.PREFIX + "attn"' in f.read() and scope == "lakesoul.lm." + "swa"


def test_attn_key_tiles_counter_is_the_tile_tables(monkeypatch):
    """``lakesoul_train_attn_key_tiles_total{kind="run"|"causal"}`` after one
    step against ``_flash_pairs``' own tables: 384 tokens as 3 x 3 tiles of
    128 (the kernels in the interpreter), a window of 100, one window layer
    and one full: 5 of a causal list's 6 steps and all 6, over 2 rows and 2
    key-value heads.  And the host count at the Trinity-Mini cell's shapes,
    from an abstract trace of the mixers: 280 and 544 steps a key-value head,
    61.2% over four window layers and a full one."""
    from lakesoul_tpu.models import attention
    from lakesoul_tpu.models.train import ATTN_KEY_TILES_FAMILY, make_lm_train_state, make_lm_train_step
    from lakesoul_tpu.obs import registry
    from lakesoul_tpu.parallel.mesh import make_mesh

    def series(kind):
        return registry().snapshot().get(f'{ATTN_KEY_TILES_FAMILY}{{kind="{kind}"}}', 0)

    whole = _afmoe_cfg(
        hidden_size=2048, num_attention_heads=32, num_key_value_heads=4, head_dim=128, sliding_window=2048, dtype="bfloat16"
    )
    weights = jax.eval_shape(whole.init, jax.random.key(0))["layers"]
    rows = jax.ShapeDtypeStruct((2, 8192, 2048), jnp.bfloat16)
    counts = {kind: attention.mixer_counts(whole.mixer(kind)[0], rows, weights[layer][kind])
              for kind, layer in (("swa", 0), ("attn", 2))}
    found = {kind: (n["attn_tiles_run"], n["attn_tiles_causal"]) for kind, n in counts.items()}
    assert found == {"swa": (2 * 4 * 280, 2 * 4 * 544), "attn": (2 * 4 * 544, 2 * 4 * 544)}
    run, causal = (4 * found["swa"][i] + found["attn"][i] for i in (0, 1))
    assert round(100 * run / causal, 1) == 61.2

    monkeypatch.setattr(attention, "FLASH_KEYS", 128)
    monkeypatch.setattr(attention, "FLASH_ROWS", 256)
    cfg = _afmoe_cfg(
        hidden_size=64, num_hidden_layers=2, layer_types=("sliding_attention", "full_attention"),
        num_attention_heads=4, num_key_value_heads=2, sliding_window=100,
    )
    assert len(attention._flash_pairs(384, 128, 128, 100)) == 5 and len(attention._flash_pairs(384, 128, 128)) == 6
    plan = make_mesh(jax.devices()[:1], dp=1, tp=1, sp=1)
    params, opt_state, tx, shardings = make_lm_train_state(cfg, plan)
    step = make_lm_train_step(cfg, plan, tx, shardings)
    before = {kind: series(kind) for kind in ("run", "causal")}
    ids = jnp.zeros((2, 384), jnp.int32)
    step(params, opt_state, ids, ids)
    assert (step.counts()["attn_tiles_run"], step.counts()["attn_tiles_causal"]) == (2 * 2 * (5 + 6), 2 * 2 * (6 + 6))
    assert {kind: series(kind) - before[kind] for kind in before} == {"run": 44, "causal": 48}
    with open(os.path.join(REPO, "benchmarks", "chip", "layer_metrics", "attn_tiles_run_pct.py")) as f:
        assert f'COUNTER = "{ATTN_KEY_TILES_FAMILY}"' in f.read()


@pytest.mark.parametrize("check", ["share_of_hand_counts", "nothing_without_the_series"])
@pytest.mark.parametrize("reader", ["attn_operands_fused_pct", "attn_out_token_major_pct", "attn_pair_maps_run_pct"])
def test_attn_row_counter_readers(reader, check):
    """``attn_operands_fused_pct``, ``attn_out_token_major_pct`` and
    ``attn_pair_maps_run_pct`` through
    their own self-tests, and the series each reads under the name the LM step
    feeds."""
    import importlib.util

    from lakesoul_tpu.models import train

    selftest_file, family = {
        "attn_operands_fused_pct": ("operand_rows.py", train.ATTN_OPERAND_ROWS_FAMILY),
        "attn_out_token_major_pct": ("output_rows.py", train.ATTN_OUTPUT_ROWS_FAMILY),
        "attn_pair_maps_run_pct": ("pair_maps.py", train.ATTN_PAIR_TILES_FAMILY),
    }[reader]
    spec = importlib.util.spec_from_file_location(
        "chipbench_selftest_" + selftest_file[:-3], os.path.join(REPO, "benchmarks", "chip", "selftest", selftest_file)
    )
    selftest = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(selftest)
    assert [t.__name__ for t in selftest.TESTS] == ["test_share_of_hand_counts", "test_nothing_without_the_series"]
    getattr(selftest, "test_" + check)()
    assert selftest.FAMILY == family
    with open(os.path.join(REPO, "benchmarks", "chip", "layer_metrics", reader + ".py")) as f:
        assert f'COUNTER = "{family}"' in f.read()


def test_attn_pair_key_tiles_counter_is_two_maps_a_pair(monkeypatch):
    """``lakesoul_train_attn_pair_key_tiles_total{kind="run"|"required"}``
    after one Phi-4-mini-flash step at test widths: four heads of 64 on two
    key-value heads (one key-value pair of two query pairs), 384 tokens as
    3 x 3 tiles of 128 (the kernels in the interpreter), a window layer under
    a window of 100 (5 of a causal list's 6 steps), a full and a cross layer,
    2 rows: two key-value heads a pair run what two maps a pair require, and
    every step of the attention kernels is a pair's.  And the host count at
    the cell's shapes, from an abstract trace of the mixers: 20 key-value
    heads a row, 31 steps each under the window of 512 and 136 without."""
    from lakesoul_tpu.models import attention, causal_lm, phi4flash
    from lakesoul_tpu.models.train import (
        ATTN_KEY_TILES_FAMILY,
        ATTN_PAIR_TILES_FAMILY,
        make_lm_train_state,
        make_lm_train_step,
    )
    from lakesoul_tpu.obs import registry
    from lakesoul_tpu.parallel.mesh import make_mesh

    def series():
        found = registry().snapshot()
        return {kind: found.get(f'{ATTN_PAIR_TILES_FAMILY}{{kind="{kind}"}}', 0) for kind in ("run", "required")} | {
            "all": found.get(f'{ATTN_KEY_TILES_FAMILY}{{kind="run"}}', 0)}

    whole = phi4flash.Phi4FlashConfig(layers_held=(15, 16, 17, 18, 19))
    assert whole.layer_kinds() == ("swa", "ssm", "attn", "gmu", "xattn")
    shapes = jax.eval_shape(whole.init, jax.random.key(0))
    weights = {kind: causal_lm._mixer_weights(shapes["layers"][layer], kind, shapes["buffers"]["layers"][layer])
               for kind, layer in (("swa", 0), ("attn", 2))}
    row = jax.ShapeDtypeStruct((1, 8192, 2560), jnp.bfloat16)
    kv = jax.ShapeDtypeStruct((1, 8192, 20 * 64), jnp.bfloat16)
    swa = attention.mixer_counts(whole.mixer("swa")[0], row, weights["swa"])
    full = attention.mixer_counts(lambda x, p: whole.mixer("attn")[0](x[0], p, x[1]), (row, (kv, kv)), weights["attn"])
    assert (swa["attn_pair_tiles_run"], swa["attn_pair_tiles"], swa["attn_tiles_run"]) == (20 * 31,) * 3
    assert (full["attn_pair_tiles_run"], full["attn_pair_tiles"], full["attn_tiles_run"]) == (20 * 136,) * 3

    monkeypatch.setattr(attention, "FLASH_KEYS", 128)
    monkeypatch.setattr(attention, "FLASH_ROWS", 256)
    cfg = phi4flash.Phi4FlashConfig(
        vocab_size=64, hidden_size=256, intermediate_size=48, num_attention_heads=4, num_key_value_heads=2,
        mamba_dt_rank=2, mamba_d_state=4, num_hidden_layers=8, layers_held=(3, 4, 5, 6, 7), sliding_window=100,
        dtype="float32",
    )
    assert cfg.layer_kinds() == ("swa", "ssm", "attn", "gmu", "xattn") and cfg.head_dim == 64
    assert attention._flash_tiles(384, 2, 64, 128) == (128, 128)
    plan = make_mesh(jax.devices()[:1], dp=1, tp=1, sp=1)
    params, opt_state, tx, shardings = make_lm_train_state(cfg, plan)
    step = make_lm_train_step(cfg, plan, tx, shardings)
    before = series()
    ids = jnp.zeros((2, 384), jnp.int32)
    step(params, opt_state, ids, ids)
    steps = 2 * 2 * (5 + 6 + 6)  # rows x a pair's two maps x the three layers' lists
    counts = step.counts()
    assert (counts["attn_pair_tiles_run"], counts["attn_pair_tiles"], counts["attn_tiles_run"]) == (steps,) * 3
    assert {kind: n - before[kind] for kind, n in series().items()} == {"run": 68, "required": 68, "all": 68}
    assert ATTN_PAIR_TILES_FAMILY == "lakesoul_train_attn_pair_key_tiles_total"
    with open(os.path.join(REPO, "benchmarks", "chip", "layer_metrics", "attn_pair_maps_run_pct.py")) as f:
        assert f'COUNTER = "{ATTN_PAIR_TILES_FAMILY}"' in f.read()


def test_attn_operand_rows_counter_is_the_rule(monkeypatch):
    """``lakesoul_train_attn_operand_rows_total{path="kernel"|"xla"}`` from a
    step: two rows through a window layer and a full one at a head of 128 (the
    operand kernels, in the interpreter), then the same stack at a head of 64
    (the ``jnp`` lines): the step's softmax-attention layer-rows by the path
    :func:`_operand_tiles` picks, host integers like the tile counts.  And the
    host count at the three cells' shapes that list the metric."""
    from lakesoul_tpu.models import attention
    from lakesoul_tpu.models.train import ATTN_OPERAND_ROWS_FAMILY, make_lm_train_state, make_lm_train_step
    from lakesoul_tpu.obs import registry
    from lakesoul_tpu.parallel.mesh import make_mesh

    def series():
        found = registry().snapshot()
        return {path: found.get(f'{ATTN_OPERAND_ROWS_FAMILY}{{path="{path}"}}', 0) for path in ("kernel", "xla")}

    # Trinity-Mini (both kinds of layer), Qwen3-Next (64 of 256 channels turned), LFM2 (a head of 64)
    assert attention._operand_tiles(8192, 32, 4, 128, 128) == attention._operand_tiles(8192, 32, 4, 128, None) == 512
    assert attention._operand_tiles(8192, 16, 2, 256, 64) is None and attention._operand_tiles(8192, 32, 8, 64, 64) is None
    monkeypatch.setattr(attention, "FLASH_KEYS", 128)
    monkeypatch.setattr(attention, "FLASH_ROWS", 256)
    plan = make_mesh(jax.devices()[:1], dp=1, tp=1, sp=1)
    ids = jnp.zeros((2, 384), jnp.int32)
    for head, want in ((128, {"kernel": 4, "xla": 0}), (64, {"kernel": 0, "xla": 4})):
        cfg = _afmoe_cfg(
            hidden_size=64, num_hidden_layers=2, layer_types=("sliding_attention", "full_attention"),
            num_attention_heads=4, num_key_value_heads=2, head_dim=head, sliding_window=100,
        )
        params, opt_state, tx, shardings = make_lm_train_state(cfg, plan)
        step = make_lm_train_step(cfg, plan, tx, shardings)
        before = series()
        step(params, opt_state, ids, ids)
        counts = step.counts()
        assert {path: counts["attn_operands_" + path] for path in want} == want
        assert {path: n - before[path] for path, n in series().items()} == want


def test_attn_output_rows_counter_is_the_rule(monkeypatch):
    """``lakesoul_train_attn_output_rows_total{layout="tokens"|"heads"}`` from
    a step: two rows through a window layer and a full one at a head of 128
    (the flash kernels write the output token-major, in the interpreter), then
    the same stack at a head of 64 (heads first, the transpose after): the
    step's attention layer-rows by what :func:`_token_major` says, host
    integers like the tile counts.  And the rule at the five cells' shapes."""
    from lakesoul_tpu.models import attention
    from lakesoul_tpu.models.train import ATTN_OUTPUT_ROWS_FAMILY, make_lm_train_state, make_lm_train_step
    from lakesoul_tpu.obs import registry
    from lakesoul_tpu.parallel.mesh import make_mesh

    def series():
        found = registry().snapshot()
        return {layout: found.get(f'{ATTN_OUTPUT_ROWS_FAMILY}{{layout="{layout}"}}', 0) for layout in ("tokens", "heads")}

    # Trinity-Mini, Ouro, GLM (latent attention), Qwen3-Next: heads of one and two lane tiles; LFM2: half a tile
    assert all(attention._token_major(8192, groups, d) for groups, d in ((8, 128), (1, 128), (1, 256), (8, 256)))
    assert not attention._token_major(8192, 4, 64) and attention._flash_tiles(8192, 4, 64) is not None
    assert not attention._token_major(150, 8, 128)  # a row the kernels refuse: the twin writes heads first
    monkeypatch.setattr(attention, "FLASH_KEYS", 128)
    monkeypatch.setattr(attention, "FLASH_ROWS", 256)
    plan = make_mesh(jax.devices()[:1], dp=1, tp=1, sp=1)
    ids = jnp.zeros((2, 384), jnp.int32)
    for head, want in ((128, {"tokens": 4, "heads": 0}), (64, {"tokens": 0, "heads": 4})):
        cfg = _afmoe_cfg(
            hidden_size=64, num_hidden_layers=2, layer_types=("sliding_attention", "full_attention"),
            num_attention_heads=4, num_key_value_heads=2, head_dim=head, sliding_window=100,
        )
        params, opt_state, tx, shardings = make_lm_train_state(cfg, plan)
        step = make_lm_train_step(cfg, plan, tx, shardings)
        before = series()
        step(params, opt_state, ids, ids)
        counts = step.counts()
        assert {layout: counts["attn_out_" + layout] for layout in want} == want
        assert {layout: n - before[layout] for layout, n in series().items()} == want


AFMOE_READER_CHECKS = [
    "window_share_of_a_hand_step", "window_share_gives_nothing_without_its_scope", "tiles_run_of_hand_counts",
    "tiles_run_gives_nothing_without_the_series", "a_window_of_the_row_length_counts_as_the_causal_mask",
    "roofline_shares_of_hand_events", "roofline_readers_give_nothing_without_their_events",
]


@pytest.mark.parametrize("check", AFMOE_READER_CHECKS)
def test_afmoe_readers(check):
    """The four readers the Trinity-Mini cell added and the kernels' cost
    functions through their own self-test, and the series one of them divides
    under the name the LM step feeds."""
    import importlib.util

    from lakesoul_tpu.models.train import ATTN_KEY_TILES_FAMILY

    spec = importlib.util.spec_from_file_location(
        "chipbench_selftest_afmoe_readers", os.path.join(REPO, "benchmarks", "chip", "selftest", "afmoe_readers.py")
    )
    selftest = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(selftest)
    assert [t.__name__ for t in selftest.TESTS] == ["test_" + name for name in AFMOE_READER_CHECKS]
    getattr(selftest, "test_" + check)()
    assert selftest.FAMILY == ATTN_KEY_TILES_FAMILY


# -------------------------------------------- the fifth causal-LM family

OURO_SCOPES = {
    "lakesoul.lm.exit": ("layer_metrics/exit_step_share_pct.py", 'SCOPE = "exit"'),
}
OURO_PASSES, OURO_LAYERS = 3, 2


def _ouro_cfg(**changed):
    from lakesoul_tpu.models.ouro import OuroConfig

    # two layers run three times, two heads of 128 on two key-value heads: every mixer takes its kernels (the
    # flash pair and the operand pair, without head norms) at 128 tokens
    sizes = dict(
        vocab_size=64, hidden_size=256, num_hidden_layers=OURO_LAYERS, intermediate_size=48, num_attention_heads=2,
        num_key_value_heads=2, head_dim=128, total_ut_steps=OURO_PASSES,
    )
    return OuroConfig(**(sizes | changed))


@pytest.fixture(scope="module")
def ouro_step_compiled_for_a_v5e() -> str:
    return _family_step_compiled_for_a_v5e(_ouro_cfg())


def _computation_of(text: str) -> dict[str, str]:
    """{instruction: the computation that holds it} of a compiled module."""
    held, current = {}, None
    for line in text.splitlines():
        if not line.startswith((" ", "\t")):
            opened = _HLO_COMPUTATION.match(line)
            current = opened.group(1) if opened else current
            continue
        found = _HLO_INSTRUCTION.match(line)
        if found:
            held[found.group(1)] = current
    return held


def _loop_bodies(text: str) -> dict[str, str]:
    """{a ``while``'s body computation: the computation that holds the ``while``}."""
    held = _computation_of(text)
    return {body: held[name] for name, body in re.findall(r"^\s*%?([\w.\-]+) = .*? while\(.*?body=%?([\w.\-]+)", text, re.MULTILINE)}


def test_ouro_step_holds_each_layers_kernels_once_inside_the_pass_loops_body(ouro_step_compiled_for_a_v5e):
    """The passes are one loop: the step program holds each layer's body ONCE
    forward and once backward whatever the number of passes, so the flash pair
    stands once a layer (``flash_attention_fwd.<n>``, ``flash_attention_bwd.<n>``)
    and the operand pair beside it (forward twice: the row and its
    rematerialisation), every one under ``lakesoul.lm.attn`` by the adaptor's
    scope map and every one inside a ``while`` body nested in the pass loop's
    (the loop over rows inside the loop over passes): which is what
    ``flash_fwd_roofline_pct``, ``flash_bwd_roofline_pct`` and
    ``attn_step_share_pct`` read in the cell.  The step's scopes are the
    fourteen's subset a reader sums, ``lakesoul.lm.exit`` among them."""
    text = ouro_step_compiled_for_a_v5e
    calls = _kernel_calls(text)
    names = sorted(name.rsplit(".", 1)[0] for name in calls)
    assert names == (["attn_operands_bwd"] * OURO_LAYERS + ["attn_operands_fwd"] * 2 * OURO_LAYERS
                     + ["flash_attention_bwd"] * OURO_LAYERS + ["flash_attention_fwd"] * OURO_LAYERS), names
    scope_of = _adaptor("ouro_clm").scopes_of(text)
    assert {scope_of.get(name) for name in calls} == {"lakesoul.lm.attn"}
    held, bodies = _computation_of(text), _loop_bodies(text)
    for name in calls:  # a kernel's computation is a loop's body, and that loop stands inside another loop's body
        inner = held[name]
        assert inner in bodies and bodies[inner] in bodies, (name, inner)
    shared = set(LM_SCOPES) - {"lakesoul.lm.gdn", "lakesoul.lm.moe.route", "lakesoul.lm.moe.experts", "lakesoul.lm.moe.shared"}
    assert set(scope_of.values()) == shared | set(OURO_SCOPES) | {"lakesoul.lm.mlp"}
    assert len(set(LM_SCOPES) | set(LFM2_SCOPES) | set(GLM_SCOPES) | set(AFMOE_SCOPES) | set(OURO_SCOPES)) == 14
    assert sum(scope == "lakesoul.lm.exit" for scope in scope_of.values()) >= 3  # the gate, the distribution, their transpose


@pytest.mark.parametrize("scope", sorted(OURO_SCOPES))
def test_ouro_scope_names_the_share_readers_search_for(scope):
    from lakesoul_tpu.models import causal_lm
    from lakesoul_tpu.models.train import make_lm_train_state, make_lm_train_step
    from lakesoul_tpu.parallel.mesh import make_mesh

    plan = make_mesh(jax.devices()[:1], dp=1, tp=1, sp=1)
    cfg = _ouro_cfg()
    params, opt_state, tx, shardings = make_lm_train_state(cfg, plan)
    ids = jnp.zeros((2, 16), jnp.int32)
    text = make_lm_train_step(cfg, plan, tx, shardings).lower(params, opt_state, ids, ids).as_text(debug_info=True)
    assert "module @jit_train_step " in text
    assert f"{scope}/" in text or f"{scope})" in text or f'{scope}"' in text
    assert scope == causal_lm.EXIT_SCOPE
    reader, constant = OURO_SCOPES[scope]
    with open(os.path.join(REPO, "benchmarks", "chip", reader)) as f:
        assert constant in f.read()
    with open(os.path.join(REPO, "benchmarks", "chip", "consumers", "ouro_clm.py")) as f:
        assert 'STEP_MODULE = "jit_train_step"' in f.read()


def test_loop_series_are_the_steps_shapes():
    """``lakesoul_train_loop_layer_passes_total{kind="run"|"layers"}``,
    ``lakesoul_train_head_positions_total{kind="loop"|"all"}`` and the gauge
    ``lakesoul_train_loop_exit_mass{pass=...}`` after two steps of two rows:
    rows x layers x passes over rows x layers, the labelled positions of the
    passes before the last over those of every pass, a distribution; the
    attention counters times the passes; under the names the three readers
    of the cell divide."""
    from lakesoul_tpu.models.train import (
        ATTN_KEY_TILES_FAMILY,
        ATTN_OPERAND_ROWS_FAMILY,
        HEAD_POSITIONS_FAMILY,
        LOOP_EXIT_MASS_FAMILY,
        LOOP_LAYER_PASSES_FAMILY,
        make_lm_train_state,
        make_lm_train_step,
    )
    from lakesoul_tpu.models import attention
    from lakesoul_tpu.obs import registry
    from lakesoul_tpu.parallel.mesh import make_mesh

    def series():
        snapshot = registry().snapshot()
        return {key: value for key, value in snapshot.items() if key.split("{")[0] in (
            LOOP_LAYER_PASSES_FAMILY, HEAD_POSITIONS_FAMILY, ATTN_KEY_TILES_FAMILY, ATTN_OPERAND_ROWS_FAMILY)}

    plan = make_mesh(jax.devices()[:1], dp=1, tp=1, sp=1)
    cfg = _ouro_cfg()
    params, opt_state, tx, shardings = make_lm_train_state(cfg, plan)
    step = make_lm_train_step(cfg, plan, tx, shardings)
    rows, steps = 2, 2
    ids = jnp.zeros((rows, LM_TOKENS), jnp.int32)
    labels = jnp.concatenate([ids[:, 1:], jnp.full((rows, 1), -100, jnp.int32)], axis=1)
    before = series()
    for _ in range(steps):
        params, opt_state, _loss = step(params, opt_state, ids, labels)
    moved = {key: value - before.get(key, 0) for key, value in series().items()}
    layer_rows, labelled = steps * rows * OURO_LAYERS, steps * rows * (LM_TOKENS - 1)
    tiles = attention.key_tile_steps(LM_TOKENS, 1, 128)[0] * 2  # two key-value heads a layer-row
    want = {
        f'{LOOP_LAYER_PASSES_FAMILY}{{kind="run"}}': OURO_PASSES * layer_rows,
        f'{LOOP_LAYER_PASSES_FAMILY}{{kind="layers"}}': layer_rows,
        f'{HEAD_POSITIONS_FAMILY}{{kind="all"}}': OURO_PASSES * labelled,
        f'{HEAD_POSITIONS_FAMILY}{{kind="loop"}}': (OURO_PASSES - 1) * labelled,
        f'{HEAD_POSITIONS_FAMILY}{{kind="mtp"}}': 0,
        f'{ATTN_KEY_TILES_FAMILY}{{kind="run"}}': OURO_PASSES * layer_rows * tiles,
        f'{ATTN_KEY_TILES_FAMILY}{{kind="causal"}}': OURO_PASSES * layer_rows * tiles,
        f'{ATTN_OPERAND_ROWS_FAMILY}{{path="kernel"}}': OURO_PASSES * layer_rows,
        f'{ATTN_OPERAND_ROWS_FAMILY}{{path="xla"}}': 0,
    }
    assert {key: moved.get(key) for key in want} == want
    mass = [registry().snapshot()[f'{LOOP_EXIT_MASS_FAMILY}{{pass="{t + 1}"}}'] for t in range(OURO_PASSES)]
    assert abs(sum(mass) - 1.0) < 1e-4 and all(0.0 < m < 1.0 for m in mass), mass
    for reader, constant in (("loop_passes_per_layer", f'COUNTER = "{LOOP_LAYER_PASSES_FAMILY}"'),
                             ("loop_head_positions_pct", f'COUNTER = "{HEAD_POSITIONS_FAMILY}"')):
        with open(os.path.join(REPO, "benchmarks", "chip", "layer_metrics", reader + ".py")) as f:
            assert constant in f.read()


OURO_READER_CHECKS = [
    "the_looped_steps_shares_are_the_step_whole", "exit_share_gives_nothing_without_its_scope",
    "passes_per_layer_of_hand_counts", "passes_per_layer_gives_nothing_without_the_series",
    "loop_head_positions_of_hand_counts", "loop_head_positions_gives_nothing_without_the_series",
    "the_kernels_inside_the_pass_loops_body_are_charged_to_the_mixers_scope",
    "the_flash_rooflines_read_this_cells_shape",
]


@pytest.mark.parametrize("check", OURO_READER_CHECKS)
def test_ouro_readers(check):
    """The three readers the looped cell added, the scope charging inside the
    pass loop's body and the accepted rooflines at the cell's shape, through
    their own self-test, and the series two of them divide under the names the
    LM step feeds."""
    import importlib.util

    from lakesoul_tpu.models.train import HEAD_POSITIONS_FAMILY, LOOP_LAYER_PASSES_FAMILY

    spec = importlib.util.spec_from_file_location(
        "chipbench_selftest_ouro_readers", os.path.join(REPO, "benchmarks", "chip", "selftest", "ouro_readers.py")
    )
    selftest = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(selftest)
    assert [t.__name__ for t in selftest.TESTS] == ["test_" + name for name in OURO_READER_CHECKS]
    getattr(selftest, "test_" + check)()
    assert (selftest.PASSES, selftest.HEAD) == (LOOP_LAYER_PASSES_FAMILY, HEAD_POSITIONS_FAMILY)


PHI4FLASH_READER_CHECKS = [
    "the_hybrid_steps_shares_are_the_step_whole", "the_new_shares_give_nothing_without_their_scopes",
    "the_scans_required_work", "the_scan_rooflines_of_hand_events", "scan_kernel_share_of_hand_counts",
    "shared_reads_of_hand_counts", "the_adaptors_operations_a_row", "the_scan_kernels_are_charged_to_the_mamba_scope",
]


@pytest.mark.parametrize("check", PHI4FLASH_READER_CHECKS)
def test_phi4flash_readers(check):
    """The six readers the decoder-hybrid-decoder cell added, the scan's
    required work, the adaptor's operation count and the scan kernels' scope,
    through their own self-test, and the series two of them read under the
    names the LM step feeds."""
    import importlib.util

    from lakesoul_tpu.models.train import SHARED_READS_FAMILY, SSM_SCAN_ROWS_FAMILY

    spec = importlib.util.spec_from_file_location(
        "chipbench_selftest_phi4flash_readers",
        os.path.join(REPO, "benchmarks", "chip", "selftest", "phi4flash_readers.py"),
    )
    selftest = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(selftest)
    assert [t.__name__ for t in selftest.TESTS] == ["test_" + name for name in PHI4FLASH_READER_CHECKS]
    getattr(selftest, "test_" + check)()
    assert (selftest.SCAN_ROWS, selftest.SHARED_READS) == (SSM_SCAN_ROWS_FAMILY, SHARED_READS_FAMILY)
    for reader, family in (("ssm_scan_kernel_pct", SSM_SCAN_ROWS_FAMILY), ("shared_reads_step", SHARED_READS_FAMILY)):
        with open(os.path.join(REPO, "benchmarks", "chip", "layer_metrics", reader + ".py")) as f:
            assert f'COUNTER = "{family}"' in f.read()


# ---------------------------------------- the step named whole (PR 38)

_HLO_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%?([\w.\-]+)\s.*\{\s*$")
_HLO_INSTRUCTION = re.compile(r"^\s*(?:ROOT\s+)?%?([\w.\-]+)\s*=\s*(?:\(.*?\)|\S+)\s+([\w\-]+)\(")
_HLO_CALLED = re.compile(r"(?:calls|to_apply)=%?([\w.\-]+)")
_NO_EVENT = {"parameter", "tuple", "get-tuple-element", "bitcast", "constant"}


def _unscoped_written_by_the_program(text: str, scope_of: dict) -> list[str]:
    """The ``op_name`` of every instruction of a compiled module that a device trace shows as an
    event of its own (one of a computation that no fusion and no reduction calls), that carries the
    program's metadata (``op_name="jit(step)/..."``) and that the scope map charges to nothing.
    What the compiler inserts bears no such ``op_name`` (copies between memory spaces, async slices,
    layout copies; a parameter's copy is named after the parameter) and is not the program's to name."""
    computations: dict[str, list] = {}
    called, current = set(), None
    for line in text.splitlines():
        if not line.startswith((" ", "\t")):
            opened = _HLO_COMPUTATION.match(line)
            current = opened.group(1) if opened else current
            computations.setdefault(current, [])
            continue
        found = _HLO_INSTRUCTION.match(line)
        if found and current is not None:
            computations[current].append((found.group(1), found.group(2), line))
            if found.group(2) not in ("while", "conditional", "call"):  # their computations run as events
                called.update(_HLO_CALLED.findall(line))
    out = []
    for name, instructions in computations.items():
        if name in called:
            continue
        for instruction, opcode, line in instructions:
            op_name = re.search(r'op_name="(jit\([^"]*)"', line)
            if op_name and opcode not in _NO_EVENT and instruction not in scope_of:
                out.append(op_name.group(1))
    return out


# family → (its step compiled for a v5e, the operations the program wrote into it that stand under no scope)
UNSCOPED = {
    "qwen3_next_clm": ("lm_step_compiled_for_a_v5e", 14),
    "lfm2_moe_clm": ("lfm2_step_compiled_for_a_v5e", 4),
    "glm4_moe_lite_clm": ("glm_step_compiled_for_a_v5e", 0),
    # the operand kernels read the positions as two whole-head tables, ``[cos | cos]`` and ``[-sin | sin]``: a
    # negation and two concatenations more than the two angle constants the ``jnp`` lines had; 10 while the gate's
    # product ran over ``[.., heads, D]`` arrays and the loop over rows hoisted each layer's ``w_gate`` cast to
    # the top of the step: over ``[.., heads x D]`` the cast stays in the mixer, under its scope
    "afmoe_clm": ("afmoe_step_compiled_for_a_v5e", 5),
}


@pytest.mark.parametrize("family", sorted(UNSCOPED))
def test_the_program_writes_nothing_more_into_the_step_under_no_scope(family, request):
    """At the values found.  What is left is computed from weights and positions alone (a norm's
    ``1 + w``, the rotary angles and ``theta ** ...``, the DeltaNet decay's constants): JAX's partial
    evaluation of ``jax.checkpoint`` and ``lax.map`` under ``value_and_grad`` stages such an operation
    out at the top of the step, where the scope it was written under no longer stands; kilobytes each
    (``PERF.md`` section 5).  The parent of the PR that named the optimizer, the embedding, the loss's
    loop, the checkpoints' own copies and the residual adds has over a hundred in each step.  A PR
    that drops a scope, or writes an operation into the step under none, fails here on a CPU and
    shows in no ledger."""
    fixture, bound = UNSCOPED[family]
    text = request.getfixturevalue(fixture)
    scope_of = _adaptor(family).scopes_of(text)
    unscoped = _unscoped_written_by_the_program(text, scope_of)
    assert len(unscoped) <= bound, sorted(unscoped)
    assert all(name.count("/") == 1 or ";" in name for name in unscoped), sorted(unscoped)  # staged out at the top
    # and the two new scopes hold what they are for: the optimizer's updates, the lookup and its scatter-add
    by_scope = {s: sum(v == s for v in scope_of.values()) for s in ("lakesoul.lm.optim", "lakesoul.lm.embed")}
    assert by_scope["lakesoul.lm.optim"] >= 10 and by_scope["lakesoul.lm.embed"] >= 2, by_scope


TRAIN_STEP_READER_CHECKS = [
    "shares_of_a_step_named_whole", "scope_readers_on_the_parent", "recorded_step_from_before_the_scopes",
    "scopes_of_reads_the_two_new_scopes", "new_owners_by_hand", "recorded_trace_with_the_two_owners",
    "span_readers_on_the_parent",
]


@pytest.mark.parametrize("check", TRAIN_STEP_READER_CHECKS)
def test_train_step_readers(check):
    """The six readers that name the step whole through their own self-test, and the scope and span
    names they search for under the constants the program opens them by."""
    import importlib.util

    from lakesoul_tpu.models import causal_lm, train

    spec = importlib.util.spec_from_file_location(
        "chipbench_selftest_train_step_readers",
        os.path.join(REPO, "benchmarks", "chip", "selftest", "train_step_readers.py"),
    )
    selftest = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(selftest)
    assert [t.__name__ for t in selftest.TESTS] == ["test_" + name for name in TRAIN_STEP_READER_CHECKS]
    getattr(selftest, "test_" + check)()
    assert (train.OPTIM_SCOPE, causal_lm.EMBED_SCOPE, causal_lm.HEAD_SCOPE) == (
        "lakesoul.lm.optim", "lakesoul.lm.embed", "lakesoul.lm.head"
    )
    assert (selftest.PLACE, selftest.DISPATCH) == ("lakesoul.train.place", "lakesoul.train.dispatch")


_PASS_LOOP = re.compile(r"jit\(step\)/(?:jvp\(\)|transpose\(jvp\(\)\))/while(?:/(?:body|cond)/(\w+))?$")


def test_ouro_step_under_no_scope_holds_the_staged_constants_and_the_pass_loops_own_bookkeeping(
    ouro_step_compiled_for_a_v5e,
):
    """What the looped program writes into its step under no scope, counted
    as the other families' 14 / 4 / 0 / 5 are.  At the top, 7: the rotary's
    ``theta ** ...``, the two position tables' negation, concatenations and
    cast, and the zeros the pass loop's transpose starts its weight-gradient
    sums from.  And the pass loop's OWN bookkeeping, which no family had: the
    two ``while``s (forward, and the transpose that adds the shared weights'
    gradients up), their counters, the ``dynamic_update_slice`` that stacks
    what the backward pass keeps of a pass and the ``squeeze`` that reads it
    back, the calls around the checkpointed bodies.  Everything a layer, the
    norm between passes, the head and the objective compute stands under its
    scope inside the loop's body (the case above)."""
    text = ouro_step_compiled_for_a_v5e
    scope_of = _adaptor("ouro_clm").scopes_of(text)
    unscoped = _unscoped_written_by_the_program(text, scope_of)
    loops = [name for name in unscoped if _PASS_LOOP.match(name)]
    top = sorted(set(unscoped) - set(loops))
    assert len([name for name in unscoped if name in top]) <= 7 and all(name.count("/") == 1 or ";" in name or
                                                                        name.endswith("/broadcast_in_dim") for name in top), top
    kinds = {_PASS_LOOP.match(name).group(1) for name in loops}
    assert kinds <= {None, "add", "sub", "lt", "closed_call", "dynamic_update_slice", "squeeze"}, kinds
    assert sum(name.endswith("/while") for name in loops) == 2  # the passes forward, and their transpose
    assert 0 < len(loops) <= 36, sorted(loops)
    by_scope = {s: sum(v == s for v in scope_of.values()) for s in ("lakesoul.lm.optim", "lakesoul.lm.embed")}
    assert by_scope["lakesoul.lm.optim"] >= 10 and by_scope["lakesoul.lm.embed"] >= 2, by_scope
