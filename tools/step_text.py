"""The train step of a benchmark cell as the program a chip would be handed:
the normalised StableHLO text of ``models/train.py: _CountedStep``'s jitted
step, lowered for the ``tpu`` platform on this host (no chip, nothing
compiled or run), and its sha256.

    python tools/step_text.py trinity_mini_clm_pk            # a configuration's steps
    python tools/step_text.py bert_base_mlm_pk.dp4_mor_stream  # one cell's
    python tools/step_text.py --all [--out DIR]              # every trainer cell's

What a refactor of the step is held to: the text at the parent commit and at
the change, byte for byte (run this file from a ``git archive`` of each).  A
step is a configuration at its published widths (``benchmarks/chip/configs``)
on a cell's mesh and batch (``benchmarks/chip/workloads``; both only read);
cells that share all three share a step, so ``--all`` writes eight texts for
the nine trainer cells.  State and step are the ones the configuration's
adaptor builds for a run (``benchmarks/chip/consumers/<consumer>.py: build``,
on this host's CPU devices: gigabytes for a causal LM), the kernels take the
branch the chip takes (``utils/platform.py: on_tpu`` true), and ONE thing is
normalised: a Mosaic kernel's serialized body carries the file names and line
numbers of its Python source, so each body is serialized from its module
re-parsed without locations (``tests/test_step_text.py`` holds both).
"""

from __future__ import annotations

import argparse
import hashlib
import json
import os
import sys

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(REPO, "benchmarks", "chip")
sys.path[:0] = [REPO, BENCH]  # the package, and the benchmark's ``chipbench`` beside its adaptors


def _read(folder: str, name: str) -> dict:
    with open(os.path.join(BENCH, folder, name + ".json")) as f:
        return json.load(f)


def steps_of(names: list[str]) -> dict[str, tuple[str, int, int]]:
    """{label: (configuration, dp, rows a chip)} of the distinct steps behind
    the trainer cells ``names`` match (a cell's name or its configuration's;
    none: every trainer cell)."""
    with open(os.path.join(REPO, "BENCHMARK.json")) as f:
        cells = json.load(f)["workloads"]
    steps = {}
    for cell in cells:
        if names and not {cell["name"], cell["config"]} & set(names):
            continue
        if _read("configs", cell["config"])["kind"] != "trainer":
            continue
        workload = _read("workloads", cell["name"])
        dp, rows = workload["mesh"]["dp"], workload["per_chip_batch"]
        steps[f"{cell['config']}.dp{dp}.rows{rows}"] = (cell["config"], dp, rows)
    return steps


def _kernel_bodies_without_locations():
    """Serialize every Mosaic kernel's body from its module re-parsed without
    debug locations (the one normalisation)."""
    from jax._src import tpu_custom_call
    from jax._src.lib.mlir import ir

    serialize = tpu_custom_call._lower_mosaic_module_to_asm

    def without_locations(module, **kwargs):
        with module.context:
            bare = ir.Module.parse(module.operation.get_asm(enable_debug_info=False))
        return serialize(bare, **kwargs)

    tpu_custom_call._lower_mosaic_module_to_asm = without_locations


def step_text(config: dict, dp: int, rows: int) -> str:
    """The step of ``config`` (a file of ``benchmarks/chip/configs``, read) as
    its consumer's adaptor builds it, lowered for ``rows`` a chip of ``dp``."""
    import jax
    import jax.numpy as jnp

    from chipbench.spec import load_module
    from lakesoul_tpu.parallel.mesh import make_mesh

    plan = make_mesh(jax.devices()[:dp], dp=dp, tp=1, sp=1)
    consumer = load_module(os.path.join(BENCH, "consumers", config["consumer"] + ".py")).build(config, plan, 0)
    ids = jax.ShapeDtypeStruct((rows * dp, config["table"]["seq"]), jnp.int32)
    mask = (jax.ShapeDtypeStruct(ids.shape, jnp.bool_),) if config["consumer"] == "bert_mlm" else ()
    lowered = consumer._step.lower(consumer.params, consumer.opt_state, ids, ids, *mask, lowering_platforms=("tpu",))
    return lowered.as_text()


def main() -> int:
    parser = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    parser.add_argument("names", nargs="*", help="configurations or cells of BENCHMARK.json")
    parser.add_argument("--all", action="store_true", help="every trainer cell's step")
    parser.add_argument("--out", default=None, help="a directory to write <label>.stablehlo.txt into")
    args = parser.parse_args()
    if bool(args.names) == args.all:
        parser.error("name configurations or cells, or pass --all")
    steps = steps_of(args.names)
    if not steps:
        parser.error(f"no trainer cell matches {args.names}")
    os.environ.setdefault("JAX_PLATFORMS", "cpu")
    os.environ.setdefault("XLA_FLAGS", f"--xla_force_host_platform_device_count={max(dp for _, dp, _ in steps.values())}")
    _kernel_bodies_without_locations()
    from lakesoul_tpu.utils import platform

    platform.on_tpu = lambda: True  # the branch the chip takes
    for label, (config, dp, rows) in steps.items():
        text = step_text(_read("configs", config), dp, rows)
        if args.out:
            os.makedirs(args.out, exist_ok=True)
            with open(os.path.join(args.out, label + ".stablehlo.txt"), "w") as f:
                f.write(text)
        print(hashlib.sha256(text.encode()).hexdigest(), len(text), label, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
