"""Device-mesh construction and sharding plans.

The reference's parallelism axes are storage-level (hash buckets, scan-unit
round robin — SURVEY.md §2.8); the TPU build adds the model-side axes needed
by its north-star consumers (ResNet-50 / BERT training, BASELINE.json):

- ``dp``  — data parallel over batch
- ``tp``  — tensor parallel over heads / ffn
- ``sp``  — sequence parallel (ring attention / Ulysses) for long context
- ``pp``  — pipeline parallel over the layer stack (parallel/pipeline.py)
- ``ep``  — expert parallel: the axis the expert exchange will run over
  (parallel/moe.py holds one chip's share; no program uses ``ep > 1`` yet)

Every mesh carries all five axis names (unused axes have size 1 — free, and
it keeps PartitionSpecs valid across configurations).  Meshes are pure
``jax.sharding.Mesh`` objects; shardings are expressed with
``NamedSharding`` + ``PartitionSpec`` so XLA inserts all collectives over ICI.
"""

from __future__ import annotations

from dataclasses import dataclass

import numpy as np
import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P


@dataclass(frozen=True)
class MeshPlan:
    """A named mesh plus the framework's canonical axis names."""

    mesh: Mesh
    dp: int
    tp: int
    sp: int
    pp: int = 1
    ep: int = 1

    @property
    def axis_names(self):
        return self.mesh.axis_names

    def sharding(self, *spec) -> NamedSharding:
        return NamedSharding(self.mesh, P(*spec))

    @property
    def batch_sharding(self) -> NamedSharding:
        return self.sharding("dp")

    @property
    def replicated(self) -> NamedSharding:
        return self.sharding()


def spec_axes(spec: P) -> tuple[str, ...]:
    """The mesh axes a PartitionSpec splits over, in order."""
    return tuple(
        a for part in spec if part is not None
        for a in (part if isinstance(part, tuple) else (part,))
    )


def _factor(n: int) -> tuple[int, int, int]:
    """Split n devices into (dp, tp, sp) with dp ≥ 2 preserved: data
    parallelism is the default axis for a data-loading framework, so tp/sp
    only peel a factor of 2 each while at least dp=2 remains."""
    dp, tp, sp = n, 1, 1
    if dp % 2 == 0 and dp >= 4:
        dp //= 2
        tp = 2
    if dp % 2 == 0 and dp >= 4:
        dp //= 2
        sp = 2
    return dp, tp, sp


def make_mesh(
    devices=None,
    *,
    dp: int | None = None,
    tp: int | None = None,
    sp: int | None = None,
    pp: int | None = None,
    ep: int | None = None,
) -> MeshPlan:
    """Build a (dp, tp, sp, pp, ep) mesh over the given (default: all)
    devices.  Unspecified axis sizes are inferred from the device count
    (pp/ep default to 1 — they are opted into explicitly)."""
    if devices is None:
        devices = jax.devices()
    n = len(devices)
    pp = pp or 1
    ep = ep or 1
    if dp is None and tp is None and sp is None:
        if n % (pp * ep):
            raise ValueError(f"pp*ep={pp * ep} does not divide {n} devices")
        dp, tp, sp = _factor(n // (pp * ep))
    else:
        dp = dp or 1
        tp = tp or 1
        sp = sp or max(1, n // (dp * tp * pp * ep))
    if dp * tp * sp * pp * ep != n:
        raise ValueError(f"mesh {dp}x{tp}x{sp}x{pp}x{ep} != {n} devices")
    arr = np.array(devices).reshape(dp, tp, sp, pp, ep)
    mesh = Mesh(arr, ("dp", "tp", "sp", "pp", "ep"))
    return MeshPlan(mesh=mesh, dp=dp, tp=tp, sp=sp, pp=pp, ep=ep)
