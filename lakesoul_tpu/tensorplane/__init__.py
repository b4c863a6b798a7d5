"""Tensor plane — the device-first data plane (ROADMAP item 5).

Four pieces, one goal: rows that are *tensors* (embeddings, token blocks,
image patches) should travel from LSF buffers into a JAX training step
without being re-discovered, re-collated, or re-copied every epoch:

- :mod:`columns` — first-class fixed-shape tensor column declarations:
  ``tensor_field("emb", (16, 16), "float32")`` builds a
  ``fixed_size_list`` field carrying its logical shape in field metadata
  (full-fidelity through the IPC schema the catalog stores; the Spark-JSON
  mirror spells it as an array with ``fixedLength`` — see
  ``meta/entity.py``).  The writer validates every declared column on
  write with typed :class:`~lakesoul_tpu.errors.TensorColumnError`\\ s, so
  a malformed batch dies at the table boundary, not three stages into a
  training run; the collate layer reshapes to the declared shape from a
  spec computed ONCE per loader instead of probing Arrow types per batch.
- :mod:`dlpack` — the hand-off from collated host buffers into jax:
  ``deliver()`` places a batch on the sharding it was given (else the
  default device) and checks that every leaf landed there, and the
  empirical :func:`~lakesoul_tpu.tensorplane.dlpack.device_put_copies`
  probe measures whether ``device_put`` on THIS backend actually copies.
- :mod:`replay` — :class:`~lakesoul_tpu.tensorplane.replay.
  DeviceReplayCache`: an HBM-budgeted residency manager
  (``LAKESOUL_REPLAY_BUDGET_BYTES``) that pins epoch-1's collated,
  device-put shards per device and serves every later epoch straight from
  device memory — zero storage/host/link traffic — with an optional
  seeded on-device permutation per epoch.  Past the budget it spills
  *gracefully*: the typed, metered spill record marks the cache hybrid,
  and epoch ≥ 2 replays the resident prefix then re-streams only the
  tail.
- :mod:`smoke` — the on-chip claim register: every Pallas kernel in the
  repo (enumerated from lakelint's device index, so the register provably
  covers 100%), the multichip shapes, and the tensorplane delivery/replay
  paths, each as one check that takes its sizes and its Pallas mode from
  the caller.  ``chip_smoke.py`` runs it compiled at deployed sizes on the
  chip; tier-1 runs it tiny in the interpreter.
"""

from lakesoul_tpu.tensorplane.columns import (
    TensorSpec,
    tensor_field,
    tensor_shape_of,
    tensor_specs,
    validate_tensor_batch,
)
from lakesoul_tpu.tensorplane.dlpack import (
    aligned_empty,
    deliver,
    device_put_copies,
)
from lakesoul_tpu.tensorplane.replay import DeviceReplayCache, ReplaySpill

__all__ = [
    "TensorSpec",
    "tensor_field",
    "tensor_shape_of",
    "tensor_specs",
    "validate_tensor_batch",
    "aligned_empty",
    "deliver",
    "device_put_copies",
    "DeviceReplayCache",
    "ReplaySpill",
]
