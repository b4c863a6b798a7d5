"""Freshness layer: the ingest-to-train staleness contract, measured.

The stack already has every ingredient of an always-fresh lakehouse —
exactly-once CDC ingest (streaming/cdc.py), a leased auto-compaction
service (compaction/), a resilience policy engine (runtime/resilience.py)
and streaming follow plans (meta/client.poll_scan_plan) — but until this
subsystem nothing MEASURED how long a committed upsert takes to reach a
training loop, let alone guaranteed it.  LakeSoul's defining loop is
exactly CDC + auto-compaction feeding readers (PAPER.md §0), and the
reproducibility discipline of arxiv 2604.21275 says a throughput claim for
a training-data pipeline only counts when measured end-to-end under the
full concurrent workload.  This package closes that gap:

- :mod:`~lakesoul_tpu.freshness.slo` — :class:`SloMonitor` turns each
  delivered commit into a commit-to-visible latency observation
  (``lakesoul_freshness_seconds``) and evaluates it against a DECLARED
  target (``LAKESOUL_FRESHNESS_SLO_S``) with error-budget accounting
  (``lakesoul_slo_violations_total{slo=}``); :class:`ThroughputSlo` does
  the same for sustained rows/s.
- :mod:`~lakesoul_tpu.freshness.follower` — the bounded-staleness
  follower: ``scan.follow()``'s poll/decode loop hardened onto the PR-6
  :class:`~lakesoul_tpu.runtime.resilience.RetryPolicy` (transient
  store/meta faults retry on the seeded schedule instead of killing the
  stream; permanent failures raise typed), with an exactly-once resumable
  position (:class:`FollowerState`) and a batch-source seam adapter
  (:class:`FollowBatchSource`) so ``scan.to_jax_iter(follow=...)`` is a
  continuous training source.
- ``python -m lakesoul_tpu.freshness writer`` — the real CDC-writer
  process role of the three-role chaos harness
  (tests/test_freshness_chaos.py):
  writer + leased compactor + follower trainer run as real processes, the
  compactor is SIGKILLed mid-run and flaky-store faults injected, and the
  run must hold BOTH the freshness SLO and the throughput SLO with the
  follower's delivered rows exactly matching the writer's oracle.
"""

from __future__ import annotations

from lakesoul_tpu.freshness.follower import (
    FollowBatchSource,
    FollowerState,
    FreshFollower,
)
from lakesoul_tpu.freshness.slo import SloMonitor, ThroughputSlo

__all__ = [
    "FollowBatchSource",
    "FollowerState",
    "FreshFollower",
    "SloMonitor",
    "ThroughputSlo",
]
