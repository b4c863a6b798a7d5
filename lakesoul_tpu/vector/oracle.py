"""Exact brute-force recall oracle, shared by autotune, tests and the chip smoke.

One definition of ground truth for every recall@k claim in the repo: the
``tune_nprobe`` autotuner, the single-shard vs multi-shard parity tests and
``chip_smoke.py`` all measure against THIS oracle, so a recall number from
any of them means the same thing.  :func:`exact_topk` is one batched gram
matmul for all queries (the tune_nprobe formulation, hoisted here).

Recall semantics match the autotuner's: the denominator is the *achievable*
hit count (truth sets can be smaller than k on tiny or duplicate-id
corpora; a perfect search must be able to reach recall 1.0)."""

from __future__ import annotations

import numpy as np


def subsample_queries(queries: np.ndarray, max_queries: int, seed: int) -> np.ndarray:
    """Seeded query subsample so repeated oracle runs measure the same set."""
    queries = np.asarray(queries, np.float32)
    if len(queries) <= max_queries:
        return queries
    rng = np.random.default_rng(seed)
    return queries[rng.choice(len(queries), max_queries, replace=False)]


def exact_topk(
    base: np.ndarray, base_ids: np.ndarray, queries: np.ndarray, k: int
) -> list[set]:
    """Exact L2 top-k truth sets, one per query.

    ONE batched gram matmul for all queries (not a per-query base pass);
    ``k`` is clamped to the corpus size."""
    base = np.asarray(base, np.float32)
    base_ids = np.asarray(base_ids)
    queries = np.asarray(queries, np.float32)
    d2 = (
        np.sum(queries**2, axis=1, keepdims=True)
        - 2.0 * queries @ base.T
        + np.sum(base**2, axis=1)[None, :]
    )
    k_eff = min(k, d2.shape[1])
    part = np.argpartition(d2, k_eff - 1, axis=1)[:, :k_eff]
    return [set(base_ids[row].tolist()) for row in part]


def recall_at_k(truth: list[set], got_ids) -> float:
    """Achievable-hit recall: |truth ∩ got| summed over queries, divided by
    the total achievable hits (``sum(len(t))``, not ``Q * k``)."""
    hits = sum(
        len(truth[i] & {int(x) for x in got_ids[i]}) for i in range(len(truth))
    )
    return hits / max(1, sum(len(t) for t in truth))
