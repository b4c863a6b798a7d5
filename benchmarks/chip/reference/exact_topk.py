"""Exact nearest neighbours and recall: the ANN cells' plain reference.

A copy of ``lakesoul_tpu/vector/oracle.py``'s ``exact_topk`` and
``recall_at_k`` (same distance, same achievable-hit denominator), chunked over
the corpus so a million 512-d vectors need no ``[Q, N]`` matrix.  numpy only.
"""

from __future__ import annotations

import numpy as np


def exact_topk_ids(base: np.ndarray, base_ids: np.ndarray, queries: np.ndarray,
                   k: int, *, chunk: int = 131072) -> np.ndarray:
    """Exact L2 top-``k`` ids per query, ``[Q, k]`` (unordered within a row)."""
    queries = np.asarray(queries, np.float32)
    q_sq = np.sum(queries**2, axis=1, keepdims=True)
    best_d = np.full((len(queries), k), np.inf, np.float32)
    best_i = np.zeros((len(queries), k), np.uint64)
    for lo in range(0, len(base), chunk):
        block = np.asarray(base[lo:lo + chunk], np.float32)
        d2 = q_sq - 2.0 * queries @ block.T + np.sum(block**2, axis=1)[None, :]
        cand_d = np.concatenate([best_d, d2.astype(np.float32)], axis=1)
        cand_i = np.concatenate(
            [best_i, np.broadcast_to(base_ids[lo:lo + chunk], d2.shape)], axis=1
        )
        part = np.argpartition(cand_d, k - 1, axis=1)[:, :k]
        best_d = np.take_along_axis(cand_d, part, axis=1)
        best_i = np.take_along_axis(cand_i, part, axis=1)
    return best_i


def recall_hits(truth_row: np.ndarray, got_ids) -> int:
    """How many of one query's exact neighbours an answer holds."""
    return len(set(truth_row.tolist()) & {int(x) for x in got_ids})
