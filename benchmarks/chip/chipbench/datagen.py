"""Seeded data for the cells: token-table writes and a Gaussian-mixture corpus.

Copies of ``chip_smoke.py``'s generators (``build_token_table``, the mixture
in ``stage_ann_server``), cut loose from the program so that no later PR can
change what the benchmark feeds.  Everything here is numpy on the host and a
pure function of its arguments.
"""

from __future__ import annotations

import numpy as np

FIRST_TOKEN = 1000  # ids below are BERT's special and unused tokens


def token_writes(*, rows: int, seq: int, vocab: int, commits: int,
                 upsert_waves: int, upsert_fraction: float, seed: int):
    """Yield ``(kind, ids, tokens)`` for every write of the table, in commit
    order: ``commits`` appends that together cover keys ``0..rows-1`` once,
    then ``upsert_waves`` upserts, each over ``upsert_fraction`` of the keys.

    ``ids`` is sorted int64, ``tokens`` is ``[len(ids), seq]`` int32 drawn
    uniformly from ``[FIRST_TOKEN, vocab)``.  The same arguments give the same
    writes, which is what lets the reference rebuild them without the table."""
    rng = np.random.default_rng(seed)
    edges = np.linspace(0, rows, commits + 1).astype(np.int64)
    for lo, hi in zip(edges[:-1], edges[1:]):
        ids = np.arange(lo, hi, dtype=np.int64)
        yield "append", ids, rng.integers(FIRST_TOKEN, vocab, (len(ids), seq), dtype=np.int32)
    for _ in range(upsert_waves):
        ids = np.sort(rng.choice(rows, int(rows * upsert_fraction), replace=False)).astype(np.int64)
        yield "upsert", ids, rng.integers(FIRST_TOKEN, vocab, (len(ids), seq), dtype=np.int32)


def mixture_corpus(*, rows: int, dim: int, components: int, spread: float,
                   queries: int, seed: int, chunk: int = 65536):
    """A unit-normalised Gaussian-mixture corpus and held-out queries.

    Returns ``(vectors [rows, dim] f32, ids [rows] u64, queries [queries, dim]
    f32)``.  Each vector is a component centre (standard normal times
    ``spread``) plus standard-normal noise, then scaled to unit length, as a
    CLIP embedding is.  Rows are in random component order, so every shard of
    a plane built over a row range sees the whole mixture."""
    rng = np.random.default_rng(seed)
    centers = rng.standard_normal((components, dim), dtype=np.float32) * np.float32(spread)

    def draw(n: int) -> np.ndarray:
        out = centers[rng.integers(0, components, n)]
        out += rng.standard_normal((n, dim), dtype=np.float32)
        out /= np.linalg.norm(out, axis=1, keepdims=True)
        return out

    vectors = np.empty((rows, dim), np.float32)
    for lo in range(0, rows, chunk):
        hi = min(rows, lo + chunk)
        vectors[lo:hi] = draw(hi - lo)
    return vectors, np.arange(rows, dtype=np.uint64), draw(queries)
