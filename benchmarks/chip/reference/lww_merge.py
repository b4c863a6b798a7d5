"""Last write wins: the merge-on-read table's plain reference.

The table's guarantee is that a scan returns every primary key exactly once,
carrying the row of the newest commit that wrote it.  Given the writes in
commit order that is one assignment per write.  numpy only, and it never
touches the table: the benchmark regenerates the writes from the seed.
"""

from __future__ import annotations

import numpy as np


def merge_last_write_wins(writes, *, rows: int, seq: int) -> np.ndarray:
    """``writes`` yields ``(kind, ids, tokens)`` in commit order; returns the
    ``[rows, seq]`` int32 table a reader must see, indexed by key."""
    merged = np.zeros((rows, seq), np.int32)
    written = np.zeros(rows, bool)
    for _kind, ids, tokens in writes:
        merged[ids] = tokens
        written[ids] = True
    if not written.all():
        raise ValueError(f"{int((~written).sum())} keys were never written")
    return merged
