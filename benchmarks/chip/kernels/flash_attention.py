"""Operations and bytes of one call of the causal LMs' attention kernels
(``lakesoul_tpu/models/causal_lm.py``: ``flash_attention_fwd``,
``flash_attention_bwd``), from what the configuration states: heads, head
size, row length and window.

The work is the MASK's, not the tile list's.  A query head's row has ``pairs``
visible (query, key) pairs: the triangle ``T (T + 1) / 2`` under the causal
mask, and under a window ``W`` the band ``W (W + 1) / 2 + (T - W) W`` (a query
sees its own position and the ``W - 1`` before it).  Per pair and query head

    forward     4 x D operations    the score (2 D) and its share of the values (2 D)
    backward   10 x D operations    five products over the same pairs: the scores
                                    again, dP = dO V^T, dV, dK and dQ

and the bytes are what the algorithm has to move once: forward q, k, v and o
(bfloat16) and the log-sum-exp (float32); backward q, k, v, dO (bfloat16), the
log-sum-exp and delta (float32) in, dQ (bfloat16) and dK, dV (float32) out.
Whatever tiles an implementation runs, the reading is the same: the pairs a
tile multiplies beyond its mask (the corners an edge cuts off) lower it, and
it cannot pass 100%.  Softmax's exponentials, the maxima and the rescaling are
left out.  At 8,192 tokens the products bound every case by two orders of
magnitude (half an operation per byte would be the memory's side).
"""

from __future__ import annotations


def visible_pairs(seq: int, window: int | None) -> int:
    """(query, key) pairs of one head's row that the mask lets through; a
    window of the row's length or more is the causal mask."""
    if window is None or window >= seq:
        return seq * (seq + 1) // 2
    return window * (window + 1) // 2 + (seq - window) * window


def cost(*, kernel: str, heads: int, kv_heads: int, head_dim: int, seq: int, window: int | None) -> tuple[float, float]:
    """(operations, bytes) of one call over one row: ``kernel`` is ``"fwd"``
    or ``"bwd"``."""
    pairs = visible_pairs(seq, window)
    queries, keys = heads * seq * head_dim, kv_heads * seq * head_dim  # elements of q (o, dO, dQ) and of k (v, dK, dV)
    per_query = heads * seq                                            # the log-sum-exp, delta
    if kernel == "fwd":
        return 4.0 * head_dim * heads * pairs, 2.0 * (2 * queries + 2 * keys) + 4.0 * per_query
    if kernel == "bwd":
        return 10.0 * head_dim * heads * pairs, 2.0 * (3 * queries + 2 * keys) + 4.0 * (2 * per_query + 2 * keys)
    raise ValueError(f"kernel is 'fwd' or 'bwd', not {kernel!r}")
