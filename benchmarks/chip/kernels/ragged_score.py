"""Operations and bytes of one ``ragged_score`` call, from its shapes.

The kernel (``lakesoul_tpu/annplane/ragged.py``) runs one grid step per work
item: a ``[tile, d]`` block of float32 codes times the item's query row, then
an affine correction.  Per item the algorithm needs

    operations  2 * tile * d           (the matvec; the correction is 4 * tile more)
    bytes       tile * d * 4           the codes block
              + d * 4                  the query row
              + 3 * tile * 4           a, b, h
              + tile * 4               the scores written

which is half an operation per byte: the memory bound applies on any chip.
Pad items (the item count is rounded up to a power of two) move the same bytes
and are counted, because the trace shows only the padded call.
"""

from __future__ import annotations

import re


def cost(*, items: int, tile: int, d: int, code_bytes: int = 4) -> tuple[float, float]:
    flops = items * (2.0 * tile * d + 4.0 * tile)
    moved = items * (tile * d * code_bytes + d * 4.0 + 3 * tile * 4.0 + tile * 4.0)
    return flops, moved


def from_event(name: str) -> tuple[float, float]:
    """Shapes from the HLO instruction the trace names the event with: the
    result is ``f32[items, 1, tile]`` and the codes operand is the only
    ``[rows, d]`` matrix."""
    result = re.search(r"=\s*\(?f32\[(\d+),1,(\d+)\]", name)
    if not result:
        raise ValueError(f"not a ragged_score call: {name[:120]}")
    items, tile = int(result.group(1)), int(result.group(2))
    matrices = [(int(r), int(c)) for r, c in re.findall(r"f32\[(\d+),(\d+)\]\{1,0", name)]
    d = next(cols for rows, cols in matrices if rows > 1)
    return cost(items=items, tile=tile, d=d)
