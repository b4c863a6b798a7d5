"""Operations and bytes of one call of a Mamba-1 layer's selective scan over
one row (``lakesoul_tpu/models/selective_scan.py``: ``selective_scan_fwd``,
``selective_scan_bwd``), from what the configuration states: row length
``T``, channels ``E`` (``mamba_expand x hidden_size``) and states ``N``
(``mamba_d_state``), and the types the layer states (u, y and their
cotangents bfloat16; Delta, B, C, A, D and their cotangents float32).

The work is the RECURRENCE's, whatever implements it: per token, channel and
state ``s = exp(Delta A) s + (Delta u) B`` and ``y += s C`` are three
multiply-adds (the decay's argument, the update, the output's sum), and per
token and channel two more (``Delta u``, ``D u``): ``T E (3 N + 2)``
multiply-adds forward, 2 operations each.  The backward pass is counted as
twice the forward (each multiply-add's transpose is two); the states it
computes again are a recomputation and count nothing.  The ``T E N``
exponentials are left out of the operations (the peaks table has no
transcendental row).

The bytes are what the algorithm has to move once: forward u, Delta, B, C, A
and D in and y out; backward those and dy in, and du, dDelta, dB, dC, dA, dD
out.  No ``[T, E, N]`` tensor is among them: the state lives on the chip.

Against the float32 peak and the HBM bandwidth the bytes bound both passes at
N = 16 (0.41 ms forward, 0.72 ms backward at T 8,192 and E 5,120 on a v5e,
against 0.09 and 0.17 ms of multiply-adds).  The scan runs on the vector and
transcendental units, for which the table has no row, so the reading is a
FLOOR on how near its own limit the kernel is: it cannot pass 100%, and a
kernel at its vector-unit limit still reads well under it.
"""

from __future__ import annotations


def cost(*, kernel: str, seq: int, channels: int, states: int) -> tuple[float, float]:
    """(operations, bytes) of one call over one row: ``kernel`` is ``"fwd"``
    or ``"bwd"``."""
    macs = seq * channels * (3 * states + 2)
    tokens, coupled = seq * channels, seq * states        # elements of u (Delta, y) and of B (C)
    fixed = channels * states + channels                  # A and D
    forward = 2.0 * tokens + 4.0 * tokens + 4.0 * (2 * coupled + fixed) + 2.0 * tokens   # u, Delta, B C A D; y
    if kernel == "fwd":
        return 2.0 * macs, forward
    if kernel == "bwd":
        return 4.0 * macs, forward + 2.0 * tokens + 4.0 * tokens + 4.0 * (2 * coupled + fixed)  # dy in place of y; du, dDelta, ...
    raise ValueError(f"kernel is 'fwd' or 'bwd', not {kernel!r}")
