"""The forward attention kernel's share of its roofline: over the traced
events named ``flash_attention_fwd.<n>`` (one a row and attention layer), the
least time the peaks table allows for the work over the time taken, as
``ragged_score_roofline`` is read.  The work is the mask's (``4 x head_dim``
operations a query head and visible pair, ``kernels/flash_attention.py``; a
window layer's by the configuration's ``sliding_window``, told from a full
layer's by the instruction's scope: ``chipbench/flash_roofline.py``), so tiles
the kernel multiplies beyond the mask lower the reading and it cannot pass
100.  The products bound every case (bfloat16 peak)."""

from chipbench import flash_roofline


def read(sample):
    return flash_roofline.read(sample, "fwd")
