"""The backward attention kernel's share of its roofline: over the traced
events named ``flash_attention_bwd.<n>`` (one a row and attention layer), the
least time the peaks table allows for the work over the time taken, as
``flash_fwd_roofline_pct`` is read: five products over the pairs the layer's
mask lets through (``10 x head_dim`` operations a query head and pair,
``kernels/flash_attention.py``), q, k, v, dO, the log-sum-exp and delta in,
dQ, dK, dV out, once each."""

from chipbench import flash_roofline


def read(sample):
    return flash_roofline.read(sample, "bwd")
