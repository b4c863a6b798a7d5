"""The backward selective-scan kernel's share of its roofline, as
``ssm_scan_fwd_roofline_pct`` is read, over the events named
``selective_scan_bwd.<n>``: twice the forward's multiply-adds, and the six
cotangents' bytes beside the forward's operands.  The block states the kernel
computes again count nothing."""

from chipbench import ssm_roofline


def read(sample):
    return ssm_roofline.read(sample, "bwd")
