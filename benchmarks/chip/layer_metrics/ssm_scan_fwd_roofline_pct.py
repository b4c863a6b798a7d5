"""The forward selective-scan kernel's share of its roofline: over the traced
events named ``selective_scan_fwd.<n>`` (one a row and Mamba layer), the
least time the peaks table allows for the recurrence's work over the time
taken (``chipbench/ssm_roofline.py``; the work from
``kernels/selective_scan.py``).  The bytes bound it (u, Delta and y once);
the scan runs on the vector unit, which the table has no row for, so the
reading is a floor on how near its limit the kernel is and cannot pass 100."""

from chipbench import ssm_roofline


def read(sample):
    return ssm_roofline.read(sample, "fwd")
