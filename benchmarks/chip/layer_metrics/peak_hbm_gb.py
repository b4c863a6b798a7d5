"""Peak device memory on the fullest chip, GB (``chipbench.runtime
.memory_peak_bytes``: buffers in use plus the running program's reserved
scratch)."""


def read(sample):
    return sample["peak_hbm_bytes"] / 1e9
