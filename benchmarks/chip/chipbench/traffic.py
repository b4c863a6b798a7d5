"""One general traffic generator, driven by a cell's data file.

A cell's traffic is parameters in ``workloads/<cell>.json``; this module turns
them and the seed into a schedule.  A new mix (another rate, bursts, another
``nprobe`` mix, more requests in flight) is a new data file, never new code.

Fields it reads (the ANN driver passes the workload dict through):

``loop``          ``"open"``: requests are due at times fixed in advance;
                  ``"closed"``: ``in_flight`` requests are kept outstanding.
``rate_per_s``    open loop: mean arrivals per second (fixed, never searched).
``arrivals``      ``{"process": "gamma", "shape": k}``: inter-arrival times are
                  gamma with shape ``k`` and mean ``1 / rate``.  ``k = 1`` is a
                  Poisson process; ``k < 1`` gives bursts (BurstGPT,
                  arXiv:2401.17644, fits gamma to real arrivals).
``in_flight``     closed loop: requests kept outstanding.
``mix``           per-request parameters, each ``{"values": [...], "weights":
                  [...]}`` drawn independently from the seed (``nprobe``).
``query_pool``    how many held-out queries are cycled, in seeded order.
"""

from __future__ import annotations

import numpy as np


def arrival_times(workload: dict, *, horizon_s: float, rng: np.random.Generator) -> np.ndarray:
    """Due times in seconds from 0, ascending, covering ``horizon_s``."""
    rate = float(workload["rate_per_s"])
    arrivals = workload.get("arrivals", {"process": "gamma", "shape": 1.0})
    if arrivals["process"] != "gamma":
        raise ValueError(f"unknown arrival process {arrivals['process']!r}")
    shape = float(arrivals["shape"])
    n = int(rate * horizon_s * 1.2) + 64
    times = np.cumsum(rng.gamma(shape, 1.0 / (rate * shape), n))
    while times[-1] < horizon_s:  # a seed can run short; extend, never truncate
        more = np.cumsum(rng.gamma(shape, 1.0 / (rate * shape), n)) + times[-1]
        times = np.concatenate([times, more])
    return times[times < horizon_s]


def draw_mix(mix: dict, n: int, rng: np.random.Generator) -> dict[str, np.ndarray]:
    out = {}
    for name, spec in mix.items():
        weights = np.asarray(spec["weights"], float)
        out[name] = rng.choice(np.asarray(spec["values"]), n, p=weights / weights.sum())
    return out


def query_order(pool: int, n: int, rng: np.random.Generator) -> np.ndarray:
    """Indices into the query pool for ``n`` requests: a seeded permutation,
    cycled, so every query is used about equally often."""
    perm = rng.permutation(pool)
    return perm[np.arange(n) % pool]


class Schedule:
    """Everything the load generator needs, fixed before the run starts."""

    def __init__(self, workload: dict, *, seed: int, horizon_s: float, query_count: int):
        rng = np.random.default_rng(seed)
        self.loop = workload["loop"]
        pool = min(int(workload.get("query_pool", query_count)), query_count)
        if self.loop == "open":
            self.due = arrival_times(workload, horizon_s=horizon_s, rng=rng)
            n = len(self.due)
        elif self.loop == "closed":
            self.in_flight = int(workload["in_flight"])
            self.due = None
            # more than any run can complete; the generator stops at the window's end
            n = int(workload.get("closed_loop_requests", 1 << 20))
        else:
            raise ValueError(f"unknown loop {self.loop!r}")
        self.n = n
        self.query = query_order(pool, n, rng)
        self.params = draw_mix(workload.get("mix", {}), n, rng)
