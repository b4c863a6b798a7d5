"""Deltas of the program's counters and histograms over the window.

The program records into one process-wide registry
(``lakesoul_tpu.obs.registry()``).  The benchmark snapshots it when the window
opens and when it closes and hands the differences to the per-layer readers;
it defines no counter of its own inside the program.
"""

from __future__ import annotations

_LOWERING_EVENT = "/jax/core/compile/jaxpr_to_mlir_module_duration"


def snapshot() -> dict[str, float]:
    """``{series key: value}``; a histogram gives ``<key>:sum`` and
    ``<key>:count``.  Series keys are ``name{label="v",...}`` as the registry
    prints them."""
    from lakesoul_tpu.obs import registry

    out: dict[str, float] = {}
    for key, value in registry().snapshot().items():
        if isinstance(value, dict):
            out[key + ":sum"] = float(value.get("sum", 0.0))
            out[key + ":count"] = float(value.get("count", 0))
        else:
            out[key] = float(value)
    return out


def delta(before: dict[str, float], after: dict[str, float]) -> dict[str, float]:
    return {k: v - before.get(k, 0.0) for k, v in after.items()}


def family_sum(deltas: dict[str, float], family: str, suffix: str = "", **labels: str) -> float:
    """Sum of every series of ``family`` whose labels include ``labels``."""
    total = 0.0
    for key, value in deltas.items():
        if suffix and not key.endswith(suffix):
            continue
        body = key[: len(key) - len(suffix)] if suffix else key
        name, _, rest = body.partition("{")
        if name != family:
            continue
        if all(f'{k}="{v}"' in rest for k, v in labels.items()):
            total += value
    return total


class LoweringCounter:
    """Counts top-level lowerings: each is one executable built, or fetched
    from the persistent cache, for a jitted function.  Inside the measured
    window the count must stay 0."""

    def __init__(self):
        import jax

        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, name, _seconds, **_kwargs):
        if name == _LOWERING_EVENT:
            self.count += 1
