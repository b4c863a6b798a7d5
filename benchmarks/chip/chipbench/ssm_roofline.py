"""The selective-scan kernels' share of their roofline, for the two readers
``layer_metrics/ssm_scan_fwd_roofline_pct.py`` and
``ssm_scan_bwd_roofline_pct.py``.

A traced event of a kernel is one call over the rows of one Mamba layer the
row loop handed it (one row).  The work of a call comes from
``kernels/selective_scan.py`` and the sizes the configuration states
(``table.seq``, ``model.mamba_expand x model.hidden_size``,
``model.mamba_d_state``), never from the blocks the kernel ran; the least
time is the larger of its bytes over the HBM bandwidth and its multiply-adds
over ``f32_flops`` (the peaks table has no vector-unit row: the reading is a
floor).  Gives ``None`` without a trace, for a configuration without those
sizes, or where the trace holds no event of the kernel (a program without
it).
"""

from __future__ import annotations

import os

from chipbench import trace
from chipbench.peaks import least_seconds
from chipbench.spec import load_module

_COST = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "kernels", "selective_scan.py")


def share_pct(events, model: dict, seq: int, peaks: dict, kernel: str) -> float | None:
    """``events``: ``(event name, seconds)`` of the kernel's calls → the least
    time the peaks allow for their work over the time they took, in percent."""
    flops, moved = load_module(_COST).cost(
        kernel=kernel, seq=seq, channels=model["mamba_expand"] * model["hidden_size"], states=model["mamba_d_state"]
    )
    least, _which = least_seconds(
        flops=flops, bytes_moved=moved, flops_peak=peaks["f32_flops"], bytes_peak=peaks["hbm_bytes_per_s"]
    )
    took = sum(seconds for _, seconds in events)
    return 100.0 * least * len(events) / took if took else None


def read(sample, kernel: str) -> float | None:
    model = sample["config"].get("model", {})
    if sample.get("trace_plain") is None or "mamba_d_state" not in model:
        return None
    events = trace.kernel_events(sample["trace_plain"], "selective_scan_" + kernel)
    if not events:
        return None
    return share_pct(events, model, sample["config"]["table"]["seq"], sample["peaks"], kernel)
