"""The attention kernels' share of their roofline, for the two readers
``layer_metrics/flash_fwd_roofline_pct.py`` and ``flash_bwd_roofline_pct.py``.

A traced event of the kernel is one call over one row of one attention layer.
Which mask the layer has is read off the compiled step's scope map (the
adaptor's ``step_scopes.json``, as ``chipbench/scopes.py`` reads it): an
instruction under ``lakesoul.lm.swa`` is a window layer's, at the
configuration's ``sliding_window``; under ``lakesoul.lm.attn`` a full layer's.
The work of a call comes from ``kernels/flash_attention.py`` and the sizes the
configuration states, never from the tiles the kernel ran.  Gives ``None``
without a trace, without the map, where an event's instruction carries neither
scope, or where the trace holds no event of the kernel.
"""

from __future__ import annotations

import json
import os

from chipbench import program_spans, scopes, trace
from chipbench.peaks import least_seconds
from chipbench.spec import load_module

_COST = os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))), "kernels", "flash_attention.py")
WINDOW_SCOPE, FULL_SCOPE = scopes.PREFIX + "swa", scopes.PREFIX + "attn"


def scope_map() -> dict | None:
    """``{instruction: scope}`` the adaptor wrote beside the run's newest trace."""
    path = program_spans.newest_xplane()
    if path is None:
        return None
    logdir = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(path))))
    try:
        with open(os.path.join(logdir, scopes.SCOPES_FILE)) as f:
            return json.load(f)
    except FileNotFoundError:
        return None


def share_pct(events, scope_of: dict, model: dict, seq: int, peaks: dict, kernel: str) -> float | None:
    """``events``: ``(event name, seconds)`` of the kernel's calls → the least
    time the peaks allow for their work over the time they took, in percent."""
    cost = load_module(_COST).cost
    sizes = dict(heads=model["num_attention_heads"], kv_heads=model["num_key_value_heads"],
                 head_dim=model["head_dim"], seq=seq)
    least_of = {}  # a call's least seconds by its layer's scope
    for scope, window in ((WINDOW_SCOPE, model.get("sliding_window")), (FULL_SCOPE, None)):
        flops, moved = cost(kernel=kernel, window=window, **sizes)
        least_of[scope], _which = least_seconds(
            flops=flops, bytes_moved=moved, flops_peak=peaks["bf16_flops"], bytes_peak=peaks["hbm_bytes_per_s"]
        )
    least = took = 0.0
    for name, seconds in events:
        scope = scope_of.get(scopes.instruction_name(name))
        if scope not in least_of:
            return None
        least += least_of[scope]
        took += seconds
    return 100.0 * least / took if took else None


def read(sample, kernel: str) -> float | None:
    if sample.get("trace_plain") is None:
        return None
    events = trace.kernel_events(sample["trace_plain"], "flash_attention_" + kernel)
    scope_of = scope_map() if events else None
    if not scope_of:
        return None
    config = sample["config"]
    return share_pct(events, scope_of, config["model"], config["table"]["seq"], sample["peaks"], kernel)
