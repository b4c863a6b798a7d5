"""Device time inside the train step by the program's ``jax.named_scope``.

A TPU profile names every ``XLA Ops`` event by its HLO instruction
(``%fusion.12 = ...``) and carries no operation metadata, so the scope a
``jax.named_scope`` wrote into ``op_name`` is not in the trace.  It is in the
compiled program's text.  The consumer adaptor writes ``{instruction name:
scope}`` of the compiled step beside the trace (``step_scopes.json``, see
``consumers/qwen3_next_clm.py: scopes_of``); this module charges every event
inside an execution of the step program to its instruction's scope, by self
time (a ``while`` event spans its loop, the body's operations are events of
their own inside it), and gives each scope's share of the step's busy time.
A fusion was charged to its root's scope when the map was written.

``chipbench/trace.py: load_xplane`` cuts event names for its own readers, so
the run's newest ``.xplane.pb`` is read again here, as ``program_spans.py``
does.  A run without a trace, without the map (a program with no such scopes)
or without an execution of the step gives ``None``, and so does every reader
built on it.
"""

from __future__ import annotations

import bisect
import functools
import json
import os
import sys

from chipbench import program_spans
from chipbench import trace as T

SCOPES_FILE = "step_scopes.json"
PREFIX = "lakesoul.lm."
UNATTRIBUTED = "(no scope)"
_NAME_LIMIT = 64  # an instruction's name ends well before


def load(path: str) -> dict:
    """The plain structure of ``trace.py`` with the device lines ``XLA Ops``
    and ``XLA Modules`` only."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        if not plane.name.startswith(T.DEVICE_PREFIX):
            continue
        lines = [
            {"name": line.name,
             "events": [[e.name[:_NAME_LIMIT], e.start_ns, e.duration_ns] for e in line.events]}
            for line in plane.lines if line.name in (T.OPS_LINE, T.MODULES_LINE)
        ]
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


def instruction_name(event_name: str) -> str:
    """``%fusion.12 = f32[...`` → ``fusion.12``."""
    return event_name.split("=", 1)[0].strip().lstrip("%")


def shares(trace: dict, scope_of: dict[str, str], module_prefix: str) -> dict | None:
    """``{"seconds": {scope: self seconds}, "step_s": busy seconds inside the
    step's executions, "steps": executions}`` over every device; ``None`` where
    the step never ran.  ``seconds`` holds ``UNATTRIBUTED`` too, and its values
    add up to ``step_s``."""
    seconds: dict[str, float] = {}
    steps = 0
    for plane in trace["planes"]:
        if not plane["name"].startswith(T.DEVICE_PREFIX):
            continue
        runs = sorted((s, s + d) for name, s, d in T._line(plane, T.MODULES_LINE)
                      if name.startswith(module_prefix))
        if not runs:
            continue
        steps += len(runs)
        starts = [s for s, _ in runs]
        ops = sorted(T._line(plane, T.OPS_LINE), key=lambda e: (e[1], -e[2]))
        inside = []
        for name, start, dur in ops:
            i = bisect.bisect_right(starts, start) - 1
            if i >= 0 and start < runs[i][1]:
                inside.append([name, start, dur])
        for name, sec in T.self_seconds(inside):
            scope = scope_of.get(instruction_name(name), UNATTRIBUTED)
            seconds[scope] = seconds.get(scope, 0.0) + sec
    if not steps:
        return None
    return {"seconds": seconds, "step_s": sum(seconds.values()), "steps": steps}


@functools.lru_cache(maxsize=1)
def _of_file(path: str, module_prefix: str) -> dict | None:
    logdir = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(path))))
    try:
        with open(os.path.join(logdir, SCOPES_FILE)) as f:
            scope_of = json.load(f)
    except FileNotFoundError:
        return None
    result = shares(load(path), scope_of, module_prefix)
    if result is not None:
        total = result["step_s"] or 1.0
        report = ", ".join(f"{scope} {100 * sec / total:.2f}%"
                           for scope, sec in sorted(result["seconds"].items(), key=lambda kv: -kv[1]))
        print(f"[scopes] step time by scope over {result['steps']} executions: {report}",
              file=sys.stderr, flush=True)
    return result


def of_run(sample: dict) -> dict | None:
    if sample.get("trace_plain") is None or "step_module" not in sample:
        return None
    path = program_spans.newest_xplane()
    return None if path is None else _of_file(path, sample["step_module"])


def share_pct(sample: dict, *scopes: str) -> float | None:
    """The scopes' self time over the step's busy time, in percent."""
    result = of_run(sample)
    if result is None or not result["step_s"]:
        return None
    return 100.0 * sum(result["seconds"].get(PREFIX + s, 0.0) for s in scopes) / result["step_s"]
