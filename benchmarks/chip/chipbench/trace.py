"""From a profiler trace to numbers: busy and idle time, kernel time, exposed
collectives, and who owned each idle gap.

``jax.profiler`` writes an ``.xplane.pb``; :func:`load_xplane` turns it into a
plain structure (``{"planes": [{"name", "lines": [{"name", "events": [[name,
start_ns, duration_ns], ...]}]}]}``) and everything else here works on that
structure, so the self-test can check the arithmetic on a small recorded trace
kept as JSON.

What a TPU trace looks like (v5e, jax 0.9; looked at by hand in PR 22): one
plane per chip named ``/device:TPU:<n>`` with the lines ``XLA Modules`` (one
event per executed program, named ``jit_<function>(<fingerprint>)``), ``XLA
Ops`` (the core's sequential stream of HLO operations; an event's name is the
whole HLO instruction, ``%name.N = shape opcode(operands...)``), ``Async XLA
Ops`` (DMAs and asynchronous collectives, start to done) and ``Steps``.  The
host is the plane ``/host:CPU`` with one line per thread;
``jax.profiler.TraceAnnotation`` spans appear there by name, on the same
clock as the device events (nanoseconds from the start of the session).
"""

from __future__ import annotations

import bisect
import glob
import os
import re
import statistics

DEVICE_PREFIX = "/device:TPU:"
HOST_PLANE = "/host:CPU"
OPS_LINE = "XLA Ops"
MODULES_LINE = "XLA Modules"
SPAN_PREFIX = "bench."
COLLECTIVE_OPCODES = (
    "all-reduce", "all-gather", "reduce-scatter", "all-to-all", "collective-permute",
    "collective-broadcast",
)
_NAME_LIMIT = 200          # characters kept of an ordinary event name
_CUSTOM_CALL_LIMIT = 1500  # a kernel's operands (its shapes) sit far into the name

_INSTR = re.compile(r"^%?([\w.\-]+)\s*=\s*(.*)$", re.S)
_OPCODE = re.compile(r"\}?\s([a-z][a-z0-9\-]*)\(")


# ------------------------------------------------------------------ loading


def find_xplane(logdir: str) -> str:
    paths = sorted(glob.glob(os.path.join(logdir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {logdir}")
    return paths[-1]


def load_xplane(path: str) -> dict:
    """Read an ``.xplane.pb`` with JAX alone into the plain structure; keeps
    device planes whole and, of the host, only the ``bench.*`` spans."""
    from jax.profiler import ProfileData

    planes = []
    for plane in ProfileData.from_file(path).planes:
        on_device = plane.name.startswith(DEVICE_PREFIX)
        if not on_device and plane.name != HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            events = []
            for e in line.events:
                name = e.name
                if on_device:
                    limit = _CUSTOM_CALL_LIMIT if "custom-call(" in name else _NAME_LIMIT
                    events.append([name[:limit], e.start_ns, e.duration_ns])
                elif name.startswith(SPAN_PREFIX):
                    events.append([name, e.start_ns, e.duration_ns])
            if events:
                lines.append({"name": line.name, "events": events})
        planes.append({"name": plane.name, "lines": lines})
    return {"planes": planes}


# ---------------------------------------------------------------- intervals


def union(intervals: list[tuple[float, float]]) -> list[tuple[float, float]]:
    """Merge overlapping or touching ``(start, end)`` intervals."""
    out: list[tuple[float, float]] = []
    for start, end in sorted(intervals):
        if out and start <= out[-1][1]:
            if end > out[-1][1]:
                out[-1] = (out[-1][0], end)
        else:
            out.append((start, end))
    return out


def total(intervals: list[tuple[float, float]]) -> float:
    return sum(end - start for start, end in intervals)


def clip(intervals, lo: float, hi: float):
    return [(max(s, lo), min(e, hi)) for s, e in intervals if e > lo and s < hi]


def gaps(busy: list[tuple[float, float]], lo: float, hi: float):
    """The idle intervals of ``[lo, hi]`` given merged busy intervals."""
    out, cursor = [], lo
    for start, end in busy:
        if start > cursor:
            out.append((cursor, start))
        cursor = max(cursor, end)
    if hi > cursor:
        out.append((cursor, hi))
    return out


# -------------------------------------------------------------------- names


def instruction(name: str) -> tuple[str, str]:
    """``(instruction name without its number, opcode)`` of an ``XLA Ops``
    event: ``%all-reduce.5 = f32[8]{0} all-reduce(...)`` gives
    ``("all-reduce", "all-reduce")``, a Pallas kernel gives
    ``("_ragged_score_pallas_call", "custom-call")``."""
    m = _INSTR.match(name)
    if not m:
        return name, ""
    instr = re.sub(r"\.\d+$", "", m.group(1))
    op = _OPCODE.search(" " + m.group(2))
    return instr, (op.group(1) if op else "")


def op_label(name: str) -> str:
    """A short, stable label for the breakdown: the instruction with its
    number, its opcode where the name does not say it, and the result's shape
    (``fusion.479 f32[768]``, ``_ragged_score_pallas_call.1 custom-call
    f32[32768,1,128]``)."""
    m = _INSTR.match(name)
    if not m:
        return name[:80]
    _, opcode = instruction(name)
    result = re.match(r"\(?([a-z]+\d+\[[\d,]*\])", m.group(2))
    parts = [m.group(1)]
    if opcode and opcode not in m.group(1):
        parts.append(opcode)
    if result:
        parts.append(result.group(1))
    return " ".join(parts)[:80]


def is_collective(name: str) -> bool:
    instr, opcode = instruction(name)
    return any(opcode.startswith(c) or instr.startswith(c) for c in COLLECTIVE_OPCODES)


# ---------------------------------------------------------------- reduction


def _line(plane: dict, name: str) -> list:
    for line in plane["lines"]:
        if line["name"] == name:
            return line["events"]
    return []


def _spans(trace: dict) -> list[tuple[float, float, str]]:
    out = []
    for plane in trace["planes"]:
        if plane["name"] != HOST_PLANE:
            continue
        for line in plane["lines"]:
            for name, start, dur in line["events"]:
                if name.startswith(SPAN_PREFIX):
                    out.append((start, start + dur, name))
    return sorted(out)


def self_seconds(ops: list) -> list[tuple[str, float]]:
    """``(name, seconds)`` of each operation with its nested operations taken
    out: a ``while`` event spans its whole loop and the body's operations are
    events of their own inside it."""
    out: list[list] = []
    stack: list[tuple[float, int]] = []  # (end, index into out)
    for name, start, dur in sorted(ops, key=lambda e: (e[1], -e[2])):
        while stack and start >= stack[-1][0]:
            stack.pop()
        if stack:
            out[stack[-1][1]][1] -= dur / 1e9
        out.append([name, dur / 1e9])
        stack.append((start + dur, len(out) - 1))
    return [(name, max(sec, 0.0)) for name, sec in out]


def _attribute(gap: tuple[float, float], spans, modules, module_starts, into: dict[str, float]) -> None:
    """Split one idle gap among its owners.  The part inside a running program
    is the device's own (the core waits on a DMA or a semaphore); the rest goes
    to the ``bench.*`` spans open on the host meanwhile, by overlap, and what
    no span covers is the space between two spans.  ``modules`` are merged and
    sorted, so the few a gap can touch are found by bisection: a trace holds a
    hundred thousand gaps between operations and nearly all lie inside one."""
    lo, hi = gap
    first = max(bisect.bisect_right(module_starts, lo) - 1, 0)
    running = clip(modules[first:bisect.bisect_left(module_starts, hi)], lo, hi)
    inside = total(running)
    if inside:
        into["(inside a device program)"] = into.get("(inside a device program)", 0.0) + inside / 1e9
    for start, end in gaps(running, lo, hi):
        covered = 0.0
        for s_start, s_end, name in spans:
            overlap = min(end, s_end) - max(start, s_start)
            if overlap > 0:
                into[name] = into.get(name, 0.0) + overlap / 1e9
                covered += overlap
        rest = (end - start) - covered
        if rest > 0:
            into["(between bench spans)"] = into.get("(between bench spans)", 0.0) + rest / 1e9


def reduce_trace(trace: dict, *, top: int = 10) -> dict:
    """All the device numbers the per-layer readers and the result line use.

    The window is from the first to the last event of the trace, device or
    ``bench.*`` span.  Per device: ``busy_s`` is the union of the ``XLA Ops``
    intervals; ``collective_exposed_s`` is the time the core's own operation
    stream spent in collective operations, during which by construction no
    other operation ran on that core.  ``device_ops`` lists instructions by
    total self time (:func:`self_seconds`) and ``idle_gaps`` sums idle time by
    owner (:func:`_attribute`)."""
    devices = [p for p in trace["planes"] if p["name"].startswith(DEVICE_PREFIX)]
    spans = _spans(trace)
    edges = [(s, e) for s, e, _ in spans]
    for plane in devices:
        for line in plane["lines"]:
            edges += [(s, s + d) for _, s, d in line["events"]]
    if not devices or not edges:
        return {"devices": len(devices), "window_s": 0.0, "busy_s": 0.0, "per_device": [],
                "collective_exposed_s_worst": 0.0, "device_ops": [], "idle_gaps": []}
    lo, hi = min(s for s, _ in edges), max(e for _, e in edges)

    per_device = []
    op_seconds: dict[str, float] = {}
    gap_seconds: dict[str, float] = {}
    for plane in devices:
        ops = _line(plane, OPS_LINE)
        busy = union([(s, s + d) for _, s, d in ops])
        modules = union([(s, s + d) for _, s, d in _line(plane, MODULES_LINE)])
        module_starts = [s for s, _ in modules]
        exposed = 0.0
        for name, seconds in self_seconds(ops):
            key = op_label(name)
            op_seconds[key] = op_seconds.get(key, 0.0) + seconds
            if is_collective(name):
                exposed += seconds
        for gap in gaps(busy, lo, hi):
            _attribute(gap, spans, modules, module_starts, gap_seconds)
        per_device.append({
            "name": plane["name"], "busy_s": total(busy) / 1e9,
            "collective_exposed_s": exposed,
        })
    n = len(devices)
    ranked = lambda d: [[k, v] for k, v in sorted(d.items(), key=lambda kv: -kv[1])[:top]]  # noqa: E731
    return {
        "devices": n,
        "window_s": (hi - lo) / 1e9,
        "busy_s": sum(d["busy_s"] for d in per_device) / n,
        "per_device": per_device,
        "collective_exposed_s_worst": max(d["collective_exposed_s"] for d in per_device),
        # seconds per entry are summed over the devices
        "device_ops": ranked(op_seconds),
        "idle_gaps": ranked(gap_seconds),
    }


def module_busy_ms(trace: dict, module_prefix: str) -> list[float]:
    """Device busy milliseconds inside each execution of the programs whose
    name starts with ``module_prefix`` (``jit_train_step``), every device."""
    out = []
    for plane in trace["planes"]:
        if not plane["name"].startswith(DEVICE_PREFIX):
            continue
        busy = union([(s, s + d) for _, s, d in _line(plane, OPS_LINE)])
        for name, start, dur in _line(plane, MODULES_LINE):
            if name.startswith(module_prefix):
                out.append(total(clip(busy, start, start + dur)) / 1e6)
    return out


def kernel_events(trace: dict, instr_substring: str) -> list[tuple[str, float]]:
    """``(event name, seconds)`` of every ``XLA Ops`` custom-call whose
    instruction name contains ``instr_substring``."""
    out = []
    for plane in trace["planes"]:
        if not plane["name"].startswith(DEVICE_PREFIX):
            continue
        for name, _, dur in _line(plane, OPS_LINE):
            instr, opcode = instruction(name)
            if opcode == "custom-call" and instr_substring in instr:
                out.append((name, dur / 1e9))
    return out


def median(values: list[float]) -> float | None:
    return statistics.median(values) if values else None
