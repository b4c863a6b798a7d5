"""Device idle time split by what the program's loader was doing.

The program holds a ``jax.profiler.TraceAnnotation`` for as long as one of its
stages is open (``lakesoul_tpu/obs/stages.py: stage``): ``lakesoul.scan.decode``,
``.merge``, ``.fill`` and ``lakesoul.loader.rebatch``, ``.collate``, ``.queue``,
``.device_put``.  They land in the ``/host:CPU`` plane, one line a thread, on
the clock of the device planes, so no offset is estimated here.

``chipbench/trace.py: load_xplane`` keeps of the host only the ``bench.*``
spans, so this module reads the run's ``.xplane.pb`` again (:func:`load`): of
the host the ``lakesoul.*`` and ``bench.*`` events line by line, of each device
the lines ``XLA Ops`` and ``XLA Modules``.

:func:`split` then answers, for the idle time of each device that lies outside
any device program:

- **who owned it**: the consumer thread is the host line that holds the
  ``bench.next_batch`` spans; an instant belongs to the innermost ``lakesoul.*``
  span open on that line, and to nobody where none is open (step dispatch, the
  loss read, the loop itself);
- **what a queue stall waited for**: while the owner is
  ``lakesoul.loader.queue`` the consumer waits on the host pipeline, and that
  part is split again, not exclusively, by the ``lakesoul.*`` spans open
  meanwhile on any other line.

Everything is divided by devices and by the ``bench.step`` spans of the trace:
milliseconds of idle device a step.  A trace without a device plane, without a
consumer line or without one ``lakesoul.*`` span (a program from before the
seam) gives ``None``, and so does every reader built on it.
"""

from __future__ import annotations

import functools
import glob
import os

from chipbench import trace as T

PROGRAM_PREFIX = "lakesoul."
QUEUE = "lakesoul.loader.queue"
CONSUMER_MARK = "bench.next_batch"
STEP = "bench.step"
INSIDE_PROGRAM = "(inside a device program)"  # trace.py's name for idle time a program owns
_OP_NAME_LIMIT = 48  # nothing here reads an operation's name past its instruction

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__)))))


# ------------------------------------------------------------------ loading


def newest_xplane(root: str = ROOT) -> str | None:
    """The newest ``.xplane.pb`` under ``Tracer``'s directories.  A reader is
    not told its cell; one run at a time uses a checkout, and ``Tracer.start``
    empties its directory."""
    found = []
    for logdir in glob.glob(os.path.join(root, ".bench_data", "chip", "trace", "*")):
        try:
            found.append(T.find_xplane(logdir))
        except FileNotFoundError:
            pass
    return max(found, key=os.path.getmtime, default=None)


def load(path: str) -> dict:
    """The plain structure of ``trace.py`` (``{"planes": [{"name", "lines":
    [{"name", "events": [[name, start_ns, duration_ns], ...]}]}]}``) with the
    program's spans kept and every host line named.  ``window_ns`` is the
    window ``trace.reduce_trace`` takes: first to last event of any device line
    or ``bench.*`` span."""
    from jax.profiler import ProfileData

    planes = []
    edges: list[float] = []
    for plane in ProfileData.from_file(path).planes:
        on_device = plane.name.startswith(T.DEVICE_PREFIX)
        if not on_device and plane.name != T.HOST_PLANE:
            continue
        lines = []
        for line in plane.lines:
            kept = []
            for e in line.events:
                name, start, dur = e.name, e.start_ns, e.duration_ns
                if on_device:
                    edges += (start, start + dur)
                    if line.name in (T.OPS_LINE, T.MODULES_LINE):
                        kept.append([name[:_OP_NAME_LIMIT], start, dur])
                elif name.startswith(T.SPAN_PREFIX):
                    edges += (start, start + dur)
                    kept.append([name, start, dur])
                elif name.startswith(PROGRAM_PREFIX):
                    kept.append([name, start, dur])
            if kept:
                lines.append({"name": line.name, "events": kept})
        planes.append({"name": plane.name, "lines": lines})
    out = {"planes": planes}
    if edges:
        out["window_ns"] = [min(edges), max(edges)]
    return out


# ---------------------------------------------------------------- intervals


def _common(a, b):
    """The intervals two merged, sorted lists share."""
    out, i, j = [], 0, 0
    while i < len(a) and j < len(b):
        lo, hi = max(a[i][0], b[j][0]), min(a[i][1], b[j][1])
        if hi > lo:
            out.append((lo, hi))
        if a[i][1] < b[j][1]:
            i += 1
        else:
            j += 1
    return out


def innermost(events) -> dict[str, list[tuple[float, float]]]:
    """``{span name: merged intervals}`` during which a span of that name was
    the innermost one open on its line."""
    owned: dict[str, list] = {}
    open_: list[list] = []  # [name, end, where its own time resumes]

    def shut(limit: float) -> None:
        while open_ and open_[-1][1] <= limit:
            name, end, resumes = open_.pop()
            if end > resumes:
                owned.setdefault(name, []).append((resumes, end))
            if open_:
                open_[-1][2] = max(open_[-1][2], end)

    for name, start, dur in sorted(events, key=lambda e: (e[1], -e[2])):
        shut(start)
        if open_ and start > open_[-1][2]:
            owned.setdefault(open_[-1][0], []).append((open_[-1][2], start))
        open_.append([name, start + dur, start])
    shut(float("inf"))
    return {name: T.union(parts) for name, parts in owned.items()}


# -------------------------------------------------------------------- split


def _window(trace: dict, devices: list, host: list) -> tuple[float, float]:
    if "window_ns" in trace:
        return tuple(trace["window_ns"])
    edges = [(s, s + d) for plane in devices for line in plane["lines"] for _, s, d in line["events"]]
    edges += [(s, s + d) for line in host for n, s, d in line["events"] if n.startswith(T.SPAN_PREFIX)]
    return min(s for s, _ in edges), max(e for _, e in edges)


def split(trace: dict) -> dict | None:
    """Idle time outside device programs by owner and, for queue stalls, by
    what the pipeline was doing; see the module's docstring.  Seconds,
    summed over the devices."""
    devices = [p for p in trace["planes"] if p["name"].startswith(T.DEVICE_PREFIX)]
    host = [line for p in trace["planes"] if p["name"] == T.HOST_PLANE for line in p["lines"]]
    program = lambda line: [e for e in line["events"] if e[0].startswith(PROGRAM_PREFIX)]  # noqa: E731
    consumer, others = [], []
    for line in host:
        (consumer if any(n == CONSUMER_MARK for n, _, _ in line["events"]) else others).append(line)
    steps = sum(n == STEP for line in host for n, _, _ in line["events"])
    if not devices or not consumer or not steps or not any(program(line) for line in host):
        return None
    lo, hi = _window(trace, devices, host)

    owned: dict[str, list] = {}
    for line in consumer:
        for name, parts in innermost(program(line)).items():
            owned[name] = T.union(owned.get(name, []) + parts)
    nobody = T.gaps(T.union([iv for parts in owned.values() for iv in parts]), lo, hi)
    producing: dict[str, list] = {}
    for line in others:
        for name, start, dur in program(line):
            producing.setdefault(name, []).append((start, start + dur))
    producing = {name: T.union(parts) for name, parts in producing.items()}

    owner_s = dict.fromkeys(owned, 0.0)
    blame_s = dict.fromkeys(producing, 0.0)
    unowned_s = 0.0
    for plane in devices:
        # a program's interval is the device's own even where no operation runs
        running = T.union([(s, s + d) for name in (T.OPS_LINE, T.MODULES_LINE)
                           for _, s, d in T._line(plane, name)])
        outside = T.gaps(T.clip(running, lo, hi), lo, hi)
        for name, parts in owned.items():
            owner_s[name] += T.total(_common(outside, parts)) / 1e9
        unowned_s += T.total(_common(outside, nobody)) / 1e9
        stalled = _common(outside, owned.get(QUEUE, []))
        for name, parts in producing.items():
            blame_s[name] += T.total(_common(stalled, parts)) / 1e9
    return {
        "devices": len(devices), "steps": steps,
        "owner_s": owner_s, "unowned_s": unowned_s, "blame_s": blame_s,
        "outside_s": sum(owner_s.values()) + unowned_s,
    }


def outside_programs_s(reduced: dict) -> float:
    """The same total by ``trace.reduce_trace``: its idle gaps less the part
    inside a device program, seconds summed over the devices."""
    return sum(s for owner, s in reduced["idle_gaps"] if owner != INSIDE_PROGRAM)


def check(result: dict, reduced: dict) -> None:
    """The owners and nobody together are the whole idle time outside
    programs, as the benchmark's own reduction counts it."""
    mine, theirs = result["outside_s"], outside_programs_s(reduced)
    if abs(mine - theirs) > 0.01 * max(mine, theirs):
        raise ValueError(
            f"idle time outside device programs: {mine:.6f} s by program spans,"
            f" {theirs:.6f} s by trace.reduce_trace; is the newest trace this run's?"
        )


# ------------------------------------------------------------------ readers


@functools.lru_cache(maxsize=1)
def _split_file(path: str) -> dict | None:
    """Memoised by path: five readers, one parse; the loaded trace is dropped."""
    return split(load(path))


def of_run(sample: dict) -> dict | None:
    """:func:`split` of the run whose sample this is, checked against the
    run's own reduction; ``None`` where there is nothing to read."""
    plain = sample.get("trace_plain")
    if plain is None or not any(p["name"].startswith(T.DEVICE_PREFIX) for p in plain["planes"]):
        return None
    path = newest_xplane()
    result = None if path is None else _split_file(path)
    if result is not None:
        check(result, sample["trace"])
    return result


def _ms_step(sample: dict, seconds) -> float | None:
    """``seconds(split of the run)`` as milliseconds a device and step."""
    result = of_run(sample)
    return None if result is None else 1e3 * seconds(result) / (result["devices"] * result["steps"])


def owner_ms_step(sample: dict, span: str) -> float | None:
    return _ms_step(sample, lambda r: r["owner_s"].get(span, 0.0))


def unowned_ms_step(sample: dict) -> float | None:
    return _ms_step(sample, lambda r: r["unowned_s"])


def blame_ms_step(sample: dict, span: str) -> float | None:
    return _ms_step(sample, lambda r: r["blame_s"].get(span, 0.0))
