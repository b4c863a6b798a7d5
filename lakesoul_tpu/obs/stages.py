"""Stage attribution of the scan→train path, from a file's bytes to the
train step's dispatch.

One histogram family — ``lakesoul_scan_stage_seconds{stage=...}`` — shared
by every leg of the scan→train path, so the per-stage cost breakdown the
hot-path work is judged against (arxiv 2604.21275's discipline: measure per
stage, then delete what the measurement exposes) is a queryable series, not
a guess:

==================  =========================================================
``decode``          file bytes → Arrow batches (format readers)
``merge``           MOR merge-apply: loser tree / argsort + row gather
``fill``            schema-evolution uniform (cast/null-fill) + partition columns
``rebatch``         fixed-size window assembly in the loader
``collate``         Arrow window → numpy pytree (+ user transform)
``queue``           consumer stall on the loader's prefetch queue
``device_put``      host batch → device transfer dispatch
``train.place``     the step's wrapper placing the batch on its pinned
                    shardings (``models/train.py: _CountedStep.__call__``)
``train.dispatch``  the jitted step's dispatch: the host side of the call,
                    which returns before the device is done (the first call
                    of a shape traces and compiles inside it)
==================  =========================================================

The family is the data path's up to and through the step's door: the two
``train.*`` stages are the last host time a batch costs before the device has
it, so they sit beside ``device_put`` and not in a family of their own
(:data:`SCAN_STAGES`, what :func:`stage_seconds` sums, stays the seven the
loader owns).

On a compacted no-PK table the contract is DEGENERACY: ``merge`` and
``fill`` must report ~0 — the scan is a plain decode plan
(tests/test_scan_stages.py::TestDegeneracy holds it).

Two label dimensions beyond ``stage``:

- ``consumer=`` on the ``queue`` stage: with several concurrent loaders in
  one process (a trainer fleet on one host) an unlabeled stall histogram
  cannot say WHICH client starved — every loader tags its queue series
  (default ``local``).
- ``worker=`` on producer stages merged from another process: a scanplane
  worker ships its per-range (sum, count) deltas with each spooled range
  and the client folds them into its own registry via :func:`stage_merge`,
  so one snapshot shows remote decode/merge next to local collate/queue.

Aggregation helpers (:func:`stage_seconds` / :func:`stage_counts`) sum
across ALL series of a stage regardless of extra labels — the degeneracy
tests and the chip benchmark's readers see one number per stage, the
labeled series stay queryable for attribution.

Handles are memoized module-level (the registry is a process singleton).

:func:`stage` is the one seam every scan, loader and step-wrapper call site goes through:
``with stage("merge"):`` observes the stage's SELF time into the family
above and, for as long as it is open, holds a
``jax.profiler.TraceAnnotation`` named ``lakesoul.scan.<stage>`` (decode,
merge, fill), ``lakesoul.loader.<stage>`` (the loader's four) or
``lakesoul.<name>`` (any other: ``lakesoul.train.place``).  The annotation
lands on the profiler's clock, the one the device planes use, so a reader of
the trace can say which stage was open while the device sat idle; outside a
profiler session it records nothing.  The session is the only switch.
"""

from __future__ import annotations

import sys
import threading
import time

from lakesoul_tpu.obs.metrics import Histogram, registry

SCAN_STAGES = (
    "decode", "merge", "fill", "rebatch", "collate", "queue", "device_put",
)

STAGE_FAMILY = "lakesoul_scan_stage_seconds"

_handles: dict[tuple, Histogram] = {}


def stage_histogram(stage: str, **labels: str) -> Histogram:
    """The ``lakesoul_scan_stage_seconds`` histogram for one stage (plus
    optional attribution labels, e.g. ``consumer=`` for queue stalls or
    ``worker=`` for merged remote stages)."""
    key = (stage, tuple(sorted(labels.items())))
    h = _handles.get(key)
    if h is None:
        h = registry().histogram(STAGE_FAMILY, stage=stage, **labels)
        _handles[key] = h
    return h


# the profiler span of each stage; any other name is ``lakesoul.<name>``
_SPAN_NAMES = {
    s: ("lakesoul.scan." if s in ("decode", "merge", "fill") else "lakesoul.loader.") + s
    for s in SCAN_STAGES
}
_annotation = None  # jax.profiler.TraceAnnotation, once this process has jax
_open = threading.local()  # .stack: the stages open on this thread


def _trace_annotation():
    # never force the jax import: a process that has not imported jax has
    # no profiler session to record into, and a scan worker must not pay
    # XLA start-up for telemetry
    global _annotation
    if _annotation is None:
        profiler = getattr(sys.modules.get("jax"), "profiler", None)
        _annotation = getattr(profiler, "TraceAnnotation", None)
    return _annotation


class stage:
    """``with stage(name, **labels):`` — time one stage call.

    On exit the stage's self time (its duration less the stages opened
    inside it on the same thread: ``merge`` contains ``fill``) goes into
    ``lakesoul_scan_stage_seconds{stage=name, **labels}``, so the stages
    stay additive; the profiler span is the whole interval.  ``elapsed`` is
    that whole interval in seconds, for a caller that also feeds another
    series.  Never keep one open across a ``yield``: the stack is the
    thread's."""

    __slots__ = ("_hist", "_span", "_t0", "_inner", "elapsed")

    def __init__(self, name: str, **labels: str):
        self._hist = stage_histogram(name, **labels)
        annotation = _trace_annotation()
        self._span = None if annotation is None else annotation(
            _SPAN_NAMES.get(name) or "lakesoul." + name
        )

    def __enter__(self) -> "stage":
        try:
            stack = _open.stack
        except AttributeError:
            stack = _open.stack = []
        stack.append(self)
        self._inner = 0.0
        if self._span is not None:
            self._span.__enter__()
        self._t0 = time.perf_counter()
        return self

    def __exit__(self, *exc) -> None:
        self.elapsed = elapsed = time.perf_counter() - self._t0
        if self._span is not None:
            self._span.__exit__(*exc)
        stack = _open.stack
        stack.pop()
        if stack:
            stack[-1]._inner += elapsed
        self._hist.observe(max(0.0, elapsed - self._inner))


def stage_merge(stage: str, seconds: float, count: int, **labels: str) -> None:
    """Fold a cross-process (sum, count) stage delta into this process's
    registry — how a scanplane worker's decode/merge/fill time travels with
    its spooled ranges into the consuming client's snapshot."""
    stage_histogram(stage, **labels).merge(seconds, count)


def _family_series() -> list[tuple[dict, Histogram]]:
    return registry().series(STAGE_FAMILY)


def stage_seconds() -> dict[str, float]:
    """Cumulative seconds per stage since process start, summed across all
    labeled series of each stage (subtract two snapshots for a window's
    delta)."""
    out = {s: 0.0 for s in SCAN_STAGES}
    for labels, h in _family_series():
        stage = labels.get("stage")
        if stage in out:
            out[stage] += h.value["sum"]
    return out


def stage_counts() -> dict[str, int]:
    out = {s: 0 for s in SCAN_STAGES}
    for labels, h in _family_series():
        stage = labels.get("stage")
        if stage in out:
            out[stage] += h.value["count"]
    return out
