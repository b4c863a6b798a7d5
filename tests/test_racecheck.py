"""racecheck: the runtime Eraser detector must catch the seeded
shared-state race (with both access stacks), stay silent on locked and
init-phase writes, and instrument/restore the hot classes cleanly."""

from __future__ import annotations

import threading

import pytest

from lakesoul_tpu.analysis import racecheck


@pytest.fixture()
def clean_racecheck():
    racecheck.reset()
    yield
    racecheck.disable()
    racecheck.reset()


# ------------------------------------------------------------ lockset core


def test_catches_seeded_unsynchronized_writes(clean_racecheck):
    from fixtures import racebugs

    with racecheck.watch() as w:
        racecheck.instrument_class(racebugs.UnsyncCounter)
        c = racebugs.unsynchronized_writes()
    assert c.value == 100  # instrumentation must not change behavior
    kinds = {v.kind for v in w.violations}
    assert kinds == {"shared-state-write"}
    v = w.violations[0]
    assert "UnsyncCounter.value" in v.message
    assert "no common lock" in v.message
    # both access stacks ship with the report: the first writer's and the
    # racing writer's — the evidence a torn update never leaves on its own
    assert len(v.stacks) == 2
    assert "first writer" in v.stacks[0]
    assert "racing writer" in v.stacks[1]


def test_silent_on_synchronized_writes(clean_racecheck):
    from fixtures import racebugs

    with racecheck.watch() as w:
        racecheck.instrument_class(racebugs.SyncCounter)
        c = racebugs.synchronized_writes()
    assert c.value == 100
    assert w.violations == [], "\n".join(v.render() for v in w.violations)


def test_silent_on_init_phase_then_locked_publish(clean_racecheck):
    """Eraser's Virgin→Exclusive: the constructing thread writes unlocked
    (construction happens-before publication); a second thread publishing
    under a lock afterwards is the sanctioned hand-off."""
    from fixtures import racebugs

    with racecheck.watch() as w:
        racecheck.instrument_class(racebugs.HandoffFlag)
        f = racebugs.locked_publish_after_init()
    assert f.fenced is True
    assert w.violations == [], "\n".join(v.render() for v in w.violations)


def test_lockset_refines_not_first_lock(clean_racecheck):
    """Two threads alternating two different locks share NO common lock —
    the intersection (not any single access) is what must be non-empty."""

    class TwoLocks:
        def __init__(self):
            self.a = threading.Lock()
            self.b = threading.Lock()
            self.field = 0

        def via_a(self):
            with self.a:
                self.field += 1

        def via_b(self):
            with self.b:
                self.field += 1

    with racecheck.watch() as w:
        racecheck.instrument_class(TwoLocks)
        obj = TwoLocks()
        for fn in (obj.via_a, obj.via_b):
            t = threading.Thread(target=fn)
            t.start()
            t.join()
    assert {v.kind for v in w.violations} == {"shared-state-write"}
    assert "TwoLocks.field" in w.violations[0].message


def test_instrumentation_restores_on_disable(clean_racecheck):
    from lakesoul_tpu.runtime.resilience import CircuitBreaker

    racecheck.enable()
    assert hasattr(CircuitBreaker.__dict__.get("__setattr__"), "_racecheck_orig")
    racecheck.disable()
    assert "__setattr__" not in CircuitBreaker.__dict__ or not hasattr(
        CircuitBreaker.__dict__["__setattr__"], "_racecheck_orig"
    )


def test_hot_classes_run_clean_under_instrumentation(clean_racecheck):
    """The real resilience machinery (breaker under concurrent load) is the
    locked-discipline exemplar: zero violations."""
    from lakesoul_tpu.runtime.resilience import AdmissionController, CircuitBreaker

    with racecheck.watch() as w:
        breaker = CircuitBreaker("racecheck-probe", failure_threshold=2)
        gate = AdmissionController("racecheck-probe", max_inflight=2, max_queue=8)

        def hammer():
            for _ in range(50):
                try:
                    breaker.call(lambda: 1)
                except Exception:
                    pass
                with gate.admit():
                    pass

        threads = [threading.Thread(target=hammer) for _ in range(4)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
    assert w.violations == [], "\n".join(v.render() for v in w.violations)


def test_env_gate(monkeypatch):
    monkeypatch.delenv("LAKESOUL_RACECHECK", raising=False)
    assert not racecheck.env_requested()
    monkeypatch.setenv("LAKESOUL_RACECHECK", "1")
    assert racecheck.env_requested()
