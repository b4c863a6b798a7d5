"""lakelint: project-native static analysis + runtime lock-order and
retrace detection.

Four complementary layers:

- :mod:`engine` + :mod:`rules` — AST lint over the package with
  project-specific rules (thread discipline, lock-held blocking calls,
  stage determinism, reader lifetimes, env-var docs, metric naming, sqlite
  scope), a checked-in ``baseline.json`` and inline
  ``# lakelint: ignore[rule]`` pragmas.  CLI:
  ``python -m lakesoul_tpu.analysis`` (also installed as ``lakesoul-lint``
  and the console's ``lint`` command); CI gate:
  ``tests/test_analysis_clean.py``.
- :mod:`callgraph` + :mod:`dataflow` — the interprocedural layer: a
  project-wide call graph (conservative unknown edges for dynamic
  dispatch) and a forward taint framework, powering the whole-program
  rules (``rbac-gate-reachability``, ``taint-path-segments``,
  ``transitive-lock-held-call``, ``interprocedural-unclosed-reader``).
  Output/CI upgrades ride along: ``--format sarif`` (:mod:`sarif`) and the
  diff-aware ``--diff BASE`` gate (:mod:`gitdiff`).
- :mod:`rules.jaxtpu` — the device pack: five JAX/TPU trace-safety rules
  (``trace-impure-call``, ``trace-host-sync``, ``tpu-dtype-width``,
  ``jit-static-arg-shape``, ``pallas-blockspec``) over a shared device
  index (jit entries, pallas kernels, the traced-function closure) and
  the taint framework's device-value lattice.
- :mod:`threadroots` + :mod:`rules.races` + :mod:`rules.lifetime` — the
  concurrency-soundness pack: thread-root inference over the call graph
  (Thread targets, pool submissions, pipeline stages, ``do_*`` handlers)
  feeding Eraser-style static locksets (``shared-state-race``,
  ``racy-check-then-act``) and the zero-copy buffer-lifetime rules
  (``view-escapes-release``).
- :mod:`rules.boundedness` + :mod:`leakcheck` — the resource-boundedness
  pack: five lifecycle rules over the shared thread-root/call-graph
  indexes (``unbounded-queue``, ``unbounded-growth``,
  ``thread-lifecycle``, ``child-reap``, ``shm-debris``) paired with the
  runtime leak detector — ``LAKESOUL_LEAKCHECK=1`` patches the creation
  seams (``Thread.start``, ``Popen``, ``mkdtemp``, atomicio staging) and
  diffs per-scope fd/thread/child/artifact/heap inventories, reporting
  each leak with its creation stack; tests/test_leakcheck.py holds the
  counts flat over repeated open→scan→serve→close cycles.
- :mod:`lockgraph` / :mod:`tracecheck` / :mod:`racecheck` /
  :mod:`fscheck` / :mod:`txncheck` — the opt-in runtime detectors:
  ``LAKESOUL_LOCKCHECK=1`` instruments ``Lock``/``RLock`` to record the
  per-thread acquisition graph (lock-order cycles,
  lock-held-across-``pool.submit``); ``LAKESOUL_TRACECHECK=1`` wraps jit
  entry points to count distinct abstract signatures per function and
  flags functions that recompile beyond their budget;
  ``LAKESOUL_RACECHECK=1`` runs Eraser lockset tracking on the
  instrumented hot classes' field writes and arms the collate ring's
  canary/poison mode; ``LAKESOUL_FSCHECK=1`` replays every publication's
  crash prefixes ALICE-style at teardown; ``LAKESOUL_TXNCHECK=1``
  replays committed metadata transactions under READ COMMITTED
  interleavings.  All are wired into the test suite via conftest
  fixtures, and all record violations rather than raise.
"""

from lakesoul_tpu.analysis.engine import (
    Baseline,
    EngineError,
    Finding,
    Rule,
    default_baseline_path,
    run,
    run_repo,
)

__all__ = [
    "Baseline",
    "EngineError",
    "Finding",
    "Rule",
    "default_baseline_path",
    "run",
    "run_repo",
]
