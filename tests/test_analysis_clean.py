"""CI gate: the repo must lint clean — under ALL 39 rules: the 15
per-function ones (incl. ad-hoc-retry, wall-clock-lease,
hot-path-materialize, raw-process, unstoppable-loop,
replay-host-roundtrip, fleet-identity-label and hardcoded-endpoint), the
4 interprocedural ones (call graph + dataflow), the 5 device-pack ones
(jit/pallas trace safety), the 3 concurrency-pack ones (thread-root
locksets + buffer lifetimes), the 3 durability-pack ones (atomic
publication discipline over the runtime/atomicio seam), the 4
isolation-pack ones (READ COMMITTED portability of the metadata path),
and the 5 boundedness-pack ones (resource budgets + lifecycles — what a
soak run dies of).

``python -m lakesoul_tpu.analysis`` must exit 0 — zero unsuppressed
findings over the whole package — and the checked-in baseline must stay
honest: every suppression justified, none stale.  A new finding here means
either fix the code or add a *justified* baseline entry in the same PR."""

from __future__ import annotations

from lakesoul_tpu.analysis import run_repo
from lakesoul_tpu.analysis.engine import Baseline, default_baseline_path

EXPECTED_RULES = {
    # per-function (PR 3; ad-hoc-retry joined with the resilience layer,
    # wall-clock-lease with the lease table, hot-path-materialize with the
    # zero-copy scan path, raw-process with the scan-plane topology,
    # unstoppable-loop with the freshness follower, replay-host-roundtrip
    # with the tensor plane, fleet-identity-label with the fleet obs
    # plane, hardcoded-endpoint with the fleet transport plane)
    "raw-thread", "lock-held-call", "stage-nondeterminism",
    "unclosed-reader", "undocumented-env", "metric-name", "sqlite-scope",
    "ad-hoc-retry", "wall-clock-lease", "hot-path-materialize",
    "raw-process", "unstoppable-loop", "replay-host-roundtrip",
    "fleet-identity-label", "hardcoded-endpoint",
    # interprocedural
    "rbac-gate-reachability", "taint-path-segments",
    "transitive-lock-held-call", "interprocedural-unclosed-reader",
    # device pack (jit/pallas trace safety)
    "trace-impure-call", "trace-host-sync", "tpu-dtype-width",
    "jit-static-arg-shape", "pallas-blockspec",
    # concurrency pack (thread-root locksets + buffer lifetimes)
    "shared-state-race", "racy-check-then-act",
    "view-escapes-release",
    # durability pack (every publication rides runtime/atomicio; barriers
    # land after the data they cover)
    "torn-publish", "unfsynced-rename", "barrier-order",
    # isolation pack (the metadata path must survive PG at READ COMMITTED)
    "cas-guard", "read-modify-write", "txn-boundary", "sqlite-ism",
    # boundedness pack (bounded memory + clean resource lifecycles)
    "unbounded-queue", "unbounded-growth", "thread-lifecycle",
    "child-reap", "shm-debris",
}

DEVICE_RULES = {
    "trace-impure-call", "trace-host-sync", "tpu-dtype-width",
    "jit-static-arg-shape", "pallas-blockspec",
}

CONCURRENCY_RULES = {
    "shared-state-race", "racy-check-then-act",
    "view-escapes-release",
}

DURABILITY_RULES = {"torn-publish", "unfsynced-rename", "barrier-order"}

ISOLATION_RULES = {"cas-guard", "read-modify-write", "txn-boundary", "sqlite-ism"}

BOUNDEDNESS_RULES = {
    "unbounded-queue", "unbounded-growth", "thread-lifecycle",
    "child-reap", "shm-debris",
}


def test_all_rules_registered():
    """run_repo runs the full catalog — a rule silently dropped from the
    registry would turn this gate into a no-op for its invariant."""
    from lakesoul_tpu.analysis.rules import rule_ids

    ids = rule_ids()
    assert len(ids) == len(set(ids)) == 39
    assert set(ids) == EXPECTED_RULES


def test_package_lints_clean():
    findings, _ = run_repo()
    assert findings == [], "unsuppressed lint findings:\n" + "\n".join(
        f.render() for f in findings
    )


def test_interprocedural_rules_clean_repo_wide_without_baseline():
    """The four interprocedural rules hold with NO baseline entries at all:
    every intentionally-unguarded site carries an inline pragma whose
    reason names the invariant (the baseline is reserved for the
    pre-existing per-function suppressions)."""
    from lakesoul_tpu.analysis import Baseline, run
    from lakesoul_tpu.analysis.rules import all_rules

    interproc = [r for r in all_rules() if r.id in {
        "rbac-gate-reachability", "taint-path-segments",
        "transitive-lock-held-call", "interprocedural-unclosed-reader",
    }]
    assert len(interproc) == 4
    findings, _ = run(rules=interproc, baseline=Baseline([]))
    assert findings == [], "\n".join(f.render() for f in findings)


def test_baseline_entries_all_used_and_justified():
    baseline = Baseline.load(default_baseline_path())
    for e in baseline.entries:
        reason = e.get("reason", "")
        assert reason and "TODO" not in reason, (
            f"baseline entry for {e['path']} lacks a real justification"
        )
    _, baseline = run_repo()
    stale = baseline.stale_entries()
    assert stale == [], "stale baseline entries (delete them):\n" + "\n".join(
        f"[{e['rule']}] {e['path']}: {e['message']}" for e in stale
    )


def test_cli_gate_exit_zero(capsys):
    from lakesoul_tpu.analysis.__main__ import main

    assert main([]) == 0
    assert "clean" in capsys.readouterr().out


def test_console_lint_command(tmp_warehouse):
    from lakesoul_tpu import LakeSoulCatalog
    from lakesoul_tpu.service.console import Console

    c = Console(LakeSoulCatalog(str(tmp_warehouse)))
    out = c.execute("lint")
    assert "lint clean" in out
    assert "lint" in c.execute("help")


def test_device_pack_clean_repo_wide_without_baseline():
    """The five device rules hold with NO baseline entries at all: every
    intentionally-unguarded site carries an inline pragma whose reason
    names the invariant (same contract as the interprocedural rules)."""
    from lakesoul_tpu.analysis import Baseline, run
    from lakesoul_tpu.analysis.rules import all_rules

    device = [r for r in all_rules() if r.id in DEVICE_RULES]
    assert len(device) == 5
    findings, _ = run(rules=device, baseline=Baseline([]))
    assert findings == [], "\n".join(f.render() for f in findings)


def test_concurrency_pack_clean_repo_wide_without_baseline():
    """The four concurrency rules hold with NO baseline entries at all —
    the real shared-state findings this PR surfaced were FIXED (page-cache
    index under its lock, pipeline thread/queue registries under _lock,
    heartbeat publishes under a guard), not suppressed."""
    from lakesoul_tpu.analysis import Baseline, run
    from lakesoul_tpu.analysis.rules import all_rules

    conc = [r for r in all_rules() if r.id in CONCURRENCY_RULES]
    assert len(conc) == 3
    findings, _ = run(rules=conc, baseline=Baseline([]))
    assert findings == [], "\n".join(f.render() for f in findings)


def test_durability_pack_clean_repo_wide_without_baseline():
    """The three durability rules hold with NO baseline entries at all —
    the real findings this PR surfaced were FIXED by consolidating every
    publication (obs fleet docs, spool segments + session manifests, the
    spill rung, LATEST/PLANE store pointers, the freshness oracle doc)
    onto the runtime/atomicio seam, not suppressed."""
    from lakesoul_tpu.analysis import Baseline, run
    from lakesoul_tpu.analysis.rules import all_rules

    dur = [r for r in all_rules() if r.id in DURABILITY_RULES]
    assert len(dur) == 3
    findings, _ = run(rules=dur, baseline=Baseline([]))
    assert findings == [], "\n".join(f.render() for f in findings)


def test_isolation_pack_clean_repo_wide_without_baseline():
    """The four isolation rules hold with NO baseline entries at all — the
    real findings this PR surfaced were FIXED (client-side lease CAS,
    merge helpers made transactional, update_global_config's read locked,
    the :memory: cursor growing .rowcount), the four store call sites
    whose CAS shape the parser cannot see carry inline pragmas naming the
    predicate, and everything else holds by construction."""
    from lakesoul_tpu.analysis import Baseline, run
    from lakesoul_tpu.analysis.rules import all_rules

    iso = [r for r in all_rules() if r.id in ISOLATION_RULES]
    assert len(iso) == 4
    findings, _ = run(rules=iso, baseline=Baseline([]))
    assert findings == [], "\n".join(f.render() for f in findings)


def test_boundedness_pack_clean_repo_wide_without_baseline():
    """The five boundedness rules hold with NO baseline entries at all —
    the real findings this PR surfaced were FIXED (the exporter's serve
    thread joined on the shutdown path, the autoscaler's retire() handing
    terminated children to a reaped retiring list, default spool dirs
    pid-stamped + atexit-swept + prune_stale_spools for SIGKILLed owners),
    and the two window-bounded pipeline deques carry inline pragmas naming
    their structural bound."""
    from lakesoul_tpu.analysis import Baseline, run
    from lakesoul_tpu.analysis.rules import all_rules

    bound = [r for r in all_rules() if r.id in BOUNDEDNESS_RULES]
    assert len(bound) == 5
    findings, _ = run(rules=bound, baseline=Baseline([]))
    assert findings == [], "\n".join(f.render() for f in findings)


def test_readme_env_table_names_are_all_read():
    """The reverse of ``undocumented-env``: every ``LAKESOUL_*`` name in the
    README's environment table (a wildcard row by its prefix) is read
    somewhere in the package, ``chip_smoke.py`` or ``examples/``, so a row
    cannot outlive the code that read it."""
    import re

    from lakesoul_tpu.analysis.engine import package_root
    from lakesoul_tpu.analysis.rules.conventions import _ENV_DOC_RE

    root = package_root().parent
    sources = [*package_root().rglob("*.py"), root / "chip_smoke.py",
               *(root / "examples").rglob("*.py")]
    read = set()
    for path in sources:
        read.update(re.findall(r"LAKESOUL_[A-Z0-9_]+", path.read_text()))
    unread = []
    for line in (root / "README.md").read_text().splitlines():
        if not line.startswith("| `LAKESOUL_"):
            continue
        for name in _ENV_DOC_RE.findall(line.split("|")[1]):
            if name.endswith("*"):
                # a dynamic-prefix constant ("LAKESOUL_PROXY_S3_" + key) reads the family
                if not any(r.startswith(name[:-1]) for r in read):
                    unread.append(name)
            elif name not in read:
                unread.append(name)
    assert unread == [], f"README rows for names nothing reads: {unread}"
