"""lakelint: every rule must catch its seeded fixture bug, suppression must
work both ways (pragma + baseline), the call-graph builder must resolve
what it claims to resolve (and record what it cannot as unknown edges),
the interprocedural rules must catch their seeded cross-function bugs, the
SARIF/diff output contracts must hold, and the lockgraph detector must
catch the seeded lock-order inversion and lock-held-across-submit — and
stay silent on correct code, including the real runtime/meta paths."""

from __future__ import annotations

import json
import pathlib
import subprocess
import threading

import pytest

from lakesoul_tpu.analysis import Baseline, run
from lakesoul_tpu.analysis import lockgraph
from lakesoul_tpu.analysis.engine import Module, Project
from lakesoul_tpu.analysis.rules.determinism import StageNondeterminismRule
from lakesoul_tpu.analysis.rules.security import (
    RbacGateReachabilityRule,
    TaintPathSegmentsRule,
)

FIXTURES = pathlib.Path(__file__).resolve().parent / "fixtures"
LINT = FIXTURES / "lint"
INTERPROC = LINT / "interproc"


def lint_fixture(name: str, rules=None):
    findings, _ = run([LINT / name], root=LINT, rules=rules)
    return findings


def assert_seed_lines(findings, fixture_rel: str, rule: str):
    """Every finding for ``rule`` sits on a line carrying its SEED marker,
    and every SEED marker in the fixture is found — no misses, no drift."""
    src = (LINT / fixture_rel).read_text().splitlines()
    seeded = {
        i + 1 for i, line in enumerate(src) if f"SEED: {rule}" in line
    }
    got = {f.line for f in findings if f.rule == rule}
    assert got == seeded, (rule, sorted(got), sorted(seeded))


# --------------------------------------------------------------- lint rules


def test_raw_thread_rule_catches_both_primitives():
    found = lint_fixture("bad_threads.py")
    rules = [f.rule for f in found]
    assert rules.count("raw-thread") == 2
    lines = {f.line for f in found if f.rule == "raw-thread"}
    src = (LINT / "bad_threads.py").read_text().splitlines()
    for line in lines:
        assert "SEED: raw-thread" in src[line - 1]


def test_lock_held_call_rule_catches_each_blocking_call():
    found = [f for f in lint_fixture("bad_locks.py") if f.rule == "lock-held-call"]
    called = sorted(f.message.split("(", 1)[0] for f in found)
    assert len(found) == 5, found
    assert any("submit" in c for c in called)
    assert any("result" in c for c in called)
    assert any("sleep" in c for c in called)
    assert any("worker_thread.join" in c for c in called)
    assert any(c.strip() == "open" for c in called)
    # the closure body must NOT be flagged (runs outside the lock)
    src = (LINT / "bad_locks.py").read_text().splitlines()
    for f in found:
        assert "SEED: lock-held-call" in src[f.line - 1]


def test_stage_nondeterminism_rule():
    rules = [StageNondeterminismRule(scope=("bad_stage.py",))]
    found = [
        f for f in lint_fixture("bad_stage.py", rules=rules)
        if f.rule == "stage-nondeterminism"
    ]
    assert len(found) == 3, found
    src = (LINT / "bad_stage.py").read_text().splitlines()
    for f in found:
        assert "SEED: stage-nondeterminism" in src[f.line - 1]
    # out-of-scope module: silent even with violations present
    assert lint_fixture("bad_stage.py") == []


def test_ad_hoc_retry_rule_line_exact():
    """The 17th rule: for-range retry loops (swallowed exceptions) and
    sleep-based backoff are flagged line-exactly; re-raising handlers,
    while-polls, and plain range loops stay silent."""
    found = [f for f in lint_fixture("bad_retry.py") if f.rule == "ad-hoc-retry"]
    assert len(found) == 3, found
    assert_seed_lines(found, "bad_retry.py", "ad-hoc-retry")
    messages = sorted(f.message for f in found)
    assert sum(m.startswith("for-range loop") for m in messages) == 2
    assert sum(m.startswith("sleep-based backoff") for m in messages) == 1


def test_wall_clock_lease_rule_line_exact():
    """The 18th rule: time.time() arithmetic in TTL/deadline/lease math is
    flagged line-exactly; plain epoch stamping, monotonic math, and
    keyword-free control expressions stay silent."""
    from lakesoul_tpu.analysis.rules.wallclock import WallClockLeaseRule

    rules = [WallClockLeaseRule(scope=("bad_wallclock.py",))]
    found = [
        f for f in lint_fixture("bad_wallclock.py", rules=rules)
        if f.rule == "wall-clock-lease"
    ]
    assert len(found) == 5, found
    assert_seed_lines(found, "bad_wallclock.py", "wall-clock-lease")
    # out-of-scope path (fixture root isn't service/compaction/meta): the
    # default-scoped catalog stays silent even with violations present
    assert lint_fixture("bad_wallclock.py") == []


def test_durability_rules_line_exact():
    """The durability pack: bare write-mode opens on publication paths
    (torn-publish, including the interprocedural rename-of-callee-written
    flow), renames whose flow never fsyncs (unfsynced-rename), and
    barriers — CRC sidecars, LATEST pointers — published before their
    data (barrier-order) are flagged line-exactly; the atomicio-routed,
    fsynced, data-then-barrier shapes stay silent."""
    from lakesoul_tpu.analysis.rules.durability import (
        BarrierOrderRule,
        TornPublishRule,
        UnfsyncedRenameRule,
    )

    scope = ("bad_durability.py",)
    rules = [
        TornPublishRule(scope=scope),
        UnfsyncedRenameRule(scope=scope),
        BarrierOrderRule(scope=scope),
    ]
    found = lint_fixture("bad_durability.py", rules=rules)
    assert len(found) == 9, found
    assert_seed_lines(found, "bad_durability.py", "torn-publish")
    assert_seed_lines(found, "bad_durability.py", "unfsynced-rename")
    assert_seed_lines(found, "bad_durability.py", "barrier-order")
    messages = " ".join(f.message for f in found)
    assert "runtime/atomicio" in messages
    assert "empty inode" in messages
    assert "barrier" in messages
    # the fixture is outside the default publication-module scope: the
    # full default catalog stays silent on it
    assert lint_fixture("bad_durability.py") == []


def test_isolation_cas_guard_line_exact():
    """Blind coordination-table writes: PK-only lease updates, CAS whose
    rowcount is never read, DELETE FROM lease (tombstone invariant), and
    partition writes missing the version column are flagged line-exactly;
    the full-CAS-with-rowcount shape stays silent."""
    from lakesoul_tpu.analysis.rules.isolation import CasGuardRule

    found = lint_fixture(
        "bad_isolation.py", rules=[CasGuardRule(scope=("bad_isolation.py",))]
    )
    assert len(found) == 4, found
    assert_seed_lines(found, "bad_isolation.py", "cas-guard")
    messages = " ".join(f.message for f in found)
    assert "tombstoned" in messages
    assert ".rowcount" in messages
    assert "READ COMMITTED" in messages


def test_isolation_read_modify_write_line_exact():
    """Store reads flowing into dependent blind writes — direct and split
    across a helper — are flagged at the sink; the same pair inside a
    ``with store.transaction()`` block is sanctioned."""
    from lakesoul_tpu.analysis.rules.isolation import ReadModifyWriteRule

    found = lint_fixture(
        "bad_isolation.py",
        rules=[ReadModifyWriteRule(scope=("bad_isolation.py",))],
    )
    assert len(found) == 2, found
    assert_seed_lines(found, "bad_isolation.py", "read-modify-write")
    # the interprocedural flow names both hops
    chains = " ".join(f.message for f in found)
    assert "rmw_via_helper" in chains and "_publish" in chains


def test_isolation_txn_boundary_line_exact():
    """Autocommit write statements and seam reach-arounds
    (store._exec/_txn/_conn outside meta/store.py) are flagged
    line-exactly; transaction()-wrapped writes and conn-routed helpers
    stay silent."""
    from lakesoul_tpu.analysis.rules.isolation import TxnBoundaryRule

    found = lint_fixture(
        "bad_isolation.py",
        rules=[TxnBoundaryRule(scope=("bad_isolation.py",))],
    )
    assert len(found) == 5, found
    assert_seed_lines(found, "bad_isolation.py", "txn-boundary")


def test_isolation_sqlite_ism_line_exact():
    """sqlite-only SQL outside the sqlite backend class — OR REPLACE,
    datetime('now'), rowid, AUTOINCREMENT, PRAGMA, and qmark/OR-IGNORE
    bound past translate_sql via a raw execute — is flagged line-exactly;
    the Sqlite* class speaks sqlite freely."""
    from lakesoul_tpu.analysis.rules.isolation import SqliteIsmRule

    found = lint_fixture(
        "bad_isolation.py", rules=[SqliteIsmRule(scope=("bad_isolation.py",))]
    )
    assert len(found) == 7, found
    assert_seed_lines(found, "bad_isolation.py", "sqlite-ism")


def test_isolation_default_scope_is_the_metadata_path():
    """The per-module isolation rules default to meta/ (and txn-boundary
    to the package): the fixture sits outside all of them, so the
    default-scoped instances stay silent even with violations present.
    (read-modify-write is repo-wide by design — flows START anywhere.)"""
    from lakesoul_tpu.analysis.rules.isolation import (
        CasGuardRule,
        SqliteIsmRule,
        TxnBoundaryRule,
    )

    rules = [CasGuardRule(), TxnBoundaryRule(), SqliteIsmRule()]
    assert lint_fixture("bad_isolation.py", rules=rules) == []


def test_durability_sanctioned_seam_exempt_from_torn_publish():
    """runtime/atomicio.py is the ONE module allowed to hold raw
    write-mode opens — torn-publish skips it while unfsynced-rename and
    barrier-order still apply (the seam itself fsyncs before renaming)."""
    from lakesoul_tpu.analysis import Baseline, run
    from lakesoul_tpu.analysis.rules.durability import (
        BarrierOrderRule,
        TornPublishRule,
        UnfsyncedRenameRule,
    )

    rules = [TornPublishRule(), UnfsyncedRenameRule(), BarrierOrderRule()]
    findings, _ = run(rules=rules, baseline=Baseline([]))
    atomicio = [f for f in findings if "atomicio" in f.path]
    assert atomicio == [], "\n".join(f.render() for f in atomicio)


def test_raw_process_rule_line_exact():
    """The 24th rule: ad-hoc subprocess spawning (dotted and from-imported),
    multiprocessing (import and calls), os.fork, and raw socket-server
    construction are flagged line-exactly; the pragma escape hatch and
    merely process-shaped attribute names stay silent."""
    found = [f for f in lint_fixture("bad_process.py") if f.rule == "raw-process"]
    assert len(found) == 8, found
    assert_seed_lines(found, "bad_process.py", "raw-process")
    messages = " ".join(f.message for f in found)
    assert "unsupervised child process" in messages
    assert "multiprocessing" in messages
    assert "raw serving socket" in messages


def test_raw_process_allows_topology_layers(tmp_path):
    """The same shapes inside scanplane//runtime/ (and the sanctioned
    serving entries) are the POINT of those layers — the rule keys on the
    module path, so the real package lints clean (test_analysis_clean)
    while the fixture catches every seeded site."""
    from lakesoul_tpu.analysis.rules.process import RawProcessRule

    rule = RawProcessRule()
    src = (LINT / "bad_process.py").read_text()
    for rel in (
        "lakesoul_tpu/scanplane/service.py",
        "lakesoul_tpu/runtime/pool.py",
        "lakesoul_tpu/obs/exporter.py",
        "lakesoul_tpu/service/storage_proxy.py",
    ):
        p = tmp_path / rel
        p.parent.mkdir(parents=True, exist_ok=True)
        p.write_text(src)
        mod = Module.load(p, tmp_path)
        assert list(rule.check(mod)) == [], rel


def test_unstoppable_loop_rule_line_exact():
    """The 25th rule: while-True poll loops that sleep blind in the
    service layers are flagged line-exactly; event-riding waits,
    while-not-stop conditions, in-body stop checks, attempt budgets that
    raise, and sleepless data-drain loops stay silent."""
    from lakesoul_tpu.analysis.rules.loops import UnstoppableLoopRule

    rules = [UnstoppableLoopRule(scope=("bad_loop.py",))]
    found = [
        f for f in lint_fixture("bad_loop.py", rules=rules)
        if f.rule == "unstoppable-loop"
    ]
    assert len(found) == 2, found
    assert_seed_lines(found, "bad_loop.py", "unstoppable-loop")
    assert "stop" in found[0].message
    # out-of-scope path (fixture root isn't streaming//compaction//
    # scanplane//freshness/): the default-scoped catalog stays silent
    assert lint_fixture("bad_loop.py") == []


def test_unstoppable_loop_allows_real_service_loops(tmp_path):
    """The settled real-code idioms — compaction's run_forever
    (stop.wait-paced), the scan-plane client's attempt-budget reconnect
    loop — stay silent under the default scope."""
    import pathlib

    from lakesoul_tpu.analysis.rules.loops import UnstoppableLoopRule

    rule = UnstoppableLoopRule()
    repo = pathlib.Path(__file__).resolve().parents[1]
    for rel in (
        "lakesoul_tpu/compaction/service.py",
        "lakesoul_tpu/scanplane/client.py",
        "lakesoul_tpu/scanplane/worker.py",
        "lakesoul_tpu/streaming/db_sync.py",
        "lakesoul_tpu/freshness/follower.py",
    ):
        mod = Module.load(repo / rel, repo)
        assert mod is not None, rel
        assert list(rule.check(mod)) == [], rel


def test_hot_path_materialize_rule_line_exact():
    """The 19th rule: concat_tables / .combine_chunks() / .to_pandas() in
    the scan/loader hot-path modules are flagged line-exactly; zero-copy
    window assembly and pragma'd bounded copies stay silent."""
    from lakesoul_tpu.analysis.rules.perf import HotPathMaterializeRule

    rules = [HotPathMaterializeRule(scope=("bad_hotpath.py",))]
    found = [
        f for f in lint_fixture("bad_hotpath.py", rules=rules)
        if f.rule == "hot-path-materialize"
    ]
    assert len(found) == 4, found
    assert_seed_lines(found, "bad_hotpath.py", "hot-path-materialize")
    # out-of-scope path (fixture root isn't the scan/loader modules): the
    # default-scoped catalog stays silent even with violations present
    assert lint_fixture("bad_hotpath.py") == []


def test_shared_state_race_rule_line_exact():
    """The lockset rule: fields written from ≥2 thread roots with no common
    lock are flagged line-exactly; one-lock-everywhere fields,
    condition-aliased locks, and single-root writers stay silent."""
    from lakesoul_tpu.analysis.rules.races import SharedStateRaceRule

    rules = [SharedStateRaceRule(scope=("bad_races.py",))]
    found = [
        f for f in lint_fixture("bad_races.py", rules=rules)
        if f.rule == "shared-state-race"
    ]
    assert len(found) == 2, found
    assert_seed_lines(found, "bad_races.py", "shared-state-race")
    msgs = "\n".join(f.message for f in found)
    assert "self.count" in msgs and "self.pending" in msgs
    assert "thread:Telemetry.worker_loop" in msgs and "main" in msgs
    assert "self.synced" not in msgs  # locked twin
    assert "self.depth" not in msgs  # condition-aliased lock agrees
    assert "self.cursor" not in msgs  # single-root writer
    # out-of-scope (the default scope is the package): the catalog's only
    # finding on this fixture is the raw Thread the race needs to exist
    assert {f.rule for f in lint_fixture("bad_races.py")} == {"raw-thread"}


def test_racy_check_then_act_rule_line_exact():
    from lakesoul_tpu.analysis.rules.races import RacyCheckThenActRule

    rules = [RacyCheckThenActRule(scope=("bad_races.py",))]
    found = [
        f for f in lint_fixture("bad_races.py", rules=rules)
        if f.rule == "racy-check-then-act"
    ]
    assert len(found) == 2, found
    assert_seed_lines(found, "bad_races.py", "racy-check-then-act")
    msgs = "\n".join(f.message for f in found)
    assert "self.pending" in msgs and "TOCTOU" in msgs
    # the locked twin (drain_locked) must stay silent — the check and the
    # act are atomic under the class lock; a non-lock `with` (spill's
    # open()) shields nothing


def test_view_escapes_release_rule_line_exact():
    from lakesoul_tpu.analysis.rules.lifetime import ViewEscapesReleaseRule

    rules = [ViewEscapesReleaseRule(scope=("bad_viewescape.py",))]
    found = [
        f for f in lint_fixture("bad_viewescape.py", rules=rules)
        if f.rule == "view-escapes-release"
    ]
    assert len(found) == 4, found
    assert_seed_lines(found, "bad_viewescape.py", "view-escapes-release")
    msgs = "\n".join(f.message for f in found)
    assert "is stored" in msgs and "is returned" in msgs
    assert "is closed over" in msgs
    # the sanctioned shape stays silent: the view-travels-with-its-batch
    # tuple (push_ok)
    # out-of-scope default: the rule default-scopes to data/jax_iter.py
    assert lint_fixture("bad_viewescape.py") == []


def test_replay_host_roundtrip_rule_line_exact():
    """The 26th rule: np.asarray / .tolist() / .to_pandas() host
    materializations inside the tensor plane are flagged line-exactly;
    device-side accounting/permutation and the pragma'd verification
    readback stay silent."""
    from lakesoul_tpu.analysis.rules.replay import ReplayHostRoundtripRule

    rules = [ReplayHostRoundtripRule(scope=("bad_replay.py",))]
    found = [
        f for f in lint_fixture("bad_replay.py", rules=rules)
        if f.rule == "replay-host-roundtrip"
    ]
    assert len(found) == 4, found
    assert_seed_lines(found, "bad_replay.py", "replay-host-roundtrip")
    msgs = "\n".join(f.message for f in found)
    assert "asarray" in msgs and ".tolist()" in msgs and ".to_pandas()" in msgs
    # out-of-scope default: the rule scopes to lakesoul_tpu/tensorplane/
    assert lint_fixture("bad_replay.py") == []


def test_thread_root_inference_on_fixture():
    """The root index must see the Thread(target=) entry, keep the worker
    off the main root, and leave uncalled public methods main-rooted."""
    from lakesoul_tpu.analysis.threadroots import thread_roots

    project = Project(root=LINT)
    project.modules.append(Module.load(LINT / "bad_races.py", LINT))
    idx = thread_roots(project)
    assert ("thread", "bad_races.py::Telemetry.worker_loop") in idx.entries
    worker = idx.roots_of("bad_races.py::Telemetry.worker_loop")
    assert worker == {"thread:bad_races.py::Telemetry.worker_loop"}
    assert idx.roots_of("bad_races.py::Telemetry.reset") == {"main"}


def test_thread_root_inference_on_real_loader():
    """Real-repo shapes: the pipeline source generator carries the pipeline
    root, the lease heartbeat its thread root, the Flight verbs handler
    roots — and the per-request HTTP handler collapses to ONE root."""
    from lakesoul_tpu.analysis.engine import package_root
    from lakesoul_tpu.analysis.threadroots import thread_roots

    project = Project(root=package_root().parent)
    for rel in (
        "data/jax_iter.py", "compaction/service.py", "service/flight.py",
        "service/storage_proxy.py",
    ):
        mod = Module.load(package_root() / rel, package_root().parent)
        assert mod is not None
        project.modules.append(mod)
    idx = thread_roots(project)
    kinds = {k for k, _ in idx.entries}
    assert {"thread", "pipeline", "handler"} <= kinds
    hb = idx.roots_of(
        "lakesoul_tpu/compaction/service.py::_LeaseHeartbeat._run"
    )
    assert any(r.startswith("thread:") for r in hb)
    src = idx.roots_of(
        "lakesoul_tpu/data/jax_iter.py::JaxBatchIterator._epoch_windows"
    )
    assert any(r.startswith("pipeline:") for r in src)
    # every do_* verb of the per-request proxy handler shares one root
    proxy_roots = {
        r
        for q, roots in idx.roots.items()
        if "storage_proxy.py::StorageProxy.__init__.Handler.do_" in q
        for r in roots
        if r.startswith("handler:")
    }
    assert len(proxy_roots) == 1, proxy_roots


def test_concurrency_rules_silent_on_real_hot_modules():
    """The fixed runtime/pipeline, page cache, loader, serving and
    heartbeat modules hold under the whole concurrency pack with NO
    baseline: the PR-8/PR-6 machinery is lockset-clean."""
    from lakesoul_tpu.analysis.rules.lifetime import ViewEscapesReleaseRule
    from lakesoul_tpu.analysis.rules.races import (
        RacyCheckThenActRule,
        SharedStateRaceRule,
    )

    findings, _ = run(rules=[
        SharedStateRaceRule(), RacyCheckThenActRule(),
        ViewEscapesReleaseRule(),
    ], baseline=Baseline([]))
    assert findings == [], "\n".join(f.render() for f in findings)


def test_hot_path_modules_clean_without_baseline():
    """The three hot-path modules hold under the rule with NO baseline at
    all: every surviving materialization carries an inline pragma whose
    reason names why the copy is legal (zero-copy chunk-list ops, bounded
    remainder copies)."""
    from lakesoul_tpu.analysis.rules.perf import HotPathMaterializeRule

    found, _ = run(rules=[HotPathMaterializeRule()], baseline=Baseline([]))
    assert [f for f in found if f.rule == "hot-path-materialize"] == [], found


def test_ad_hoc_retry_rule_exempts_resilience_module(tmp_path):
    """The one legal retry loop lives in runtime/resilience.py — the same
    shape there must not be flagged."""
    mod = tmp_path / "runtime"
    mod.mkdir()
    target = mod / "resilience.py"
    target.write_text(
        "import time\n"
        "def run(fn):\n"
        "    for attempt in range(3):\n"
        "        try:\n"
        "            return fn()\n"
        "        except OSError:\n"
        "            time.sleep(0.01)\n"
    )
    found, _ = run([target], root=tmp_path)
    assert [f for f in found if f.rule == "ad-hoc-retry"] == []


def test_unclosed_reader_rule_flags_each_leak_tier_only():
    found = [f for f in lint_fixture("bad_resources.py") if f.rule == "unclosed-reader"]
    src = (LINT / "bad_resources.py").read_text().splitlines()
    assert len(found) == 3, found
    for f in found:
        assert "SEED: unclosed-reader" in src[f.line - 1]


def test_undocumented_env_rule_reads_readme_table():
    found = [f for f in lint_fixture("bad_env.py") if f.rule == "undocumented-env"]
    assert len(found) == 1
    assert "LAKESOUL_UNDOCUMENTED_KNOB" in found[0].message


def test_undocumented_env_wildcard_direction(tmp_path):
    """A wildcard README row covers vars UNDER the prefix and explicit
    dynamic-prefix constants (ending in "_"), but a var that merely happens
    to be a prefix of the row must NOT pass."""
    (tmp_path / "README.md").write_text(
        "| `LAKESOUL_PROXY_S3_*` | unset | proxy config |\n"
    )
    (tmp_path / "mod.py").write_text(
        'import os\n'
        'a = os.environ.get("LAKESOUL_PROXY_S3_ENDPOINT")  # covered\n'
        'b = "LAKESOUL_PROXY_S3_"  # dynamic prefix: covered\n'
        'c = os.environ.get("LAKESOUL_PROXY")  # NOT documented\n'
    )
    found, _ = run([tmp_path / "mod.py"], root=tmp_path)
    env = [f for f in found if f.rule == "undocumented-env"]
    assert len(env) == 1, env
    assert env[0].message.startswith("LAKESOUL_PROXY ")


def test_metric_name_rule_scheme_suffixes_and_kind_clash():
    found = [f for f in lint_fixture("bad_metrics.py") if f.rule == "metric-name"]
    msgs = "\n".join(f.message for f in found)
    assert "'BadCamelName'" in msgs
    assert "'lakesoul_widget_count'" in msgs and "_total" in msgs
    assert "'lakesoul_widget_latency'" in msgs and "_seconds" in msgs
    assert "multiple kinds" in msgs and "'lakesoul_clash_total'" in msgs
    assert len(found) == 4, found


def test_fleet_identity_label_rule_seed_exact():
    """Literal and f-string identity labels (role=/service_id=/worker=) at
    metric/stage call sites are flagged line-exactly; values routed through
    the obs.fleet identity helpers (or any variable/attribute) pass."""
    findings = [
        f for f in lint_fixture("bad_identity.py")
        if f.rule == "fleet-identity-label"
    ]
    assert_seed_lines(findings, "bad_identity.py", "fleet-identity-label")
    msgs = "\n".join(f.message for f in findings)
    assert "role=" in msgs and "service_id=" in msgs and "worker=" in msgs
    assert all("identity_labels()" in f.message for f in findings)


def test_hardcoded_endpoint_rule_seed_exact():
    """Literal endpoints (URI with nonzero port, bare host:port with a
    real host, loopback URIs) are flagged line-exactly; port-0 ephemeral
    binds, env-lookup defaults, and word:digits labels pass."""
    findings = [
        f for f in lint_fixture("bad_endpoint.py")
        if f.rule == "hardcoded-endpoint"
    ]
    assert_seed_lines(findings, "bad_endpoint.py", "hardcoded-endpoint")
    msgs = "\n".join(f.message for f in findings)
    assert "grpc://10.0.0.5:8815" in msgs
    assert all("configuration" in f.message for f in findings)


def test_sqlite_scope_rule():
    found = [f for f in lint_fixture("bad_sqlite.py") if f.rule == "sqlite-scope"]
    assert len(found) >= 2  # import + connect (cursor heuristic is a bonus)
    msgs = "\n".join(f.message for f in found)
    assert "import sqlite3" in msgs
    assert "sqlite3.connect" in msgs


# ---------------------------------------------------------------- callgraph


def _interproc_project() -> Project:
    project = Project(root=LINT)
    for p in sorted(INTERPROC.glob("*.py")):
        mod = Module.load(p, LINT)
        if mod is not None:
            project.modules.append(mod)
    return project


def test_callgraph_builds_nodes_and_resolves_edges():
    graph = _interproc_project().callgraph()
    # module functions, class methods and the module pseudo-node all exist
    assert "interproc/bad_lockchain.py::_helper" in graph.functions
    assert "interproc/bad_gate.py::BadServer.do_action" in graph.functions
    fn = graph.functions["interproc/bad_gate.py::BadServer.do_action"]
    assert fn.is_method and fn.class_qname == "interproc/bad_gate.py::BadServer"
    # plain-name resolution: do_work → _helper → _inner
    edges = graph.callees("interproc/bad_lockchain.py::do_work")
    assert any(e.callee == "interproc/bad_lockchain.py::_helper" for e in edges)
    edges = graph.callees("interproc/bad_lockchain.py::_helper")
    assert any(e.callee == "interproc/bad_lockchain.py::_inner" for e in edges)
    # self.<method> resolution through the enclosing class
    edges = graph.callees("interproc/bad_gate.py::BadServer.do_action")
    assert any(
        e.callee == "interproc/bad_gate.py::BadServer._mutate_helper"
        for e in edges
    )


def test_callgraph_records_unknown_edges_conservatively():
    graph = _interproc_project().callgraph()
    # self.catalog.drop_table: dynamic receiver → unknown edge with the
    # receiver/attr text preserved for rules to pattern-match
    edges = graph.callees("interproc/bad_gate.py::BadServer._mutate_helper")
    dyn = [e for e in edges if e.attr == "drop_table"]
    assert len(dyn) == 1 and not dyn[0].resolved
    assert dyn[0].receiver == "self.catalog"
    assert dyn[0].raw == "self.catalog.drop_table"
    stats = graph.stats()
    assert stats["unknown_edges"] >= 1 and stats["resolved_edges"] >= 4


def test_callgraph_resolves_base_class_methods():
    """``self._check`` on the Flight SQL server resolves into the base
    gateway class — the real cross-module shape the RBAC rule leans on."""
    from lakesoul_tpu.analysis.engine import package_root

    project = Project(root=package_root().parent)
    for rel in ("service/flight.py", "service/flight_sql.py"):
        mod = Module.load(package_root() / rel, package_root().parent)
        assert mod is not None
        project.modules.append(mod)
    graph = project.callgraph()
    q = graph.resolve_method(
        "lakesoul_tpu/service/flight_sql.py::LakeSoulFlightSqlServer", "_check"
    )
    assert q == "lakesoul_tpu/service/flight.py::LakeSoulFlightServer._check"


# ------------------------------------------------------ interprocedural rules


def test_rbac_gate_reachability_catches_gate_skipping_helper():
    rules = [RbacGateReachabilityRule(scope=("interproc/bad_gate.py",))]
    found = lint_fixture("interproc/bad_gate.py", rules=rules)
    assert_seed_lines(found, "interproc/bad_gate.py", "rbac-gate-reachability")
    assert len(found) == 1
    msg = found[0].message
    assert "do_action" in msg and "_mutate_helper" in msg


def test_taint_path_segments_catches_laundered_segment():
    rules = [TaintPathSegmentsRule(scope=("interproc/bad_taint.py",))]
    found = lint_fixture("interproc/bad_taint.py", rules=rules)
    assert_seed_lines(found, "interproc/bad_taint.py", "taint-path-segments")
    assert len(found) == 1
    assert "do_PUT" in found[0].message and "_write_to" in found[0].message


def test_transitive_lock_held_call_catches_chain():
    found = [
        f for f in lint_fixture("interproc/bad_lockchain.py")
        if f.rule == "transitive-lock-held-call"
    ]
    assert_seed_lines(
        found, "interproc/bad_lockchain.py", "transitive-lock-held-call"
    )
    assert len(found) == 1
    assert "time.sleep" in found[0].message and "_inner" in found[0].message
    # the lexical rule must NOT double-report the chain
    assert not [
        f for f in lint_fixture("interproc/bad_lockchain.py")
        if f.rule == "lock-held-call"
    ]


def test_interprocedural_unclosed_reader_catches_drops():
    found = [
        f for f in lint_fixture("interproc/bad_reader_drop.py")
        if f.rule == "interprocedural-unclosed-reader"
    ]
    assert_seed_lines(
        found, "interproc/bad_reader_drop.py", "interprocedural-unclosed-reader"
    )
    assert len(found) == 2  # handed-to-dropping-helper + factory result dropped
    msgs = "\n".join(f.message for f in found)
    assert "drops it" in msgs and "returns an open reader" in msgs


def test_interproc_rules_silent_on_real_gateways():
    """The real service/ modules (post-fix) must be clean under the
    interprocedural rules without any baseline — pragmas only."""
    from lakesoul_tpu.analysis.engine import package_root
    from lakesoul_tpu.analysis.rules.concurrency import TransitiveLockHeldCallRule

    findings, _ = run(
        [package_root() / "service"],
        rules=[
            RbacGateReachabilityRule(),
            TaintPathSegmentsRule(),
            TransitiveLockHeldCallRule(),
        ],
    )
    assert findings == [], "\n".join(f.render() for f in findings)


# ------------------------------------------- boundedness pack (rules 35-39)


def _boundedness_rules():
    from lakesoul_tpu.analysis.rules.boundedness import (
        ChildReapRule,
        ShmDebrisRule,
        ThreadLifecycleRule,
        UnboundedGrowthRule,
        UnboundedQueueRule,
    )

    scope = ("bad_leaks.py",)
    return {
        "unbounded-queue": UnboundedQueueRule(scope=scope),
        "unbounded-growth": UnboundedGrowthRule(scope=scope),
        "thread-lifecycle": ThreadLifecycleRule(scope=scope),
        "child-reap": ChildReapRule(scope=scope),
        "shm-debris": ShmDebrisRule(scope=scope),
    }


def test_unbounded_queue_line_exact():
    """Queue()/deque()/SimpleQueue() without a bound are flagged
    line-exactly; every capacity-carrying construction stays silent."""
    found = lint_fixture(
        "bad_leaks.py", rules=[_boundedness_rules()["unbounded-queue"]]
    )
    assert len(found) == 3, found
    assert_seed_lines(found, "bad_leaks.py", "unbounded-queue")
    messages = " ".join(f.message for f in found)
    assert "SimpleQueue" in messages and "maxlen" in messages


def test_unbounded_growth_line_exact():
    """The background service loop appending to an unevicted self-list is
    flagged; the draining and ring-bounded variants stay silent."""
    found = lint_fixture(
        "bad_leaks.py", rules=[_boundedness_rules()["unbounded-growth"]]
    )
    assert len(found) == 1, found
    assert_seed_lines(found, "bad_leaks.py", "unbounded-growth")
    (f,) = found
    assert "_events" in f.message and "LeakyCollector" in f.message
    # the report names the background root that reaches the loop
    assert "thread:" in f.message


def test_thread_lifecycle_line_exact():
    """Anonymous, escaped-local, and unjoined-attr thread starts are each
    flagged; joined handles and stop-event-wired publishers stay silent."""
    found = lint_fixture(
        "bad_leaks.py", rules=[_boundedness_rules()["thread-lifecycle"]]
    )
    assert len(found) == 3, found
    assert_seed_lines(found, "bad_leaks.py", "thread-lifecycle")
    messages = " ".join(f.message for f in found)
    assert "without keeping the handle" in messages
    assert "_pump_t" in messages


def test_child_reap_line_exact():
    """The bare spawn, the never-reaped registry, and the
    terminate-without-wait zombie are flagged; the reaped spawner with
    poll()-based reap and wait-with-kill-fallback stays silent."""
    found = lint_fixture(
        "bad_leaks.py", rules=[_boundedness_rules()["child-reap"]]
    )
    assert len(found) == 3, found
    assert_seed_lines(found, "bad_leaks.py", "child-reap")
    messages = " ".join(f.message for f in found)
    assert "zombie" in messages and "_procs" in messages


def test_shm_debris_line_exact():
    """mkdtemp and /dev/shm makedirs with no prune seam are flagged; the
    atexit-registered and class-owned cleanup shapes stay silent."""
    found = lint_fixture(
        "bad_leaks.py", rules=[_boundedness_rules()["shm-debris"]]
    )
    assert len(found) == 2, found
    assert_seed_lines(found, "bad_leaks.py", "shm-debris")


def test_boundedness_pack_all_rules_together():
    """One run with all five rules reproduces exactly the union of the
    fixture's SEED lines — the shared per-class index serves every rule."""
    found = lint_fixture("bad_leaks.py", rules=list(_boundedness_rules().values()))
    src = (LINT / "bad_leaks.py").read_text().splitlines()
    seeded = {
        (line.split("SEED: ")[1].strip(), i + 1)
        for i, line in enumerate(src)
        if "SEED: " in line
    }
    got = {(f.rule, f.line) for f in found}
    assert got == seeded, (sorted(got - seeded), sorted(seeded - got))


# ------------------------------------------------------------------- sarif


def test_sarif_output_shape():
    from lakesoul_tpu.analysis.rules import all_rules
    from lakesoul_tpu.analysis.sarif import to_sarif

    findings = lint_fixture("bad_threads.py")
    assert findings
    log = to_sarif(findings, all_rules())
    # the SARIF 2.1.0 shape code-scanning consumers read
    assert log["version"] == "2.1.0"
    assert log["$schema"].endswith("sarif-2.1.0.json")
    (run_,) = log["runs"]
    driver = run_["tool"]["driver"]
    assert driver["name"] == "lakesoul-lint"
    rule_ids = [r["id"] for r in driver["rules"]]
    assert len(rule_ids) == 39 and "rbac-gate-reachability" in rule_ids
    assert "unbounded-queue" in rule_ids and "unbounded-growth" in rule_ids
    assert "thread-lifecycle" in rule_ids and "child-reap" in rule_ids
    assert "shm-debris" in rule_ids
    assert "cas-guard" in rule_ids and "read-modify-write" in rule_ids
    assert "txn-boundary" in rule_ids and "sqlite-ism" in rule_ids
    assert "torn-publish" in rule_ids and "unfsynced-rename" in rule_ids
    assert "barrier-order" in rule_ids
    assert "raw-process" in rule_ids
    assert "unstoppable-loop" in rule_ids
    assert "replay-host-roundtrip" in rule_ids
    assert "fleet-identity-label" in rule_ids
    assert "hardcoded-endpoint" in rule_ids
    assert "pallas-blockspec" in rule_ids
    assert "shared-state-race" in rule_ids and "view-escapes-release" in rule_ids
    for r in driver["rules"]:
        assert r["shortDescription"]["text"]
    assert len(run_["results"]) == len(findings)
    for res, f in zip(run_["results"], findings):
        assert res["ruleId"] == f.rule
        assert res["message"]["text"] == f.message
        (loc,) = res["locations"]
        phys = loc["physicalLocation"]
        assert phys["artifactLocation"]["uri"] == f.path
        assert phys["region"]["startLine"] == f.line


def test_cli_sarif_flag(capsys):
    from lakesoul_tpu.analysis.__main__ import main

    rc = main([str(LINT / "bad_threads.py"), "--no-baseline", "--sarif"])
    assert rc == 1
    log = json.loads(capsys.readouterr().out)
    assert log["version"] == "2.1.0"
    assert {r["ruleId"] for r in log["runs"][0]["results"]} == {"raw-thread"}


# ----------------------------------------------------------------- diff mode


def _git(cwd, *args):
    subprocess.run(
        ["git", "-c", "user.email=t@t", "-c", "user.name=t", *args],
        cwd=str(cwd), check=True, capture_output=True,
    )


def test_diff_mode_reports_only_changed_lines(tmp_path):
    """Two-commit synthetic repo: the legacy violation predates BASE, the
    new one lands in the diff — only the new one may fail the gate."""
    from lakesoul_tpu.analysis.gitdiff import changed_lines, filter_to_diff

    _git(tmp_path, "init", "-q")
    mod = tmp_path / "mod.py"
    mod.write_text(
        "import threading\n"
        "\n"
        "def legacy():\n"
        "    return threading.Thread(target=print)\n"
    )
    _git(tmp_path, "add", "mod.py")
    _git(tmp_path, "commit", "-qm", "base")
    mod.write_text(
        "import threading\n"
        "\n"
        "def legacy():\n"
        "    return threading.Thread(target=print)\n"
        "\n"
        "def fresh():\n"
        "    return threading.Thread(target=print)\n"
    )
    _git(tmp_path, "add", "mod.py")
    _git(tmp_path, "commit", "-qm", "new code")

    findings, _ = run([mod], root=tmp_path)
    raw = [f for f in findings if f.rule == "raw-thread"]
    assert {f.line for f in raw} == {4, 7}  # both, pre-filter

    changed = changed_lines("HEAD~1", tmp_path)
    assert changed == {"mod.py": {5, 6, 7}}

    kept = filter_to_diff(raw, "HEAD~1", tmp_path)
    assert [f.line for f in kept] == [7]
    # a base equal to HEAD: nothing changed, nothing reported
    assert filter_to_diff(raw, "HEAD", tmp_path) == []

    # user git config must not change the '+++' prefix out from under the
    # parser (a 'w/' prefix would silently empty the map → vacuous gate)
    _git(tmp_path, "config", "diff.mnemonicprefix", "true")
    assert changed_lines("HEAD~1", tmp_path) == {"mod.py": {5, 6, 7}}


def test_diff_mode_engine_error_is_exit_2(capsys):
    from lakesoul_tpu.analysis.__main__ import main

    rc = main([str(LINT / "bad_threads.py"), "--no-baseline",
               "--diff", "no-such-ref-xyzzy"])
    assert rc == 2
    assert "engine error" in capsys.readouterr().err


# ------------------------------------------------------------- CLI filters


def test_cli_rule_filter_and_formats(capsys):
    from lakesoul_tpu.analysis.__main__ import main

    # --rule filters to one id; --format json parses
    rc = main([str(LINT / "bad_locks.py"), "--no-baseline",
               "--rule", "lock-held-call", "--format", "json"])
    out = capsys.readouterr().out
    assert rc == 1
    assert {f["rule"] for f in json.loads(out)} == {"lock-held-call"}
    # filtering to a rule with no findings in the file exits clean
    rc = main([str(LINT / "bad_locks.py"), "--no-baseline",
               "--rule", "sqlite-scope"])
    capsys.readouterr()
    assert rc == 0
    # unknown rule id is an engine error, not findings
    rc = main(["--rule", "not-a-rule"])
    assert rc == 2
    assert "unknown rule id" in capsys.readouterr().err
    # --write-baseline under a rule filter would destroy the other rules'
    # suppressions: refused as an engine error before touching the file
    rc = main(["--rule", "raw-thread", "--write-baseline"])
    assert rc == 2
    assert "--write-baseline with --rule" in capsys.readouterr().err


def test_console_lint_mirrors_cli_filters(tmp_warehouse):
    from lakesoul_tpu import LakeSoulCatalog
    from lakesoul_tpu.service.console import Console

    c = Console(LakeSoulCatalog(str(tmp_warehouse)))
    out = c.execute("lint --rule raw-thread --format json")
    assert json.loads(out) == []  # repo is clean under the filter
    sarif = json.loads(c.execute("lint --format sarif"))
    assert sarif["version"] == "2.1.0"
    assert c.execute("lint --rule nope").startswith("lint: engine error")


# ------------------------------------------------------------- suppression


def test_inline_pragma_suppresses_finding():
    assert lint_fixture("ok_pragma.py") == []
    # the same code without the pragma IS a finding
    mod = Module.load(LINT / "ok_pragma.py", LINT)
    assert mod.pragma_rules(7) == {"raw-thread"}


def test_baseline_suppresses_and_reports_stale(tmp_path):
    findings, _ = run([LINT / "bad_threads.py"], root=LINT)
    assert findings
    entries = [
        {"rule": f.rule, "path": f.path, "message": f.message, "reason": "test"}
        for f in findings
    ]
    stale = {
        "rule": "raw-thread",
        "path": "gone.py",
        "message": "was fixed long ago",
        "reason": "test",
    }
    bl_path = tmp_path / "baseline.json"
    bl_path.write_text(
        json.dumps({"version": 1, "suppressions": entries + [stale]})
    )
    baseline = Baseline.load(bl_path)
    left, baseline = run([LINT / "bad_threads.py"], root=LINT, baseline=baseline)
    assert left == []
    stales = baseline.stale_entries()
    assert len(stales) == 1 and stales[0]["path"] == "gone.py"


def test_baseline_requires_reasons(tmp_path):
    p = tmp_path / "b.json"
    p.write_text(json.dumps({
        "version": 1,
        "suppressions": [{"rule": "x", "path": "y", "message": "z"}],
    }))
    with pytest.raises(ValueError, match="justified"):
        Baseline.load(p)


def test_cli_exit_codes_and_json(tmp_path, capsys):
    from lakesoul_tpu.analysis.__main__ import main

    rc = main([str(LINT / "bad_threads.py"), "--no-baseline", "--json"])
    out = capsys.readouterr().out
    assert rc == 1
    payload = json.loads(out)
    assert {f["rule"] for f in payload} == {"raw-thread"}

    rc = main([str(LINT / "ok_pragma.py"), "--no-baseline"])
    assert rc == 0


# ---------------------------------------------------------------- lockgraph


@pytest.fixture()
def clean_lockgraph():
    lockgraph.reset()
    yield
    lockgraph.disable()
    lockgraph.reset()


def test_lockgraph_catches_seeded_inversion(clean_lockgraph):
    from fixtures import lockbugs

    with lockgraph.watch() as w:
        lockbugs.lock_order_inversion()
    kinds = [v.kind for v in w.violations]
    assert kinds == ["lock-cycle"]
    v = w.violations[0]
    assert "inverts an existing lock order" in v.message
    assert v.stacks  # the acquiring stacks ship with the report


def test_lockgraph_catches_submit_while_locked(clean_lockgraph):
    from fixtures import lockbugs
    from lakesoul_tpu.runtime.pool import shutdown_pool

    try:
        with lockgraph.watch() as w:
            lockbugs.submit_while_locked()
    finally:
        shutdown_pool()
    kinds = [v.kind for v in w.violations]
    assert kinds == ["submit-while-locked"]
    assert "pool.submit while holding" in w.violations[0].message


def test_lockgraph_silent_on_correct_code(clean_lockgraph):
    from fixtures import lockbugs

    with lockgraph.watch() as w:
        lockbugs.well_ordered()
    assert w.violations == []


def test_lockgraph_handles_condition_and_queue(clean_lockgraph):
    """Checked locks must stay duck-compatible with Condition/Queue — the
    places a wrapper with missing protocol methods corrupts bookkeeping."""
    import queue

    with lockgraph.watch() as w:
        q: queue.Queue = queue.Queue(maxsize=2)

        def produce():
            for i in range(10):
                q.put(i)

        t = threading.Thread(target=produce)
        t.start()
        got = [q.get() for _ in range(10)]
        t.join()
        assert got == list(range(10))

        cond = threading.Condition()
        hits = []

        def waiter():
            with cond:
                while not hits:
                    cond.wait(timeout=5)

        t = threading.Thread(target=waiter)
        t.start()
        with cond:
            hits.append(1)
            cond.notify_all()
        t.join()
    assert w.violations == []


def test_lockgraph_no_false_cycle_from_address_reuse(clean_lockgraph):
    """Edges are keyed by per-wrapper serials: GC'd locks whose id() gets
    reused must not poison the graph with stale edges (regression: 200
    fresh a->b pairs used to yield dozens of false cycles)."""
    with lockgraph.watch() as w:
        for _ in range(200):
            a, b = threading.Lock(), threading.Lock()
            with a:
                with b:
                    pass
    assert w.violations == [], "\n".join(v.render() for v in w.violations)


def test_lockgraph_cross_thread_release_clears_hold(clean_lockgraph):
    """A plain Lock released by another thread (handoff/gate pattern) must
    clear the acquiring thread's hold — no phantom submit-while-locked."""
    from lakesoul_tpu.runtime.pool import get_pool, shutdown_pool

    try:
        with lockgraph.watch() as w:
            gate = threading.Lock()
            gate.acquire()

            def release_from_other_thread():
                gate.release()

            t = threading.Thread(target=release_from_other_thread)
            t.start()
            t.join()
            assert lockgraph.current_held() == []
            assert get_pool().submit(lambda: 1).result() == 1
    finally:
        shutdown_pool()
    assert w.violations == [], "\n".join(v.render() for v in w.violations)


def test_lockgraph_disable_restores_primitives(clean_lockgraph):
    real_lock, real_rlock = threading.Lock, threading.RLock
    with lockgraph.watch():
        assert threading.Lock is not real_lock
        assert threading.RLock is not real_rlock
    assert threading.Lock is real_lock
    assert threading.RLock is real_rlock


def test_lockgraph_clean_on_real_data_path(clean_lockgraph, tmp_path):
    """Integration guard: the runtime pipeline + meta store under
    instrumentation — the two subsystems whose race classes this PR exists
    to keep dead — must produce zero violations."""
    import pyarrow as pa

    from lakesoul_tpu.runtime.pipeline import pipeline
    from lakesoul_tpu.runtime.pool import shutdown_pool

    try:
        with lockgraph.watch() as w:
            it = (
                pipeline("lockcheck")
                .source(range(64))
                .map_parallel(lambda x: x * 2, workers=4, name="double")
                .prefetch(2)
                .run()
            )
            assert list(it) == [x * 2 for x in range(64)]
            it.close()

            from lakesoul_tpu import LakeSoulCatalog

            catalog = LakeSoulCatalog(
                str(tmp_path / "wh"), db_path=str(tmp_path / "meta.db")
            )
            t = catalog.create_table(
                "lockcheck_t", pa.schema([("id", pa.int64())])
            )
            t.write_arrow(pa.table({"id": list(range(100))}))
            assert t.to_arrow().num_rows == 100
    finally:
        shutdown_pool()
    assert w.violations == [], "\n".join(v.render() for v in w.violations)


# ------------------------------------------------------- device rule pack


JAXF = LINT / "jax"


def jax_fixture(name: str, rules=None):
    findings, _ = run([JAXF / name], root=LINT, rules=rules)
    return findings


def test_trace_impure_call_catches_each_side_effect():
    found = [
        f for f in jax_fixture("bad_impure.py")
        if f.rule == "trace-impure-call"
    ]
    assert_seed_lines(found, "jax/bad_impure.py", "trace-impure-call")
    msgs = "\n".join(f.message for f in found)
    # the scan callback is traced without any enclosing jit
    assert "scan_body" in msgs
    assert "captured container" in msgs
    assert "jax.debug.print" in msgs


def test_trace_host_sync_catches_syncs_and_loader_stage():
    from lakesoul_tpu.analysis.rules.jaxtpu import TraceHostSyncRule

    found = [
        f for f in jax_fixture(
            "bad_host_sync.py",
            rules=[TraceHostSyncRule(hot_path=("bad_host_sync.py",))],
        )
        if f.rule == "trace-host-sync"
    ]
    assert_seed_lines(found, "jax/bad_host_sync.py", "trace-host-sync")
    # the helper's sink is found interprocedurally (tainted arg one call deep)
    assert any("np.asarray(v)" in f.message for f in found)


def test_trace_host_sync_clean_half_without_hot_path_scope():
    """With the default (real) hot-path scope the fixture's traced-code
    seeds still fire; only the stand-in loader stage needs the scope."""
    found = [
        f for f in jax_fixture("bad_host_sync.py")
        if f.rule == "trace-host-sync"
    ]
    assert {f.line for f in found} == {11, 17, 18, 19, 20}


def test_tpu_dtype_width_catches_traced_and_host_flows():
    from lakesoul_tpu.analysis.rules.jaxtpu import TpuDtypeWidthRule

    found = [
        f for f in jax_fixture(
            "bad_dtype.py", rules=[TpuDtypeWidthRule(scope=("bad_dtype.py",))]
        )
        if f.rule == "tpu-dtype-width"
    ]
    assert_seed_lines(found, "jax/bad_dtype.py", "tpu-dtype-width")
    msgs = "\n".join(f.message for f in found)
    assert "device_put" in msgs  # host value crossing the boundary
    assert "searcher" in msgs  # jit entry as the boundary
    assert "4000000000" in msgs  # promoting literal


def test_jit_static_arg_shape_catches_each_shape_hazard():
    found = [
        f for f in jax_fixture("bad_static_shape.py")
        if f.rule == "jit-static-arg-shape"
    ]
    assert_seed_lines(found, "jax/bad_static_shape.py", "jit-static-arg-shape")
    msgs = "\n".join(f.message for f in found)
    assert "static_argnames" in msgs
    assert "boolean-mask" in msgs
    assert "pad to a bucketed size" in msgs


def test_pallas_blockspec_catches_each_mismatch():
    found = [
        f for f in jax_fixture("bad_blockspec.py")
        if f.rule == "pallas-blockspec"
    ]
    assert_seed_lines(found, "jax/bad_blockspec.py", "pallas-blockspec")
    msgs = "\n".join(f.message for f in found)
    assert "grid has rank" in msgs
    assert "VMEM" in msgs
    assert "never writes output ref" in msgs
    assert "drops" in msgs


def test_device_pack_fixture_files_trip_only_their_own_rule():
    """Cross-contamination guard: each device fixture seeds exactly one
    rule (the clean twins in every file stay silent under the whole
    catalog, minus the scope-parameterized halves tested above)."""
    for name, rule in [
        ("bad_impure.py", "trace-impure-call"),
        ("bad_static_shape.py", "jit-static-arg-shape"),
        ("bad_blockspec.py", "pallas-blockspec"),
    ]:
        others = [
            f for f in jax_fixture(name)
            if f.rule != rule and f.rule != "undocumented-env"
        ]
        assert others == [], (name, others)


def test_device_index_shapes():
    """The shared device index must classify the fixture correctly:
    decorated entries, transform callbacks, pallas kernels."""
    from lakesoul_tpu.analysis.engine import Module, Project
    from lakesoul_tpu.analysis.rules.jaxtpu import device_index

    project = Project(root=LINT)
    for name in ("bad_impure.py", "bad_blockspec.py"):
        project.modules.append(Module.load(JAXF / name, LINT))
    idx = device_index(project)
    entries = {q.rsplit("::", 1)[-1] for q in idx.jit_entries}
    assert {"stamped_step", "clean_step"} <= entries
    traced = {q.rsplit("::", 1)[-1] for q in idx.traced}
    assert "scan_body" in traced  # lax.scan callback
    assert "host_wrapper" not in traced  # host code stays host
    kernels = {q.rsplit("::", 1)[-1] for q in idx.pallas_kernels}
    assert {"_scale_kernel", "_forgets_output"} <= kernels


def test_device_rules_in_sarif_and_diff(tmp_path):
    """The new rules ride the same output contracts: SARIF carries their
    ids, and --diff BASE keeps only findings on changed lines."""
    from lakesoul_tpu.analysis.gitdiff import filter_to_diff
    from lakesoul_tpu.analysis.rules import all_rules
    from lakesoul_tpu.analysis.sarif import to_sarif

    findings = [
        f for f in jax_fixture("bad_static_shape.py")
        if f.rule == "jit-static-arg-shape"
    ]
    log = to_sarif(findings, all_rules())
    ids = {r["id"] for r in log["runs"][0]["tool"]["driver"]["rules"]}
    assert {
        "trace-impure-call", "trace-host-sync", "tpu-dtype-width",
        "jit-static-arg-shape", "pallas-blockspec",
    } <= ids
    assert all(
        r["ruleId"] == "jit-static-arg-shape" for r in log["runs"][0]["results"]
    )

    _git(tmp_path, "init", "-q")
    mod = tmp_path / "mod.py"
    mod.write_text(
        "import jax\n"
        "import jax.numpy as jnp\n"
        "\n"
        "@jax.jit\n"
        "def legacy(x):\n"
        "    return x[x > 0]\n"
    )
    _git(tmp_path, "add", "mod.py")
    _git(tmp_path, "commit", "-qm", "base")
    mod.write_text(
        "import jax\n"
        "import jax.numpy as jnp\n"
        "\n"
        "@jax.jit\n"
        "def legacy(x):\n"
        "    return x[x > 0]\n"
        "\n"
        "@jax.jit\n"
        "def fresh(x):\n"
        "    return jnp.unique(x)\n"
    )
    _git(tmp_path, "add", "mod.py")
    _git(tmp_path, "commit", "-qm", "new code")
    findings, _ = run([mod], root=tmp_path)
    shape = [f for f in findings if f.rule == "jit-static-arg-shape"]
    assert {f.line for f in shape} == {6, 10}
    kept = filter_to_diff(shape, "HEAD~1", tmp_path)
    assert [f.line for f in kept] == [10]


def test_pallas_blockspec_scratch_and_positional_out_shape(tmp_path):
    """Pallas ref order is (in, out, scratch): the output-write check must
    target the middle params, and a positional multi-output out_shape must
    count toward the kernel arity."""
    from lakesoul_tpu.analysis.rules.jaxtpu import PallasBlockSpecRule

    (tmp_path / "m.py").write_text(
        "import jax\n"
        "import jax.numpy as jnp\n"
        "from jax.experimental import pallas as pl\n"
        "\n"
        "def good(x_ref, o_ref, acc_ref):\n"
        "    o_ref[...] = x_ref[...] + acc_ref[...]\n"
        "\n"
        "def bad(x_ref, o_ref, acc_ref):\n"
        "    acc_ref[...] = x_ref[...]\n"
        "\n"
        "def two_out(x_ref, a_ref, b_ref):\n"
        "    a_ref[...] = x_ref[...]\n"
        "    b_ref[...] = x_ref[...]\n"
        "\n"
        "def calls(x):\n"
        "    a = pl.pallas_call(good,\n"
        "        out_shape=jax.ShapeDtypeStruct((64, 64), jnp.float32),\n"
        "        grid=(2,),\n"
        "        in_specs=[pl.BlockSpec((32, 64), lambda i: (i, 0))],\n"
        "        out_specs=pl.BlockSpec((32, 64), lambda i: (i, 0)),\n"
        "        scratch_shapes=(1,))(x)\n"
        "    b = pl.pallas_call(bad,\n"
        "        out_shape=jax.ShapeDtypeStruct((64, 64), jnp.float32),\n"
        "        grid=(2,),\n"
        "        in_specs=[pl.BlockSpec((32, 64), lambda i: (i, 0))],\n"
        "        out_specs=pl.BlockSpec((32, 64), lambda i: (i, 0)),\n"
        "        scratch_shapes=(1,))(x)\n"
        "    c = pl.pallas_call(two_out,\n"
        "        (jax.ShapeDtypeStruct((64, 64), jnp.float32),\n"
        "         jax.ShapeDtypeStruct((64, 64), jnp.float32)),\n"
        "        grid=(2,),\n"
        "        in_specs=[pl.BlockSpec((32, 64), lambda i: (i, 0))],\n"
        "        out_specs=(pl.BlockSpec((32, 64), lambda i: (i, 0)),\n"
        "                   pl.BlockSpec((32, 64), lambda i: (i, 0))))(x)\n"
        "    return a, b, c\n"
    )
    findings, _ = run(
        [tmp_path / "m.py"], root=tmp_path, rules=[PallasBlockSpecRule()]
    )
    assert len(findings) == 1, [f.render() for f in findings]
    assert "bad" in findings[0].message and "'o_ref'" in findings[0].message


def test_trace_impure_skips_bare_name_callback_targets(tmp_path):
    """`from jax import pure_callback` + a bare-name call must still exclude
    the callback target from the traced closure (host I/O there is the
    sanctioned pattern)."""
    from lakesoul_tpu.analysis.rules.jaxtpu import TraceImpureCallRule

    (tmp_path / "m.py").write_text(
        "import jax\n"
        "from jax import pure_callback\n"
        "\n"
        "def log_row(x):\n"
        "    print('row', x)\n"
        "    return x\n"
        "\n"
        "@jax.jit\n"
        "def step(x):\n"
        "    return pure_callback(log_row, x, x)\n"
    )
    findings, _ = run(
        [tmp_path / "m.py"], root=tmp_path, rules=[TraceImpureCallRule()]
    )
    assert findings == [], [f.render() for f in findings]


def test_device_rules_allow_store_staticnum_and_const_slices(tmp_path):
    """False-positive guards: pl.store counts as an output write,
    static_argnums params are static (host math on them is legal), and
    constant-expression slice bounds are not data-dependent."""
    from lakesoul_tpu.analysis.rules.jaxtpu import (
        JitStaticArgShapeRule,
        PallasBlockSpecRule,
        TraceHostSyncRule,
    )

    (tmp_path / "m.py").write_text(
        "import functools\n"
        "import jax\n"
        "import jax.numpy as jnp\n"
        "from jax.experimental import pallas as pl\n"
        "\n"
        "def store_kernel(x_ref, o_ref):\n"
        "    pl.store(o_ref, (pl.dslice(0, 32),), x_ref[...])\n"
        "\n"
        "def call(x):\n"
        "    return pl.pallas_call(store_kernel,\n"
        "        out_shape=jax.ShapeDtypeStruct((64, 64), jnp.float32),\n"
        "        grid=(2,),\n"
        "        in_specs=[pl.BlockSpec((32, 64), lambda i: (i, 0))],\n"
        "        out_specs=pl.BlockSpec((32, 64), lambda i: (i, 0)))(x)\n"
        "\n"
        "@functools.partial(jax.jit, static_argnums=(1,))\n"
        "def topk(x, k):\n"
        "    width = int(k)\n"
        "    return jnp.sort(x)[:width]\n"
        "\n"
        "def host(codes, n):\n"
        "    a = topk(codes[:-1], 4)\n"
        "    b = topk(codes[:2 * 8], 4)\n"
        "    c = topk(codes[:n], 4)  # the only dynamic slice\n"
        "    return a, b, c\n"
    )
    rules = [PallasBlockSpecRule(), TraceHostSyncRule(), JitStaticArgShapeRule()]
    findings, _ = run([tmp_path / "m.py"], root=tmp_path, rules=rules)
    assert len(findings) == 1, [f.render() for f in findings]
    assert findings[0].rule == "jit-static-arg-shape"
    assert "codes[:n]" in (tmp_path / "m.py").read_text().splitlines()[
        findings[0].line - 1
    ]


def test_pallas_blockspec_skips_non_literal_grid_and_out_shape(tmp_path):
    """Literal-first, never guessed: a name holding the grid tuple or the
    out_shape must skip the rank/arity checks rather than assume rank 1 /
    one output."""
    from lakesoul_tpu.analysis.rules.jaxtpu import PallasBlockSpecRule

    (tmp_path / "m.py").write_text(
        "import jax\n"
        "import jax.numpy as jnp\n"
        "from jax.experimental import pallas as pl\n"
        "\n"
        "GRID = (2, 2)\n"
        "OUT = (jax.ShapeDtypeStruct((64, 64), jnp.float32),\n"
        "       jax.ShapeDtypeStruct((64, 64), jnp.float32))\n"
        "\n"
        "def k(x_ref, o_ref):\n"
        "    o_ref[...] = x_ref[...]\n"
        "\n"
        "def k2(x_ref, a_ref, b_ref):\n"
        "    a_ref[...] = x_ref[...]\n"
        "    b_ref[...] = x_ref[...]\n"
        "\n"
        "def call_var_grid(x):\n"
        "    return pl.pallas_call(k,\n"
        "        out_shape=jax.ShapeDtypeStruct((64, 64), jnp.float32),\n"
        "        grid=GRID,\n"
        "        in_specs=[pl.BlockSpec((32, 32), lambda i, j: (i, j))],\n"
        "        out_specs=pl.BlockSpec((32, 32), lambda i, j: (i, j)))(x)\n"
        "\n"
        "def call_var_out(x):\n"
        "    return pl.pallas_call(k2, OUT, grid=(2,),\n"
        "        in_specs=[pl.BlockSpec((32, 64), lambda i: (i, 0))],\n"
        "        out_specs=(pl.BlockSpec((32, 64), lambda i: (i, 0)),\n"
        "                   pl.BlockSpec((32, 64), lambda i: (i, 0))))(x)\n"
    )
    findings, _ = run(
        [tmp_path / "m.py"], root=tmp_path, rules=[PallasBlockSpecRule()]
    )
    assert findings == [], [f.render() for f in findings]
