"""Test configuration: run JAX on a virtual 8-device CPU mesh.

Multi-chip TPU hardware is not available in CI; sharding logic is validated on
``xla_force_host_platform_device_count=8`` CPU devices, mirroring how the
reference fakes "multi-node" with many clients on one PG instance
(SURVEY.md §4 takeaway).  Must run before jax initializes its backends.
"""

import os

# force the CPU even on a machine that has a chip: the suite needs the
# 8-device mesh, and a chip belongs to one process at a time — a test run
# that claimed it would starve (or hang behind) whatever is measuring on it.
# The environment covers the subprocesses tests spawn; the config update
# covers a jax that some plugin imported before this file ran.
os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()

import jax

jax.config.update("jax_platforms", "cpu")

import pathlib
import sys

import pytest

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent.parent))


@pytest.fixture()
def tmp_warehouse(tmp_path):
    """A throwaway warehouse dir + metadata db for catalog tests."""
    wh = tmp_path / "warehouse"
    wh.mkdir()
    return wh


# --------------------------------------------------------------- lockcheck
# LAKESOUL_LOCKCHECK=1 arms lakelint's runtime lock-order/race detector
# (lakesoul_tpu/analysis/lockgraph.py) for the modules whose race classes
# have bitten before: the runtime pool/pipelines (nested-pool deadlock) and
# the metadata store (shared :memory: sqlite cursor race).  Any lock-order
# cycle or lock-held-across-pool.submit recorded during such a test fails
# it at teardown.

_LOCKCHECK_MODULES = ("test_runtime", "test_metadata")

# -------------------------------------------------------------- tracecheck
# LAKESOUL_TRACECHECK=1 arms lakelint's runtime retrace detector
# (lakesoul_tpu/analysis/tracecheck.py) for the suites that drive jit entry
# points hard: the ANN kernels (test_vector), the sharded model steps
# (test_models_parallel), and the loader path (test_catalog).  A function
# that accumulates more distinct abstract signatures than its budget during
# one test — each one a fresh XLA compilation — fails that test at
# teardown with the triggering shapes/dtypes.

_TRACECHECK_MODULES = ("test_vector", "test_models_parallel", "test_catalog")

# --------------------------------------------------------------- racecheck
# LAKESOUL_RACECHECK=1 arms lakelint's runtime race detector
# (lakesoul_tpu/analysis/racecheck.py) for the suites that drive the
# concurrent hot classes hard: the pipeline/pool machinery (test_runtime),
# the admission/breaker/ANN serving surfaces (test_resilience), and the
# lease heartbeat (test_topology).  Eraser lockset tracking on instrumented
# class fields: a field written by two threads with no common lock fails the
# test at teardown with both access stacks.

_RACECHECK_MODULES = ("test_runtime", "test_resilience", "test_topology")


@pytest.fixture(autouse=True)
def _racecheck(request):
    mod = getattr(request.node, "module", None)
    name = getattr(mod, "__name__", "") or ""
    if name.rpartition(".")[2] not in _RACECHECK_MODULES:
        yield
        return
    from lakesoul_tpu.analysis import racecheck

    if not racecheck.env_requested() or racecheck.enabled():
        # not armed, or something else already manages the detector
        yield
        return
    racecheck.reset()
    racecheck.enable()
    try:
        yield
    finally:
        violations = racecheck.violations()
        racecheck.disable()
        racecheck.reset()
    assert not violations, "racecheck violations:\n" + "\n\n".join(
        v.render() for v in violations
    )


@pytest.fixture(autouse=True)
def _tracecheck(request):
    mod = getattr(request.node, "module", None)
    name = getattr(mod, "__name__", "") or ""
    if name.rpartition(".")[2] not in _TRACECHECK_MODULES:
        yield
        return
    from lakesoul_tpu.analysis import tracecheck

    if not tracecheck.env_requested() or tracecheck.enabled():
        # not armed, or something else already manages the detector
        yield
        return
    tracecheck.reset()
    tracecheck.enable()
    try:
        yield
    finally:
        violations = tracecheck.violations()
        tracecheck.disable()
        tracecheck.reset()
    assert not violations, "tracecheck violations:\n" + "\n\n".join(
        v.render() for v in violations
    )


@pytest.fixture(autouse=True)
def _lockcheck(request):
    mod = getattr(request.node, "module", None)
    name = getattr(mod, "__name__", "") or ""
    if name.rpartition(".")[2] not in _LOCKCHECK_MODULES:
        yield
        return
    from lakesoul_tpu.analysis import lockgraph

    if not lockgraph.env_requested() or lockgraph.enabled():
        # not armed, or something else already manages the detector
        yield
        return
    lockgraph.reset()
    lockgraph.enable()
    try:
        yield
    finally:
        violations = lockgraph.violations()
        lockgraph.disable()
        lockgraph.reset()
    assert not violations, "lockgraph violations:\n" + "\n\n".join(
        v.render() for v in violations
    )


# ----------------------------------------------------------------- fscheck
# LAKESOUL_FSCHECK=1 arms lakelint's crash-prefix replay detector
# (lakesoul_tpu/analysis/fscheck.py) for the suites that publish
# cross-process artifacts: the spool/session protocol (test_scanplane),
# the spill rung + fleet docs (test_fleet), and the lease/topology docs
# (test_topology).  Every traced publication is replayed at teardown — the
# filesystem state after a crash at EVERY op prefix is materialized in a
# scratch dir and the real readers must see old-complete or new-complete,
# never torn; any violation fails the test with both stacks.

_FSCHECK_MODULES = ("test_scanplane", "test_fleet", "test_topology")


@pytest.fixture(autouse=True)
def _fscheck(request):
    mod = getattr(request.node, "module", None)
    name = getattr(mod, "__name__", "") or ""
    if name.rpartition(".")[2] not in _FSCHECK_MODULES:
        yield
        return
    from lakesoul_tpu.analysis import fscheck

    if not fscheck.env_requested() or fscheck.enabled():
        # not armed, or something else already manages the detector
        yield
        return
    fscheck.reset()
    fscheck.enable()
    try:
        yield
    finally:
        try:
            fscheck.replay()
        finally:
            violations = fscheck.violations()
            fscheck.disable()
            fscheck.reset()
    assert not violations, "fscheck violations:\n" + "\n\n".join(
        v.render() for v in violations
    )


# ---------------------------------------------------------------- txncheck
# LAKESOUL_TXNCHECK=1 arms lakelint's transaction-interleaving replayer
# (lakesoul_tpu/analysis/txncheck.py) for the suites that drive the
# metadata store's concurrent protocols.  Every committed transaction's
# statement trace is recorded at the store seam; teardown replays the
# history under READ COMMITTED interleavings and fails the test on any
# lost-update window or fencing-token regression, with both transactions'
# statement stacks.

_TXNCHECK_MODULES = ("test_metadata", "test_lease", "test_topology")


@pytest.fixture(autouse=True)
def _txncheck(request):
    mod = getattr(request.node, "module", None)
    name = getattr(mod, "__name__", "") or ""
    if name.rpartition(".")[2] not in _TXNCHECK_MODULES:
        yield
        return
    from lakesoul_tpu.analysis import txncheck

    if not txncheck.env_requested() or txncheck.enabled():
        # not armed, or something else already manages the detector
        yield
        return
    txncheck.reset()
    txncheck.enable()
    try:
        yield
    finally:
        try:
            txncheck.replay()
        finally:
            violations = txncheck.violations()
            txncheck.disable()
            txncheck.reset()
    assert not violations, "txncheck violations:\n" + "\n\n".join(
        v.render() for v in violations
    )


# --------------------------------------------------------------- leakcheck
# LAKESOUL_LEAKCHECK=1 arms lakelint's resource-leak detector
# (lakesoul_tpu/analysis/leakcheck.py) for the suites that open, serve,
# spawn, and spool the hardest: the pipeline/pool machinery
# (test_runtime), the spool/session protocol (test_scanplane), the worker
# autoscaler (test_fleet), the serving surfaces (test_resilience), and
# the follower plane (test_freshness).  Each test runs inside a resource
# scope — /proc/self/fd, live threads, tracked children, and tracked
# scratch artifacts are snapshotted before and diffed after; any thread,
# child, tmpfs fd, or staged tmp that outlives the test fails it at
# teardown with its creation stack.

_LEAKCHECK_MODULES = (
    "test_runtime",
    "test_scanplane",
    "test_fleet",
    "test_resilience",
    "test_freshness",
)


@pytest.fixture(autouse=True)
def _leakcheck(request):
    mod = getattr(request.node, "module", None)
    name = getattr(mod, "__name__", "") or ""
    if name.rpartition(".")[2] not in _LEAKCHECK_MODULES:
        yield
        return
    from lakesoul_tpu.analysis import leakcheck

    if not leakcheck.env_requested() or leakcheck.enabled():
        # not armed, or something else already manages the detector
        yield
        return
    leakcheck.reset()
    leakcheck.enable()
    try:
        with leakcheck.scope(request.node.nodeid):
            yield
    finally:
        violations = leakcheck.violations()
        leakcheck.disable()
        leakcheck.reset()
    assert not violations, "leakcheck violations:\n" + "\n\n".join(
        v.render() for v in violations
    )
