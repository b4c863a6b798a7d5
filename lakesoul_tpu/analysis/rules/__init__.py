"""lakelint rule catalog.

Every rule encodes one invariant this codebase has already been burned by
(or will be at production scale).  The catalog, with rationale, lives in
ARCHITECTURE.md §Analysis; adding a rule = subclass
:class:`~lakesoul_tpu.analysis.engine.Rule` in a module here and list it in
:func:`all_rules`.

Two generations: the PR 3 per-function rules (``check(module)`` over one
file's shared AST) and the interprocedural rules (``finalize(project)``
over the shared project call graph — ``Project.callgraph()``).  On top of
those ride the themed packs — device (jit/pallas trace safety),
concurrency (thread-root locksets + buffer lifetimes), durability (atomic
publication), isolation (READ COMMITTED portability), and boundedness
(resource budgets + thread/child/scratch lifecycles) — 39 rules total.
"""

from __future__ import annotations

from lakesoul_tpu.analysis.engine import Rule

from lakesoul_tpu.analysis.rules.concurrency import (
    LockHeldCallRule,
    RawThreadRule,
    SqliteScopeRule,
    TransitiveLockHeldCallRule,
)
from lakesoul_tpu.analysis.rules.conventions import (
    MetricNameRule,
    UndocumentedEnvRule,
)
from lakesoul_tpu.analysis.rules.determinism import StageNondeterminismRule
from lakesoul_tpu.analysis.rules.durability import (
    BarrierOrderRule,
    TornPublishRule,
    UnfsyncedRenameRule,
)
from lakesoul_tpu.analysis.rules.boundedness import (
    ChildReapRule,
    ShmDebrisRule,
    ThreadLifecycleRule,
    UnboundedGrowthRule,
    UnboundedQueueRule,
)
from lakesoul_tpu.analysis.rules.endpoint import HardcodedEndpointRule
from lakesoul_tpu.analysis.rules.identity import FleetIdentityLabelRule
from lakesoul_tpu.analysis.rules.isolation import (
    CasGuardRule,
    ReadModifyWriteRule,
    SqliteIsmRule,
    TxnBoundaryRule,
)
from lakesoul_tpu.analysis.rules.lifetime import ViewEscapesReleaseRule
from lakesoul_tpu.analysis.rules.loops import UnstoppableLoopRule
from lakesoul_tpu.analysis.rules.perf import HotPathMaterializeRule
from lakesoul_tpu.analysis.rules.process import RawProcessRule
from lakesoul_tpu.analysis.rules.races import (
    RacyCheckThenActRule,
    SharedStateRaceRule,
)
from lakesoul_tpu.analysis.rules.replay import ReplayHostRoundtripRule
from lakesoul_tpu.analysis.rules.jaxtpu import (
    JitStaticArgShapeRule,
    PallasBlockSpecRule,
    TpuDtypeWidthRule,
    TraceHostSyncRule,
    TraceImpureCallRule,
)
from lakesoul_tpu.analysis.rules.resources import (
    InterproceduralUnclosedReaderRule,
    UnclosedReaderRule,
)
from lakesoul_tpu.analysis.rules.robustness import AdHocRetryRule
from lakesoul_tpu.analysis.rules.security import (
    RbacGateReachabilityRule,
    TaintPathSegmentsRule,
)
from lakesoul_tpu.analysis.rules.wallclock import WallClockLeaseRule

__all__ = ["all_rules", "rule_ids"]


def all_rules() -> list[Rule]:
    return [
        # per-function (PR 3)
        RawThreadRule(),
        LockHeldCallRule(),
        StageNondeterminismRule(),
        UnclosedReaderRule(),
        UndocumentedEnvRule(),
        MetricNameRule(),
        SqliteScopeRule(),
        AdHocRetryRule(),
        WallClockLeaseRule(),
        HotPathMaterializeRule(),
        RawProcessRule(),
        UnstoppableLoopRule(),
        ReplayHostRoundtripRule(),
        FleetIdentityLabelRule(),
        HardcodedEndpointRule(),
        # interprocedural (call graph + dataflow)
        RbacGateReachabilityRule(),
        TaintPathSegmentsRule(),
        TransitiveLockHeldCallRule(),
        InterproceduralUnclosedReaderRule(),
        # concurrency-soundness pack (thread roots + locksets + lifetimes)
        SharedStateRaceRule(),
        RacyCheckThenActRule(),
        ViewEscapesReleaseRule(),
        # device pack (jit/pallas trace safety)
        TraceImpureCallRule(),
        TraceHostSyncRule(),
        TpuDtypeWidthRule(),
        JitStaticArgShapeRule(),
        PallasBlockSpecRule(),
        # durability pack (atomic-publication discipline)
        TornPublishRule(),
        UnfsyncedRenameRule(),
        BarrierOrderRule(),
        # isolation pack (READ COMMITTED portability of the metadata path)
        CasGuardRule(),
        ReadModifyWriteRule(),
        TxnBoundaryRule(),
        SqliteIsmRule(),
        # boundedness pack (resource budgets + lifecycles for soak runs)
        UnboundedQueueRule(),
        UnboundedGrowthRule(),
        ThreadLifecycleRule(),
        ChildReapRule(),
        ShmDebrisRule(),
    ]


def rule_ids() -> list[str]:
    return [r.id for r in all_rules()]
