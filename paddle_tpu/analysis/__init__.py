"""Framework static-analysis suite + runtime sanitizers (PR 7, PR 11).

Static half: a pure-stdlib AST analysis engine (engine.py), since PR 11
INTERPROCEDURAL — callgraph.py builds a project-wide symbol table + call
graph before any checker runs, so rules can follow calls across files —
with these checker families:

- concurrency.py        C001 daemon= explicit, C002 acquire/release
                        discipline, C003 no silent except-swallows,
                        C004 lock-owning modules guard global writes
- collective_safety.py  X001 raw lax collectives stay in distributed/
                        (baseline ZERO: model code uses the sanctioned
                        collective.in_trace_psum/pmax helpers),
                        X002 eager collectives ride execute_collective,
                        X003 no rank-conditional collective branches,
                        X004 no rank-conditional branch TRANSITIVELY
                        reaching a collective through the call graph
- trace_purity.py       T001 no wall-clock/host-RNG/host-sync in traced fns,
                        T002 grad_comm wire codecs stay pure jnp (the
                        eager/traced shared-verbatim contract, ISSUE 8),
                        T003 no impurity through ANY call chain from a
                        traced fn (confident edges; _in_trace()-guarded
                        dual-path functions are trusted boundaries)
- registry_drift.py     R001 FLAGS_* declared in framework/flags.py,
                        R002 metric label schemas consistent
- resource_release.py   F001 path-aware resource release over the CFG —
                        acquired lane-gathered buffers release on EVERY
                        path to function exit incl. early-return and
                        exception edges (supersedes the syntactic S001,
                        kept as a waiver alias); F002 future-await —
                        BucketFuture/GatherFuture/sync_async handles are
                        awaited, drained, or escape on every path;
                        F005 span close — begin_span() results reach
                        end_span() (or escape) on every path, exception
                        edges included (ISSUE 18 trace spans)
- commit_order.py       F003 checkpoint commit functions write the
                        MANIFEST last: the manifest write post-dominates
                        every payload write on the normal-flow CFG (the
                        PR-2 crash-safety invariant, machine-checked)
- mesh_axes.py          X005 mesh-axis validity — axis names that
                        resolvably reach psum/all_gather/constrain/
                        shard_map sites (reaching-defs + one-hop call
                        graph) exist in the mesh-axis registry
- signal_safety.py      S002 signal.signal handler bodies only set
                        flags/latches (the async-signal-safe preemption
                        latch contract, ISSUE 10)
- donation.py           D001 no read of a donated binding after the
                        donating jit call, D002 donated-buffer outputs
                        ordered before batch outputs in the return tuple
                        (the PR-8 TrainStep donation-alias bug, ISSUE 11)
- kernel_gates.py       K001 every pl.pallas_call resolves interpret=
                        through the target_platform() seam — no literal
                        True/False, no missing kwarg (ISSUE 13: CPU
                        tier-1 can never silently pin a TPU-only path)

Since PR 12 the engine is additionally FLOW-SENSITIVE: dataflow.py builds
per-function CFGs (if/while/for/try/except/finally/with/return/raise/
break/continue, exception edges into handlers and finallys, panic edges
for unprotected raises) and runs a generic worklist solver (forward +
backward, union or intersection meet) with packaged reaching-definitions,
liveness, and post-dominator instances — memoized per function in
``shared["dataflow"]`` and persisted in the parsed-AST pickle cache.

Runtime half: lock_order.py — a lock-order witness (lockdep/TSan style)
that wraps framework locks under FLAGS_lock_order_check and reports
ABBA-inversion cycles, plus the post-suite thread-leak check — and
host_sync.py (ISSUE 11) — patches the device→host sync points under
FLAGS_host_sync_check to record blocking syncs inside train-step spans.

Gate: ``tools/check_static.py --baseline tools/static_baseline.json``
runs everything over paddle_tpu/ in tier-1; new findings exit 1, stale
baseline entries OR stale inline waivers exit 2. ``--changed-only`` /
``--sarif`` / the parsed-AST cache serve CI (a warm cache parses no
file: tests/test_static_analysis.py).
"""
from __future__ import annotations

from . import callgraph  # noqa: F401  (pure stdlib)
from . import dataflow  # noqa: F401  (pure stdlib)
from . import host_sync  # noqa: F401  (standalone-safe: lazy jax import)
from . import lock_order  # noqa: F401  (standalone-safe, pure stdlib)
from .callgraph import ProjectIndex, build_index
from .collective_safety import CollectiveSafetyChecker
from .commit_order import CommitOrderChecker
from .concurrency import ConcurrencyChecker
from .donation import DonationSafetyChecker
from .engine import (Analysis, AstCache, Checker, Finding, RULES,
                     diff_against_baseline, findings_to_baseline,
                     load_baseline)
from .kernel_gates import KernelGateChecker
from .mesh_axes import MeshAxisChecker
from .registry_drift import RegistryDriftChecker
from .resource_release import ResourceReleaseChecker
from .signal_safety import SignalSafetyChecker
from .trace_purity import TracePurityChecker

__all__ = [
    "Analysis", "AstCache", "Checker", "Finding", "ProjectIndex", "RULES",
    "build_index", "default_checkers", "analyze_tree", "analyze_sources",
    "diff_against_baseline", "findings_to_baseline", "load_baseline",
    "callgraph", "dataflow", "host_sync", "lock_order",
]


def default_checkers():
    return [
        ConcurrencyChecker(),
        CollectiveSafetyChecker(),
        TracePurityChecker(),
        RegistryDriftChecker(),
        ResourceReleaseChecker(),
        CommitOrderChecker(),
        MeshAxisChecker(),
        SignalSafetyChecker(),
        DonationSafetyChecker(),
        KernelGateChecker(),
    ]


def analyze_tree(root: str, rel_root: str = ""):
    """All default checkers over a source tree; returns sorted Findings."""
    return Analysis(default_checkers(), rel_root=rel_root).run_path(root)


def analyze_sources(sources):
    """All default checkers over in-memory {path: source} fixtures."""
    return Analysis(default_checkers()).run_sources(sources)
