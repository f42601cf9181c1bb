"""Slow-marker contract: the tier-1 runtime budget, as a lint rule.

Folded in from `tests/test_marker_audit.py` (which survives as a thin
wrapper over this checker): ROADMAP's tier-1 command runs `-m 'not
slow'` under a hard timeout, and that budget only holds if every test
module is either slow-marked or consciously admitted to FAST_MODULES.
The audit enforces MEMBERSHIP, not runtime — admission is the review
point. Three findings classes:

- a module neither slow-marked nor allowlisted (the seed's tier-1 went
  red exactly this way);
- a stale allowlist entry (names no module, or names a slow-marked one
  — either silently shrinks tier-1 coverage);
- a known soak that lost its slow mark (reintroduces the timeout).
"""

from __future__ import annotations

import ast
import pathlib

from ripplemq_tpu.analysis.framework import Finding, Repo

RULE = "markers"

TESTS_DIR = "tests"

# Modules vetted fast on the CPU backend (per-module timings recorded
# while repairing the seed's tier-1 timeout). Annotate anything over
# ~15 s so the next budget squeeze knows where the time goes.
FAST_MODULES = {
    "test_append_kernel",      # ~2 min: Mosaic-interpreter kernel parity
    "test_broker",
    "test_chain",
    "test_chaos",               # ~20 s: fixed-seed chaos smoke (3 seeds)
    "test_chip_smoke",          # ~30 s: chip_smoke.py --tiny on CPU (3
                                # broker + 2 client processes) must
                                # pass every check but the device's
    "test_client",
    "test_cold_restart",
    "test_control_fusion",
    "test_controller_failover",
    "test_core_step",
    "test_dataplane",
    "test_degradation",
    "test_deployment_files",    # ~1 s: every benchmark config, parsed
    "test_failover",
    "test_fence_view",          # ~8 s: fence view units + two 3-broker drives
    "test_follower_reads",      # ~50 s: plane/lease units, 2-mode byte
                                # identity, 3 chaos smokes (1 proc)
    "test_gather",              # ~12 s: bare planes on a hand-moved clock
    "test_graft",
    "test_group_waves",         # ~5 s: wave-apply units + one cluster run
    "test_groups",              # ~30 s: coordinator units + one cluster run
    "test_hostraft",
    "test_idempotence",         # ~25 s: dedup units + failover replay
    "test_keyed_producer",      # ~10 s: send()/produce.multi vs the reference
    "test_linearizable_reads",  # ~25 s: staged stale-controller clusters
    "test_lint",                # ripplelint fixtures + whole-repo clean run
    "test_lockwitness",         # witness units: private locks, no cluster
    "test_concurrency_triage",  # directed repros for the PR 11 race fixes
    "test_consume_session",     # ~10 s: one 4-broker cluster, 128 partitions
    "test_parked_fetch",        # ~30 s: in-proc clusters of 16 partitions,
                                # the parked fetch against a plain model
    "test_follower_fetch",      # ~12 s: the follower's fetch against a
                                # plain model (no cluster), one 3-broker
                                # cluster across two deaths (~8 s)
    "test_log_matching",
    "test_marker_audit",
    "test_metadata",
    "test_model_check",
    "test_multichip_smoke",     # tier-1 fused-spmd canary on the 8-dev mesh
    "test_spans",               # ~25 s: span units + one proc-backend
                                # acceptance tree (striped)
    "test_observability",
    "test_op_split",
    "test_packaging",
    "test_pid_expiry",          # ~10 s: reaper units + one churn cluster
    "test_proc_chaos",          # ~2 min: 2-seed real-subprocess chaos smoke
    "test_process_cluster",     # ~20 s: real-subprocess broker boot
    "test_program_store",       # ~30 s: store units on a tmp_path, two
                                # bare planes' warm-ups, 7 child processes
    "test_read_batching",
    "test_read_cache",
    "test_settle_pipeline",
    "test_settled_gap",
    "test_slo",                 # fake-clock control-loop units
    "test_slo_chaos",           # ~20 s: one 3-broker slo chaos smoke
    "test_split",               # ~15 s: split/merge units + one e2e cluster
    "test_split_chaos",         # ~45 s: elastic chaos smokes (1 proc)
    "test_term_skew",
    "test_repl_pipeline",       # ~6 s: stub-client sender window units
    "test_retention",
    "test_retry_policy",
    "test_rs",
    "test_shard_distribution",
    "test_soak",                # ~15 s: the bounded hand-written soak
    "test_spmd",
    "test_staging",             # ~25 s: bare planes, not started; five launches
    "test_storage",
    "test_store_gc",            # ~17 s: GC/retention store churn
    "test_stripes",             # ~30 s: any-k matrix + 3 striped clusters
    "test_store_migrate",
    "test_stride_rule",
    "test_wide_rows",           # ~35 s: the served path, kernel parity and
                                # RS at slot_bytes 1152; one v5e compile
                                # (the VMEM rule's three edges: -m slow)
    "test_wire",
}

# The modules that took the seed's tier-1 over its timeout must keep
# their slow marks (deleting a mark reintroduces the timeout).
PINNED_SLOW = (
    "test_multihost", "test_soak_random", "test_soak_gc",
    "test_lockstep_drill", "test_chaos_soak", "test_proc_chaos_soak",
    "test_obs_soak",
)


def is_slow_marked(tree: ast.AST) -> bool:
    """True iff the module carries a top-level slow pytestmark
    (`pytestmark = pytest.mark.slow` or a list containing it)."""
    for node in tree.body:
        if not isinstance(node, ast.Assign):
            continue
        if not any(isinstance(t, ast.Name) and t.id == "pytestmark"
                   for t in node.targets):
            continue
        if "slow" in ast.dump(node.value):
            return True
    return False


def check(repo: Repo) -> list[Finding]:
    findings: list[Finding] = []
    modules = {
        pathlib.PurePosixPath(p).stem: p
        for p in repo.py_files(TESTS_DIR)
        if pathlib.PurePosixPath(p).name.startswith("test_")
    }
    slow = {name for name, p in modules.items()
            if is_slow_marked(repo.tree(p))}

    for name, path in sorted(modules.items()):
        if name not in FAST_MODULES and name not in slow:
            findings.append(Finding(
                rule=RULE, path=path, line=1, key=f"unvetted::{name}",
                message=(f"test module {name} neither slow-marked nor "
                         f"vetted fast — mark `pytestmark = "
                         f"pytest.mark.slow` (soaks/drills) or vet it "
                         f"under ~30 s on CPU and add it to "
                         f"analysis/markers.py FAST_MODULES"),
            ))
    for name in sorted(FAST_MODULES - set(modules)):
        findings.append(Finding(
            rule=RULE, path="ripplemq_tpu/analysis/markers.py", line=1,
            key=f"stale::{name}",
            message=f"FAST_MODULES entry {name} names no test module",
        ))
    for name in sorted(FAST_MODULES & slow):
        findings.append(Finding(
            rule=RULE, path=modules[name], line=1, key=f"double::{name}",
            message=(f"{name} is both allowlisted and slow-marked — drop "
                     f"one (a stale allowlist entry hides shrinking "
                     f"tier-1 coverage)"),
        ))
    for name in PINNED_SLOW:
        if name not in modules:
            findings.append(Finding(
                rule=RULE, path=TESTS_DIR, line=1, key=f"pinned-gone::{name}",
                message=f"pinned soak module {name} vanished",
            ))
        elif name not in slow:
            findings.append(Finding(
                rule=RULE, path=modules[name], line=1,
                key=f"pinned::{name}",
                message=f"{name} lost its slow mark — that reintroduces "
                        f"the seed's tier-1 timeout",
            ))
    return findings
