"""``adam-tpu check`` — AST-based contract checker for the cross-cutting
conventions the streamed TPU pipeline's correctness rests on.

Eight PRs of device code left five *conventions* that no compiler
enforces: every device->host fetch routes through
``utils/transfer.device_fetch`` (or the PR 7 transfer-byte ledger
under-counts), every jit dispatch is ``compile_ledger.track``-wrapped
against a prewarm entry (or ``device.compile.in_window`` lies), every
durability-bearing publish goes through ``utils/durability`` (or a
power loss can tear a part), every fault-injection site names a
``faults.KNOWN_POINTS`` member (or the chaos matrix silently tests
nothing), and shared mutable state in thread-spawning modules stays
behind its lock.  This package turns each convention into a static
rule over the Python AST, so drift is caught at review time instead of
by a runtime assertion three PRs later (docs/STATIC_ANALYSIS.md).

Entry points: ``adam-tpu check`` (CLI subcommand),
``python -m adam_tpu.staticcheck`` and ``scripts/staticcheck``.
"""

from adam_tpu.staticcheck.core import (  # noqa: F401
    EXIT_CLEAN,
    EXIT_ERROR,
    EXIT_FINDINGS,
    Finding,
    Project,
    Report,
    Rule,
    all_rules,
    register,
    run_checks,
)

__all__ = [
    "EXIT_CLEAN", "EXIT_FINDINGS", "EXIT_ERROR",
    "Finding", "Project", "Report", "Rule",
    "all_rules", "register", "run_checks",
]
