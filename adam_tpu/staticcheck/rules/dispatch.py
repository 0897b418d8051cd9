"""Rule ``dispatch-ledger``: every jit/shard_map dispatch in the
streamed path is ``compile_ledger.track``-wrapped and its kernel has a
prewarm registry entry.

PR 7's compile ledger makes "did the prewarm cover this shape?" a
first-class observable (``device.compile.in_window``), and PR 8
asserts it is **zero** on clean runs — but both hold only if every
dispatch site actually wraps itself in :func:`compile_ledger.track`
with a key the prewarm registry also builds.  PR 7 caught the
realigned-tail observe gap only at runtime; this rule catches the next
one at review time.

Three checks:

* **coverage** — a call to a jit-compiled callable (``@jax.jit``
  functions, ``jax.jit(...)`` bindings, ``*_kernel`` names, the mesh
  ``markdup_window``/``fused_bc_window`` collectives)
  in a streamed-path module must sit inside ``with
  compile_ledger.track(...)``.  The dominant idiom nests the dispatch
  in a local ``def dispatch(): ...`` retried via ``retry_call`` inside
  the tracked block — a call is also covered when its enclosing nested
  function is *referenced* inside a tracked block of the same outer
  function.
* **prewarm cross-check** — every kernel name appearing as the first
  element of a ``track((kernel, *dims), ...)`` key tuple must appear in
  a prewarm entry key built in ``parallel/`` (the ``*_entry``/
  ``*prewarm*`` builders in ``device_pool.py``/``partitioner.py``),
  keeping the ledger's key space and the prewarm's in lockstep by
  construction.
* **pallas containment** — a ``pl.pallas_call`` anywhere in the
  package must sit inside a ``*_body``/``*_kernel``/``*_pallas``
  function: those are the dispatchable surfaces the compile ledger
  and the chip compile tests (``tests/test_chip_compile.py``) reach,
  so a stray pallas site elsewhere would dodge both."""

from __future__ import annotations

import ast

from adam_tpu.staticcheck.core import Finding, Rule, register
from adam_tpu.staticcheck.rules._astutil import (
    _is_jit_expr,
    collect_jit_callables,
    enclosing_function,
    in_warmup_function,
    is_jit_decorated,
    terminal_name,
)

#: The streamed device path: the modules whose dispatches land inside
#: timed windows (ISSUE: jit/shard_map sites "in the streamed path").
SCOPE_FILES = frozenset({
    "adam_tpu/pipelines/markdup.py",
    "adam_tpu/pipelines/bqsr.py",
    "adam_tpu/pipelines/realign.py",
    "adam_tpu/pipelines/streamed.py",
    "adam_tpu/parallel/device_pool.py",
    "adam_tpu/parallel/partitioner.py",
    "adam_tpu/parallel/dist.py",
})

#: Where prewarm entry keys are built (the registry side of the
#: cross-check).
PREWARM_FILES = ("adam_tpu/parallel/device_pool.py",
                 "adam_tpu/parallel/partitioner.py")

MESH_WINDOW_METHODS = frozenset({"markdup_window", "fused_bc_window"})

#: Function-name suffixes a ``pl.pallas_call`` site may live under:
#: the jit-able math (``*_body``), a dispatchable binding
#: (``*_kernel``) or the Pallas port itself (``*_pallas``).  Anywhere
#: else the call escapes the ledger/prewarm machinery.
PALLAS_HOST_SUFFIXES = ("_body", "_kernel", "_pallas")


def _is_track_call(expr) -> bool:
    return (isinstance(expr, ast.Call)
            and terminal_name(expr.func) == "track")


def _kernel_of_track(call) -> str | None:
    """The kernel-name literal of a ``track((kernel, *dims), dev)``."""
    if call.args and isinstance(call.args[0], ast.Tuple):
        elts = call.args[0].elts
        if elts and isinstance(elts[0], ast.Constant) and isinstance(
            elts[0].value, str
        ):
            return elts[0].value
    return None


@register
class DispatchLedgerRule(Rule):
    name = "dispatch-ledger"
    summary = ("streamed jit/shard_map dispatches not wrapped in "
               "compile_ledger.track, or tracked kernels with no "
               "prewarm registry entry")
    contract = (
        "Every streamed-path jit dispatch wraps in compile_ledger."
        "track keyed identically to a prewarm entry, so device.compile"
        ".in_window == 0 is a compile-time property (docs/PERF.md "
        "'prewarm coverage boundary', tests/test_mesh.py)."
    )

    def __init__(self):
        self._tracked: dict[str, tuple] = {}  # kernel -> (path, line)
        self._prewarmed: set[str] = set()

    def visit(self, ctx):
        # collect both sides of the cross-check (package code only —
        # tests exercise the ledger with synthetic kernel keys)
        if ctx.relpath.startswith("adam_tpu/"):
            for node in ast.walk(ctx.tree):
                if isinstance(node, ast.Call) and _is_track_call(node):
                    k = _kernel_of_track(node)
                    if k is not None and k not in self._tracked:
                        self._tracked[k] = (ctx.relpath, node.lineno)
        if ctx.relpath in PREWARM_FILES:
            self._collect_prewarm_kernels(ctx.tree)
        # pallas containment (package-wide): a pallas_call outside a
        # *_body/*_kernel/*_pallas function is a dispatch surface the
        # backend selector and the ledger cannot key on
        if ctx.relpath.startswith("adam_tpu/"):
            yield from self._check_pallas_sites(ctx)
        if ctx.relpath not in SCOPE_FILES:
            return
        dispatchables = collect_jit_callables(ctx.tree)
        for node in ast.walk(ctx.tree):
            if not isinstance(node, ast.Call):
                continue
            if _is_jit_expr(node):
                continue  # jax.jit(...) builds a callable, no dispatch
            if self._in_traced_code(ctx, node):
                continue  # inside a @jax.jit body: trace-time call
            if in_warmup_function(ctx, node):
                # prewarm entry thunks run under the pool/mesh
                # prewarm's own track; probe/bench dispatches are
                # deliberately outside any window
                continue
            func = node.func
            name = terminal_name(func)
            if isinstance(func, ast.Call) and terminal_name(
                func.func
            ).endswith("_jit"):
                # factory()(...) — dispatch via a *_jit factory result
                name = terminal_name(func.func) + "()"
            elif (name in dispatchables
                  or name in MESH_WINDOW_METHODS
                  or name.endswith("_kernel")):
                outer = ctx.parents.get(node)
                if isinstance(outer, ast.Call) and outer.func is node:
                    continue  # bare factory: the outer call is flagged
            else:
                continue
            if self._covered(ctx, node):
                continue
            yield ctx.finding(
                self.name, node,
                f"jit dispatch '{name}' outside compile_ledger.track — "
                "the compile ledger (and the in_window == 0 invariant) "
                "cannot see this site",
            )

    def _check_pallas_sites(self, ctx):
        for node in ast.walk(ctx.tree):
            if not (isinstance(node, ast.Call)
                    and terminal_name(node.func) == "pallas_call"):
                continue
            fn = enclosing_function(ctx, node)
            if fn is not None and fn.name.endswith(PALLAS_HOST_SUFFIXES):
                continue
            where = fn.name if fn is not None else "module scope"
            yield ctx.finding(
                self.name, node,
                f"pallas_call in '{where}' — Pallas call sites must "
                "live inside a *_body/*_kernel/*_pallas function so the "
                "compile ledger keys on them",
            )

    @staticmethod
    def _in_traced_code(ctx, node) -> bool:
        for anc in ctx.ancestors(node):
            if isinstance(anc, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                    and is_jit_decorated(anc):
                return True
        return False

    # ---- coverage -------------------------------------------------------
    def _covered(self, ctx, call) -> bool:
        for anc in ctx.ancestors(call):
            if isinstance(anc, (ast.With, ast.AsyncWith)):
                for item in anc.items:
                    if _is_track_call(item.context_expr):
                        return True
        # nested-def idiom: def dispatch(): <call> ... with track(...):
        #   retry_call(dispatch, ...)
        fn = enclosing_function(ctx, call)
        while fn is not None:
            outer = enclosing_function(ctx, fn)
            if outer is None:
                return False
            if self._referenced_under_track(outer, fn.name):
                return True
            fn = outer
        return False

    @staticmethod
    def _referenced_under_track(outer_fn, name: str) -> bool:
        for node in ast.walk(outer_fn):
            if not isinstance(node, (ast.With, ast.AsyncWith)):
                continue
            if not any(_is_track_call(i.context_expr) for i in node.items):
                continue
            for sub in ast.walk(node):
                if isinstance(sub, ast.Name) and sub.id == name and \
                        isinstance(sub.ctx, ast.Load):
                    return True
        return False

    # ---- prewarm registry side ------------------------------------------
    def _collect_prewarm_kernels(self, tree) -> None:
        for node in ast.walk(tree):
            if not isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                continue
            fname = node.name.lower()
            if "entry" not in fname and "prewarm" not in fname:
                continue
            for sub in ast.walk(node):
                if isinstance(sub, ast.Tuple) and sub.elts:
                    first = sub.elts[0]
                    if isinstance(first, ast.Constant) and isinstance(
                        first.value, str
                    ) and "." in first.value:
                        self._prewarmed.add(first.value)

    def finalize(self, project):
        for kernel, (path, line) in sorted(self._tracked.items()):
            if kernel not in self._prewarmed:
                yield Finding(
                    self.name, path, line, 0,
                    f"kernel '{kernel}' is ledger-tracked but no "
                    "prewarm registry entry builds this key "
                    "(parallel/device_pool.py / partitioner.py) — its "
                    "first dispatch cold-compiles inside a timed "
                    "window",
                    "",
                )
