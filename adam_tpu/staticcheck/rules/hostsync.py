"""Rule ``host-sync``: hot-path device->host synchronization must route
through ``utils/transfer.device_fetch``.

The PR 7 device ledger (``device.d2h.bytes``/``device.d2h.bps`` and the
per-pass ``transfers`` attribution) is complete only **by convention**:
every fetch of a device-resident array crosses in
``transfer.device_fetch``, which also carries the fetch deadline
watchdog, the transient retry and the ``device.fetch`` fault point
(docs/ROBUSTNESS.md).  A stray ``np.asarray(device_value)`` in the hot
path is an unledgered, unwatched, unretryable d2h RPC — exactly the
drift this rule kills.

Detection is a per-function forward taint pass: values produced by
jit-compiled callables (``@jax.jit`` functions, ``jax.jit(...)``
bindings, ``*_kernel``/``*_jit`` names, the mesh window methods, a
``putter(...)``-made placer) are *device-tainted*; taint follows
assignment, tuple unpacking, indexing, attribute access and method
calls; ``device_fetch`` launders it.  Applying ``np.asarray`` /
``np.array`` / ``np.ascontiguousarray`` / ``float`` / ``int`` /
``bool`` / ``.item()`` / ``.tolist()`` to a tainted value — or calling
``jax.device_get`` / ``.block_until_ready()`` at all — inside
``pipelines/``, ``parallel/`` or ``ops/`` is a finding.  An
``isinstance(x, np.ndarray)`` test narrows ``x`` to host inside the
guarded branch (the standard host-short-circuit idiom)."""

from __future__ import annotations

import ast
import fnmatch

from adam_tpu.staticcheck.core import Rule, register
from adam_tpu.staticcheck.rules._astutil import (
    WARMUP_FN_PATTERNS,
    collect_jit_callables,
    dotted_name,
    is_jit_decorated,
    terminal_name,
)


def _is_warmup_fn(fn) -> bool:
    """warm/prewarm/probe/bench functions force compiles and sync on
    purpose — their body is not hot-path code (the pool/mesh prewarm
    executes these thunks under its own span/track umbrella)."""
    return any(fnmatch.fnmatchcase(fn.name, p) for p in WARMUP_FN_PATTERNS)

SCOPE_PREFIXES = ("adam_tpu/pipelines/", "adam_tpu/parallel/",
                  "adam_tpu/ops/", "adam_tpu/serve/",
                  "adam_tpu/gateway/")

#: Callable-name patterns whose results are device-resident (or may
#: be): kernels, jit factories, the mesh per-window collectives, the
#: backend-polymorphic observe.  fnmatch'd against the call's terminal
#: name, so cross-module ``bqsr_mod._observe_device(...)`` matches too.
DEVICE_CALL_PATTERNS = (
    "*_kernel",
    "*_jit",
    "_observe_device",
    "markdup_window",
    "device_lexsort",
    "*_columns_dispatch",
    "device_put",
    "put_replicated",
)

#: Calls that launder taint: the result is host-resident numpy.
SANITIZERS = ("device_fetch",)

_NP_SINKS = {
    "np.asarray", "numpy.asarray",
    "np.array", "numpy.array",
    "np.ascontiguousarray", "numpy.ascontiguousarray",
}
_BUILTIN_SINKS = {"float", "int", "bool"}
_METHOD_SINKS = {"item", "tolist"}


def _matches_device_call(name: str) -> bool:
    return any(fnmatch.fnmatchcase(name, p) for p in DEVICE_CALL_PATTERNS)


@register
class HostSyncRule(Rule):
    name = "host-sync"
    summary = ("hot-path d2h sync (np.asarray/.item()/float()/"
               "block_until_ready on device values) outside "
               "transfer.device_fetch")
    contract = (
        "Every device->host fetch in pipelines/, parallel/ and ops/ "
        "routes through utils/transfer.device_fetch so the transfer-byte "
        "ledger, fetch watchdog, retry and fault point stay complete "
        "by construction (docs/PERF.md 'Device ledger measurements', "
        "docs/ROBUSTNESS.md)."
    )

    def visit(self, ctx):
        if not ctx.relpath.startswith(SCOPE_PREFIXES):
            return
        jit_names = collect_jit_callables(ctx.tree)
        # names bound from putter(...) place arrays on device
        placers: set[str] = set()
        for node in ast.walk(ctx.tree):
            if isinstance(node, ast.Assign) and isinstance(
                node.value, ast.Call
            ):
                if terminal_name(node.value.func) in ("putter",):
                    for t in node.targets:
                        if isinstance(t, ast.Name):
                            placers.add(t.id)
        self._jit_names = jit_names
        self._placers = placers

        findings: list = []
        # one walk from the module body: _walk_block recurses into
        # every function/class it encounters exactly once (including
        # defs nested in module-level if/try), skipping jit-decorated
        # bodies (trace-time code where jnp ops are the point, not a
        # sync) and warm/probe functions
        self._walk_block(ctx, ctx.tree.body, set(), findings)
        yield from findings

    # ---- helpers --------------------------------------------------------
    def _is_device_call(self, call: ast.Call, tainted) -> bool:
        func = call.func
        name = terminal_name(func)
        if name in SANITIZERS:
            return False
        if name in self._jit_names or name in self._placers:
            return True
        if _matches_device_call(name):
            return True
        d = dotted_name(func)
        if d.startswith(("jnp.", "jax.numpy.")):
            return True
        # method on a tainted value stays tainted (t.astype(...), t.sum())
        if isinstance(func, ast.Attribute) and self._tainted(
            func.value, tainted
        ):
            return True
        return False

    def _tainted(self, expr, tainted) -> bool:
        if isinstance(expr, ast.Name):
            return expr.id in tainted
        if isinstance(expr, ast.Call):
            if terminal_name(expr.func) in SANITIZERS:
                return False
            return self._is_device_call(expr, tainted)
        if isinstance(expr, ast.Attribute):
            # array metadata is host-resident even on device arrays
            if expr.attr in ("shape", "ndim", "dtype", "size", "nbytes"):
                return False
            return self._tainted(expr.value, tainted)
        if isinstance(expr, (ast.Subscript, ast.Starred)):
            return self._tainted(expr.value, tainted)
        if isinstance(expr, (ast.Tuple, ast.List, ast.Set)):
            return any(self._tainted(e, tainted) for e in expr.elts)
        if isinstance(expr, (ast.ListComp, ast.SetComp, ast.GeneratorExp)):
            return self._tainted(expr.elt, tainted)
        if isinstance(expr, ast.BinOp):
            return (self._tainted(expr.left, tainted)
                    or self._tainted(expr.right, tainted))
        if isinstance(expr, ast.UnaryOp):
            return self._tainted(expr.operand, tainted)
        if isinstance(expr, ast.IfExp):
            return (self._tainted(expr.body, tainted)
                    or self._tainted(expr.orelse, tainted))
        if isinstance(expr, ast.NamedExpr):
            return self._tainted(expr.value, tainted)
        return False

    def _assign_names(self, target, value_tainted: bool, tainted) -> None:
        if isinstance(target, ast.Name):
            if value_tainted:
                tainted.add(target.id)
            else:
                tainted.discard(target.id)
        elif isinstance(target, (ast.Tuple, ast.List)):
            for elt in target.elts:
                self._assign_names(elt, value_tainted, tainted)
        elif isinstance(target, ast.Starred):
            self._assign_names(target.value, value_tainted, tainted)

    def _check_exprs(self, ctx, node, tainted, findings) -> None:
        """Scan every Call inside ``node`` for sink applications, and
        record NamedExpr bindings."""
        for sub in ast.walk(node):
            if isinstance(sub, ast.NamedExpr) and isinstance(
                sub.target, ast.Name
            ):
                self._assign_names(
                    sub.target, self._tainted(sub.value, tainted), tainted
                )
            if not isinstance(sub, ast.Call):
                continue
            func = sub.func
            d = dotted_name(func)
            name = terminal_name(func)
            args_tainted = any(
                self._tainted(a, tainted) for a in sub.args
            )
            if d in _NP_SINKS and args_tainted:
                findings.append(ctx.finding(
                    self.name, sub,
                    f"{d}() on a device value — route the fetch "
                    "through transfer.device_fetch (ledger + watchdog "
                    "+ retry)",
                ))
            elif (isinstance(func, ast.Name)
                  and func.id in _BUILTIN_SINKS
                  and len(sub.args) == 1 and args_tainted):
                findings.append(ctx.finding(
                    self.name, sub,
                    f"{func.id}() on a device value forces a blocking "
                    "d2h sync — fetch through transfer.device_fetch "
                    "first",
                ))
            elif (isinstance(func, ast.Attribute)
                  and func.attr in _METHOD_SINKS
                  and self._tainted(func.value, tainted)):
                findings.append(ctx.finding(
                    self.name, sub,
                    f".{func.attr}() on a device value forces a "
                    "blocking d2h sync — fetch through "
                    "transfer.device_fetch first",
                ))
            elif d == "jax.device_get":
                findings.append(ctx.finding(
                    self.name, sub,
                    "jax.device_get bypasses transfer.device_fetch "
                    "(unledgered, unwatched d2h)",
                ))
            elif (isinstance(func, ast.Attribute)
                  and func.attr == "block_until_ready") or (
                      d == "jax.block_until_ready"):
                findings.append(ctx.finding(
                    self.name, sub,
                    "block_until_ready in the hot path stalls the "
                    "dispatch pipeline — fetch through "
                    "transfer.device_fetch or keep the value lazy",
                ))

    def _walk_block(self, ctx, stmts, tainted, findings) -> None:
        """Forward walk over a statement block, threading the tainted
        name set through assignments and branch structure."""
        for stmt in stmts:
            if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
                if not is_jit_decorated(stmt) and not _is_warmup_fn(stmt):
                    # closure sees the taint state at its definition point
                    self._walk_block(ctx, stmt.body, set(tainted), findings)
                continue
            if isinstance(stmt, ast.ClassDef):
                for item in stmt.body:
                    if isinstance(
                        item, (ast.FunctionDef, ast.AsyncFunctionDef)
                    ) and not is_jit_decorated(item) \
                            and not _is_warmup_fn(item):
                        self._walk_block(ctx, item.body, set(), findings)
                continue
            if isinstance(stmt, ast.Assign):
                self._check_exprs(ctx, stmt.value, tainted, findings)
                vt = self._tainted(stmt.value, tainted)
                for t in stmt.targets:
                    self._assign_names(t, vt, tainted)
                continue
            if isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
                self._check_exprs(ctx, stmt.value, tainted, findings)
                self._assign_names(
                    stmt.target, self._tainted(stmt.value, tainted), tainted
                )
                continue
            if isinstance(stmt, ast.AugAssign):
                self._check_exprs(ctx, stmt.value, tainted, findings)
                if self._tainted(stmt.value, tainted):
                    self._assign_names(stmt.target, True, tainted)
                continue
            if isinstance(stmt, ast.If):
                self._check_exprs(ctx, stmt.test, tainted, findings)
                narrowed = set(tainted)
                for n in _isinstance_ndarray_names(stmt.test):
                    narrowed.discard(n)
                else_taint = set(tainted)
                self._walk_block(ctx, stmt.body, narrowed, findings)
                self._walk_block(ctx, stmt.orelse, else_taint, findings)
                # conservative join: anything tainted in either branch
                tainted |= narrowed | else_taint
                continue
            if isinstance(stmt, (ast.For, ast.AsyncFor)):
                self._check_exprs(ctx, stmt.iter, tainted, findings)
                self._assign_names(
                    stmt.target, self._tainted(stmt.iter, tainted), tainted
                )
                self._walk_block(ctx, stmt.body, tainted, findings)
                self._walk_block(ctx, stmt.orelse, tainted, findings)
                continue
            if isinstance(stmt, ast.While):
                self._check_exprs(ctx, stmt.test, tainted, findings)
                self._walk_block(ctx, stmt.body, tainted, findings)
                self._walk_block(ctx, stmt.orelse, tainted, findings)
                continue
            if isinstance(stmt, (ast.With, ast.AsyncWith)):
                for item in stmt.items:
                    self._check_exprs(
                        ctx, item.context_expr, tainted, findings
                    )
                    if item.optional_vars is not None:
                        self._assign_names(
                            item.optional_vars,
                            self._tainted(item.context_expr, tainted),
                            tainted,
                        )
                self._walk_block(ctx, stmt.body, tainted, findings)
                continue
            if isinstance(stmt, ast.Try):
                self._walk_block(ctx, stmt.body, tainted, findings)
                for h in stmt.handlers:
                    self._walk_block(ctx, h.body, set(tainted), findings)
                self._walk_block(ctx, stmt.orelse, tainted, findings)
                self._walk_block(ctx, stmt.finalbody, tainted, findings)
                continue
            # leaf statements: Expr, Return, Raise, Assert, Delete...
            self._check_exprs(ctx, stmt, tainted, findings)


def _isinstance_ndarray_names(test) -> set:
    """Names proven host-resident by an ``isinstance(x, np.ndarray)``
    test (possibly inside an ``and``)."""
    names: set[str] = set()
    nodes = [test]
    if isinstance(test, ast.BoolOp) and isinstance(test.op, ast.And):
        nodes = list(test.values)
    for n in nodes:
        if (isinstance(n, ast.Call)
                and terminal_name(n.func) == "isinstance"
                and len(n.args) == 2
                and isinstance(n.args[0], ast.Name)
                and dotted_name(n.args[1]) in
                ("np.ndarray", "numpy.ndarray")):
            names.add(n.args[0].id)
    return names
