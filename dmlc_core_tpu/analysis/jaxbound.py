"""Pass 9 — jaxbound: host↔device boundary discipline.

PR 7 put the feed pipeline on a uint8 wire diet and routed every transfer
through ONE accounting wrapper (``_accounted_place``, bridge/loader.py),
so the trace CLI's critical path can split transfer from compute and the
``dmlc_transfer_bytes_total`` contract stays truthful.  Nothing enforced
that discipline until now — a stray ``jax.device_put`` in bridge code
ships bytes off the books, a float32 cast on the binned payload silently
re-inflates the wire 4x, and a ``jax.jit`` rebuilt per call retraces on
every request (the PR 5 knee-bench bug, found by hand then).

``jaxbound-unaccounted-transfer``
    A ``jax.device_put`` / ``jnp.asarray`` / ``jnp.array`` call inside
    ``dmlc_core_tpu/bridge/`` whose enclosing function is neither passed
    to ``_accounted_place`` (nor defined inside it) nor reachable from a
    traced root (where ``asarray`` of a tracer is free).  Every transfer
    the feed pipeline makes must go through the wrapper so the byte/span
    accounting cannot drift between paths.

``jaxbound-wide-wire``
    A value produced by the narrow-wire binning path (``.transform()`` /
    ``apply_bins`` / ``binned_batches``) that is cast to float32/float64
    (``.astype``, ``np.asarray(..., dtype=...)``, ``np.float32(...)``)
    and then flows into a transfer sink (``device_put`` or an accounted
    place function) within one function.  The wire dtype ladder exists so
    PCIe and HBM carry uint8/uint16; widen ON DEVICE inside the jit
    (``models/gbdt.py _widen_bins``), never before the transfer.

``jaxbound-jit-in-hot-path``
    A ``jax.jit``/``pjit`` wrapper that is rebuilt per call: immediately
    invoked (``jax.jit(f)(x)``) or bound to a local that is only ever
    called, inside a function that is not an acknowledged
    construction-time context (module level, ``__init__``, an
    ``lru_cache``/``cache``/``cached_property``-decorated builder).  A
    fresh wrapper has an empty compile cache — every call of the
    enclosing function pays a full retrace; when the wrapped callable
    also closes over ``self`` the staleness is worse (trace-time state is
    baked in).  Store the wrapper on the instance/module, or build it
    under a memoizing decorator.

Scope: ``unaccounted-transfer`` and ``wide-wire`` apply to
``dmlc_core_tpu/bridge/`` (the feed pipeline owns the wire diet; models
legitimately take float input, and bench.py's staging keeps its own
labeled accounting).  ``jit-in-hot-path`` applies project-wide.
"""

from __future__ import annotations

import ast
from typing import Iterable, List, Optional, Set

from dmlc_core_tpu.analysis.driver import (FileContext, Finding, dotted_name,
                                           keyword_arg)
from dmlc_core_tpu.analysis.graph import (ProjectGraph, resolve_callable,
                                          walk_in_scope)
from dmlc_core_tpu.analysis.purity import _reachable, _trace_roots

__all__ = ["run_project", "BRIDGE_PREFIX", "ACCOUNTED_WRAPPER"]

BRIDGE_PREFIX = "dmlc_core_tpu/bridge/"
ACCOUNTED_WRAPPER = "_accounted_place"

_TRANSFER_CALLS = {"device_put"}
_IMPLICIT_TRANSFER = {"asarray", "array"}  # on jnp/jax.numpy only
_JIT_NAMES = {"jit", "pjit"}
_WIDE_DTYPES = {"float32", "float64", "float_", "double"}
_NARROW_SOURCES = {"transform", "apply_bins", "binned_batches"}
_MEMO_DECORATORS = {"lru_cache", "cache", "cached_property"}


def _jnp_aliases(ctx: FileContext) -> Set[str]:
    """Local names bound to jax.numpy (``jnp``, ``jax.numpy``)."""
    out = {alias for alias, mod in ctx.module_aliases.items()
           if mod in ("jax.numpy", "jax")}
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.ImportFrom) and node.module == "jax":
            for alias in node.names:
                if alias.name == "numpy":
                    out.add(alias.asname or "numpy")
    return out


# -- accounted-function discovery ---------------------------------------------

def _accounted_functions(ctx: FileContext) -> Set[int]:
    """id()s of function nodes whose transfers are accounted: functions
    passed to ``_accounted_place`` and functions defined inside it."""
    out: Set[int] = set()
    defs = ctx.defs_by_name
    aliases = ctx.assign_aliases
    for node in ast.walk(ctx.tree):
        if isinstance(node, ast.Call):
            name = dotted_name(node.func) or ""
            if name.rsplit(".", 1)[-1] == ACCOUNTED_WRAPPER and node.args:
                for fn in resolve_callable(ctx, node.args[0], defs, aliases):
                    out.add(id(fn))
        elif isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and node.name == ACCOUNTED_WRAPPER:
            for sub in ast.walk(node):
                if isinstance(sub, (ast.FunctionDef, ast.AsyncFunctionDef,
                                    ast.Lambda)) and sub is not node:
                    out.add(id(sub))
    return out


def _enclosing_chain(ctx: FileContext, node: ast.AST) -> Iterable[ast.AST]:
    cur = ctx.parents.get(node)
    while cur is not None:
        if isinstance(cur, (ast.FunctionDef, ast.AsyncFunctionDef,
                            ast.Lambda)):
            yield cur
        cur = ctx.parents.get(cur)


def _check_bridge_file(ctx: FileContext) -> List[Finding]:
    findings: List[Finding] = []
    accounted = _accounted_functions(ctx)
    traced = {id(fn) for fn in _reachable(ctx, _trace_roots(ctx))}
    jnp_names = _jnp_aliases(ctx)

    def is_exempt(node: ast.AST) -> bool:
        return any(id(fn) in accounted or id(fn) in traced
                   for fn in _enclosing_chain(ctx, node))

    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        name = dotted_name(node.func) or ""
        parts = name.split(".")
        short = parts[-1]
        hit = None
        if short in _TRANSFER_CALLS:
            hit = name
        elif short in _IMPLICIT_TRANSFER and len(parts) >= 2 \
                and parts[0] in jnp_names:
            hit = name
        if hit is None or is_exempt(node):
            continue
        findings.append(Finding(
            "jaxbound-unaccounted-transfer", ctx.relpath, node.lineno,
            ctx.qualname(node),
            f"{hit}() moves host bytes to device outside the "
            "_accounted_place wrapper (bridge/loader.py) — this transfer "
            "is invisible to dmlc_transfer_bytes_total and the trace "
            "critical path; route it through the wrapper"))
    findings += _check_wide_wire(ctx, accounted)
    return findings


# -- wide-wire def-use --------------------------------------------------------

def _dtype_token(node: Optional[ast.AST]) -> Optional[str]:
    if node is None:
        return None
    if isinstance(node, ast.Constant) and isinstance(node.value, str):
        return node.value
    name = dotted_name(node)
    return name.rsplit(".", 1)[-1] if name else None


def _is_wide_cast(call: ast.Call) -> bool:
    name = dotted_name(call.func) or ""
    short = name.rsplit(".", 1)[-1]
    if short == "astype" and call.args:
        return _dtype_token(call.args[0]) in _WIDE_DTYPES
    if short in ("asarray", "array", "ascontiguousarray"):
        return _dtype_token(keyword_arg(call, "dtype")) in _WIDE_DTYPES
    return short in _WIDE_DTYPES  # np.float32(x) constructor cast
    # (bare float32 literals with no operand are dtype mentions, but they
    # only matter when the RESULT flows to a sink, which requires args)


def _check_wide_wire(ctx: FileContext,
                     accounted: Set[int]) -> List[Finding]:
    findings: List[Finding] = []
    accounted_names: Set[str] = set()
    for node in ast.walk(ctx.tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)) \
                and id(node) in accounted:
            accounted_names.add(node.name)
    for fn in [n for n in ast.walk(ctx.tree)
               if isinstance(n, (ast.FunctionDef, ast.AsyncFunctionDef))]:
        narrow: Set[str] = set()
        widened: Set[str] = set()
        # two passes over the straight-line def-use so chains that span
        # assignments resolve regardless of walk order
        for _ in range(2):
            for node in walk_in_scope(fn):
                if not (isinstance(node, ast.Assign)
                        and len(node.targets) == 1
                        and isinstance(node.targets[0], ast.Name)):
                    continue
                target = node.targets[0].id
                value = node.value
                if isinstance(value, ast.Call):
                    name = dotted_name(value.func) or ""
                    short = name.rsplit(".", 1)[-1]
                    operands = ([dotted_name(a) for a in value.args]
                                + ([dotted_name(value.func.value)]
                                   if isinstance(value.func, ast.Attribute)
                                   else []))
                    if short in _NARROW_SOURCES:
                        narrow.add(target)
                    elif _is_wide_cast(value) and any(
                            o and o.split(".")[0] in narrow
                            for o in operands):
                        widened.add(target)
                elif isinstance(value, ast.Name):
                    if value.id in narrow:
                        narrow.add(target)
                    if value.id in widened:
                        widened.add(target)
        if not widened:
            continue
        for node in walk_in_scope(fn):
            if not isinstance(node, ast.Call):
                continue
            name = dotted_name(node.func) or ""
            short = name.rsplit(".", 1)[-1]
            is_sink = (short in _TRANSFER_CALLS
                       or short in accounted_names)
            if not is_sink:
                continue
            for arg in node.args:
                aname = dotted_name(arg)
                if aname and aname.split(".")[0] in widened:
                    findings.append(Finding(
                        "jaxbound-wide-wire", ctx.relpath, node.lineno,
                        ctx.qualname(node),
                        f"{aname} carries binned (narrow-wire) data "
                        "widened to a float dtype before the transfer — "
                        "this re-inflates the wire ~4x; ship the narrow "
                        "dtype and widen on device inside the jit "
                        "(models/gbdt.py _widen_bins)"))
    return findings


# -- jit-in-hot-path ----------------------------------------------------------

def _decorator_names(fn: ast.AST) -> Set[str]:
    out: Set[str] = set()
    for dec in getattr(fn, "decorator_list", []):
        base = dec.func if isinstance(dec, ast.Call) else dec
        name = dotted_name(base) or ""
        out.add(name.rsplit(".", 1)[-1])
    return out


def _jit_context_exempt(ctx: FileContext, call: ast.Call) -> bool:
    """Construction-time contexts where building a jit wrapper is fine."""
    chain = list(_enclosing_chain(ctx, call))
    if not chain:
        return True  # module level: runs once
    for fn in chain:
        if getattr(fn, "name", "") == "__init__":
            return True
        if _decorator_names(fn) & _MEMO_DECORATORS:
            return True
    return False


def _local_stored(fn: ast.AST, name: str, binding: ast.AST) -> bool:
    """Is the jit wrapper bound to ``name`` parked anywhere that outlives
    the call (returned / attr / subscript / container / passed on)?
    Merely CALLING it (``fn(x)``) parks nothing — that is exactly the
    rebuilt-per-call shape."""
    from dmlc_core_tpu.analysis.escape import _direct_owner

    def is_it(expr: ast.AST) -> bool:
        return isinstance(expr, ast.Name) and expr.id == name

    for node in walk_in_scope(fn):
        if isinstance(node, ast.Return) and node.value is not None:
            if _direct_owner(node.value, is_it):
                return True
        elif isinstance(node, ast.Assign) and node is not binding:
            if any(isinstance(t, (ast.Attribute, ast.Subscript))
                   for t in node.targets) and \
                    _direct_owner(node.value, is_it):
                return True
        elif isinstance(node, ast.Call):
            for arg in list(node.args) + [kw.value for kw in node.keywords]:
                if is_it(arg):
                    return True
    return False


def _closes_over_self(arg: ast.AST, ctx: FileContext) -> bool:
    if isinstance(arg, ast.Attribute):
        return (isinstance(arg.value, ast.Name)
                and arg.value.id == "self")  # jit(self.method)
    if isinstance(arg, (ast.Lambda,)):
        return any(isinstance(n, ast.Name) and n.id == "self"
                   for n in ast.walk(arg.body))
    if isinstance(arg, ast.Name):
        fns = ctx.defs_by_name.get(arg.id, [])
        return any(any(isinstance(n, ast.Name) and n.id == "self"
                       for n in ast.walk(f))
                   for f in fns)
    return False


def _check_jit_hot_path(ctx: FileContext) -> List[Finding]:
    findings: List[Finding] = []
    for node in ast.walk(ctx.tree):
        if not isinstance(node, ast.Call):
            continue
        name = dotted_name(node.func) or ""
        if name.rsplit(".", 1)[-1] not in _JIT_NAMES:
            continue
        # only the real wrappers: jax.jit / pjit / bare jit import —
        # method calls like obj.jit() are not trace entry points
        root = name.split(".")[0]
        if root not in ("jax", "jit", "pjit") and name not in _JIT_NAMES:
            continue
        if _jit_context_exempt(ctx, node):
            continue
        parent = ctx.parents.get(node)
        rebuilt = None
        if isinstance(parent, ast.Call) and parent.func is node:
            rebuilt = "immediately invoked"
        elif (isinstance(parent, ast.Assign) and len(parent.targets) == 1
              and isinstance(parent.targets[0], ast.Name)):
            fn = ctx.enclosing(node, ast.FunctionDef, ast.AsyncFunctionDef,
                               ast.Lambda)
            if fn is not None and not _local_stored(
                    fn, parent.targets[0].id, parent):
                rebuilt = "bound to a local that is only called"
        if rebuilt is None:
            continue
        closure = (node.args and _closes_over_self(node.args[0], ctx))
        extra = (" — and the wrapped callable closes over self, so "
                 "trace-time instance state is baked into each rebuild"
                 if closure else "")
        findings.append(Finding(
            "jaxbound-jit-in-hot-path", ctx.relpath, node.lineno,
            ctx.qualname(node),
            f"{name}(...) is {rebuilt}: the wrapper is rebuilt on every "
            "call of the enclosing function, so its compile cache is "
            "always empty and every call retraces (the PR 5 knee-bench "
            "bug class); store the jitted fn on the instance/module or "
            f"build it under a memoizing decorator{extra}"))
    return findings


# -- the pass -----------------------------------------------------------------

def run_project(graph: ProjectGraph) -> List[Finding]:
    findings: List[Finding] = []
    for mod in graph.modules.values():
        ctx = mod.ctx
        if ctx.relpath.startswith(BRIDGE_PREFIX):
            findings += _check_bridge_file(ctx)
        findings += _check_jit_hot_path(ctx)
    return findings
