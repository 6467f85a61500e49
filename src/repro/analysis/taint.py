"""Secret-flow taint analyzer.

Tracks per-tenant secret material (morph cores, token/channel/output
permutations, seeds, snapshot payloads) from source expressions to sinks
where it would cross the provider trust boundary: log/print/warn calls,
exception constructor text, assert messages, ``wire.encode_*`` frames
(the decode lane's request frames carry prompt ids and ``max_new_tokens``,
its result frames ``scores`` in the header), ``DeliveryResult.metadata`` and
``DeliveryResult.scores``, and snapshot serializers.

The analysis is intraprocedural and flow-insensitive: two propagation
sweeps over each function body compute the set of tainted local names,
then a sink sweep reports flows.  Attribute reads whose terminal segment
is a known secret field are tainted wherever they appear; calls to known
secret producers taint their results; redaction helpers
(``describe_array``/``short_digest``) and other sanitizers clear taint.
Objects whose ``__repr__`` is redacted (``morpher``/``embed_morpher``
attributes) may be repr'd directly — the redacted repr is a safe sink.

Legitimate flows carry ``# analysis: declassified(<reason>)``.
"""

from __future__ import annotations

import ast
from typing import Optional

from .base import Finding, Module, source_snippet, terminal_name

NAME = "taint"
BIT = 1

# Attribute reads that ARE raw secret values.
RAW_SECRET_ATTRS = frozenset({
    "perm", "inv_perm", "out_perm", "_perm", "_core",
})

# Shape/dtype metadata of a secret array is public (it is config-derived,
# identical across tenants) and clears taint.
PUBLIC_ATTRS = frozenset({"shape", "dtype", "ndim", "size", "nbytes"})

# Attribute reads that are secret-BEARING objects with a redacted
# __repr__: tainted as values, but safe to repr()/str()/format directly.
REDACTED_BEARER_ATTRS = frozenset({"morpher", "embed_morpher"})

# Calls (by terminal name) whose result is secret material.
SECRET_CALLS = frozenset({
    "make_core", "random_channel_perm", "randbits", "token_bytes",
    "_resolve_seed", "snapshot_state", "_session_state", "snapshot",
    "stacked_cores", "stacked_perms", "stacked_embed_cores",
    "slot_core", "slot_perm", "slot_embed_core",
})

# Parameters that seed taint by name (key material handed in).
SECRET_PARAMS = frozenset({"seed"})

# Calls that launder taint: their result reveals nothing recoverable.
SANITIZERS = frozenset({
    "len", "type", "id", "bool", "isinstance", "hasattr",
    "describe_array", "short_digest",
})

LOG_METHODS = frozenset({
    "debug", "info", "warning", "error", "exception", "critical",
    "warn", "log",
})

WIRE_SINKS = frozenset({
    "encode_frame", "frame_head", "encode_request", "encode_result",
    "encode_result_parts", "encode_reject", "encode_bye",
})

# Functions whose return value is serialized out of process: returning
# secrets from one of these requires an explicit declassification.
SERIALIZERS = frozenset({"snapshot", "snapshot_state", "_session_state"})


def _target_names(node) -> list:
    """Plain names bound by an assignment target (tuple-coarse)."""
    if isinstance(node, ast.Name):
        return [node.id]
    if isinstance(node, (ast.Tuple, ast.List)):
        out = []
        for elt in node.elts:
            out.extend(_target_names(elt))
        return out
    if isinstance(node, ast.Starred):
        return _target_names(node.value)
    return []


def _container_base(node) -> Optional[str]:
    """Base local name of ``x[...] = v`` / ``x.a = v`` store targets."""
    while isinstance(node, (ast.Subscript, ast.Attribute)):
        node = node.value
    if isinstance(node, ast.Name):
        return node.id
    return None


class _FunctionTaint:
    """Taint state for one function (or the module body)."""

    def __init__(self, module: Module, name: str, body, params,
                 inherited=None):
        self.module = module
        self.name = name
        self.body = body
        self.tainted = set(inherited or ())
        for p in params:
            if p in SECRET_PARAMS:
                self.tainted.add(p)
        self.findings: list = []

    # -- expression taint ------------------------------------------------

    def is_tainted(self, node) -> bool:
        if node is None:
            return False
        if isinstance(node, ast.Name):
            return node.id in self.tainted
        if isinstance(node, ast.Attribute):
            if node.attr in PUBLIC_ATTRS:
                return False  # dimensional metadata of a secret is public
            if node.attr in RAW_SECRET_ATTRS:
                return True
            if node.attr in REDACTED_BEARER_ATTRS:
                return True
            return self.is_tainted(node.value)
        if isinstance(node, ast.Subscript):
            return self.is_tainted(node.value) or self.is_tainted(node.slice)
        if isinstance(node, ast.Call):
            return self._call_tainted(node)
        if isinstance(node, ast.JoinedStr):
            return any(self.is_tainted(v) for v in node.values)
        if isinstance(node, ast.FormattedValue):
            return self._format_tainted(node.value)
        if isinstance(node, ast.BinOp):
            return self.is_tainted(node.left) or self.is_tainted(node.right)
        if isinstance(node, ast.BoolOp):
            return any(self.is_tainted(v) for v in node.values)
        if isinstance(node, ast.UnaryOp):
            return self.is_tainted(node.operand)
        if isinstance(node, ast.Compare):
            return False  # a comparison result is a bool, not the secret
        if isinstance(node, ast.IfExp):
            return self.is_tainted(node.body) or self.is_tainted(node.orelse)
        if isinstance(node, (ast.Tuple, ast.List, ast.Set)):
            return any(self.is_tainted(e) for e in node.elts)
        if isinstance(node, ast.Dict):
            return any(self.is_tainted(k) for k in node.keys if k) or any(
                self.is_tainted(v) for v in node.values
            )
        if isinstance(node, (ast.ListComp, ast.SetComp, ast.GeneratorExp)):
            return self.is_tainted(node.elt) or any(
                self.is_tainted(g.iter) for g in node.generators
            )
        if isinstance(node, ast.DictComp):
            return (
                self.is_tainted(node.key)
                or self.is_tainted(node.value)
                or any(self.is_tainted(g.iter) for g in node.generators)
            )
        if isinstance(node, ast.Starred):
            return self.is_tainted(node.value)
        if isinstance(node, ast.Await):
            return self.is_tainted(node.value)
        if isinstance(node, ast.NamedExpr):
            return self.is_tainted(node.value)
        return False

    def _format_tainted(self, value) -> bool:
        """Taint of a formatted/repr'd expression.  Directly formatting a
        redacted-bearer attribute is safe — its __repr__ is redacted."""
        if (
            isinstance(value, ast.Attribute)
            and value.attr in REDACTED_BEARER_ATTRS
        ):
            return False
        return self.is_tainted(value)

    def _call_tainted(self, node: ast.Call) -> bool:
        fname = terminal_name(node.func)
        if fname in SANITIZERS:
            return False
        if fname in ("repr", "str", "format") and len(node.args) == 1:
            return self._format_tainted(node.args[0])
        if fname in SECRET_CALLS:
            return True
        if self.is_tainted(node.func):
            return True
        if any(self.is_tainted(a) for a in node.args):
            return True
        return any(self.is_tainted(kw.value) for kw in node.keywords)

    # -- propagation -----------------------------------------------------

    def propagate(self) -> None:
        # Two sweeps reach a fixpoint for loop-carried assignments in
        # practice (chains longer than one loop round-trip do not occur
        # in lint-relevant code).
        for _ in range(2):
            for stmt in self.body:
                self._propagate_stmt(stmt)

    def _propagate_stmt(self, stmt) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return  # nested scopes are analyzed separately
        if isinstance(stmt, ast.Assign):
            self._bind(stmt.targets, stmt.value)
        elif isinstance(stmt, ast.AnnAssign) and stmt.value is not None:
            self._bind([stmt.target], stmt.value)
        elif isinstance(stmt, ast.AugAssign):
            self._bind([stmt.target], stmt.value)
        elif isinstance(stmt, (ast.For, ast.AsyncFor)):
            if self.is_tainted(stmt.iter):
                self.tainted.update(_target_names(stmt.target))
        # walrus bindings anywhere in the statement's expressions
        for node in ast.walk(stmt):
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef,
                                 ast.ClassDef, ast.Lambda)):
                continue
            if isinstance(node, ast.NamedExpr) and self.is_tainted(node.value):
                self.tainted.update(_target_names(node.target))
        for child in ast.iter_child_nodes(stmt):
            if isinstance(child, (ast.stmt,)):
                self._propagate_stmt(child)
            elif isinstance(child, (ast.ExceptHandler,)):
                for s in child.body:
                    self._propagate_stmt(s)

    def _bind(self, targets, value) -> None:
        tainted = self.is_tainted(value)
        if not tainted:
            return
        for t in targets:
            names = _target_names(t)
            if names:
                self.tainted.update(names)
            else:
                # Storing into x[...] or x.attr taints the container x —
                # except `self`/`cls`, where stashing a secret on the
                # object must not poison every later attribute read.
                base = _container_base(t)
                if base is not None and base not in ("self", "cls"):
                    self.tainted.add(base)

    # -- sinks -----------------------------------------------------------

    def check_sinks(self) -> None:
        for stmt in self.body:
            self._sink_stmt(stmt)

    def _sink_stmt(self, stmt) -> None:
        if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef,
                             ast.ClassDef)):
            return
        for node in self._walk_exprs(stmt):
            if isinstance(node, ast.Call):
                self._sink_call(node)
        if isinstance(stmt, ast.Raise) and stmt.exc is not None:
            self._sink_raise(stmt)
        elif isinstance(stmt, ast.Assert):
            if stmt.msg is not None and self.is_tainted(stmt.msg):
                self._emit("assert-leak", stmt.msg,
                           "assert message carries secret material")
        elif isinstance(stmt, ast.Return):
            if (
                self.name in SERIALIZERS
                and stmt.value is not None
                and self.is_tainted(stmt.value)
            ):
                self._emit(
                    "serialized-secret", stmt,
                    f"{self.name}() returns secret material for "
                    "serialization outside the process",
                )
        elif isinstance(stmt, ast.Assign):
            for t in stmt.targets:
                if (
                    isinstance(t, ast.Subscript)
                    and isinstance(t.value, ast.Attribute)
                    and t.value.attr == "metadata"
                    and self.is_tainted(stmt.value)
                ):
                    self._emit("metadata-leak", stmt,
                               "secret material stored into result metadata")
        for child in ast.iter_child_nodes(stmt):
            if isinstance(child, ast.stmt):
                self._sink_stmt(child)
            elif isinstance(child, ast.ExceptHandler):
                for s in child.body:
                    self._sink_stmt(s)

    def _walk_exprs(self, stmt):
        for child in ast.iter_child_nodes(stmt):
            if isinstance(child, (ast.stmt, ast.ExceptHandler,
                                  ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef, ast.Lambda)):
                continue
            yield from self._walk_expr_tree(child)

    def _walk_expr_tree(self, node):
        yield node
        for child in ast.iter_child_nodes(node):
            if isinstance(child, (ast.FunctionDef, ast.AsyncFunctionDef,
                                  ast.ClassDef, ast.Lambda)):
                continue
            yield from self._walk_expr_tree(child)

    def _call_args_tainted(self, node: ast.Call) -> bool:
        return any(self.is_tainted(a) for a in node.args) or any(
            self.is_tainted(kw.value) for kw in node.keywords
        )

    def _sink_call(self, node: ast.Call) -> None:
        fname = terminal_name(node.func)
        is_log = (
            isinstance(node.func, ast.Attribute) and fname in LOG_METHODS
        ) or (isinstance(node.func, ast.Name) and fname == "print")
        if is_log and self._call_args_tainted(node):
            self._emit("log-leak", node,
                       "secret material reaches a log/print/warn call")
        elif fname in WIRE_SINKS and self._call_args_tainted(node):
            self._emit("wire-leak", node,
                       f"secret material reaches wire sink {fname}()")
        for kw in node.keywords:
            if kw.arg == "metadata" and self.is_tainted(kw.value):
                self._emit("metadata-leak", node,
                           "secret material passed as metadata")
            elif kw.arg == "scores" and self.is_tainted(kw.value):
                # Decode results send their scores in the frame header.
                self._emit("scores-leak", node,
                           "secret material passed as result scores")

    def _sink_raise(self, stmt: ast.Raise) -> None:
        exc = stmt.exc
        leaked = False
        if isinstance(exc, ast.Call):
            leaked = self._call_args_tainted(exc)
        else:
            leaked = self.is_tainted(exc)
        if leaked:
            self._emit("exception-leak", exc,
                       "secret material embedded in exception text")

    def _emit(self, rule: str, node, message: str) -> None:
        snippet = source_snippet(self.module, node)
        if snippet:
            message = f"{message}: `{snippet}`"
        f = Finding(NAME, rule, self.module.path,
                    getattr(node, "lineno", 0), getattr(node, "col_offset", 0),
                    message)
        reason = self.module.declassify_reason(node)
        if reason:
            f.declassified = reason
        self.findings.append(f)


def _param_names(node) -> list:
    a = node.args
    params = [p.arg for p in a.posonlyargs + a.args + a.kwonlyargs]
    if a.vararg:
        params.append(a.vararg.arg)
    if a.kwarg:
        params.append(a.kwarg.arg)
    return params


def _analyze_scope(module: Module, name: str, body, params, inherited):
    ft = _FunctionTaint(module, name, body, params, inherited)
    ft.propagate()
    ft.check_sinks()
    findings = ft.findings
    # Nested defs (closures) inherit the enclosing tainted-name set.
    for stmt in body:
        findings.extend(_collect_nested(module, stmt, ft.tainted))
    return findings


def _collect_nested(module: Module, stmt, inherited):
    out = []
    if isinstance(stmt, (ast.FunctionDef, ast.AsyncFunctionDef)):
        out.extend(
            _analyze_scope(module, stmt.name, stmt.body,
                           _param_names(stmt), inherited)
        )
        return out
    if isinstance(stmt, ast.ClassDef):
        for s in stmt.body:
            out.extend(_collect_nested(module, s, set()))
        return out
    for child in ast.iter_child_nodes(stmt):
        if isinstance(child, ast.stmt):
            out.extend(_collect_nested(module, child, inherited))
        elif isinstance(child, ast.ExceptHandler):
            for s in child.body:
                out.extend(_collect_nested(module, s, inherited))
    return out


def run(modules) -> list:
    findings: list = []
    for module in modules:
        findings.extend(
            _analyze_scope(module, "<module>", module.tree.body, [], set())
        )
    return findings
