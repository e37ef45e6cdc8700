"""O-rules: repro.obs trace-span pairing and emission placement.

O001  Span pairing: every ``tracer.span_begin(...)`` site must reach a
      matching ``span_end``/``span_abort`` site. In per-function scopes
      (``serving/server.py``) this is the R002 CFG walk -- no path
      begin -> function exit may avoid every close, including the
      CancelledError / admission-retraction paths. In module-pairing
      scopes (``core/serving/engine.py``, where submit opens the span
      that step/abort close) the module must contain a close site, and
      the R001 entries in ``RELEASE_COMPLETENESS`` pin the specific
      closes to their functions.
O002  No event emission inside a Pallas kernel body: tracer calls in a
      traced/vmapped kernel are Python side effects that fire once at
      trace time (or never, on cached executables) -- they measure
      nothing and poison the zero-overhead-when-off guarantee. Emit
      from the host wrapper around the ``pallas_call``.
O003  Profiler-site pairing: every ``profiler.site_begin(...)`` or
      ``wait_begin(...)`` must reach a matching ``site_end``/``site_drop``
      /``wait_end`` on every CFG path of the SAME function (profiler
      sites measure a synchronous region, so unlike trace spans they
      never pair across function boundaries). A leaked begin corrupts the
      self/total attribution of every enclosing site. Begins and closes
      pair by site name (the call's first argument, literal or f-string).

Site matching understands the ``if <x>.enabled:`` guard idiom: the
guard's ``if`` header is the CFG site, so the infeasible
"enabled at begin, disabled at close" branch combination is not
reported (every real path crosses the guard header).
"""
from __future__ import annotations

import ast
from typing import Iterable, List

from repro.analysis.cfg import ENTRY, EXIT, build_cfg, function_defs
from repro.analysis.findings import Finding
from repro.analysis.registry import Rule, register
from repro.analysis.tables import (PROFILE_BEGIN_CALLS,
                                   PROFILE_CLOSE_CALLS, PROFILE_SCOPES,
                                   SPAN_BEGIN_CALLS, SPAN_CLOSE_CALLS,
                                   SPAN_SCOPES, TRACER_EMIT_CALLS,
                                   _own_nodes)


def _callee(node: ast.Call) -> str:
    f = node.func
    if isinstance(f, ast.Name):
        return f.id
    if isinstance(f, ast.Attribute):
        return f.attr
    return ""


def _subtree_calls(nodes: Iterable[ast.AST]) -> Iterable[str]:
    for root in nodes:
        for n in ast.walk(root):
            if isinstance(n, ast.Call):
                yield _callee(n)


def _is_enabled_guard(stmt: ast.stmt) -> bool:
    """``if <expr>.enabled:`` -- the tracer's zero-overhead gate."""
    return (isinstance(stmt, ast.If)
            and any(isinstance(n, ast.Attribute) and n.attr == "enabled"
                    for n in ast.walk(stmt.test)))


def _span_site(stmt: ast.stmt, names) -> bool:
    """``stmt`` emits one of ``names``: the call in its own expressions,
    or stmt is the ``if ...enabled:`` guard whose body holds the call
    (the guard header is the node every path crosses)."""
    if _is_enabled_guard(stmt):
        return any(c in names for c in _subtree_calls(stmt.body))
    return any(isinstance(n, ast.Call) and _callee(n) in names
               for n in _own_nodes(stmt))


def _site_names(stmt: ast.stmt, names) -> set:
    """The first arguments (as dumped ASTs: a literal or an f-string) of
    the calls to ``names`` that ``stmt`` emits, read as `_span_site`
    reads the statement."""
    if _is_enabled_guard(stmt):
        calls = [n for root in stmt.body for n in ast.walk(root)
                 if isinstance(n, ast.Call)]
    else:
        calls = [n for n in _own_nodes(stmt) if isinstance(n, ast.Call)]
    return {ast.dump(c.args[0]) if c.args else "" for c in calls
            if _callee(c) in names}


def _pairing_findings(rule: Rule, tree: ast.AST, path: str, scopes,
                      begin_calls, close_calls, module_msg: str,
                      leak_msg: str, by_name: bool = False
                      ) -> List[Finding]:
    """Shared begin/close pairing walk (O001 trace spans, O003 profiler
    sites): module-pairing scopes require at least one close site in the
    module; per-function scopes run the CFG walk -- no path from a begin
    site to the function exit may avoid every close site (``by_name``:
    every close site of the same name). Message templates take ``{fn}``
    (function name) / ``{scope}`` (description)."""
    out: List[Finding] = []
    for scope in scopes:
        if not path.endswith(scope.path_suffix):
            continue
        if scope.module_pairing:
            stmts = [n for n in ast.walk(tree) if isinstance(n, ast.stmt)]
            begins = [s for s in stmts if _span_site(s, begin_calls)]
            if begins and not any(_span_site(s, close_calls)
                                  for s in stmts):
                out.append(rule.finding(
                    path, begins[0].lineno,
                    module_msg.format(scope=scope.description)))
            continue
        for fn in function_defs(tree):
            body = [n for n in ast.walk(fn)
                    if isinstance(n, ast.stmt) and n is not fn]
            begins = [s for s in body if _span_site(s, begin_calls)]
            if not begins:
                continue
            closes = [s for s in body if _span_site(s, close_calls)]
            graph = build_cfg(fn)
            for b in begins:
                if b not in graph.succ:
                    continue                # nested def: out of this walk
                if by_name:
                    groups = [{s for s in closes
                               if name in _site_names(s, close_calls)}
                              for name in _site_names(b, begin_calls)]
                else:
                    groups = [set(closes)]
                if any(graph.path_avoiding(ENTRY, b, ok)
                       and graph.path_avoiding(b, EXIT, ok - {b})
                       for ok in groups):
                    out.append(rule.finding(
                        path, b.lineno, leak_msg.format(fn=fn.name)))
    return out


@register
class SpanPairingRule(Rule):
    rule_id = "O001"
    family = "O"
    severity = "error"
    description = ("a tracer span_begin site can reach a function exit "
                   "without a matching span_end/span_abort")

    def applies(self, path: str) -> bool:
        return any(path.endswith(s.path_suffix) for s in SPAN_SCOPES)

    def check(self, tree: ast.AST, src: str, path: str) -> List[Finding]:
        return _pairing_findings(
            self, tree, path, SPAN_SCOPES, SPAN_BEGIN_CALLS,
            SPAN_CLOSE_CALLS,
            "module opens trace spans but contains no span_end/"
            "span_abort site -- every span it begins is an orphan "
            "({scope})",
            "span opened here in `{fn}` can reach a function exit "
            "without span_end/span_abort -- orphan span on that path")


@register
class ProfileSitePairingRule(Rule):
    rule_id = "O003"
    family = "O"
    severity = "error"
    description = ("a profiler site_begin can reach a function exit "
                   "without a matching site_end")

    def applies(self, path: str) -> bool:
        return any(path.endswith(s.path_suffix) for s in PROFILE_SCOPES)

    def check(self, tree: ast.AST, src: str, path: str) -> List[Finding]:
        return _pairing_findings(
            self, tree, path, PROFILE_SCOPES, PROFILE_BEGIN_CALLS,
            PROFILE_CLOSE_CALLS,
            "module opens profiler sites but contains no site_end "
            "({scope})",
            "profiler site opened here in `{fn}` can reach a function "
            "exit without its site_end -- the open frame corrupts self/"
            "total attribution for every later site", by_name=True)


def _mentions_tracer(expr: ast.expr) -> bool:
    return any((isinstance(n, ast.Name) and n.id == "tracer")
               or (isinstance(n, ast.Attribute) and n.attr == "tracer")
               for n in ast.walk(expr))


@register
class KernelEmissionRule(Rule):
    rule_id = "O002"
    family = "O"
    severity = "error"
    description = ("tracer event emission inside a Pallas kernel body "
                   "(fires at trace time, not per step)")

    def applies(self, path: str) -> bool:
        return "kernels/" in path or path.endswith("_kernel.py")

    def check(self, tree: ast.AST, src: str, path: str) -> List[Finding]:
        from repro.analysis.rules_kernels import _sites
        out: List[Finding] = []
        kernels = []
        for site in _sites(tree):
            kern = site.kernel_fn()
            if kern is not None and kern not in kernels:
                kernels.append(kern)
        for kern in kernels:
            for node in ast.walk(kern):
                if not isinstance(node, ast.Call):
                    continue
                name = _callee(node)
                # span_* names are distinctive; the generic names
                # (slice/counter/instant) only count on a tracer object,
                # so jax.lax.slice etc. never false-positive
                span_call = name in SPAN_BEGIN_CALLS + SPAN_CLOSE_CALLS
                tracer_call = (name in TRACER_EMIT_CALLS
                               and isinstance(node.func, ast.Attribute)
                               and _mentions_tracer(node.func.value))
                if span_call or tracer_call:
                    out.append(self.finding(
                        path, node.lineno,
                        f"kernel `{kern.name}` emits trace event "
                        f"`{name}` inside the kernel body; a traced "
                        "kernel runs this once at trace time (or never "
                        "from a cached executable) -- emit from the "
                        "host wrapper around the pallas_call"))
        return out
