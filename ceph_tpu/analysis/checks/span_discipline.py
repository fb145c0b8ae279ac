"""span-discipline: spans finish on every path; stage names are declared.

Two contracts, both rooted in PR 8's observability layer:

1. **Every ``start_span`` reaches ``finish()``.**  A span that never
   finishes never archives — the trace silently loses a subtree, and
   nothing fails.  Accepted shapes: the span is a ``with`` context
   manager, or its assignment target (name or dotted attribute) has a
   matching ``.finish()`` call in the enclosing function (nested
   closures count — commit callbacks finish their op's span), with a
   module-wide fallback for handles finished by a sibling method
   (``op.span`` set in submit, finished in the reply dispatcher).
   A ``start_span`` that is neither assigned nor entered is always a
   violation — nothing can ever finish it.

2. **Stage names come from the registry.**  Timeline/stage names used
   with ``mark_event`` / ``PG._op_stage`` must be string literals
   declared in ``tracing.STAGES`` (a typo'd stage is a dead timeline
   row that never feeds its latency histogram), and a ``annotate``
   call whose argument is a PLAIN string literal must name a declared
   stage too — free-form detail annotations use f-strings/variables,
   which are exempt.

3. **Recorder span names come from their registry too.**  The name
   given to ``tracing.span(...)`` must be a string literal declared in
   ``tracing.SPANS``, the table that says which per-layer metric reads
   each span: a typo'd name is a span no reader ever sums.

Never baselineable: the observability layer ships with this check, so
there is no accepted debt — like the failpoint-name registry.
"""

from __future__ import annotations

import ast
from typing import Dict, List, Sequence, Set

from ceph_tpu.analysis.framework import (
    NEVER_BASELINE_PREFIXES, Check, SourceFile, Violation, call_name,
    dotted, enclosing_scope,
)

# files that implement the machinery itself (the registry, the tracer,
# the tracker): their internal uses of these names are the mechanism,
# not call sites
_SELF = ("core/tracing.py", "core/optracker.py")


def _functions(tree: ast.AST):
    for node in ast.walk(tree):
        if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
            yield node


class SpanDiscipline(Check):
    name = "span-discipline"
    description = ("start_span must reach finish() on all paths; "
                   "mark_event/_op_stage/literal-annotate names must "
                   "be declared in tracing.STAGES, tracing.span names "
                   "in tracing.SPANS")
    scopes = ("ceph_tpu", "tools")

    def run(self, files: Sequence[SourceFile]) -> List[Violation]:
        from ceph_tpu.core.tracing import SPANS, STAGES

        out: List[Violation] = []
        for f in files:
            if any(f.rel.endswith(s) for s in _SELF):
                continue
            out.extend(self._check_stage_names(f, STAGES, SPANS))
            out.extend(self._check_span_finish(f))
        return out

    # -- stage-name registry ------------------------------------------------
    def _check_stage_names(self, f: SourceFile, stages,
                           spans) -> List[Violation]:
        out: List[Violation] = []
        for node in ast.walk(f.tree):
            if not isinstance(node, ast.Call):
                continue
            base = call_name(node).rsplit(".", 1)[-1]
            table, registry = stages, "tracing.STAGES"
            if call_name(node).endswith("tracing.span") and node.args:
                base, arg = "tracing.span", node.args[0]
                table, registry = spans, "tracing.SPANS"
            elif base == "mark_event" and node.args:
                arg = node.args[0]
            elif base == "_op_stage" and len(node.args) >= 2:
                # PG._op_stage(msg, "<stage>", ...) — stage is arg 2
                # at a call site, arg index differs for the bound form
                arg = node.args[1] if not isinstance(
                    node.args[0], ast.Constant) else node.args[0]
            elif base == "annotate" and node.args:
                arg = node.args[0]
                if not (isinstance(arg, ast.Constant)
                        and isinstance(arg.value, str)):
                    continue  # f-string/variable detail: free-form
            else:
                continue
            if not (isinstance(arg, ast.Constant)
                    and isinstance(arg.value, str)):
                out.append(Violation(
                    check=self.name, path=f.rel, line=node.lineno,
                    scope=enclosing_scope(f.tree, node.lineno),
                    detail=f"{base}(<dynamic>)",
                    message=(f"{base}() name must be a string "
                             "literal — a dynamic name evades the "
                             "registry and every grep"),
                ))
                continue
            if arg.value not in table:
                out.append(Violation(
                    check=self.name, path=f.rel, line=node.lineno,
                    scope=enclosing_scope(f.tree, node.lineno),
                    detail=f"{base}({arg.value!r})",
                    message=(f"name {arg.value!r} is not declared in "
                             f"{registry} — a typo'd name is a dead "
                             "timeline row that nothing reads"),
                ))
        return out

    # -- finish-on-all-paths --------------------------------------------------
    def _check_span_finish(self, f: SourceFile) -> List[Violation]:
        out: List[Violation] = []
        # module-wide set of dotted names that have a .finish() call
        module_finished: Set[str] = set()
        for node in ast.walk(f.tree):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "finish"):
                base = dotted(node.func.value)
                if base:
                    module_finished.add(base)

        # map every start_span call to its innermost enclosing function
        # (or module) and the targets it is bound to
        func_of: Dict[ast.AST, ast.AST] = {}
        for fn in _functions(f.tree):
            for child in ast.walk(fn):
                func_of.setdefault(child, fn)

        for node in ast.walk(f.tree):
            if not isinstance(node, ast.Call):
                continue
            if call_name(node).rsplit(".", 1)[-1] != "start_span":
                continue
            scope_node = func_of.get(node, f.tree)
            if self._span_handled(node, scope_node, module_finished):
                continue
            out.append(Violation(
                check=self.name, path=f.rel, line=node.lineno,
                scope=enclosing_scope(f.tree, node.lineno),
                detail="start_span-unfinished",
                message=("start_span() result is neither a `with` "
                         "context manager nor bound to a target with "
                         "a matching .finish() — the span can never "
                         "archive"),
            ))
        return out

    @staticmethod
    def _span_handled(call: ast.Call, scope: ast.AST,
                      module_finished: Set[str]) -> bool:
        targets: List[str] = []
        for node in ast.walk(scope):
            # with tracer.start_span(...) [as s]: finish via __exit__
            if isinstance(node, (ast.With, ast.AsyncWith)):
                for item in node.items:
                    if item.context_expr is call:
                        return True
            if isinstance(node, ast.Assign) and node.value is call:
                for t in node.targets:
                    name = dotted(t)
                    if name:
                        targets.append(name)
            if (isinstance(node, (ast.AnnAssign, ast.AugAssign))
                    and getattr(node, "value", None) is call):
                name = dotted(node.target)
                if name:
                    targets.append(name)
            # span = x or tr.start_span(...) style defaults
            if isinstance(node, ast.Assign) and isinstance(
                    node.value, (ast.BoolOp, ast.IfExp)):
                sub = ast.walk(node.value)
                if any(s is call for s in sub):
                    for t in node.targets:
                        name = dotted(t)
                        if name:
                            targets.append(name)
        if not targets:
            return False
        # accept when the enclosing function (closures included) calls
        # .finish() on the same target; fall back to a module-wide
        # match for handles finished by a sibling method
        finished_here: Set[str] = set()
        for node in ast.walk(scope):
            if (isinstance(node, ast.Call)
                    and isinstance(node.func, ast.Attribute)
                    and node.func.attr == "finish"):
                base = dotted(node.func.value)
                if base:
                    finished_here.add(base)
        for t in targets:
            # an attribute target like `rnd.span` matches a finish on
            # `rnd.span` or on any alias ending with the same attr
            # (`self._round.span.finish()` / `op.span.finish()`)
            tail = t.rsplit(".", 1)[-1]
            for got in finished_here | module_finished:
                if got == t or got.rsplit(".", 1)[-1] == tail:
                    return True
        return False


# the observability layer ships WITH this check: no accepted debt,
# violations are hard errors everywhere
NEVER_BASELINE_PREFIXES.append((SpanDiscipline.name, ""))
