"""trace-coherence: every span/instant name the tracer records is in
the docs/tracing.md taxonomy.

The tracing page promises a complete span taxonomy — it is how an
operator staring at a perfetto view (or a traceview.py table) maps a
slice name back to code and meaning. PR 12's cross-node propagation
review found link/flow names that existed only in code; this rule is
the metrics-coherence discipline applied to the flight recorder: a
literal name passed to ``span()``/``instant()``/``flow_start()``/
``flow_end()``/``link()`` — on the ``trace`` module, on any tracer
object, or called bare after ``from tendermint_tpu.utils.trace import
span`` (absolute or relative) — must appear in docs/tracing.md. Dynamically built names
(``"consensus." + step``) are out of static reach and are skipped; the
step-span names they produce are documented as the per-step rows.
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterable, List, Optional, Set, Tuple

from tendermint_tpu.analysis.core import (
    FileContext,
    Project,
    Rule,
    Violation,
    register,
)

_DOCS = "docs/tracing.md"
_TRACE_MODULE = "tendermint_tpu.utils.trace"
# method name -> index of the name argument
_METHODS = {"span": 0, "instant": 0, "flow_start": 0, "flow_end": 0, "link": 1}
# tracer span names are dotted lowercase ("pipeline.execute"); the
# grammar gate keeps unrelated .span()/.instant() calls (re.Match.span,
# datetimes) from false-positiving when the receiver isn't the module
_NAME_RE = re.compile(r"^[a-z][a-z0-9_]*(\.[a-z0-9_]+)+$")


def _is_trace_module(node: ast.ImportFrom) -> bool:
    """``from tendermint_tpu.utils.trace import ...`` or a relative
    ``from ..utils.trace import ...``."""
    mod = node.module or ""
    return mod == _TRACE_MODULE or (node.level > 0 and (mod == "utils.trace" or mod == "trace"))


def _trace_aliases(tree: ast.AST) -> Tuple[Set[str], Dict[str, str]]:
    """Local names bound to the trace module in this file, and local
    names bound to its recording functions (local name -> function)."""
    out: Set[str] = set()
    funcs: Dict[str, str] = {}
    for node in ast.walk(tree):
        if isinstance(node, ast.ImportFrom):
            if node.module == "tendermint_tpu.utils" or (node.level > 0 and node.module == "utils"):
                for a in node.names:
                    if a.name == "trace":
                        out.add(a.asname or a.name)
            elif _is_trace_module(node):
                for a in node.names:
                    if a.name in _METHODS:
                        funcs[a.asname or a.name] = a.name
        elif isinstance(node, ast.Import):
            for a in node.names:
                if a.name == _TRACE_MODULE and a.asname:
                    out.add(a.asname)
    return out, funcs


def _literal_name(call: ast.Call, idx: int) -> Optional[str]:
    if len(call.args) <= idx:
        return None
    arg = call.args[idx]
    if isinstance(arg, ast.Constant) and isinstance(arg.value, str):
        return arg.value
    return None


class TraceCoherence(Rule):
    name = "trace-coherence"
    summary = (
        "every literal span/instant/flow name recorded by the tracer "
        "appears in the docs/tracing.md taxonomy"
    )

    def check_file(self, ctx: FileContext, project: Project) -> Iterable[Violation]:
        if ctx.tree is None or not ctx.in_package:
            return ()
        docs = project.docs_text(_DOCS)
        aliases, funcs = _trace_aliases(ctx.tree)
        out: List[Violation] = []
        for node in ctx.nodes:
            if not isinstance(node, ast.Call):
                continue
            if isinstance(node.func, ast.Attribute) and node.func.attr in _METHODS:
                recv = node.func.value
                on_module = isinstance(recv, ast.Name) and recv.id in aliases
                method = node.func.attr
            elif isinstance(node.func, ast.Name) and node.func.id in funcs:
                on_module, method = True, funcs[node.func.id]
            else:
                continue
            name = _literal_name(node, _METHODS[method])
            if name is None:
                continue
            if not on_module and not _NAME_RE.match(name):
                continue  # not span-name shaped and not our module: skip
            if name not in docs:
                out.append(
                    Violation(
                        self.name, ctx.rel, node.lineno,
                        f"trace name `{name}` is not in the {_DOCS} span "
                        "taxonomy (the page promises to list every "
                        "recorded name)",
                        node.col_offset,
                    )
                )
        return out


register(TraceCoherence())
