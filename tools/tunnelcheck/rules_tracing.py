"""TC09: span names registered in SPAN_CATALOG; no emission in traced code.

The TC06 pattern applied to the span journal (ISSUE 6): a typo'd span name
(``engine.queue_wiat``) doesn't fail anything — it silently splits a
request's timeline and every traceview rollup keyed on the real name reads
"missing".  ``utils/tracing.py`` carries the one catalogue of legal span
names; every literal string handed to the recorder's emit methods
(``add_span`` / ``add_event``) must appear in it.

Second invariant: span emission is HOST-ONLY.  A recorder call inside a
function this module jits or hands to ``lax.scan`` is a tracer error at
best (the timestamp would be a traced value) and a per-step host sync at
worst — the exact dispatch-path contamination the tracing module exists to
avoid (its charter: zero device dispatches on the serving path, TC07
clean).  Reuses TC03's traced-function discovery so the two rules cannot
disagree about what "traced" means.
"""

from __future__ import annotations

import ast
from typing import Iterator, List

from tools.tunnelcheck.core import ProjectContext, SourceFile, Violation
from tools.tunnelcheck.rules_jax import _traced_functions

#: The recorder's emit surface (utils.tracing.TraceRecorder) and the
#: start-up journal's (utils.flight.CompileWatch: the same two names plus
#: the block timer), whose ``startup.*`` names live in the same catalogue.
SPAN_EMIT_METHODS = {"add_span", "add_event", "startup_phase"}


def check_tc09(sf: SourceFile, ctx: ProjectContext) -> Iterator[Violation]:
    catalogue = ctx.span_names
    traced_ids = {}
    for fn, _statics in _traced_functions(sf, ctx):
        name = getattr(fn, "name", "<lambda>")
        for sub in ast.walk(fn):
            traced_ids.setdefault(id(sub), name)
    out: List[Violation] = []
    for node in ast.walk(sf.tree):
        if not (
            isinstance(node, ast.Call)
            and isinstance(node.func, ast.Attribute)
            and node.func.attr in SPAN_EMIT_METHODS
        ):
            continue
        fn_name = traced_ids.get(id(node))
        if fn_name is not None:
            out.append(
                Violation(
                    "TC09",
                    sf.path,
                    node.lineno,
                    f"span emission `{node.func.attr}(...)` inside traced "
                    f"`{fn_name}` — tracing is host-only; a recorder call "
                    "in jitted/scanned code is a tracer error or a "
                    "per-step host sync (move it to the dispatch site)",
                    end_line=node.end_lineno,
                )
            )
        if not (
            catalogue
            and node.args
            and isinstance(node.args[0], ast.Constant)
            and isinstance(node.args[0].value, str)
        ):
            continue
        name = node.args[0].value
        if name not in catalogue:
            out.append(
                Violation(
                    "TC09",
                    sf.path,
                    node.lineno,
                    f"span `{node.func.attr}(\"{name}\", ...)` uses a name "
                    "not declared in utils.tracing.SPAN_CATALOG — a typo "
                    "here silently splits the request timeline; declare it "
                    "or fix the spelling",
                    end_line=node.end_lineno,
                )
            )
    return iter(out)
