"""TC17: every dispatch-site compiled-program kind must be warmup-reachable.

The engine's readiness contract (ISSUE 12/15): after ``warmup()`` declares
the grid complete, a first-seen program key on the serving path is a
MID-SERVE COLD COMPILE — tens of seconds of stall inside a live request
at 7B widths.  The runtime detector
(``engine_cold_compiles_total``) catches the hole when traffic hits it;
this rule is its static counterpart: every ``_program_key`` spelling an
engine dispatch site can emit (the literal ``kind`` handed to
``_note_program``/``_program_key``) must be REACHABLE from the warmup/AOT
plan generators — functions named ``warmup*`` or ``_warm*`` (the
``warmup_plan`` enumeration, the per-kind warm methods) — or carry a
per-line waiver naming why that program is allowed to compile on first
use.

The regression class is the ISSUE 5 width-hint hole ``test_warmup_aot``
caught at runtime: chunk-prefill dispatches reached view buckets the
warmup enumeration never visited.  A kind that exists ONLY at a dispatch
site is the same bug one layer earlier — the plan generator cannot even
enumerate shapes for a kind it has never heard of.

Mechanics: per file, literal kinds are collected from two sides —

- **dispatch kinds**: string literals in the first argument of
  ``_note_program(...)``/``_program_key(...)`` calls inside functions NOT
  named like warm generators (an ``IfExp`` first argument contributes
  BOTH branch literals — the ``"prefill_echo" if echo else "prefill"``
  shape must not hide its echo branch);
- **warm kinds**: the same call-argument literals inside warm-named
  functions, plus the FIRST element of any tuple literal there (the
  ``warmup_plan`` ``[(kind, shape), ...]`` enumeration and the AOT jobs
  list both carry kinds in that position).

A dispatch kind absent from the file's warm kinds flags at the dispatch
site.  Files that never call ``_note_program`` are out of scope.

A key is its kind AND its shape (ISSUE 30: a chunk-prefill program is
``(rows, t, view)`` since a dispatch picks its row count): where a dispatch
site and the warm side both spell a kind's shape as a tuple literal, the
dispatch site's must have as many dimensions as one the warm side spells —
a ``chunk[t,view]`` noted at dispatch can never equal a warmed
``chunk[rows,t,view]``, so every first dispatch would count as a cold
compile (and a plan that forgot the new axis warms one rung of many).
"""

from __future__ import annotations

import ast
import re
from typing import Dict, Iterator, List, Optional, Set

from tools.tunnelcheck.core import ProjectContext, SourceFile, Violation

#: The program-accounting entry points whose first argument is a kind.
_KIND_FNS = ("_note_program", "_program_key")

#: Functions whose bodies ARE the warmup/AOT plan: the serial pass, the
#: plan enumeration, and the per-kind warm helpers.
_WARM_NAME_RE = re.compile(r"^(warmup|_warm)")

_MSG_DIMS = (
    "program kind {kind!r} is keyed by {got} dimension(s) here but the "
    "warmup/AOT plan generators key it by {want}: the two spellings of the "
    "key can never be equal, so warm-up readies programs this site never "
    "finds and every first dispatch counts as a mid-serve cold compile — "
    "give the dispatch site and warmup_plan()/_warm_* the same shape"
)

_MSG = (
    "program kind {kind!r} is dispatched here but unreachable from the "
    "warmup/AOT plan generators (no warmup*/_warm* function in this file "
    "mentions it) — a first-seen key after warmup() is a mid-serve cold "
    "compile (engine_cold_compiles_total, the test_warmup_aot width-hint "
    "hole class); add the kind to warmup_plan()/a _warm_* helper, or "
    "waive naming why first-use compilation is acceptable for it"
)


def _call_name(node: ast.Call) -> Optional[str]:
    fn = node.func
    if isinstance(fn, ast.Attribute):
        return fn.attr
    if isinstance(fn, ast.Name):
        return fn.id
    return None


def _arg0_kinds(node: ast.Call) -> List[str]:
    """Literal kind strings in a kind-fn call's first argument — plain
    constants and BOTH branches of a conditional expression."""
    if not node.args:
        return []
    a = node.args[0]
    if isinstance(a, ast.Constant) and isinstance(a.value, str):
        return [a.value]
    if isinstance(a, ast.IfExp):
        return [
            b.value for b in (a.body, a.orelse)
            if isinstance(b, ast.Constant) and isinstance(b.value, str)
        ]
    return []


def _dims(node: Optional[ast.AST]) -> Optional[int]:
    """Dimensions of a shape spelled as a tuple literal, else None."""
    if isinstance(node, ast.Tuple) and not any(
            isinstance(e, ast.Starred) for e in node.elts):
        return len(node.elts)
    return None


def check_tc17(sf: SourceFile, ctx: ProjectContext) -> Iterator[Violation]:
    warm_kinds: Set[str] = set()
    warm_dims: Dict[str, Set[int]] = {}
    dispatch_sites: List = []  # (node, kinds, dimensions of the shape)
    saw_note = [False]

    def visit_fn(fn, enclosing_warm: Optional[bool]) -> None:
        # A method/module-level def is warm by NAME; a nested def
        # inherits its enclosing function's warmth — a warm-named closure
        # inside a dispatcher is part of the dispatcher (it must not
        # launder the dispatcher's kinds), and a dispatch helper nested
        # inside a warm function runs during warmup.
        if enclosing_warm is None:
            is_warm = bool(_WARM_NAME_RE.match(fn.name))
        else:
            is_warm = enclosing_warm
        stack = list(ast.iter_child_nodes(fn))
        while stack:
            node = stack.pop()
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                visit_fn(node, is_warm)
                continue
            if isinstance(node, ast.Call):
                name = _call_name(node)
                if name in _KIND_FNS:
                    kinds = _arg0_kinds(node)
                    dims = _dims(node.args[1]) if len(node.args) > 1 else None
                    if not is_warm:
                        # BOTH spellings are dispatch sites: a program
                        # key minted via _program_key directly (ad-hoc
                        # accounting) is just as reachable-from-serving
                        # as a _note_program call.
                        saw_note[0] = True
                        if kinds:
                            dispatch_sites.append((node, kinds, dims))
                    else:
                        warm_kinds.update(kinds)
                        if dims is not None:
                            for kind in kinds:
                                warm_dims.setdefault(kind, set()).add(dims)
            elif is_warm and isinstance(node, ast.Tuple) and node.elts:
                # The plan enumeration's ("kind", shape) tuples and the
                # AOT jobs list's leading-label tuples.
                first = node.elts[0]
                if (isinstance(first, ast.Constant)
                        and isinstance(first.value, str)):
                    warm_kinds.add(first.value)
                    dims = _dims(node.elts[1]) if len(node.elts) == 2 else None
                    if dims is not None:
                        warm_dims.setdefault(first.value, set()).add(dims)
            stack.extend(ast.iter_child_nodes(node))

    def visit_scope(body) -> None:
        for node in body:
            if isinstance(node, (ast.FunctionDef, ast.AsyncFunctionDef)):
                visit_fn(node, None)
            elif isinstance(node, ast.ClassDef):
                visit_scope(node.body)

    visit_scope(sf.tree.body)
    if not saw_note[0]:
        return iter(())
    out: List[Violation] = []
    for node, kinds, dims in dispatch_sites:
        for kind in sorted(set(kinds) - warm_kinds):
            out.append(Violation(
                "TC17", sf.path, node.lineno,
                _MSG.format(kind=kind),
                end_line=node.end_lineno,
            ))
        for kind in sorted(set(kinds) & warm_kinds):
            want = warm_dims.get(kind)
            if dims is not None and want and dims not in want:
                out.append(Violation(
                    "TC17", sf.path, node.lineno,
                    _MSG_DIMS.format(
                        kind=kind, got=dims,
                        want=" or ".join(str(d) for d in sorted(want))),
                    end_line=node.end_lineno,
                ))
    return iter(out)
