"""TC16: black-box field names from the flight/postmortem registries, and
ops/debug HTTP query surfaces only through ``http11.ops_route``.

Two halves of one invariant — the engine's black box (ISSUE 12) is only
trustworthy if its vocabulary and its transport are single-sourced:

1. **Schema registry** (the TC06/TC09 catalog pattern): every keyword
   handed to ``record_iteration(...)`` must be declared in
   ``utils/flight.py``'s ``FLIGHT_SCHEMA``, and any dict-literal ``slo=``
   /extra payload keys reaching ``BlackBox.capture`` must be postmortem
   schema members.  A typo'd field doesn't fail anything — it silently
   splits the black-box vocabulary between the writer and every reader
   (traceview --flight, the bundle-identity chaos test, dashboards).
   The start-up journal (ISSUE 40) keeps to the same rule: the keywords
   of a compile event (``<...>compile_watch.note(...)``) and the attrs of
   a ``startup.*`` span (``add_span`` / ``add_event`` / ``startup_phase``
   with a literal ``"startup."`` name; ``t0`` / ``t1`` / ``t`` are the
   clock's) must be declared in ``STARTUP_SCHEMA``.

2. **Ops routing**: the serve loop, proxy, and any future debug surface
   must classify ``/healthz`` / ``/metrics`` requests through
   ``http11.ops_route`` (and test query flags against its returned flag
   set), never by hand-rolled path string matching — PR 9 unified three
   hand-rolled copies that had already diverged on reordered query
   params, and ``?postmortem=1`` would have minted a fourth.  This half
   flags, inside ``endpoints/`` modules other than ``http11.py``:
   comparisons/``startswith``/membership against ``/healthz`` or
   ``/metrics`` literals, and ``"<k>=<v>" in <something>.path`` membership
   tests (query parsing that is order- and duplicate-sensitive).
"""

from __future__ import annotations

import ast
import re
from typing import Iterator, List

from tools.tunnelcheck.core import (
    ProjectContext,
    SourceFile,
    Violation,
    dotted_name,
)

#: The write entry point whose keyword arguments are flight-record fields.
FLIGHT_WRITE = "record_iteration"
#: The capture entry point; a literal dict bound to these keywords carries
#: postmortem top-level fields.
CAPTURE_FN = "capture"
#: The start-up journal's span writers (name first, attrs as keywords) and
#: the keywords that are instants of the clock, not attrs.
STARTUP_WRITES = {"add_span", "add_event", "startup_phase"}
STARTUP_CLOCK_KW = {"t0", "t1", "t"}
#: Its compile-event writer, told from any other ``note`` by its receiver.
COMPILE_NOTE = "note"
COMPILE_NOTE_RECEIVER = "compile_watch"

#: Registry module (the schemas live here); its own internals are exempt
#: from the ops/record checks the way utils/metrics.py is for TC12.
REGISTRY_SUFFIX = "p2p_llm_tunnel_tpu/utils/flight.py"
#: The one module allowed to string-match ops paths.
OPS_ROUTER_SUFFIX = "p2p_llm_tunnel_tpu/endpoints/http11.py"

_OPS_PATHS = ("/healthz", "/metrics")
#: A raw query-flag token like ``trace=1`` / ``postmortem=1``.
_FLAG_RE = re.compile(r"^[A-Za-z_][A-Za-z0-9_]*=[A-Za-z0-9_]+$")

_SCHEMA_MSG = (
    "field {names} not declared in utils.flight.{registry} — black-box "
    "field names are a registry contract (the TC06 pattern): a typo here "
    "silently splits the vocabulary between the writer and every bundle/"
    "flight reader; declare the field or fix the spelling"
)
_OPS_MSG = (
    "hand-rolled ops-path matching on {literal!r} — route /healthz and "
    "/metrics requests through http11.ops_route (and test query flags "
    "against its returned flag set): per-site string matching diverges on "
    "reordered or repeated query parameters (the pre-ISSUE-9 three-copy "
    "drift class)"
)


def _is_ops_literal(node: ast.AST) -> bool:
    return (isinstance(node, ast.Constant) and isinstance(node.value, str)
            and node.value.split("?")[0] in _OPS_PATHS)


def _path_attr(node: ast.AST) -> bool:
    """Is this expression a ``<recv>.path`` attribute read (raw request
    path — the thing query flags must not be string-matched against)?"""
    return isinstance(node, ast.Attribute) and node.attr == "path"


def check_tc16(sf: SourceFile, ctx: ProjectContext) -> Iterator[Violation]:
    out: List[Violation] = []
    posix = sf.path.as_posix()
    in_registry = posix.endswith(REGISTRY_SUFFIX)

    # -- half 1: schema-registry field names ------------------------------
    flight_fields = ctx.flight_fields
    postmortem_fields = ctx.postmortem_fields
    startup_fields = ctx.startup_fields
    for node in ast.walk(sf.tree):
        if not isinstance(node, ast.Call):
            continue
        fn = node.func
        name = fn.attr if isinstance(fn, ast.Attribute) else (
            fn.id if isinstance(fn, ast.Name) else None
        )
        if name == FLIGHT_WRITE and flight_fields:
            bad = sorted(
                kw.arg for kw in node.keywords
                if kw.arg is not None and kw.arg not in flight_fields
            )
            if bad:
                out.append(Violation(
                    "TC16", sf.path, node.lineno,
                    _SCHEMA_MSG.format(names=bad, registry="FLIGHT_SCHEMA"),
                    end_line=node.end_lineno,
                ))
        startup_kw = None
        if startup_fields and isinstance(fn, ast.Attribute):
            first = node.args[0] if node.args else None
            if (name in STARTUP_WRITES and isinstance(first, ast.Constant)
                    and isinstance(first.value, str)
                    and first.value.startswith("startup.")):
                startup_kw = STARTUP_CLOCK_KW
            elif name == COMPILE_NOTE and (
                    dotted_name(fn.value) or "").endswith(
                        COMPILE_NOTE_RECEIVER):
                startup_kw = set()
        if startup_kw is not None:
            bad = sorted(
                kw.arg for kw in node.keywords
                if kw.arg is not None and kw.arg not in startup_kw
                and kw.arg not in startup_fields
            )
            if bad:
                out.append(Violation(
                    "TC16", sf.path, node.lineno,
                    _SCHEMA_MSG.format(names=bad, registry="STARTUP_SCHEMA"),
                    end_line=node.end_lineno,
                ))
        if name == CAPTURE_FN and postmortem_fields:
            # A dict literal handed to capture(extra=...) merges into the
            # bundle top level: its keys are postmortem fields.  (The
            # ``slo=`` payload is an objective map, not schema fields.)
            for kw in node.keywords:
                if kw.arg == "extra" and isinstance(kw.value, ast.Dict):
                    bad = sorted(
                        k.value for k in kw.value.keys
                        if isinstance(k, ast.Constant)
                        and isinstance(k.value, str)
                        and k.value not in postmortem_fields
                    )
                    if bad:
                        out.append(Violation(
                            "TC16", sf.path, node.lineno,
                            _SCHEMA_MSG.format(
                                names=bad, registry="POSTMORTEM_SCHEMA"
                            ),
                            end_line=node.end_lineno,
                        ))

    # -- half 2: ops routing only via http11.ops_route --------------------
    if ("/endpoints/" not in posix or posix.endswith(OPS_ROUTER_SUFFIX)
            or in_registry):
        return iter(out)
    for node in ast.walk(sf.tree):
        literal = None
        if isinstance(node, ast.Compare):
            # `req.path == "/healthz"` / `"/healthz" in path` — but flag
            # membership of raw query tokens ONLY against a `.path`
            # expression: `"trace=1" in route[1]` (ops_route's flag set)
            # is the sanctioned pattern.
            sides = [node.left] + list(node.comparators)
            for side in sides:
                if _is_ops_literal(side):
                    literal = side.value
            if literal is None and isinstance(
                node.ops[0], (ast.In, ast.NotIn)
            ):
                lhs = node.left
                if (isinstance(lhs, ast.Constant)
                        and isinstance(lhs.value, str)
                        and _FLAG_RE.match(lhs.value)
                        and any(_path_attr(c) for c in node.comparators)):
                    literal = lhs.value
        elif (isinstance(node, ast.Call)
              and isinstance(node.func, ast.Attribute)
              and node.func.attr == "startswith"
              and node.args and _is_ops_literal(node.args[0])):
            literal = node.args[0].value
        if literal is not None:
            out.append(Violation(
                "TC16", sf.path, node.lineno,
                _OPS_MSG.format(literal=literal),
                end_line=node.end_lineno,
            ))
    return iter(out)
