"""TC07: device dispatches inside per-request/per-slot loops on the
serving path.

An incident made permanent (ISSUE 4 satellite): the prefix-cache copy-in
originally dispatched ONE jitted copy per matched request inside the
admission loop — a 32-client wave paid 32 host↔device round trips inside
its prefill path, and nothing failed.  The fix (batch the wave into one
``prefill_rows``-wide dispatch) is invisible to tests, so the invariant lives
here: in the engine/endpoints serving modules, a loop whose subject is
requests/slots/admissions must not contain a device dispatch per
iteration.

"Device dispatch" is resolved statically, in three layers:
- direct device ops: ``jax.device_put`` / ``jax.device_get`` /
  ``jax.block_until_ready`` and ``.block_until_ready()`` method calls;
- names bound to ``jax.jit(...)`` results — including tuple-unpacked
  results of PROJECT-WIDE factory functions whose bodies call ``jax.jit``
  (``make_batch_copy_ops``), and rebindings that pass a known name back
  through a wrapper (``self._spmd.wrap("op", self._jit_x, n)``);
- functions/methods of the same module that transitively CALL any of the
  above (the r5 class: a helper that dispatches per call, invoked from a
  request loop — directly or handed to ``run_in_executor``).

Loop subjects match word-wise (identifiers split on underscores), so
``while self._running`` — the engine's main loop, whose one dispatch per
BURST is the design — does not match, while ``for run in runs`` does.

Deliberately-batched sub-batch loops (one dispatch per prefill_rows-wide
chunk) and the pipelined admission fetch loop are the legitimate
exceptions — they carry per-line waivers with reasons, which doubles as
documentation of the dispatch-granularity contract at each site.
"""

from __future__ import annotations

import ast
from typing import Iterator, List, Set

from tools.tunnelcheck.core import (
    ProjectContext,
    SourceFile,
    Violation,
    resolve_dotted,
)

#: Serving-path modules: the engine package and the tunnel endpoints.
SCOPE_PARTS = (
    "p2p_llm_tunnel_tpu/engine/",
    "p2p_llm_tunnel_tpu/endpoints/",
)

#: Loop-subject vocabulary (matched word-wise against identifiers in the
#: loop target/iterable/condition): iteration over these means "once per
#: request-shaped thing", where a per-iteration dispatch is the r5 bug.
SUBJECT_WORDS = frozenset({
    "request", "requests", "req", "reqs",
    "slot", "slots",
    "run", "runs",
    "hit", "hits",
    "admitted", "admissions",
    "prompt", "prompts",
    "entry", "entries",
    # NOT "chunk"/"chunked": warmup iterates static chunk-width buckets
    # (engine._warm_prefix) — a compile-time loop, not a request loop; the
    # genuine chunk loops all carry runs/hits/slots identifiers too.
    "segment", "segments", "segmented",
    "dispatched",
    "wave", "waves",
    "client", "clients",
    "stream", "streams",
})

DEVICE_CALLS = {
    "jax.device_put": "jax.device_put",
    "jax.device_get": "jax.device_get",
    "jax.block_until_ready": "jax.block_until_ready",
}

#: ``_offload(loop, fn, ...)`` is the engine loop's timed
#: ``run_in_executor`` (ISSUE 57): the callable is its second argument too.
_EXECUTOR_METHODS = {"run_in_executor", "_offload", "submit"}


def _in_scope(sf: SourceFile) -> bool:
    p = sf.path.as_posix()
    return any(part in p for part in SCOPE_PARTS)


def _ident_words(node: ast.AST) -> Set[str]:
    words: Set[str] = set()
    for sub in ast.walk(node):
        name = None
        if isinstance(sub, ast.Name):
            name = sub.id
        elif isinstance(sub, ast.Attribute):
            name = sub.attr
        elif isinstance(sub, ast.arg):
            name = sub.arg
        if name:
            words.update(w for w in name.lower().split("_") if w)
    return words


def _callee_name(func: ast.AST) -> str:
    if isinstance(func, ast.Attribute):
        return func.attr
    if isinstance(func, ast.Name):
        return func.id
    return ""


def _project_jit_factories(ctx: ProjectContext) -> Set[str]:
    """Names of functions ANYWHERE in the scanned set whose body contains
    a ``jax.jit(...)`` call — their return values (tuples included) are
    dispatch callables, and calling them IS a trace/dispatch.  Served by
    the shared call graph (this used to be a private project-wide scan)."""
    return ctx.callgraph.functions_calling("jax.jit")


def _dispatch_names(sf: SourceFile, factories: Set[str]) -> Set[str]:
    """Variable/attribute names bound (anywhere in the file) to dispatch
    callables: jax.jit results, jit-factory results, or wrappers fed a
    known dispatch name (fixpoint for rebinding chains)."""
    names: Set[str] = set()

    def targets_of(node) -> List[str]:
        tgts = node.targets if isinstance(node, ast.Assign) else [node.target]
        out: List[str] = []
        for t in tgts:
            if isinstance(t, ast.Tuple):
                elts = t.elts
            else:
                elts = [t]
            for e in elts:
                n = _callee_name(e)
                if n:
                    out.append(n)
        return out

    changed = True
    while changed:
        changed = False
        for node in ast.walk(sf.tree):
            if not isinstance(node, (ast.Assign, ast.AnnAssign)):
                continue
            value = getattr(node, "value", None)
            if not isinstance(value, ast.Call):
                continue
            resolved = resolve_dotted(value.func, sf.aliases)
            from_jit = resolved == "jax.jit"
            from_factory = _callee_name(value.func) in factories
            wraps_known = any(
                _callee_name(a) in names
                for a in list(value.args)
                + [kw.value for kw in value.keywords]
            )
            if from_jit or from_factory or wraps_known:
                for n in targets_of(node):
                    if n not in names:
                        names.add(n)
                        changed = True
    return names


def _dispatching_functions(
    sf: SourceFile, names: Set[str], factories: Set[str], ctx: ProjectContext
) -> Set[str]:
    """Module functions that transitively perform a device dispatch — the
    shared call graph's transitive-caller closure, seeded at defs whose
    body contains a direct device op, a jit, a dispatch-bound name, or a
    jit-factory call."""
    device_dotted = set(DEVICE_CALLS) | {"jax.jit"}

    def is_seed(fn) -> bool:
        return bool(
            fn.dotted_calls & device_dotted
            or "block_until_ready" in fn.calls
            or fn.calls & names
            or fn.calls & factories
        )

    return ctx.callgraph.transitive_callers(is_seed, within=sf.path)


def check_tc07(sf: SourceFile, ctx: ProjectContext) -> Iterator[Violation]:
    if not _in_scope(sf):
        return iter(())
    factories = _project_jit_factories(ctx)
    names = _dispatch_names(sf, factories)
    dispatching = _dispatching_functions(sf, names, factories, ctx)
    out: List[Violation] = []
    reported: Set = set()

    def report(node: ast.AST, what: str, loop: ast.AST) -> None:
        key = (node.lineno, what)
        if key in reported:
            return
        reported.add(key)
        out.append(Violation(
            "TC07",
            sf.path,
            node.lineno,
            f"device dispatch `{what}` inside a per-request/slot loop "
            f"(line {loop.lineno}) — one host↔device round trip per "
            "iteration is the prefix-copy regression class; batch the "
            "wave into one dispatch, or waive with the "
            "dispatch-granularity contract",
            end_line=node.end_lineno,
        ))

    def subject_words(loop: ast.AST) -> Set[str]:
        if isinstance(loop, (ast.For, ast.AsyncFor)):
            return _ident_words(loop.target) | _ident_words(loop.iter)
        return _ident_words(loop.test)  # while

    def scan_loop_body(loop: ast.AST) -> None:
        bodies = loop.body + getattr(loop, "orelse", [])
        for stmt in bodies:
            for sub in ast.walk(stmt):
                if not isinstance(sub, ast.Call):
                    continue
                resolved = resolve_dotted(sub.func, sf.aliases)
                if resolved in DEVICE_CALLS:
                    report(sub, resolved, loop)
                    continue
                callee = _callee_name(sub.func)
                if callee == "block_until_ready":
                    report(sub, ".block_until_ready()", loop)
                    continue
                if callee in names or callee in dispatching \
                        or callee in factories:
                    report(sub, f"{callee}(...)", loop)
                    continue
                if callee in _EXECUTOR_METHODS:
                    # run_in_executor(executor, fn, ...) / _offload(loop, fn,
                    # ...) / submit(fn, ...):
                    # the handed-off callable dispatches on another thread,
                    # still once per iteration.
                    cands = sub.args[:1] if callee == "submit" \
                        else sub.args[1:]
                    for a in cands[:1]:
                        an = _callee_name(a)
                        if an in names or an in dispatching:
                            report(sub, f"{callee}({an})", loop)

    for node in ast.walk(sf.tree):
        if isinstance(node, (ast.For, ast.AsyncFor, ast.While)):
            if subject_words(node) & SUBJECT_WORDS:
                scan_loop_body(node)
    return iter(out)
